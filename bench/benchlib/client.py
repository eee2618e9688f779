"""An OpenAI streaming client of the gateway, in process: one call to
``StreamGateway.handle_chat_completions`` per request, then the SSE
frames read one at a time, each token event stamped on the host clock
as it arrives."""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field

from benchlib.traffic import Request


@dataclass
class Record:
    req: Request
    start: float                  # due time (open loop) or send time (closed)
    sent: float = 0.0
    token_times: list = field(default_factory=list)
    token_texts: list = field(default_factory=list)
    status: int = 0
    tier: str = ""
    finish_reason: str = ""
    completion_tokens: int = -1   # from the usage chunk
    error: str = ""
    done: float = 0.0

    @property
    def ok(self) -> bool:
        return (not self.error and self.status == 200
                and len(self.token_times) > 0)

    @property
    def ttft_s(self) -> float:
        return self.token_times[0] - self.start


def chat_body(req: Request, alias: str, temperature: float) -> dict:
    return {"model": alias,
            "messages": [{"role": "user", "content": req.prompt}],
            "max_tokens": req.max_tokens, "temperature": temperature,
            "stream": True, "stream_options": {"include_usage": True}}


def send(gateway, bearer: str, req: Request, alias: str, tier: str,
         temperature: float = 0.0, start: float | None = None) -> Record:
    """Send ``req`` and read its stream to the end. ``start`` is when the
    request was due; it defaults to the send time."""
    sent = time.perf_counter()
    rec = Record(req=req, start=sent if start is None else start, sent=sent)
    try:
        resp = gateway.handle_chat_completions(
            chat_body(req, alias, temperature), bearer=bearer)
        rec.status = resp.status
        if resp.status != 200:
            rec.error = f"status {resp.status}: {resp.body}"
            return rec
        rec.tier = resp.headers.get("x-stream-tier", "")
        for frame in resp.stream:
            now = time.perf_counter()
            body = frame[len("data: "):].strip()
            if body == "[DONE]":
                break
            chunk = json.loads(body)
            if "error" in chunk:
                rec.error = f"stream error: {chunk['error']}"
                continue
            if "usage" in chunk:
                rec.completion_tokens = int(chunk["usage"]["completion_tokens"])
                depth = chunk.get("stream", {}).get("fallback_depth", 0)
                if depth:
                    rec.error = rec.error or f"fell back {depth} tier(s)"
                continue
            choice = chunk["choices"][0]
            delta = choice["delta"]
            if choice.get("finish_reason"):
                rec.finish_reason = choice["finish_reason"]
            elif "role" not in delta:
                # one event per generated token, empty-text ids included
                rec.token_times.append(now)
                rec.token_texts.append(delta.get("content", ""))
        if rec.tier != tier:
            rec.error = rec.error or f"served by tier {rec.tier!r}, not {tier!r}"
    except Exception as e:  # a failed request is counted, never raised
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        rec.done = time.perf_counter()
    return rec
