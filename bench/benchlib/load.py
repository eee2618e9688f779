"""Closed- and open-loop load over a measured window.

Load starts ``lead_s`` (from the traffic file) before the window opens,
so the window sees the system in its steady state and not the burst of
a cold start. A request counts for the window when it is sent (closed
loop) or due (open loop) inside it; requests of the lead-in are served
and returned too (their tokens read inside the window count for the
window's rate) but are left out of the window's latencies. Requests
still streaming when the window closes are read to their end, up to
``drain_s`` past the close; one that does not finish by then is a
failed request.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

from benchlib.client import Record, send
from benchlib.traffic import Request, per_principal


class Load:
    def __init__(self, gateway, bearers: list[str], traffic: dict, tier: str):
        self.gateway = gateway
        self.bearers = bearers
        self.traffic = traffic
        self.alias = traffic["alias"]
        self.tier = tier
        self.temperature = float(traffic.get("temperature", 0.0))
        self.lateness: list[float] = []     # open loop: dispatch - due

    def _send(self, req: Request, start: float | None = None) -> Record:
        return send(self.gateway, self.bearers[req.principal], req, self.alias,
                    self.tier, self.temperature, start)

    @property
    def lead_s(self) -> float:
        return float(self.traffic.get("lead_s", 0.0))

    def run(self, reqs: list[Request], t0: float, seconds: float,
            drain_s: float = 60.0) -> list[Record]:
        """Offer ``reqs`` from ``lead_s`` before ``t0`` (host clock) until
        ``seconds`` after it; returns every request's record, lead-in
        included (``record.start < t0``)."""
        if self.traffic["loop"] == "closed":
            return self._closed(reqs, t0, seconds, drain_s)
        return self._open(reqs, t0, seconds, drain_s)

    def _closed(self, reqs, t0, seconds, drain_s):
        t_end = t0 + seconds
        t_start = t0 - self.lead_s
        queues = per_principal(reqs, int(self.traffic["clients"]))
        out: list[Record] = []
        lock = threading.Lock()

        short = []

        def client(q):
            mine = []
            for req in q:
                if time.perf_counter() >= t_end:
                    break
                mine.append(self._send(req))
            else:
                short.append(q)
            with lock:
                out.extend(mine)

        threads = [threading.Thread(target=client, args=(q,), daemon=True,
                                    name=f"bench-client-{i}")
                   for i, q in enumerate(queues)]
        while time.perf_counter() < t_start:
            time.sleep(min(0.01, max(t_start - time.perf_counter(), 0)))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=max(t_end + drain_s - time.perf_counter(), 0.0))
        if any(t.is_alive() for t in threads):
            raise RuntimeError("closed-loop clients still streaming "
                               f"{drain_s:.0f} s after the window closed")
        if short:
            raise RuntimeError("a closed-loop client ran out of requests")
        return sorted(out, key=lambda r: r.req.index)

    def _open(self, reqs, t0, seconds, drain_s):
        t_start = t0 - self.lead_s
        due = [r for r in reqs if t_start + r.due_s < t0 + seconds]
        if len(due) == len(reqs):
            raise RuntimeError("the open-loop schedule ends inside the window")
        pool = ThreadPoolExecutor(max_workers=int(self.traffic.get(
            "max_in_flight", 256)), thread_name_prefix="bench-client")
        futs = []
        for r in due:
            t_due = t_start + r.due_s
            while (wait := t_due - time.perf_counter()) > 0:
                time.sleep(min(wait, 0.05))
            self.lateness.append(time.perf_counter() - t_due)
            futs.append(pool.submit(self._send, r, t_due))
        deadline = t0 + seconds + drain_s
        out = []
        for f in futs:
            out.append(f.result(timeout=max(deadline - time.perf_counter(), 0.0)))
        pool.shutdown(wait=True)
        return out
