"""Assemble a full STREAM deployment: tiers, judge, router, summarizer,
handler, gateway (+ the deprecated proxy shim) — server mode (all
components) in one call.

The HPC tier's endpoint gets the tier engine + relay handle injected as
worker globals (the vLLM-over-localhost analogue) and the credentials
pre-provisioned via worker_init_env — the same trust topology as the
paper: secrets live on the endpoint and the proxy, never in task args.
"""

from __future__ import annotations

import base64
import secrets as _secrets
from dataclasses import dataclass, field

import jax

from repro.configs import get_smoke_config
from repro.core.auth import ApiKeyStore, DualAuthenticator, GlobusAuthService, SlidingWindowRateLimiter
from repro.core.control_plane import ComputeEndpoint
from repro.core.crypto import new_key
from repro.core.data_plane import TokenProducer, produce_tokens
from repro.core.gateway import StreamGateway
from repro.core.handler import StreamingHandler
from repro.core.judge import CachedJudge, FeatureJudge, KeywordJudge
from repro.core.metrics import UsageTracker
from repro.core.proxy import HPCAsAPIProxy
from repro.core.relay import Relay
from repro.core.router import TierRouter
from repro.core.summarizer import DEFAULT_POLICIES, SummarizerPolicy, TierAwareSummarizer
from repro.core.tiers import CloudBackend, HPCBackend, LocalBackend, TierSpec
from repro.serving import EngineFleet, ServingEngine


@dataclass
class StreamSystem:
    handler: StreamingHandler
    router: TierRouter
    summarizer: TierAwareSummarizer
    tracker: UsageTracker
    relay: Relay
    endpoint: ComputeEndpoint
    proxy: HPCAsAPIProxy            # deprecated shim (HPC tier only)
    globus: GlobusAuthService
    api_keys: ApiKeyStore
    backends: dict
    engines: dict
    gateway: StreamGateway = None   # the OpenAI facade over ALL tiers


def build_system(*, relay_enabled: bool = True, encrypt: bool = True,
                 dispatch_latency_s: float = 0.05, cloud_ttft_s: float = 0.03,
                 judge=None, local_arch: str = "xlstm-125m",
                 hpc_arch: str = "minitron-8b", max_seq: int = 128,
                 summarizer_policies: dict | None = None,
                 hpc_fail: bool = False, cloud_fail: bool = False,
                 rate_limit: int = 1000, scheduler_slots: int = 8,
                 hpc_workers: int = 8, hpc_overrides: dict | None = None,
                 local_overrides: dict | None = None,
                 prefix_cache_pages: int = 256,
                 speculative: bool = False,
                 spec_k: int = 4, replicas: int = 1,
                 fleet_overrides: dict | None = None,
                 kv_dtype: str = "fp32",
                 quantize_mlp: bool = False, seed: int = 0) -> StreamSystem:
    """Everything wired, smoke-scale models (CPU-friendly) unless the
    overrides say otherwise: ``hpc_overrides``/``local_overrides``
    replace any config field, so passing every field of a published
    config (``dataclasses.asdict(get_config(arch, ...))``) builds that
    tier at its published widths. Random weights come from ``seed``.

    ``scheduler_slots`` sizes each tier engine's session broker (the
    shared continuous-batching decode batch concurrent sessions
    interleave in); ``hpc_workers`` sizes the control-plane worker pool
    so that many dual-channel tasks can be in flight at once — the
    workers only shepherd relay traffic, the decode work itself is
    batched on the HPC engine's broker thread.

    ``speculative=True`` (opt-in; default off so baseline numbers are
    untouched) turns on speculative decoding per tier: the local tier
    self-drafts with prompt-lookup n-grams, and the hpc tier verifies
    drafts from the LOCAL tier's model — the paper's cross-tier pairing
    — when that model implements ``propose_k`` (recurrent local archs
    fall back to n-gram drafting on the hpc tier too). Output tokens
    are identical either way; only decode speed changes.

    ``replicas=N`` (N > 1) puts an :class:`~repro.serving.EngineFleet`
    of N parameter-sharing local engines behind the local tier (and the
    cloud tier's token source): cache-aware routing, work stealing, and
    mid-stream failover, all invisible to the tier/gateway contract.
    ``fleet_overrides`` tunes the fleet (``steal_threshold``,
    ``tick_timeout_s``, ...).

    ``kv_dtype`` ("fp32" | "int8" | "fp8_e4m3") selects the paged KV
    pool's storage dtype on every tier engine — quantized pages halve
    (or better) KV bytes per device with in-kernel dequant at read time;
    non-paged pools always stay fp32. ``quantize_mlp=True`` serves both
    tiers with W4A16 AWQ-quantized MLP + attention-output weights (the
    paper's Qwen-72B-AWQ HPC tier); fleet replicas share replica-0's
    quantized params."""
    rng = jax.random.PRNGKey(seed)

    # --- engines (the per-tier model servers) ---
    # vocab >= 259 so the byte tokenizer can round-trip real text
    local_cfg = get_smoke_config(local_arch).replace(vocab_size=384)
    hpc_cfg = get_smoke_config(hpc_arch).replace(vocab_size=384)
    if hpc_overrides:
        # e.g. benchmarks scale the HPC model up toward a realistic
        # compute weight (smoke configs are contention-test sized)
        hpc_cfg = hpc_cfg.replace(**hpc_overrides)
    if local_overrides:
        local_cfg = local_cfg.replace(**local_overrides)
    spec_local, spec_hpc = {}, {}
    if speculative:
        spec_local = {"speculative": "ngram", "spec_k": spec_k}
        spec_hpc = dict(spec_local)
    local_params = hpc_params = None
    if quantize_mlp:
        # W4A16 both tiers: init the params the engines would have built
        # themselves, quantize once, hand the quantized tree to every
        # constructor (fleet peers inherit via params=local_engine.params
        # below). group 64 fits smoke-scale contraction dims; weights
        # that don't divide stay dense.
        from repro.models import build_model
        from repro.serving.quantize import quantize_mlp_tree
        local_params = quantize_mlp_tree(build_model(local_cfg).init(rng),
                                         group_size=64)
        hpc_params = quantize_mlp_tree(build_model(hpc_cfg).init(rng),
                                       group_size=64)
    local_engine = ServingEngine(local_cfg, params=local_params,
                                 max_seq=max_seq, rng=rng,
                                 scheduler_slots=scheduler_slots,
                                 prefix_cache_pages=prefix_cache_pages,
                                 kv_dtype=kv_dtype, **spec_local)
    local_tier_engine = local_engine
    if replicas > 1:
        # N - 1 more replicas sharing replica 0's params (token identity
        # across failover), all behind one fleet submit surface
        peers = [ServingEngine(local_cfg, params=local_engine.params,
                               max_seq=max_seq, rng=rng,
                               scheduler_slots=scheduler_slots,
                               prefix_cache_pages=prefix_cache_pages,
                               kv_dtype=kv_dtype, **spec_local)
                 for _ in range(replicas - 1)]
        local_tier_engine = EngineFleet([local_engine] + peers,
                                        **(fleet_overrides or {}))
    if speculative and hasattr(local_engine.model, "propose_k"):
        # cross-tier: the local tier's model (params and all) drafts
        # for the hpc-tier verifier
        spec_hpc = {"drafter_cfg": local_cfg,
                    "drafter_params": local_engine.params,
                    "spec_k": spec_k}
    hpc_engine = ServingEngine(hpc_cfg, params=hpc_params,
                               max_seq=max_seq, rng=rng,
                               scheduler_slots=scheduler_slots,
                               prefix_cache_pages=prefix_cache_pages,
                               kv_dtype=kv_dtype, **spec_hpc)
    local_tier_engine.warmup()
    hpc_engine.warmup()

    # --- data plane ---
    relay_secret = _secrets.token_urlsafe(24)
    enc_key = new_key() if encrypt else None
    relay = Relay(relay_secret) if relay_enabled else None

    # --- control plane: credentials pre-provisioned, engine injected ---
    worker_env = {"RELAY_SECRET": relay_secret}
    if enc_key is not None:
        worker_env["RELAY_ENCRYPTION_KEY"] = base64.b64encode(enc_key).decode()
    endpoint = ComputeEndpoint(
        "lakeshore-gpu", worker_init_env=worker_env,
        dispatch_latency_s=dispatch_latency_s, n_workers=hpc_workers,
        extra_globals={"ENGINE": hpc_engine, "RELAY": relay,
                       "PRODUCE_TOKENS": produce_tokens,
                       "TOKEN_PRODUCER": TokenProducer})
    if hpc_fail:
        endpoint.shutdown()

    # --- tiers ---
    specs = {
        "local": TierSpec("local", "llama-3.2-3b(sim)", 32_768),
        "hpc": TierSpec("hpc", "qwen2.5-vl-72b-awq(sim)", 65_536),
        "cloud": TierSpec("cloud", "claude-sonnet-4-6(sim)", 1_048_576,
                          cost_per_1k_prompt=0.003, cost_per_1k_completion=0.015),
    }
    backends = {
        "local": LocalBackend(specs["local"], local_tier_engine),
        "hpc": HPCBackend(specs["hpc"], endpoint, relay, relay_secret, enc_key),
        "cloud": CloudBackend(specs["cloud"], ttft_s=cloud_ttft_s,
                              engine=local_tier_engine, fail=cloud_fail),
    }

    # --- routing / summarization / handler ---
    judge = judge or CachedJudge(KeywordJudge())
    router = TierRouter(backends, judge)
    # token accounting against the REAL tokenizer, so needed()/fits()
    # thresholds agree with what the engines actually prefill
    summarizer = TierAwareSummarizer(summarizer_policies or DEFAULT_POLICIES,
                                     tokenizer=local_engine.tokenizer)
    tracker = UsageTracker()
    handler = StreamingHandler(router, summarizer, tracker)

    # --- OpenAI-compatible facade ---
    globus = GlobusAuthService()
    api_keys = ApiKeyStore()
    authenticator = DualAuthenticator(globus, api_keys)
    # the gateway fronts the FULL routed pipeline (stream-auto/-local/
    # -hpc/-cloud aliases); the deprecated proxy shim keeps the old
    # single-tier call surface alive. Separate limiters so a caller's
    # budget isn't double-counted across the two entry points.
    gateway = StreamGateway(handler, authenticator,
                            SlidingWindowRateLimiter(max_requests=rate_limit))
    proxy = HPCAsAPIProxy(backends["hpc"], authenticator,
                          SlidingWindowRateLimiter(max_requests=rate_limit))

    return StreamSystem(handler=handler, router=router, summarizer=summarizer,
                        tracker=tracker, relay=relay, endpoint=endpoint,
                        proxy=proxy, globus=globus, api_keys=api_keys,
                        backends=backends,
                        engines={"local": local_tier_engine, "hpc": hpc_engine},
                        gateway=gateway)
