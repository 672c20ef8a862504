"""Typed serving configuration.

The reference's config was env vars + code constants (SURVEY §5.6); here
it's one dataclass with env-var overrides, covering the engine shape, model
selection, and server knobs.  Per-thread config stays in the DB tier.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

from ..runtime.compile_log import compile_cache_enabled


@dataclasses.dataclass
class ServingConfig:
    # model
    model_name: str = "llama-3.2-1b"
    checkpoint_dir: Optional[str] = None  # HF safetensors dir; None=random init
    dtype: str = "bfloat16"
    # weight-only quantization: "" (bf16) or "int8" (models/quant.py) —
    # halves decode weight traffic and fits Llama-3-8B on one v5e chip
    quantize: str = ""
    # KV-cache quantization: "" or "int8" (per-slot scales,
    # runtime/kv_cache.py) — halves KV window traffic and doubles how many
    # context windows a pool holds; attention runs the XLA gather path
    kv_quantize: str = ""
    # EngineConfig.attention_backend: "auto" (the engine's rule,
    # InferenceEngine._resolve_backend), or "pallas" / "xla" pinned: a
    # deployment whose head geometry is shown to compile pins the kernels
    # where the rule, which is set for the widest geometry it was read at,
    # would send it to XLA.  No environment variable: a configuration
    # file's `serving` group (or the caller) sets it.
    attention_backend: str = "auto"
    # engine shape
    max_batch: int = 8
    page_size: int = 16
    num_pages: int = 2048
    max_pages_per_seq: int = 512
    prefill_buckets: Tuple[int, ...] = (64, 128, 256, 512, 1024, 2048)
    max_new_tokens_default: int = 1024
    # fused decode depth (EngineConfig.multi_step): steps per device
    # dispatch when the batch is busy; 1 disables fusion
    multi_step: int = 16
    # Draft-free speculative decoding (KAFKA_TPU_SPECULATIVE_K): up to K
    # n-gram prompt-lookup candidates per lane verified in one [B, K+1]
    # device dispatch (README "Speculative decoding").  0 (default)
    # disables it entirely — no verify program is compiled and the
    # dispatch paths are the plain ones.  Best on the repetition-heavy
    # agent workload (tool echoes, JSON, code spans); leave it off for
    # high-entropy creative sampling.
    speculative_k: int = 0
    # Radix prefix-cache page budget (KAFKA_TPU_PREFIX_CACHE_PAGES): how
    # many KV pool pages the cross-thread prefix cache may retain.  None =
    # bounded only by pool pressure (the engine reclaims cache pages
    # before it ever preempts a live request); 0 disables the cache.
    # Replaces the old per-thread entry-count cap — pages are what the
    # pool actually runs out of.
    prefix_cache_pages: Optional[int] = None
    # Tiered KV cache (KAFKA_TPU_KV_HOST_TIER_MB, README "KV tiering"):
    # host-RAM page tier under the pool, in MiB PER ENGINE REPLICA.
    # Prefix-cache eviction demotes page runs host-side; a returning
    # thread's lookup promotes them back instead of re-prefilling.  0
    # (default) disables the tier — all paths byte-identical to before.
    kv_host_tier_mb: int = 0
    # Disk spill dir below the host tier (KAFKA_TPU_KV_DISK_TIER_DIR):
    # host-budget overflow spills runs here (second-chance LRU) and the
    # tracing span ring persists alongside.  None = drop on overflow.
    kv_disk_tier_dir: Optional[str] = None
    # Object-store KV tier (KAFKA_TPU_KV_OBJECT_DIR, README "Object-store
    # KV tier"): a SHARED directory (or bucket mount) below host+disk
    # that makes thread state portable across hosts — runs are archived
    # content-addressed (identical prefixes dedupe across replicas/hosts)
    # and per-thread sleep manifests let dormant threads wake on ANY
    # replica with cache_source="object_tier" instead of re-prefilling.
    # POST /admin/drain/{replica} flushes a replica's warm state before
    # the autoscaler shrinks it away.  None (default) disables the tier;
    # every dispatch/eviction path is byte-identical to before.
    # An http(s):// value mounts the S3-shaped HTTPObjectStore instead
    # of a directory.  Either backend is wrapped in the StoreGuard
    # resilience layer (README "Object store resilience"), tuned by:
    #   KAFKA_TPU_KV_OBJECT_TIMEOUT_S          per-op deadline (0 = off)
    #   KAFKA_TPU_KV_OBJECT_RETRIES            retry budget (default 2)
    #   KAFKA_TPU_KV_OBJECT_BACKOFF_S          base backoff (default .05)
    #   KAFKA_TPU_KV_OBJECT_BREAKER_FAILURES   breaker trip (default 5)
    #   KAFKA_TPU_KV_OBJECT_BREAKER_OPEN_S     open window (default 10)
    #   KAFKA_TPU_KV_OBJECT_SCRUB_S            in-process janitor cadence
    #                                          (0 = off; prefer scheduling
    #                                          scripts/objstore_fsck.py)
    #   KAFKA_TPU_KV_OBJECT_SCRUB_GRACE_S      janitor grace (default 3600)
    kv_object_dir: Optional[str] = None
    # Byte budget (MiB) on the object-store references each replica holds
    # (second-chance LRU; the last dropped reference deletes the object).
    # 0 = unbounded.  KAFKA_TPU_KV_OBJECT_MB.
    kv_object_mb: int = 0
    # parallelism (SURVEY §2.2): the server builds its mesh from these.
    #   tp — tensor parallel within each engine (attention heads / MLP)
    #   sp — sequence parallel: ring-sharded chunked prefill for long
    #        prompts, composed with tp inside the same engine
    #   pp — pipeline parallel: layer stages sharded across devices for
    #        models exceeding one slice's HBM (parallel/pipeline.py);
    #        composes with tp, not with sp or dp
    #   dp — data parallel: dp independent engine replicas, each over its
    #        own tp*sp device slice, with thread-affinity request routing
    #        (runtime/dp_router.py).  dp*pp*sp*tp devices total.
    #   ep — expert parallel (MoE): expert weights shard over "ep" for
    #        Mixtral-class models; composes with tp (and dp replicas)
    tp_size: int = 1
    sp_size: int = 1
    pp_size: int = 1
    dp_size: int = 1
    ep_size: int = 1
    # Disaggregated prefill/decode (KAFKA_TPU_DP_ROLES, README
    # "Disaggregated prefill/decode"): "prefill:P,decode:D" splits the dp
    # fleet into role-specialized pools — long prefills run on the
    # prefill pool and their KV pages ship to a decode-pool replica at
    # first-token time, protecting decode-lane TPOT from prefill
    # interference (DistServe/Mooncake).  P+D must equal dp_size.  None
    # (default) = colocated serving, byte-identical to before.
    dp_roles: Optional[str] = None
    # Prompts whose UNCACHED prefill span is below this many tokens
    # prefill in place on the decode pool (shipping must never cost more
    # than it saves).  KAFKA_TPU_DISAGG_MIN_PREFILL_TOKENS.
    disagg_min_prefill_tokens: int = 512
    # long-context CP strategy when sp>1: "ring" or "ulysses"
    cp_strategy: str = "ring"
    # Request-lifecycle hardening (runtime/failpoints.py chaos-tests these
    # paths; README "Failure semantics"):
    #   max_ttft_s — a request still awaiting its FIRST token past this
    #       many seconds finishes with finish_reason="timeout" (None = off)
    #   request_timeout_s — total wall-time bound per request (None = off)
    #   max_queue_depth — bounded engine waiting queue; a submit past it
    #       answers HTTP 429 + Retry-After (0 = unbounded)
    #   drain_timeout_s — graceful-shutdown budget: /health flips to
    #       "draining", admission stops, in-flight streams get this long
    #       to finish before they are cancelled
    max_ttft_s: Optional[float] = None
    request_timeout_s: Optional[float] = None
    max_queue_depth: int = 256
    drain_timeout_s: float = 30.0
    # Cross-process fault tolerance (README "Process boundaries"):
    #   replica_quarantine_threshold — consecutive step failures before a
    #       DP replica is circuit-broken out of the router (probation +
    #       warm re-admit after a doubling backoff window).  Sandbox
    #       subprocess supervision is configured where the factory lives,
    #       straight from KAFKA_TPU_SANDBOX_RESTART_BACKOFF_S /
    #       KAFKA_TPU_SANDBOX_MAX_RESTARTS (sandbox/process.py) — no
    #       config field here, the server never constructs that factory.
    replica_quarantine_threshold: int = 3
    #   replica_rebuild_threshold — quarantine escalation: after this many
    #       quarantine TRIPS the supervisor rebuilds the replica's engine
    #       at window expiry (DataParallelEngines._rebuild_replica)
    #       instead of re-admitting it forever (0 disables).
    replica_rebuild_threshold: int = 3
    # Autoscaler control loop (KAFKA_TPU_AUTOSCALE, README "Autoscaler",
    # ISSUE 13): "off" (default — no controller built, every dispatch and
    # admission path byte-identical to before), "recommend" (full
    # decision loop + GET /admin/autoscaler log, no action taken — the
    # dry-run to watch before handing over the keys), or "act" (closes
    # the loop: scale-out/in through /admin/resize's seam, degradation
    # ladder under overload).  Poll cadence, hysteresis bands, cooldowns
    # and dp bounds come from KAFKA_TPU_AUTOSCALE_* (runtime/
    # autoscaler.AutoscalerConfig.from_env).
    autoscale: str = "off"
    # Observability (README "Observability"):
    #   trace_sample — fraction of requests traced end to end (span tree in
    #       the /debug/trace ring).  1.0 traces everything (the sampling-
    #       down knob is what's disabled by default); 0 disables tracing.
    #   trace_ring — how many finished traces the in-memory ring retains.
    #   slow_ttft_ms / slow_total_ms — requests exceeding either threshold
    #       emit ONE structured log line with their full span breakdown and
    #       count in requests.slow (None = off).
    #   log_format — "json" stamps every log record with trace_id/span_id/
    #       thread_id (kafka_tpu/logs.py); "text" keeps stdlib formatting.
    trace_sample: float = 1.0
    trace_ring: int = 256
    slow_ttft_ms: Optional[float] = None
    slow_total_ms: Optional[float] = None
    log_format: str = "text"
    # Scheduler flight recorder (README "Flight recorder", ISSUE 11):
    # per-replica ring of this many per-scheduler-iteration records
    # (decision log, measured dispatch timing, anomaly detectors,
    # postmortem capture at GET /debug/flight/{replica}).  0 disables it
    # with byte-identical dispatch paths; None defers to
    # KAFKA_TPU_FLIGHT_RING (default 256).
    flight_ring: Optional[int] = None
    # SLO targets (README "SLO telemetry", ISSUE 10): every request is
    # classified MET/MISSED at finalize against these; /metrics exports
    # attainment (total/1m/5m windows) and goodput (tokens from SLO-met
    # requests), and /admin/signals feeds them to the autoscaler.
    #   slo_ttft_ms — time-to-first-token target (default 200, the
    #       BASELINE north star; 0 disables the TTFT check)
    #   slo_tpot_ms — per-output-token target (default 0 = disabled;
    #       set it to bound decode-cadence SLOs, e.g. 50 for p99 TPOT)
    # None here = defer to KAFKA_TPU_SLO_TTFT_MS / KAFKA_TPU_SLO_TPOT_MS
    # (runtime/metrics.py reads them at engine construction).
    slo_ttft_ms: Optional[float] = None
    slo_tpot_ms: Optional[float] = None
    # server
    host: str = "0.0.0.0"
    port: int = 8000
    # optional bearer-token auth for /v1/* + /metrics (playground parity
    # with the reference's authed deployment; None = open, the dev default)
    api_token: Optional[str] = None
    db_path: str = "data/threads.db"
    local_sandbox_url: Optional[str] = None
    cors_origins: str = "*"
    # test/dev: tiny random model instead of a real checkpoint
    tiny_model: bool = False
    # Static system prompt bypassing the sectioned prompt provider
    # (reference src/kafka/v1.py:85 / src/agents/base.py:102-104 had the
    # same seam).  None = the full PromptProviderV1 persona.  Benchmarks
    # use it to keep the served prompt a realistic size under the
    # byte-level tokenizer.
    system_prompt: Optional[str] = None
    # A reply ends at its `max_tokens` only: a sampled stop token is text
    # like any other (vLLM's `ignore_eos`).  For random weights, whose stop
    # tokens mean nothing: a load generator then gets the lengths it asked
    # for.  No environment variable: a configuration file's `serving` group
    # (or the caller) sets it.
    ignore_eos: bool = False
    # compile the serving programs at boot (one tiny generation per engine)
    # so the first real request doesn't pay the 20-40s XLA compile
    warmup: bool = True
    # persistent XLA compilation cache: warm reboots reuse compiled
    # programs from disk instead of recompiling every bucket.  WHERE it
    # lives is not a serving knob: JAX_COMPILATION_CACHE_DIR when set,
    # else <checkout>/.jax_cache (runtime/compile_log.compile_cache_dir).
    # KAFKA_TPU_COMPILE_CACHE=0 turns it off — read at CONSTRUCTION time
    # (not just via from_env) because the test suite must disable it:
    # XLA has hard-aborted (uncatchably) serializing CPU SPMD executables.
    compile_cache: bool = dataclasses.field(
        default_factory=compile_cache_enabled
    )

    @classmethod
    def profile_32k(cls, **overrides) -> "ServingConfig":
        """BASELINE config 5's serving shape: 32k-context Llama-3-70B on a
        tp x sp mesh (v5p-64-class slice).

        Window math: page_size 16 x max_pages_per_seq 2048 = 32768-token
        attention window.  The pool holds num_pages = 4 full windows + 1
        trash page so a handful of long threads coexist (KV for 70B at 32k
        is ~20 GB/seq in bf16 across the slice — the pool, like the
        weights, is sharded over tp so each device holds 1/tp of it).
        Prefill buckets run to 4096 and every bucket divides sp=4: the
        ring shards each chunk across the sp axis (engine constructor
        contract), and chunked prefill walks the prompt 4096 tokens at a
        time.  dp/pp stay 1 — long-context serving spends the mesh on
        tp x sp (SURVEY §2.2, ring CP for prefill beyond one chip's HBM).
        """
        cfg = cls(
            model_name="llama-3-70b",
            tp_size=16,
            sp_size=4,
            max_batch=4,
            page_size=16,
            max_pages_per_seq=2048,
            num_pages=4 * 2048 + 1,
            prefill_buckets=(256, 1024, 2048, 4096),
            max_new_tokens_default=2048,
        )
        return dataclasses.replace(cfg, **overrides)

    @classmethod
    def from_env(cls, **overrides) -> "ServingConfig":
        env = os.environ

        def get(name: str, default, cast=str):
            raw = env.get(f"KAFKA_TPU_{name}")
            return cast(raw) if raw is not None else default

        def get_axis(name: str, default: int) -> int:
            # both spellings work: KAFKA_TPU_DP_SIZE=2 and KAFKA_TPU_DP=2
            raw = env.get(f"KAFKA_TPU_{name}_SIZE", env.get(f"KAFKA_TPU_{name}"))
            return int(raw) if raw is not None else default

        cfg = cls(
            model_name=get("MODEL", cls.model_name),
            checkpoint_dir=get("CHECKPOINT_DIR", None),
            max_batch=get("MAX_BATCH", cls.max_batch, int),
            num_pages=get("NUM_PAGES", cls.num_pages, int),
            max_pages_per_seq=get("MAX_PAGES_PER_SEQ", cls.max_pages_per_seq, int),
            multi_step=get("MULTI_STEP", cls.multi_step, int),
            # clamp negatives to 0 = disabled (same policy as the cache
            # budget below: nonsense env values must not half-enable)
            speculative_k=get("SPECULATIVE_K", cls.speculative_k,
                              lambda v: max(0, int(v))),
            # clamp nonsense (negative) values to 0 = "disabled" — a raw
            # negative budget would otherwise evict every store on sight
            # while leaving the cache machinery running
            prefix_cache_pages=get("PREFIX_CACHE_PAGES", None,
                                   lambda v: max(0, int(v))),
            # clamp negatives to 0 = disabled, same policy as above
            kv_host_tier_mb=get("KV_HOST_TIER_MB", cls.kv_host_tier_mb,
                                lambda v: max(0, int(v))),
            kv_disk_tier_dir=get("KV_DISK_TIER_DIR", None),
            kv_object_dir=get("KV_OBJECT_DIR", None),
            # clamp negatives to 0 = unbounded refs, same env policy
            kv_object_mb=get("KV_OBJECT_MB", cls.kv_object_mb,
                             lambda v: max(0, int(v))),
            tp_size=get_axis("TP", cls.tp_size),
            sp_size=get_axis("SP", cls.sp_size),
            pp_size=get_axis("PP", cls.pp_size),
            dp_size=get_axis("DP", cls.dp_size),
            ep_size=get_axis("EP", cls.ep_size),
            dp_roles=get("DP_ROLES", None),
            disagg_min_prefill_tokens=get(
                "DISAGG_MIN_PREFILL_TOKENS",
                cls.disagg_min_prefill_tokens,
                lambda v: max(1, int(v))),
            cp_strategy=get("CP_STRATEGY", cls.cp_strategy),
            max_ttft_s=get("MAX_TTFT_S", None, float),
            request_timeout_s=get("REQUEST_TIMEOUT_S", None, float),
            max_queue_depth=get("MAX_QUEUE_DEPTH", cls.max_queue_depth, int),
            drain_timeout_s=get("DRAIN_TIMEOUT_S", cls.drain_timeout_s,
                                float),
            replica_quarantine_threshold=get(
                "REPLICA_QUARANTINE_THRESHOLD",
                cls.replica_quarantine_threshold, int),
            # clamp negatives to 0 = disabled, same policy as the caches
            replica_rebuild_threshold=get(
                "REPLICA_REBUILD_THRESHOLD",
                cls.replica_rebuild_threshold,
                lambda v: max(0, int(v))),
            autoscale=get("AUTOSCALE", cls.autoscale),
            trace_sample=get("TRACE_SAMPLE", cls.trace_sample, float),
            trace_ring=get("TRACE_RING", cls.trace_ring, int),
            slow_ttft_ms=get("SLOW_TTFT_MS", None, float),
            slow_total_ms=get("SLOW_TOTAL_MS", None, float),
            # clamp negatives to 0 = disabled, same policy as the caches
            flight_ring=get("FLIGHT_RING", None,
                            lambda v: max(0, int(v))),
            slo_ttft_ms=get("SLO_TTFT_MS", None, float),
            slo_tpot_ms=get("SLO_TPOT_MS", None, float),
            log_format=get("LOG_FORMAT", cls.log_format),
            host=get("HOST", cls.host),
            port=get("PORT", cls.port, int),
            api_token=get("API_TOKEN", None),
            db_path=get("DB_PATH", cls.db_path),
            local_sandbox_url=get("SANDBOX_URL", None),
            tiny_model=get("TINY_MODEL", "0") in ("1", "true", "True"),
            system_prompt=get("SYSTEM_PROMPT", None),
            quantize=get("QUANTIZE", cls.quantize),
            kv_quantize=get("KV_QUANTIZE", cls.kv_quantize),
            warmup=get("WARMUP", "1") not in ("0", "false", "False"),
            # compile_cache omitted: its default_factory already reads
            # KAFKA_TPU_COMPILE_CACHE
        )
        return dataclasses.replace(cfg, **overrides)
