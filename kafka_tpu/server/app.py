"""The HTTP API server: OpenAI-compatible endpoints + the 4-event SSE
protocol, served by aiohttp.

Endpoint parity with the reference (server.py:384-620):
  POST /v1/chat/completions                  stateless chat (agent loop)
  POST /v1/threads/{id}/chat/completions     thread chat w/ history
  POST /v1/agent/run                         stateless agent run (SSE)
  POST /v1/threads/{id}/agent/run            thread agent run (SSE)
  POST /v1/threads                           create thread
  GET  /v1/threads                           list threads
  GET  /v1/threads/{id}                      thread metadata
  GET  /v1/threads/{id}/messages             thread history
  DELETE /v1/threads/{id}                    delete thread
  DELETE /v1/threads/{id}/messages           clear history
  PUT  /v1/threads/{id}/config               set per-thread config (ext.)
  GET  /v1/models                            served models
  GET  /health                               liveness + engine stats

One deliberate improvement over the reference: the chat path streams REAL
tokens as they decode.  The reference ran the whole agent loop first and
then re-streamed the final text in 20-char pseudo-chunks
(server.py:347-356) — its TTFT was a full agent run.  Clients still get the
same event vocabulary (OpenAI chunks / tool_result / tool_messages /
agent_done, SURVEY §5.8), so the reference playground works unmodified.
"""

from __future__ import annotations

import contextlib
import hashlib
import hmac
import json
import logging
import time
from dataclasses import replace as dataclasses_replace
from typing import Any, AsyncIterator, Dict, List, Optional

from aiohttp import web
from pydantic import ValidationError

from ..core.types import (
    ContextLengthError,
    LLMProviderError,
    ServerOverloadedError,
    Usage,
    new_completion_id,
)
from .. import tracing
from ..core.wire import AgentRunRequest, ChatCompletionRequest
from ..db import DBClient, LocalDBClient, make_db_client
from ..kafka import KafkaV1Provider, MessageAccumulator
from ..llm.base import LLMProvider
from ..tools import MCPServerConfig, Tool
from .config import ServingConfig
from .sse import sse_response

logger = logging.getLogger("kafka_tpu.server")

STATE_KEY = web.AppKey("kafka_tpu_state", dict)


# ---------------------------------------------------------------------------
# wiring
# ---------------------------------------------------------------------------


BOOT_ANNOTATION_PREFIX = "kafka.boot."


def _boot_clock() -> tracing.PhaseClock:
    """The booting thread's clock: seconds by stage (tracing.BOOT_STAGES),
    read once as /metrics `boot.<stage>_s`."""
    return tracing.PhaseClock(tracing.BOOT_STAGES, BOOT_ANNOTATION_PREFIX,
                              "rest")


def build_tpu_provider(cfg: ServingConfig,
                       boot: Optional[tracing.PhaseClock] = None
                       ) -> LLMProvider:
    """Construct tokenizer + engine + provider per the serving config.

    Parallelism wiring (the reference wired its whole stack in the server
    lifespan, server.py:89-150 — here the mesh shape is the analog):
    tp/sp build one SPMD engine over a tp×sp mesh; dp>1 builds dp replica
    engines over disjoint tp×sp device slices behind the thread-affinity
    router (runtime/dp_router.py).  Multi-host topologies initialize
    jax.distributed first (env-driven, no-op single-process).

    Multi-host + dp: replicas are per-process objects (each owns a Python
    scheduler thread), so each server process builds its replicas over its
    own *local* chips and an external load balancer spreads traffic across
    the hosts — dp_size here is replicas per host.  tp/sp SPMD engines, by
    contrast, span the global device set the way jax.distributed programs
    do.

    `boot` is the booting thread's clock (create_app's): each stage below
    is one mark on it.
    """
    boot = boot or _boot_clock()
    boot.mark("import")
    import jax

    from ..llm.tpu_provider import TPULLMProvider
    from ..models import get_config, init_params, load_checkpoint
    from ..models.tokenizer import ByteTokenizer, load_tokenizer
    from ..parallel.distributed import init_distributed
    from ..runtime import EngineConfig, InferenceEngine

    boot.mark("rest")
    # before any backend use: multi-host init when KAFKA_TPU_COORDINATOR /
    # NUM_PROCESSES are set (SURVEY §2.2 "distributed communication
    # backend"); returns False and costs nothing single-process
    init_distributed()

    # compile observatory (runtime/compile_log.py): the ring must exist
    # before the first jax.jit below so boot-phase compiles are captured;
    # KAFKA_TPU_COMPILE_RING=0 leaves it off and every instrument() seam
    # returns the jitted fn unchanged
    from ..runtime import compile_log

    compile_log.init()
    compile_log.set_phase("boot")

    if cfg.compile_cache:
        # persistent XLA compile cache: a warm reboot loads every serving
        # program from disk instead of recompiling (~30s per bucket)
        compile_log.enable_compile_cache()
    else:
        compile_log.configure_cache(None)

    # Resolve the model's ARCHITECTURE cheaply (config.json / registry —
    # no weight materialization) so the memory-fit check below can reject
    # an impossible config in milliseconds, before a multi-GiB checkpoint
    # load ever touches the device.
    if cfg.checkpoint_dir:
        import os as _os

        from ..models.config import config_from_hf_json

        tokenizer = load_tokenizer(cfg.checkpoint_dir)
        model_cfg = config_from_hf_json(
            _os.path.join(cfg.checkpoint_dir, "config.json")
        )
    elif cfg.tiny_model:
        tokenizer = ByteTokenizer()
        model_cfg = get_config("tiny").replace(
            vocab_size=tokenizer.vocab_size, dtype="float32"
        )
    else:
        # No checkpoint: random weights at the NAMED model's own shape,
        # vocabulary included (the logits head is a real share of a decode
        # step) — the byte tokenizer pads its table out to the model's
        # vocab with never-sampled filler ids.
        base = get_config(cfg.model_name)
        vocab = max(base.vocab_size, ByteTokenizer().vocab_size)
        if base.image_token_id is not None:
            # the reserved image-placeholder id must stay in-vocab
            vocab = max(vocab, base.image_token_id + 1)
        tokenizer = ByteTokenizer(vocab_size=vocab)
        model_cfg = base.replace(vocab_size=vocab, dtype=cfg.dtype)
    if cfg.quantize and cfg.quantize != "int8":
        raise ValueError(f"unknown quantize mode {cfg.quantize!r}")

    engine_cfg = EngineConfig(
        max_batch=cfg.max_batch,
        page_size=cfg.page_size,
        num_pages=cfg.num_pages,
        max_pages_per_seq=cfg.max_pages_per_seq,
        prefill_buckets=cfg.prefill_buckets,
        max_new_tokens_default=cfg.max_new_tokens_default,
        cp_strategy=cfg.cp_strategy,
        multi_step=cfg.multi_step,
        speculative_k=cfg.speculative_k,
        kv_quantize=cfg.kv_quantize,
        attention_backend=cfg.attention_backend,
        # 0 disables the radix prefix cache; None = pressure-bounded
        prefix_cache_entries=0 if cfg.prefix_cache_pages == 0 else 64,
        prefix_cache_pages=cfg.prefix_cache_pages or None,
        kv_host_tier_mb=cfg.kv_host_tier_mb,
        kv_disk_tier_dir=cfg.kv_disk_tier_dir,
        kv_object_dir=cfg.kv_object_dir,
        kv_object_mb=cfg.kv_object_mb,
        max_ttft_s=cfg.max_ttft_s,
        max_total_s=cfg.request_timeout_s,
        max_waiting=cfg.max_queue_depth,
    )
    if cfg.flight_ring is not None:
        # None defers to the EngineConfig default (KAFKA_TPU_FLIGHT_RING)
        engine_cfg = dataclasses_replace(engine_cfg,
                                         flight_ring=cfg.flight_ring)
    # Memory-fit validation (runtime/planner.py): per-device bytes under
    # the actual sharding rules, against the live device's HBM.  When the
    # WEIGHTS ALONE exceed the budget — never a false positive, the
    # activation terms are estimates but the weight bytes are exact — fail
    # here, before any weights load.
    memory_plan = None
    from ..runtime.planner import hbm_for_device, plan_for_serving

    # None off-TPU (CPU tests have no HBM to plan against); on a TPU this
    # raises on an unknown chip or an unplannable config — a deployment
    # that cannot say what it fits in does not boot
    hbm = hbm_for_device(jax.devices()[0])
    if hbm:
        memory_plan = plan_for_serving(
            cfg, hbm_bytes=hbm, model_cfg=model_cfg
        )
        if memory_plan.weight_bytes > memory_plan.usable_bytes:
            raise MemoryError(
                f"{model_cfg.name} weights alone need "
                f"{memory_plan.weight_bytes / 2**30:.1f} GiB/device, "
                f"budget {memory_plan.usable_bytes / 2**30:.1f} GiB: "
                f"{memory_plan.summary()} — shard (tp/pp), quantize, "
                "or pick a bigger topology"
            )
        log = logger.warning if not memory_plan.fits else logger.info
        log("memory plan: %s", memory_plan.summary())

    # NOW materialize weights (checkpoint load / random init); the
    # plan-validated model_cfg is the one served
    boot.mark("weights")
    if cfg.checkpoint_dir:
        _, params = load_checkpoint(cfg.checkpoint_dir, model_cfg)
    else:
        params = init_params(model_cfg, jax.random.PRNGKey(0))
    if cfg.quantize == "int8":
        from ..models import quantize_params

        params = quantize_params(params, model_cfg)
    # (jax dispatches asynchronously: random weights still being made on
    # the device are waited for by whoever first reads them, which is the
    # engine's construction)

    boot.mark("engine_build")
    if cfg.dp_roles and cfg.dp_size <= 1:
        raise ValueError(
            "KAFKA_TPU_DP_ROLES needs dp_size > 1: role pools split the "
            "DP fleet into prefill and decode replicas"
        )
    if cfg.dp_size > 1:
        if cfg.pp_size > 1:
            raise ValueError(
                "dp_size and pp_size cannot compose: DP replicates whole "
                "engines while PP exists to fit a model that does NOT fit "
                "a replica — pick one"
            )
        from ..runtime.dp_router import DataParallelEngines

        # replica engines cannot place params onto another host's
        # (non-addressable) devices — under multi-host init each process
        # builds dp replicas over its own chips (see docstring)
        local = (
            jax.local_devices() if jax.process_count() > 1 else None
        )
        engine = DataParallelEngines(
            model_cfg, params, engine_cfg,
            dp=cfg.dp_size, tp=cfg.tp_size, sp=cfg.sp_size,
            ep=cfg.ep_size,
            devices=local,
            quarantine_threshold=cfg.replica_quarantine_threshold,
            rebuild_threshold=cfg.replica_rebuild_threshold,
            # disaggregated prefill/decode pools (README "Disaggregated
            # prefill/decode"); None = colocated, byte-identical
            dp_roles=cfg.dp_roles,
            disagg_min_prefill_tokens=cfg.disagg_min_prefill_tokens,
        )
    else:
        mesh = None
        if (cfg.tp_size > 1 or cfg.sp_size > 1 or cfg.pp_size > 1
                or cfg.ep_size > 1):
            from ..parallel import MeshConfig, make_mesh, resolve_tensor_axes

            # grouped GQA: a tensor degree beyond num_kv_heads factorizes
            # into tp*tq so the KV pool shards over tp instead of fully
            # replicating; ulysses/pp keep the plain axis (see
            # parallel/mesh.py resolve_tensor_axes — shared with the
            # memory planner so the plan matches placement)
            tpk, tq = resolve_tensor_axes(
                cfg.tp_size, model_cfg.num_kv_heads,
                cp_strategy=cfg.cp_strategy, sp=cfg.sp_size,
                pp=cfg.pp_size,
            )
            mesh = make_mesh(MeshConfig(
                pp=cfg.pp_size, sp=cfg.sp_size, tp=tpk, tq=tq,
                ep=cfg.ep_size,
            ))
        engine = InferenceEngine(model_cfg, params, engine_cfg, mesh=mesh)
    boot.mark("rest")
    if memory_plan is not None:
        # live HBM accounting (runtime/planner.py MemoryMonitor): the plan
        # attaches after construction so measured bytes_in_use can report
        # plan_skew against the numbers this deployment was validated on
        for _e in getattr(engine, "engines", [engine]):
            if getattr(_e, "memory_monitor", None) is not None:
                _e.memory_monitor.plan = memory_plan
    if cfg.warmup:
        # Compile the serving programs NOW (engine is not yet driven by the
        # worker thread, so direct generate() is safe); the first real
        # request then pays serving latency, not the XLA compile.  Metrics
        # reset afterwards so /metrics percentiles reflect serving only.
        import time as _time

        from ..runtime import GenRequest
        from ..runtime.metrics import EngineMetrics

        t0 = _time.monotonic()
        compile_log.set_phase("warmup")
        engines = getattr(engine, "engines", [engine])
        # warmup is operator traffic, not client traffic: it must not trip
        # the admission bound (a small max_queue_depth would otherwise
        # reject the multi-stream warmup batch).  All engines share this
        # EngineConfig instance, so flip it once and restore after.
        _admission_bound = engine_cfg.max_waiting
        engine_cfg.max_waiting = 0
        # Every prefill bucket compiles now — a real conversation grows
        # through the bucket ladder, and each uncompiled bucket would cost
        # its first request a ~30s stall.  One prompt per bucket (sized to
        # land in it), plus enough concurrent requests per replica to also
        # compile the fused multi-step decode program (engages at >=3
        # active lanes).  Submitted straight to each replica, with no
        # prefix_key: warmup must not seed the prefix cache or the DP
        # affinity map.
        window = engine_cfg.max_window
        bucket_lens = sorted({
            min(b, window - engine_cfg.multi_step - 4)
            for b in engine_cfg.prefill_buckets
        })
        per_engine = (
            3 if engine_cfg.multi_step > 1 and cfg.max_batch >= 3 else 1
        )
        # grammar artifact for the fsm-program warmup below (None =
        # feature disabled, uncompilable, or no tokenizer eot in vocab)
        _warmup_grammar = None
        from ..llm.constrained import (
            build_tool_call_mask_fn,
            compile_grammar_for_mask_fn,
            grammar_ondevice_enabled,
        )

        if grammar_ondevice_enabled():
            boot.mark("grammar")
            from ..agents.base import IDLE_TOOL

            _warm_tools = [
                t.to_openai() for t in default_builtin_tools(cfg)
            ] + [IDLE_TOOL]
            _warm_mask = build_tool_call_mask_fn(
                tokenizer, _warm_tools, "required"
            )
            if _warm_mask is not None:
                # defer=False: boot is not serving anyone, so even a large
                # vocabulary compiles here rather than on the background
                # worker (whose first callers take the host mask path)
                _warmup_grammar = compile_grammar_for_mask_fn(
                    _warm_mask, model_cfg.vocab_size, defer=False
                )

        def _warm_engine(n: int, e) -> None:
            for j, blen in enumerate(bucket_lens):
                e.submit(GenRequest(
                    request_id=f"__warmup_b{n}_{j}",
                    prompt_ids=[3] * max(1, blen), max_new_tokens=1,
                ))
                e.run_to_completion()  # one at a time: bounded pool use
                # two concurrent same-bucket prompts fuse into the batched
                # prefill program wherever the scheduler would fuse them
                # (an admission burst is exactly that shape)
                if e.batches_prefill(next(
                        b for b in engine_cfg.prefill_buckets if b >= blen)):
                    for i in range(2):
                        e.submit(GenRequest(
                            request_id=f"__warmup_bb{n}_{j}_{i}",
                            prompt_ids=[3] * max(1, blen), max_new_tokens=1,
                        ))
                    e.run_to_completion()
            for i in range(per_engine):
                e.submit(GenRequest(
                    request_id=f"__warmup_{n}_{i}",
                    prompt_ids=[3] * min(8, window // 4),
                    max_new_tokens=engine_cfg.multi_step + 2,
                ))
            # Constrained decoding uses two more program variants (the
            # prefill program takes a mask row either way): the
            # forced-token chained decode ([B] override vector), and the
            # ambiguous masked decode ([B, V] allowed mask — step 1 below
            # returns TWO ids so it actually traces).  The first tool
            # call would otherwise compile them on the scheduler thread,
            # stalling every in-flight stream.
            e.submit(GenRequest(
                request_id=f"__warmup_con_{n}",
                prompt_ids=[3] * 4, max_new_tokens=3,
                logits_mask_fn=lambda out: (
                    [3] if len(out) == 0 else
                    [3, 4] if len(out) == 1 else None
                ),
            ))
            e.run_to_completion()
            # speculative verify program (KAFKA_TPU_SPECULATIVE_K > 0):
            # organic engagement depends on generated repetition, so the
            # engine compiles it via an all-masked dispatch (no-op at K=0)
            e.warmup_verify()
            # on-device grammar FSM programs (KAFKA_TPU_GRAMMAR_ONDEVICE):
            # compile the fsm decode/verify variants against the
            # builtin-tools + idle grammar — the schema the agent path
            # constrains to in the common (no-MCP) deployment, so the
            # first forced tool call pays serving latency, not an XLA
            # compile on the scheduler thread.  A deployment whose merged
            # MCP registry differs registers its grammar at request time
            # (one retrace if the padded table shape grows).
            if _warmup_grammar is not None:
                e.warmup_grammar(_warmup_grammar)
            # tiered-KV ship programs (KAFKA_TPU_KV_HOST_TIER_MB > 0):
            # compile the per-bucket gather/scatter transfers so the first
            # demotion/promotion pays copy latency, not an XLA compile on
            # the scheduler thread (no-op when the tier is off)
            e.warmup_kv_tier()
            # the snapshot restore of a model with a recurrent state: the
            # first prefix hit pays a copy, not a compile (no-op otherwise)
            e.warmup_state()

        # Replicas are independent engines over disjoint devices, and XLA
        # compiles each one's programs separately (the device assignment
        # is part of an executable, and of its persistent-cache key): warm
        # them side by side.  Compilation releases the GIL, so a dp boot
        # costs about one replica's compile time instead of dp of them.
        from concurrent.futures import ThreadPoolExecutor

        boot.mark("warmup")
        with ThreadPoolExecutor(len(engines)) as pool:
            for fut in [pool.submit(_warm_engine, n, e)
                        for n, e in enumerate(engines)]:
                fut.result()  # re-raise a replica's warm-up failure
        # cross-replica ship programs (KAFKA_TPU_DP_ROLES): compile the
        # per-bucket gather/scatter pairs across the pool edges so the
        # first prefill-and-hand-off pays copy latency, not an XLA
        # compile on the scheduler thread (no-op without role pools)
        warm_disagg = getattr(engine, "warmup_disagg", None)
        if warm_disagg is not None:
            warm_disagg()
        engine.run_to_completion()
        boot.mark("rest")
        engine_cfg.max_waiting = _admission_bound
        for e in engines:
            e.metrics = EngineMetrics()
        logger.info("warmup compile done in %.1fs", _time.monotonic() - t0)
    # everything compiled past this point is unexpected work under live
    # traffic: the observatory's storm detector only counts this phase
    compile_log.set_phase("first_traffic")
    vision_params = None
    if model_cfg.vision is not None:
        # vision tower (models/vision.py).  Random-init like the text
        # params when no checkpoint supplies one; a Llava checkpoint's
        # tower would load here through the same seam.
        from ..models.vision import vision_init_params

        vision_params = vision_init_params(
            model_cfg.vision, model_cfg.hidden_size, jax.random.PRNGKey(7),
            dtype=model_cfg.activation_dtype,
        )
    provider = TPULLMProvider(
        engine, tokenizer, model_name=cfg.model_name,
        vision_params=vision_params, ignore_eos=cfg.ignore_eos,
    )
    # the startup plan (actual model_cfg, live-device HBM) rides along so
    # /health reports the numbers this deployment was validated against
    provider.memory_plan = memory_plan
    return provider


def default_builtin_tools(cfg: ServingConfig) -> List[Tool]:
    from ..server_tools import builtin_tools

    return builtin_tools(sandbox_url=cfg.local_sandbox_url)


async def create_app(
    cfg: Optional[ServingConfig] = None,
    llm_provider: Optional[LLMProvider] = None,
    db: Optional[DBClient] = None,
    tools: Optional[List[Tool]] = None,
    mcp_servers: Optional[List[MCPServerConfig]] = None,
) -> web.Application:
    """Build the application; DI parameters override config-driven wiring
    (the testing seams the reference got from its ABC layering)."""
    cfg = cfg or ServingConfig.from_env()
    boot = _boot_clock()
    # late env injection (KAFKA_TPU_FAILPOINTS set after import): arm any
    # configured failpoints before the engine builds
    from ..runtime.failpoints import load_env as _load_failpoints

    _load_failpoints()
    # tracing/slow-log config is per-deployment (ServingConfig), applied
    # before the engine builds so every request is eligible from boot
    tracing.configure(
        sample=cfg.trace_sample,
        ring=cfg.trace_ring,
        slow_ttft_ms=cfg.slow_ttft_ms or 0,
        slow_total_ms=cfg.slow_total_ms or 0,
    )
    # SLO targets likewise: configured before the engine builds so every
    # EngineMetrics (including the post-warmup resets) classifies against
    # the deployment's targets.  ALWAYS called — None clears any previous
    # app build's override back to env/default, so two deployments in one
    # process cannot leak targets into each other (runtime/metrics.py).
    from ..runtime.metrics import configure_slo

    configure_slo(ttft_ms=cfg.slo_ttft_ms, tpot_ms=cfg.slo_tpot_ms)
    if llm_provider is None:
        llm_provider = build_tpu_provider(cfg, boot)
    if db is None:
        # remote (PostgREST/Supabase) when KAFKA_TPU_REMOTE_DB_URL is set
        db = make_db_client(cfg.db_path)
    await db.initialize()
    if tools is None:
        try:
            tools = default_builtin_tools(cfg)
        except Exception as e:  # server_tools are optional at boot
            logger.warning("builtin tools unavailable: %s", e)
            tools = []
    if mcp_servers is None:
        # reference server_tools/mcp_servers.py:8-13; override with
        # KAFKA_TPU_MCP_SERVERS (JSON list, '[]' disables). Unreachable
        # servers are skipped with a warning at connect time.
        from ..server_tools.mcp_servers import default_mcp_servers

        mcp_servers = default_mcp_servers()

    kafka = KafkaV1Provider(
        llm_provider,
        thread_db=db,
        tools=tools,
        mcp_servers=mcp_servers,
        default_model=cfg.model_name,
        system_prompt=cfg.system_prompt,
    )
    await kafka.initialize()

    app = web.Application(middlewares=[
        cors_middleware(cfg.cors_origins),
        tracing_middleware(),
        auth_middleware(cfg.api_token),
    ])
    state = {
        "cfg": cfg,
        "db": db,
        "llm": llm_provider,
        "tools": tools,
        "mcp_servers": list(mcp_servers or []),
        "kafka": kafka,
        "draining": False,
        "autoscaler": None,
        # /metrics `boot` and `metrics`: the boot by stage, and what
        # building the replies themselves has cost
        "boot": boot,
        "metrics_cost": {"snapshot_s": 0.0, "snapshots": 0},
        # the engine thread's account at the edges of the newest
        # /debug/profile capture, mark by mark as they are taken
        "sched_window": {},
    }
    app[STATE_KEY] = state
    # Autoscaler control loop (ISSUE 13, README "Autoscaler"): built only
    # when KAFKA_TPU_AUTOSCALE asks for it AND the provider emits the
    # signals contract — the off default constructs NOTHING, so every
    # serving path stays byte-identical to a controller-less build.  The
    # thread starts on the running loop (on_startup) because act-mode
    # resizes schedule provider.resize_dp onto it.
    from ..runtime.autoscaler import MODE_OFF, parse_mode

    if (parse_mode(cfg.autoscale) != MODE_OFF
            and getattr(llm_provider, "signals", None) is not None):
        from ..runtime.autoscaler import (
            AutoscalerConfig,
            AutoscalerController,
        )

        scaler = AutoscalerController(
            llm_provider,
            AutoscalerConfig.from_env(mode=parse_mode(cfg.autoscale)),
            is_draining=lambda: bool(state.get("draining")),
        )
        state["autoscaler"] = scaler

        async def _start_autoscaler(app: web.Application) -> None:
            import asyncio as _asyncio

            scaler.start(loop=_asyncio.get_running_loop())

        app.on_startup.append(_start_autoscaler)
    _add_routes(app)
    app.on_shutdown.append(_drain_on_shutdown)
    app.on_cleanup.append(_cleanup)
    boot.stop()
    return app


async def _drain_on_shutdown(app: web.Application) -> None:
    """Graceful drain: stop admitting, let in-flight streams finish.

    Runs while connections are still open (aiohttp on_shutdown).  /health
    flips to 503 "draining" so load balancers pull the instance, the
    admission gate rejects new serving requests with 503, and the engine
    gets ServingConfig.drain_timeout_s to finish what it holds before the
    leftovers are cancelled (each still receives its terminal event).
    """
    state = app[STATE_KEY]
    if state.get("draining"):
        return
    state["draining"] = True
    drain = getattr(state["llm"], "drain", None)
    if drain is None:
        return
    timeout = state["cfg"].drain_timeout_s
    logger.info("draining: waiting up to %.1fs for in-flight requests",
                timeout)
    clean = await drain(timeout)
    logger.info("drain %s", "complete" if clean else "timed out (cancelled "
                "remaining requests)")


async def _cleanup(app: web.Application) -> None:
    state = app[STATE_KEY]
    scaler = state.get("autoscaler")
    if scaler is not None:
        # before the provider closes: a poll racing teardown would read
        # a dying engine, and stop() also climbs any applied ladder
        # rungs.  In an executor because stop() joins a thread that may
        # be blocked on a resize_dp coroutine scheduled onto THIS loop —
        # joining inline would deadlock the loop against its own resize
        import asyncio as _asyncio

        await _asyncio.get_running_loop().run_in_executor(
            None, scaler.stop
        )
    await state["kafka"].cleanup()
    await state["db"].close()
    await state["llm"].aclose()


def cors_middleware(origins: str):
    @web.middleware
    async def mw(request: web.Request, handler):
        if request.method == "OPTIONS":
            resp: web.StreamResponse = web.Response(status=204)
        else:
            try:
                resp = await handler(request)
            except web.HTTPException as e:
                # error responses need CORS headers too, or browsers hide
                # the 400/404 body behind a CORS failure
                resp = e
        resp.headers["Access-Control-Allow-Origin"] = origins
        resp.headers["Access-Control-Allow-Methods"] = "GET,POST,PUT,DELETE,OPTIONS"
        resp.headers["Access-Control-Allow-Headers"] = "Content-Type,Authorization"
        if isinstance(resp, web.HTTPException):
            raise resp
        return resp

    return mw


# paths that never start a trace: health probes and the observability
# surface itself (incl. the autoscaler's ~1 Hz signal scrape) would
# otherwise churn the ring with noise
_TRACE_SKIP = ("/health", "/metrics", "/playground", "/debug",
               "/admin/signals", "/admin/autoscaler")


def _incoming_trace(request: web.Request):
    """Adopt an incoming trace identity: X-Request-Id (the id verbatim) or
    a W3C traceparent (00-<32hex trace>-<16hex span>-<flags> — the trace id
    is adopted and the caller's span becomes the root's parent)."""
    rid = request.headers.get("X-Request-Id", "").strip()
    if rid:
        return rid[:128], None
    tp = request.headers.get("traceparent", "").strip()
    parts = tp.split("-")
    if len(parts) == 4 and len(parts[1]) == 32 and len(parts[2]) == 16:
        return parts[1], parts[2]
    return None, None


def tracing_middleware():
    """Root-span middleware: every serving request gets (or adopts) a
    trace id; the whole handler — auth, agent loop, SSE stream — runs
    inside the http.request span.  Sampled-out requests pass through
    untouched (tracing.start_trace returns None)."""

    @web.middleware
    async def mw(request: web.Request, handler):
        if request.method == "OPTIONS" or request.path.startswith(
            _TRACE_SKIP
        ):
            return await handler(request)
        trace_id, parent_id = _incoming_trace(request)
        root = tracing.start_trace(
            request_id=trace_id,
            trace_id=trace_id,
            parent_id=parent_id,
            name="http.request",
            attrs={"method": request.method, "path": request.path},
        )
        if root is None:
            return await handler(request)
        ctx = tracing.current()
        status = None
        try:
            resp = await handler(request)
            status = resp.status
            if not resp.prepared and ctx is not None:
                # streamed responses are already on the wire; buffered
                # ones tell the client which id to ask /debug/trace for
                resp.headers["X-Request-Id"] = ctx.trace_id
            return resp
        except web.HTTPException as e:
            status = e.status
            raise
        finally:
            tracing.finish_trace(root, status=status)

    return mw


def auth_middleware(api_token: Optional[str]):
    """Two bearer tiers: the static machine token (ServingConfig.api_token)
    and per-user SESSION tokens from /v1/auth/login (`sess_…`, stored in
    the DB tier — db/base.py user-store contract; reference: Supabase
    email sessions, playground/src/components/auth-provider.tsx:19-40).

    A valid session resolves request["user_id"] (thread ownership scoping)
    and also satisfies the api_token gate — humans log in, machines carry
    the static token.  An invalid/expired session 401s even on an
    otherwise-open server: a client that presents credentials must not be
    silently downgraded to anonymous.  /health, /playground and /v1/auth/
    login stay open; SIGNUP runs under the api_token gate when one is
    configured (an open signup would mint sessions that bypass the static
    token — accounts on a closed instance are operator-provisioned, the
    invite model).  No api_token configured = anonymous access allowed,
    the reference's local-dev default.
    """
    open_paths = ("/health", "/playground", "/v1/auth/login")

    @web.middleware
    async def mw(request: web.Request, handler):
        if request.path in open_paths:
            return await handler(request)
        supplied = request.headers.get("Authorization", "")
        if supplied.startswith("Bearer sess_"):
            token = supplied[len("Bearer "):]
            try:
                user_id = await _state(request)["db"].get_session_user(token)
            except NotImplementedError:
                user_id = None
            if user_id is None:
                return web.json_response(
                    {"error": {"message": "invalid or expired session",
                               "type": "authentication_error"}},
                    status=401,
                )
            request["user_id"] = user_id
            return await handler(request)
        if api_token:
            # compare as bytes: compare_digest raises TypeError on non-ASCII
            # str inputs, which would turn a malformed credential into a 500
            if not hmac.compare_digest(
                supplied.encode("utf-8", "surrogateescape"),
                f"Bearer {api_token}".encode(),
            ):
                return web.json_response(
                    {"error": {"message": "invalid or missing bearer token",
                               "type": "authentication_error"}},
                    status=401,
                )
        return await handler(request)

    return mw


def _add_routes(app: web.Application) -> None:
    r = app.router
    r.add_post("/v1/chat/completions", chat_completions)
    r.add_post("/v1/threads/{thread_id}/chat/completions", thread_chat_completions)
    r.add_post("/v1/agent/run", agent_run)
    r.add_post("/v1/threads/{thread_id}/agent/run", thread_agent_run)
    r.add_post("/v1/threads", create_thread)
    r.add_get("/v1/threads", list_threads)
    r.add_get("/v1/threads/{thread_id}", get_thread)
    r.add_get("/v1/threads/{thread_id}/messages", get_thread_messages)
    r.add_delete("/v1/threads/{thread_id}", delete_thread)
    r.add_delete("/v1/threads/{thread_id}/messages", delete_thread_messages)
    r.add_put("/v1/threads/{thread_id}/config", set_thread_config)
    r.add_get("/v1/profiles", list_profiles)
    r.add_post("/v1/profiles", create_profile)
    r.add_get("/v1/models", list_models)
    r.add_post("/v1/auth/signup", auth_signup)
    r.add_post("/v1/auth/login", auth_login)
    r.add_get("/health", health)
    r.add_get("/metrics", metrics)
    r.add_get("/admin/signals", admin_signals)
    r.add_get("/admin/autoscaler", admin_autoscaler)
    r.add_post("/admin/resize", resize_topology)
    r.add_post("/admin/drain/{replica}", admin_drain_replica)
    r.add_post("/debug/profile", capture_profile)
    r.add_get("/debug/traces", debug_traces)
    r.add_get("/debug/trace/{request_id}", debug_trace)
    r.add_get("/debug/flight/{replica}", debug_flight)
    r.add_get("/debug/compiles", debug_compiles)
    r.add_get("/playground", playground)
    # OPTIONS preflight is answered by cors_middleware before routing


def _state(request: web.Request) -> dict:
    return request.app[STATE_KEY]


async def _parse(request: web.Request, model_cls):
    try:
        return model_cls.model_validate(await request.json())
    except ValidationError as e:
        raise web.HTTPBadRequest(
            text=e.json(), content_type="application/json"
        )
    except Exception:
        raise web.HTTPBadRequest(text='{"error": "invalid JSON body"}',
                                 content_type="application/json")


def _admission_gate(request: web.Request) -> None:
    """Reject serving requests when draining or when the engine's waiting
    queue is full (HTTP 503 / 429 + Retry-After).  Thread CRUD and health
    stay open — only endpoints that would submit engine work are gated."""
    state = _state(request)
    if state.get("draining"):
        raise web.HTTPServiceUnavailable(
            text=json.dumps({"error": {
                "message": "server is draining for shutdown",
                "type": "server_draining",
            }}),
            content_type="application/json",
            headers={"Retry-After": str(int(
                state["cfg"].drain_timeout_s
                if hasattr(state["cfg"], "drain_timeout_s") else 30
            ))},
        )
    check = getattr(state["llm"], "admission_check", None)
    if check is None:
        return
    retry_after = check()
    if retry_after is None:
        return
    record = getattr(state["llm"], "record_rejection", None)
    if record is not None:
        record()
    raise web.HTTPTooManyRequests(
        text=json.dumps({"error": {
            "message": "request queue is full; retry later "
                       "(server_overloaded)",
            "type": "server_overloaded",
        }}),
        content_type="application/json",
        headers={"Retry-After": str(max(1, int(retry_after)))},
    )


# ---------------------------------------------------------------------------
# event-stream plumbing shared by the four serving endpoints
# ---------------------------------------------------------------------------


async def _agent_events(
    request: web.Request,
    req_body,
    thread_id: Optional[str],
) -> AsyncIterator[Dict[str, Any]]:
    """Run the right kafka flavor; yield protocol events + tool_messages."""
    state = _state(request)
    sampling = dict(
        temperature=req_body.temperature if req_body.temperature is not None else 0.7,
        max_tokens=req_body.max_tokens,
    )
    if getattr(req_body, "tool_choice", None) is not None:
        sampling["tool_choice"] = req_body.tool_choice
    messages = [m.model_dump(exclude_none=True) for m in req_body.messages]
    model = req_body.model or state["cfg"].model_name
    acc = MessageAccumulator()

    if thread_id is None:
        kafka = state["kafka"]
        stream = kafka.run(messages, model=model, **sampling)
    else:
        # per-thread provider: thread config (global_prompt/playbooks/model)
        # is fetched at initialize (reference server.py:237-245)
        kafka = KafkaV1Provider(
            state["llm"],
            thread_db=state["db"],
            tools=state["tools"],
            mcp_servers=state["mcp_servers"],
            thread_id=thread_id,
            default_model=model,
            system_prompt=state["cfg"].system_prompt,
        )
        await kafka.initialize()
        stream = kafka.run_with_thread(thread_id, messages, **sampling)

    # tool_messages batching (reference server.py:330-335, adapted): the
    # CUMULATIVE tool-cycle history is re-batched before each new
    # completion's chunks (and before agent_done) whenever it has grown —
    # cumulative because the playground contract client REPLACES all its
    # tool/tool-call messages with each batch (page.tsx:195-215), so a
    # per-cycle batch would wipe earlier cycles from the transcript.
    # Plain assistant text is never batched — it streams live (our
    # improvement over the reference's re-streaming) and batching it would
    # duplicate it client-side.  All covered by tests/test_sse_contract.py.
    last_batched = None

    def _cumulative_batch():
        return [
            m.to_dict() for m in acc.messages
            if m.role == "tool" or m.tool_calls
        ]

    def _maybe_batch():
        # Re-emit whenever the canonical batch CONTENT changed, not just its
        # count — server-side sanitization can rewrite a message in place
        # (e.g. truncation differing from the streamed deltas), and the
        # client must end up holding the durable canonical form.
        nonlocal last_batched
        batch = _cumulative_batch()
        # constant-size digest (a Python hash() collision after an in-place
        # rewrite would silently skip the corrected canonical batch; the
        # raw JSON string would pin the whole batch in memory per stream)
        fingerprint = hashlib.sha256(
            json.dumps(batch, sort_keys=True, default=str).encode()
        ).hexdigest()
        if batch and fingerprint != last_batched:
            last_batched = fingerprint
            return {"type": "tool_messages", "messages": batch}
        return None

    last_cid = None
    try:
        async for event in stream:
            if event.get("object") == "chat.completion.chunk":
                # the batch can only grow between completions: check on the
                # first chunk of each new completion, not per token
                cid = event.get("id")
                if cid != last_cid:
                    last_cid = cid
                    batch_ev = _maybe_batch()
                    if batch_ev:
                        yield batch_ev
            acc.add_event(event)
            if event.get("type") == "agent_done":
                batch_ev = _maybe_batch()
                if batch_ev:
                    yield batch_ev
            yield event
    finally:
        if thread_id is not None:
            await kafka.cleanup()


async def _collect_completion(
    events: AsyncIterator[Dict[str, Any]], model: str
) -> Dict[str, Any]:
    """Drain an event stream into a non-streaming chat completion."""
    acc = MessageAccumulator()
    usage = Usage()
    async for event in events:
        acc.add_event(event)
        if event.get("object") == "chat.completion.chunk" and event.get("usage"):
            u = event["usage"]
            usage.prompt_tokens += u.get("prompt_tokens", 0)
            usage.completion_tokens += u.get("completion_tokens", 0)
            usage.total_tokens += u.get("total_tokens", 0)
            usage.cached_prompt_tokens += (
                u.get("prompt_tokens_details") or {}
            ).get("cached_tokens", 0)
    final = acc.final_content
    return {
        "id": new_completion_id(),
        "object": "chat.completion",
        "created": 0,
        "model": model,
        "choices": [
            {
                "index": 0,
                "message": {"role": "assistant", "content": final},
                "finish_reason": "stop",
            }
        ],
        "usage": usage.to_dict(),
    }


# ---------------------------------------------------------------------------
# serving endpoints
# ---------------------------------------------------------------------------


async def _completion_response(events, model: str) -> web.Response:
    """Non-streaming completion with OpenAI-style structured errors."""
    try:
        return web.json_response(await _collect_completion(events, model))
    except ServerOverloadedError as e:
        # engine-thread admission backstop: same 429 contract as the gate
        # (type server_overloaded + Retry-After), not a generic 4xx
        return web.json_response(
            {"error": {"message": str(e), "type": "server_overloaded"}},
            status=429,
            headers={"Retry-After": str(max(1, int(e.retry_after_s)))},
        )
    except LLMProviderError as e:
        status = e.status_code or 500
        return web.json_response(
            {
                "error": {
                    "message": str(e),
                    "type": "invalid_request_error"
                    if status < 500 else "server_error",
                    "code": "context_length_exceeded"
                    if isinstance(e, ContextLengthError) else None,
                }
            },
            status=status,
        )


async def chat_completions(request: web.Request) -> web.StreamResponse:
    _admission_gate(request)
    body = await _parse(request, ChatCompletionRequest)
    events = _agent_events(request, body, thread_id=None)
    if body.stream:
        return await sse_response(request, events)
    return await _completion_response(events, body.model)


async def thread_chat_completions(request: web.Request) -> web.StreamResponse:
    _admission_gate(request)
    thread_id = request.match_info["thread_id"]
    await _check_thread_owner(request, thread_id, create=True)
    body = await _parse(request, ChatCompletionRequest)
    events = _agent_events(request, body, thread_id=thread_id)
    if body.stream:
        return await sse_response(request, events)
    return await _completion_response(events, body.model)


async def agent_run(request: web.Request) -> web.StreamResponse:
    _admission_gate(request)
    body = await _parse(request, AgentRunRequest)
    return await sse_response(
        request, _agent_events(request, body, thread_id=None)
    )


async def thread_agent_run(request: web.Request) -> web.StreamResponse:
    _admission_gate(request)
    thread_id = request.match_info["thread_id"]
    await _check_thread_owner(request, thread_id, create=True)
    body = await _parse(request, AgentRunRequest)
    return await sse_response(
        request, _agent_events(request, body, thread_id=thread_id)
    )


# ---------------------------------------------------------------------------
# thread CRUD
# ---------------------------------------------------------------------------


async def create_thread(request: web.Request) -> web.Response:
    db = _state(request)["db"]
    body = {}
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            body = {}
    # profile inheritance (reference: threads join kafka_profiles for
    # global_prompt/model config, supabase.py:458-541): a thread created
    # with profile_id copies that profile's config as its own.  Validated
    # BEFORE creating the thread — a 400 must not leave an orphan row.
    pid = body.get("profile_id")
    profile = None
    if pid:
        get_profile = getattr(db, "get_profile", None)
        profile = await get_profile(pid) if get_profile else None
        if profile is None:
            raise web.HTTPBadRequest(
                text=json.dumps({"error": f"unknown profile {pid!r}"}),
                content_type="application/json",
            )
    tid = await db.create_thread(
        thread_id=body.get("thread_id"), metadata=body.get("metadata")
    )
    if request.get("user_id") is not None:
        try:
            await db.set_thread_owner(tid, request["user_id"])
        except NotImplementedError:
            pass
    if profile is not None:
        await db.set_thread_config(
            tid, {**profile["config"], "profile_id": pid}
        )
    meta = await db.get_thread_metadata(tid)
    return web.json_response(meta, status=201)


async def list_profiles(request: web.Request) -> web.Response:
    db = _state(request)["db"]
    fn = getattr(db, "list_profiles", None)
    if fn is None:
        raise web.HTTPNotImplemented(
            text='{"error": "profiles unsupported by this DB backend"}',
            content_type="application/json",
        )
    return web.json_response({"profiles": await fn()})


async def create_profile(request: web.Request) -> web.Response:
    db = _state(request)["db"]
    fn = getattr(db, "create_profile", None)
    if fn is None:
        raise web.HTTPNotImplemented(
            text='{"error": "profiles unsupported by this DB backend"}',
            content_type="application/json",
        )
    try:
        body = await request.json()
    except Exception:
        raise web.HTTPBadRequest(
            text='{"error": "invalid JSON body"}',
            content_type="application/json",
        )
    name = body.get("name")
    if not name:
        raise web.HTTPBadRequest(
            text='{"error": "profile name required"}',
            content_type="application/json",
        )
    profile = await fn(name, config=body.get("config") or {})
    return web.json_response(profile, status=201)


async def list_threads(request: web.Request) -> web.Response:
    db = _state(request)["db"]
    user = request.get("user_id")
    if user is not None:
        # per-user sidebar scope (reference: sidebar.tsx:40-80 filters by
        # the Supabase session user)
        return web.json_response(
            {"threads": await db.list_threads_for_user(user)}
        )
    try:
        # anonymous requests see only unowned threads
        threads = await db.list_threads_unowned()
    except NotImplementedError:  # backend without a user store: all open
        threads = await db.list_threads()
    return web.json_response({"threads": threads})


async def _check_thread_owner(request: web.Request, tid: str,
                              create: bool = False) -> None:
    """Enforce/establish thread ownership for session users.

    Another user's thread answers 404 (existence is not leaked — the
    reference's per-user Supabase listing has the same property).  A
    session user touching an unowned-or-new thread claims it; anonymous
    requests see only unowned threads.  DB clients without a user store
    skip enforcement entirely (the pre-auth behavior).
    """
    db = _state(request)["db"]
    user = request.get("user_id")
    try:
        owner = await db.get_thread_owner(tid)
    except NotImplementedError:
        return
    if owner is not None and owner != user:
        raise web.HTTPNotFound(
            text=f'{{"error": "thread {tid} not found"}}',
            content_type="application/json",
        )
    # claiming happens only on WRITE paths (create=True: chat/agent run) —
    # a mere GET of an unowned thread must not transfer its ownership away
    # from the anonymous client that created it
    if create and user is not None and owner is None:
        if not await db.thread_exists(tid):
            await db.create_thread(tid)
        await db.set_thread_owner(tid, user)


async def _require_thread(request: web.Request) -> str:
    db = _state(request)["db"]
    tid = request.match_info["thread_id"]
    if not await db.thread_exists(tid):
        raise web.HTTPNotFound(
            text=f'{{"error": "thread {tid} not found"}}',
            content_type="application/json",
        )
    await _check_thread_owner(request, tid)
    return tid


async def get_thread(request: web.Request) -> web.Response:
    tid = await _require_thread(request)
    return web.json_response(await _state(request)["db"].get_thread_metadata(tid))


async def get_thread_messages(request: web.Request) -> web.Response:
    tid = await _require_thread(request)
    msgs = await _state(request)["db"].get_thread_messages(tid)
    return web.json_response({"thread_id": tid, "messages": msgs})


async def delete_thread(request: web.Request) -> web.Response:
    tid = await _require_thread(request)
    await _state(request)["db"].delete_thread(tid)
    return web.json_response({"deleted": tid})


async def delete_thread_messages(request: web.Request) -> web.Response:
    tid = await _require_thread(request)
    await _state(request)["db"].delete_thread_messages(tid)
    return web.json_response({"cleared": tid})


async def set_thread_config(request: web.Request) -> web.Response:
    tid = await _require_thread(request)
    db = _state(request)["db"]
    cfg = await request.json()
    await db.set_thread_config(tid, cfg)
    return web.json_response({"thread_id": tid, "config": cfg})


# ---------------------------------------------------------------------------
# models / health
# ---------------------------------------------------------------------------


async def _session_response(db, user_id: str, email: str) -> web.Response:
    from .auth import new_session_token, session_expiry

    token = new_session_token()
    await db.create_session(user_id, token, session_expiry())
    return web.json_response(
        {"token": token, "user_id": user_id, "email": email}
    )


async def _auth_body(request: web.Request) -> tuple:
    try:
        body = await request.json()
        assert isinstance(body, dict)
    except Exception:
        raise web.HTTPBadRequest(
            text='{"error": "invalid JSON body"}',
            content_type="application/json",
        )
    return ((body.get("email") or "").strip().lower(),
            body.get("password") or "")


async def auth_signup(request: web.Request) -> web.Response:
    """Create a user + open a session (reference: Supabase email signup)."""
    import asyncio as _asyncio

    from .auth import hash_password, new_salt

    db = _state(request)["db"]
    email, password = await _auth_body(request)
    if "@" not in email or len(password) < 6:
        raise web.HTTPBadRequest(
            text='{"error": "need a valid email and a password of 6+ chars"}',
            content_type="application/json",
        )
    salt = new_salt()
    # scrypt is ~50ms of CPU: off the event loop, or every in-flight SSE
    # stream hiccups for the duration
    pw_hash = await _asyncio.to_thread(hash_password, password, salt)
    try:
        user_id = await db.create_user(email, pw_hash, salt)
    except ValueError:
        return web.json_response(
            {"error": {"message": "email already registered",
                       "type": "invalid_request_error"}},
            status=409,
        )
    except NotImplementedError:
        raise web.HTTPNotImplemented(
            text='{"error": "this DB backend has no user store"}',
            content_type="application/json",
        )
    return await _session_response(db, user_id, email)


async def auth_login(request: web.Request) -> web.Response:
    import asyncio as _asyncio

    from .auth import verify_password

    db = _state(request)["db"]
    email, password = await _auth_body(request)
    try:
        user = await db.get_user_by_email(email)
    except NotImplementedError:
        raise web.HTTPNotImplemented(
            text='{"error": "this DB backend has no user store"}',
            content_type="application/json",
        )
    if user is None or not await _asyncio.to_thread(
        verify_password, password, user["salt"], user["password_hash"]
    ):
        return web.json_response(
            {"error": {"message": "invalid email or password",
                       "type": "authentication_error"}},
            status=401,
        )
    return await _session_response(db, user["user_id"], user["email"])


async def list_models(request: web.Request) -> web.Response:
    llm = _state(request)["llm"]
    return web.json_response(
        {"object": "list", "data": llm.get_available_models()}
    )


async def health(request: web.Request) -> web.Response:
    state = _state(request)
    llm = state["llm"]
    draining = bool(state.get("draining"))
    payload: Dict[str, Any] = {
        # "draining" + 503 pulls the instance from load-balancer rotation
        # while in-flight streams finish (graceful-drain contract)
        "status": "draining" if draining else "ok",
        "kafka_initialized": state["kafka"]._initialized,
    }
    plan = getattr(llm, "memory_plan", None)  # set by build_tpu_provider
    if plan is not None:
        payload["memory_plan"] = plan.summary()
    engine = getattr(llm, "engine", None)
    if engine is not None:
        # DataParallelEngines exposes .engines; a single engine is its own
        # one-element "replica set" so the page math below is uniform
        replicas = getattr(engine, "engines", [engine])
        payload["engine"] = {
            "active": engine.num_active,
            "waiting": len(engine.waiting),
            "free_pages": sum(e.pool.free_pages for e in replicas),
            "total_pages": sum(e.pool.num_pages for e in replicas),
        }
        if len(replicas) > 1:
            payload["engine"]["dp"] = len(replicas)
        info = getattr(replicas[0], "device_info", None)
        if info is not None:
            # what is actually being served, and on what: resolved once at
            # engine construction (runtime/engine.py device_info), so a
            # client never has to infer the device from logs
            import jax

            mc = replicas[0].cfg
            payload["device"] = {
                **info,
                # devices the engines occupy vs devices jax can see
                "count": sum(e.device_info["count"] for e in replicas),
                "visible": jax.device_count(),
                "model": {
                    "name": mc.name,
                    "num_layers": mc.num_layers,
                    "hidden_size": mc.hidden_size,
                    "num_heads": mc.num_heads,
                    "num_kv_heads": mc.num_kv_heads,
                    "head_dim": mc.head_dim,
                    "kv_row_widths": list(mc.kv_row_widths()),
                    "intermediate_size": mc.intermediate_size,
                    "vocab_size": mc.vocab_size,
                    "dtype": mc.dtype,
                },
            }
        health_records = getattr(engine, "health", None)
        if health_records:
            # replica supervision at a glance: a load balancer (or a
            # human) sees which replicas are quarantined without parsing
            # the full /metrics snapshot
            payload["engine"]["replicas"] = [h.state for h in health_records]
    return web.json_response(payload, status=503 if draining else 200)


async def metrics(request: web.Request) -> web.Response:
    """Serving counters (SURVEY §5.1/5.5): TTFT/TPOT percentiles, token
    throughput, batch occupancy, pages in use, prefix-cache reuse.  These
    are the numbers bench.py reports — one source of truth."""
    state = _state(request)
    engine = getattr(state["llm"], "engine", None)
    if engine is None:
        return web.json_response({"error": "no local engine"}, status=404)
    # The reply is built and serialised on the event loop's thread with the
    # GIL held, beside an engine thread that needs it: what that has cost
    # so far is in the reply (`metrics`), and under profiling the build is
    # a span of its own.
    cost = state["metrics_cost"]
    t0 = time.monotonic()
    with _snapshot_scope():
        response = _metrics_response(request, engine, cost)
    cost["snapshot_s"] += time.monotonic() - t0
    cost["snapshots"] += 1
    return response


def _snapshot_scope():
    if not tracing.profiler_annotations_enabled():
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("kafka.metrics.snapshot")


def _metrics_response(request: web.Request, engine,
                      cost: Dict[str, Any]) -> web.Response:
    from ..runtime import program_store

    snap = engine.metrics.snapshot(engine)
    # the boot by stage (tracing.BOOT_STAGES) with the seconds the program
    # store spent loading step programs, and the seconds and count of the
    # replies built before this one
    snap["boot"] = {**_state(request)["boot"].section(),
                    "store_load_s": round(program_store.load_seconds(), 3)}
    snap["metrics"] = {"snapshot_s": round(cost["snapshot_s"], 6),
                       "snapshots": cost["snapshots"]}
    edges = _state(request)["sched_window"]
    if edges and all(edges.values()):
        # the newest /debug/profile capture's edges, as far as they have
        # been taken (its reply carries all three)
        snap["sched_window"] = dict(edges)
    # sandbox subprocess supervision counters (crashes, supervised
    # restarts, crash loops, reaped zombie handles) — module-aggregated
    # across factories, same one-source-of-truth rule as the engine
    # counters
    from ..sandbox.process import supervisor_snapshot

    snap["sandbox"] = supervisor_snapshot()
    # tracing counters + the slow-request counter (requests over the
    # configured TTFT/total thresholds) join the same snapshot
    snap["tracing"] = tracing.counters()
    if isinstance(snap.get("requests"), dict):
        snap["requests"]["slow"] = tracing.slow_count()
    # autoscaler control-loop counters (AUTOSCALER_METRIC_KEYS): one
    # controller per process, merged here like the sandbox/tracing
    # sections (absent when KAFKA_TPU_AUTOSCALE is off)
    scaler = _state(request).get("autoscaler")
    if scaler is not None:
        snap["autoscaler"] = scaler.metrics_section()
    # compile observatory counters (COMPILE_METRIC_KEYS): process-wide
    # like the sandbox/autoscaler sections — XLA compiles are per-process
    # events, not per-replica (absent when KAFKA_TPU_COMPILE_RING=0)
    from ..runtime import compile_log

    obs = compile_log.get()
    if obs is not None:
        snap["compiles"] = obs.metrics_section()
    if request.query.get("format") == "prometheus":
        from .prometheus import render_prometheus

        return web.Response(
            text=render_prometheus(snap),
            headers={"Content-Type":
                     "text/plain; version=0.0.4; charset=utf-8"},
        )
    return web.json_response(snap)


async def admin_signals(request: web.Request) -> web.Response:
    """The autoscaler signal feed (ISSUE 10): one coherent JSON snapshot
    of queue depth + trend, batch occupancy, SLO window attainment,
    goodput, and per-replica utilization + quarantine state.

    This endpoint is the documented INPUT CONTRACT for the coming
    /admin/resize control loop (README "SLO telemetry"): a scaler reads
    it at ~1 Hz and decides dp from attainment_1m, queue trend, and
    per-kind MFU/HBM headroom.  Read-only — unlike /admin/resize it
    works without a configured API token (same policy as /metrics), and
    honors the bearer gate when one is set."""
    state = _state(request)
    llm = state["llm"]
    signals = getattr(llm, "signals", None)
    if signals is None or getattr(llm, "engine", None) is None:
        return web.json_response(
            {"error": "no local engine (this deployment emits no "
                      "autoscaler signals)"},
            status=404,
        )
    payload = signals()
    # serving-state bits only the app layer knows
    payload["draining"] = bool(state.get("draining"))
    payload["admission"] = {
        "max_queue_depth": state["cfg"].max_queue_depth,
    }
    return web.json_response(payload)


async def admin_autoscaler(request: web.Request) -> web.Response:
    """The autoscaler control loop's bounded decision log + live state
    (ISSUE 13, README "Autoscaler"): mode, config, degradation-ladder
    rung, cooldowns, and every recorded decision (cause, condensed
    inputs snapshot, action, vetoes, outcome; consecutive identical
    holds collapse into one counted entry).  Read-only — same token
    policy as /admin/signals (works without a configured token, honors
    the bearer gate when one is set).  404 when KAFKA_TPU_AUTOSCALE is
    off: no controller runs, so there is nothing to report."""
    scaler = _state(request).get("autoscaler")
    if scaler is None:
        return web.json_response(
            {"error": "autoscaler not running (KAFKA_TPU_AUTOSCALE is "
                      "off, or this deployment emits no signals)"},
            status=404,
        )
    return web.json_response(scaler.snapshot())


async def resize_topology(request: web.Request) -> web.Response:
    """Rebuild the DP replica set at a new dp count (replica loss or
    scale-down) while queued requests survive: body {"dp": N, optional
    "drain_timeout_s": S, optional "roles": "prefill:P,decode:D"}.
    Started requests get the drain budget to finish; leftovers are
    cancelled with terminal events (reported as "clean": false).  When
    "roles" is present it re-shapes the prefill/decode pools in the same
    rebuild (validated by the parse_dp_roles rules, P + D == dp; "" or
    null dissolves the pools back to colocated); absent keeps the
    current spec re-derived for the new dp.  Unlike serving endpoints,
    this one is operator-destructive (it cancels whatever cannot
    drain), so the open-if-no-token dev default does NOT apply: without
    a configured KAFKA_TPU_API_TOKEN the endpoint refuses outright."""
    if not _state(request)["cfg"].api_token:
        return web.json_response(
            {"error": "admin endpoints require KAFKA_TPU_API_TOKEN to "
                      "be configured"},
            status=403,
        )
    llm = _state(request)["llm"]
    resize = getattr(llm, "resize_dp", None)
    if resize is None or not hasattr(
        getattr(llm, "engine", None), "rebuild"
    ):
        return web.json_response(
            {"error": "this deployment has no resizable DP topology"},
            status=501,
        )
    try:
        body = await request.json()
        dp = int(body["dp"])
        drain_timeout_s = float(
            body.get("drain_timeout_s",
                     _state(request)["cfg"].drain_timeout_s)
        )
        roles_given = "roles" in body
        roles = body.get("roles")
        if roles_given and roles is not None and not isinstance(roles, str):
            raise TypeError("roles must be a string or null")
    except Exception:
        return web.json_response(
            {"error": 'body must be {"dp": N[, "drain_timeout_s": S]'
                      '[, "roles": "prefill:P,decode:D"|null]}'},
            status=400,
        )
    if dp < 1:
        return web.json_response({"error": "dp must be >= 1"}, status=400)
    kwargs = {"drain_timeout_s": drain_timeout_s}
    if roles_given:
        kwargs["roles"] = roles
    try:
        # rebuild compiles are phased by the provider (_resize_locked
        # sets the observatory to "rebuild" so they don't read as a
        # compile storm) — act-mode autoscaler resizes share that path
        clean = await resize(dp, **kwargs)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    except RuntimeError as e:
        return web.json_response({"error": str(e)}, status=409)
    out = {"dp": dp, "clean": clean}
    if roles_given:
        out["roles"] = roles or None
    return web.json_response(out)


async def admin_drain_replica(request: web.Request) -> web.Response:
    """Flush one replica's warm KV state into the shared object store
    (ISSUE 14): every cached radix run is archived content-addressed and
    every thread's sleep manifest written, so the replica can be removed
    (POST /admin/resize to a smaller dp — "drain-then-shrink", which the
    act-mode autoscaler performs automatically before its scale-ins)
    without discarding any warm conversation: dormant threads wake on
    the survivors with cache_source="object_tier" instead of
    re-prefilling.  Non-destructive — the replica keeps serving
    unchanged if it is kept after all.  Requires the object tier
    (KAFKA_TPU_KV_OBJECT_DIR) and, like /admin/resize, a configured
    KAFKA_TPU_API_TOKEN (it parks the scheduler for the flush)."""
    if not _state(request)["cfg"].api_token:
        return web.json_response(
            {"error": "admin endpoints require KAFKA_TPU_API_TOKEN to "
                      "be configured"},
            status=403,
        )
    llm = _state(request)["llm"]
    drain = getattr(llm, "drain_replica", None)
    if drain is None or getattr(llm, "engine", None) is None:
        return web.json_response(
            {"error": "this deployment has no drainable engine"},
            status=501,
        )
    try:
        idx = int(request.match_info["replica"])
    except ValueError:
        return web.json_response(
            {"error": "replica must be an integer index"}, status=400
        )
    try:
        stats = await drain(idx)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    except RuntimeError as e:
        return web.json_response({"error": str(e)}, status=409)
    if not stats.get("enabled", True):
        return web.json_response(
            {"error": "object tier not configured "
                      "(set KAFKA_TPU_KV_OBJECT_DIR)", **stats},
            status=409,
        )
    return web.json_response(stats)


async def debug_traces(request: web.Request) -> web.Response:
    """Recent-traces index (newest first): ids, durations, span names —
    enough to find the trace id to pull from /debug/trace/{request_id}."""
    return web.json_response({
        "traces": tracing.recent_traces(),
        "counters": tracing.counters(),
        "sample": tracing.sample_rate(),
    })


async def debug_trace(request: web.Request) -> web.Response:
    """One request's span tree as Chrome trace-event JSON — load the body
    in Perfetto (ui.perfetto.dev) or chrome://tracing.  Keyed by the trace
    id (== the X-Request-Id the request carried or was assigned)."""
    data = tracing.chrome_trace(request.match_info["request_id"])
    if data is None:
        raise web.HTTPNotFound(
            text=json.dumps({"error": "unknown trace (evicted from the "
                             "ring, or the request was sampled out)"}),
            content_type="application/json",
        )
    return web.json_response(data)


async def debug_flight(request: web.Request) -> web.Response:
    """One replica's live flight-recorder ring (ISSUE 11): the per-
    scheduler-iteration decision log, measured dispatch timing, and the
    anomaly detector state.  `scripts/flightview.py` pretty-prints the
    payload; postmortem dumps of the same shape land next to the
    persisted traces on engine failure/quarantine."""
    llm = _state(request)["llm"]
    engine = getattr(llm, "engine", None)
    if engine is None:
        return web.json_response({"error": "no local engine"}, status=404)
    replicas = getattr(engine, "engines", [engine])
    try:
        idx = int(request.match_info["replica"])
    except ValueError:
        return web.json_response(
            {"error": "replica must be an integer index"}, status=400
        )
    if not 0 <= idx < len(replicas):
        return web.json_response(
            {"error": f"replica {idx} out of range (dp={len(replicas)})"},
            status=404,
        )
    flight = getattr(replicas[idx], "flight", None)
    if flight is None:
        return web.json_response(
            {"error": "flight recorder disabled "
                      "(KAFKA_TPU_FLIGHT_RING=0)"},
            status=404,
        )
    payload = flight.snapshot()
    payload["replica"] = idx
    payload["dp"] = len(replicas)
    return web.json_response(payload)


async def debug_compiles(request: web.Request) -> web.Response:
    """The compile observatory's bounded ring (ISSUE 18): every XLA
    compilation this process performed — label, wall seconds, cache
    hit/miss/off, and the serving phase it happened in (boot / warmup /
    first_traffic / rebuild) — plus storm-detector state and running
    totals.  `scripts/flightview.py --compiles` pretty-prints the
    payload.  Read-only, same token policy as /metrics."""
    from ..runtime import compile_log

    obs = compile_log.get()
    if obs is None:
        return web.json_response(
            {"error": "compile observatory disabled "
                      "(KAFKA_TPU_COMPILE_RING=0)"},
            status=404,
        )
    snap = obs.snapshot()
    engine = getattr(_state(request).get("llm"), "engine", None)
    first = getattr(engine, "engines", [engine])[0]
    info = getattr(first, "device_info", None)
    if info is not None:
        # which model's programs these are: its layer pattern and window
        snap["model"] = {
            "name": first.cfg.name,
            "layers": first.cfg.num_layers,
            "layer_pattern": info.get("layer_pattern"),
            "sliding_window": info.get("sliding_window"),
            "attention_backend": info.get("attention_backend"),
        }
    return web.json_response(snap)


async def playground(request: web.Request) -> web.Response:
    """The in-tree chat client (reference: playground/src/, a Next.js app).

    One static file consuming the 4-event SSE protocol with the exact
    reconstruction rules of core/sse_client.py."""
    import os

    path = os.path.join(os.path.dirname(__file__), "playground.html")
    return web.FileResponse(path)


_PROFILE_BUSY = False
_PROFILE_DIR = "/tmp/kafka_tpu_trace"


def _profile_idle(stopping) -> None:
    """Done-callback of the executor future that runs stop_trace: the
    capture guard is released only once the profiler has stopped."""
    global _PROFILE_BUSY
    _PROFILE_BUSY = False
    if not stopping.cancelled() and stopping.exception() is not None:
        logger.error("/debug/profile: stop_trace failed: %r",
                     stopping.exception())


def _sched_mark(llm) -> Optional[Dict[str, Any]]:
    """The engine thread's account as it stands, with its wall time."""
    clock = getattr(getattr(llm, "engine", None), "sched", None)
    if clock is None:
        return None
    return {"t": time.time(), "sched": clock.section()}


def _flight_seqs(llm) -> Optional[List[Dict[str, Any]]]:
    """Per-replica flight-recorder sequence cursors (None = no engine or
    recorder off everywhere)."""
    engine = getattr(llm, "engine", None)
    if engine is None:
        return None
    out = []
    for i, e in enumerate(getattr(engine, "engines", [engine])):
        flight = getattr(e, "flight", None)
        if flight is not None:
            out.append({"replica": i, "seq": flight.next_seq})
    return out or None


async def capture_profile(request: web.Request) -> web.Response:
    """Capture a jax.profiler device trace (xplane) for offline analysis.

    Body: {"seconds": 2}.  The trace (written under /tmp/kafka_tpu_trace —
    server-chosen, not client-chosen) covers whatever the engine executes
    during the window — point a load at the server first.  Gated behind
    KAFKA_TPU_PROFILING=1 (trace files can contain workload detail); one
    capture at a time.

    When an API token is configured, this endpoint requires the MACHINE
    token specifically — a per-user session that satisfies the general
    bearer middleware does not qualify (ISSUE 11 satellite: profile
    captures expose workload detail and eat device time; they are an
    operator surface like /admin/resize, not a user one).

    The response includes the flight-recorder window covering the
    capture (per-replica [start_seq, end_seq) plus wall timestamps), so
    xplane slices correlate with the scheduler's per-iteration decision
    records at GET /debug/flight/{replica}."""
    import os
    import time as _time

    if os.environ.get("KAFKA_TPU_PROFILING", "0") not in ("1", "true"):
        return web.json_response(
            {"error": "profiling disabled (set KAFKA_TPU_PROFILING=1)"},
            status=403,
        )
    cfg = _state(request)["cfg"]
    if cfg.api_token:
        supplied = request.headers.get("Authorization", "")
        if not hmac.compare_digest(
            supplied.encode("utf-8", "surrogateescape"),
            f"Bearer {cfg.api_token}".encode(),
        ):
            return web.json_response(
                {"error": {"message": "profile capture requires the "
                           "configured API token",
                           "type": "authentication_error"}},
                status=401,
            )
    global _PROFILE_BUSY
    # check-and-set with no await in between: concurrent requests must not
    # race past the guard (asyncio is single-threaded, so this is atomic)
    if _PROFILE_BUSY:
        return web.json_response(
            {"error": "a profile capture is already running"}, status=409
        )
    _PROFILE_BUSY = True
    stopping = None
    try:
        import asyncio

        import jax

        try:
            body = await request.json()
        except Exception:
            body = {}
        try:
            seconds = float(body.get("seconds", 2.0))
        except (TypeError, ValueError):
            return web.json_response(
                {"error": "'seconds' must be a number"}, status=400
            )
        if not (0.1 <= seconds <= 30.0):
            return web.json_response(
                {"error": "'seconds' must be in [0.1, 30]"}, status=400
            )
        llm = _state(request)["llm"]
        start_seqs = _flight_seqs(llm)
        # filled mark by mark, and served on /metrics meanwhile: a client
        # that stopped waiting for this reply (stop_trace can outlast its
        # patience) still finds the capture's edges there
        sched_window: Dict[str, Any] = _state(request).setdefault(
            "sched_window", {})
        sched_window.clear()
        # jax.profiler supports one trace at a time: _PROFILE_BUSY above
        # is the process-wide guard (nothing else in the program traces).
        # start_trace returns in ~40 ms and stays on the loop; stop_trace
        # serialises the capture (3.6-5.7 s for 5 s of a busy v5e with
        # the Python tracer on, PERF.md section 5) and runs in the
        # default executor, so SSE delivery goes on meanwhile (slowed:
        # the serialiser holds the GIL for much of that time).
        t_start = _time.time()
        jax.profiler.start_trace(_PROFILE_DIR)
        t_trace_on = _time.time()
        sched_window["at_start"] = _sched_mark(llm)
        try:
            await asyncio.sleep(seconds)
        finally:
            sched_window["at_stop_call"] = _sched_mark(llm)
            t_trace_off = _time.time()
            stopping = asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)
            # from here the guard belongs to the thread: it is cleared
            # when stop_trace has RETURNED, not when this handler leaves,
            # so a handler cancelled at the await below cannot let a
            # second capture call start_trace beside a running stop_trace
            stopping.add_done_callback(_profile_idle)
        # shield: cancelling the handler must not cancel the future the
        # callback hangs on (the thread would run on, unguarded)
        await asyncio.shield(stopping)
        t_end = _time.time()
        sched_window["at_stop_return"] = _sched_mark(llm)
        logger.info(
            "/debug/profile: start_trace %.3f s (on the loop), "
            "stop_trace %.3f s (in the executor), traced %.3f s",
            t_trace_on - t_start, t_end - t_trace_off,
            t_trace_off - t_trace_on,
        )
        end_seqs = _flight_seqs(llm)
    finally:
        if stopping is None:
            _PROFILE_BUSY = False
    flight_window = None
    if start_seqs is not None and end_seqs is not None:
        ends = {e["replica"]: e["seq"] for e in end_seqs}
        flight_window = {
            "t_start": round(t_start, 4),
            "t_end": round(t_end, 4),
            # t_start..t_end also brackets start_trace (on the event
            # loop, ~40 ms) and stop_trace (in the default executor,
            # seconds during which serving goes on, slowed): the traced
            # interval is t_trace_on..t_trace_off
            "t_trace_on": round(t_trace_on, 4),
            "t_trace_off": round(t_trace_off, 4),
            "replicas": [
                {"replica": s["replica"], "start_seq": s["seq"],
                 "end_seq": ends.get(s["replica"], s["seq"])}
                for s in start_seqs
            ],
        }
    return web.json_response({
        "trace_dir": _PROFILE_DIR,
        "seconds": seconds,
        # correlate xplane slices with scheduler decisions: fetch
        # /debug/flight/{replica} and select records with
        # start_seq <= seq < end_seq (or t in [t_start, t_end])
        "flight_window": flight_window,
        # the engine thread's account (/metrics `sched`) at the capture's
        # edges, each with its time.time(): at_start .. at_stop_call
        # brackets the traced seconds, so the program's by-phase
        # starvation and the capture's idle gaps can be laid side by side
        # over the SAME interval; at_start .. at_stop_return brackets what
        # the capture did to the host (stop_trace holds the GIL for
        # seconds), so a reader can take a window LESS that bracket
        "sched_window": (dict(sched_window)
                         if all(sched_window.values()) else None),
    })


def run_server(cfg: Optional[ServingConfig] = None) -> None:
    cfg = cfg or ServingConfig.from_env()
    from ..logs import setup_logging

    # KAFKA_TPU_LOG_FORMAT=json (or cfg.log_format): every record carries
    # trace_id/span_id/thread_id for cross-process correlation
    setup_logging(cfg.log_format)
    web.run_app(create_app(cfg), host=cfg.host, port=cfg.port)
