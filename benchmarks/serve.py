"""The one process of a benchmark run that holds the chip(s).

    python benchmarks/serve.py --config benchmarks/configs/<config>.json \
        --name <config> --port <p> --out <dir> --chips <n> [--rehearse]

Reads the configuration file, registers its model under its name in the
program's registry (the one place the benchmark reaches into the program's
state), builds `ServingConfig.from_env()` with the file's `serving` fields
replaced, and then does exactly what `kafka_tpu.server.app.run_server` does:
`build_tpu_provider` / `create_app` / `web.run_app`.  Between building the
provider and serving it runs the logit check (`paged_step.served_logits` on
the served weights against `reference.py`) and adds one read-only route,
`GET /bench/info`, that reports the device as JAX sees it, per-device
`memory_stats()` and the check's result.

Exit code 3: no TPU, or fewer devices than the cell needs (never a fallback).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 3


def logit_check(provider, seed: int = 0) -> dict:
    """prefill(64) + 4 decode steps of the program's `forward` on the served
    weights through a small paged cache, against the float32 reference.
    Tokens are fixed (seed 0): weights are fixed by the program
    (PRNGKey(0)), so the check is the same comparison in every run."""
    import numpy as np

    sys.path.insert(0, HERE)
    import paged_step
    import reference

    engine = provider.engine
    first = getattr(engine, "engines", [engine])[0]
    cfg, params = first.cfg, first.params
    n_prefill, n_decode = 64, 4
    ids = np.random.RandomState(seed).randint(
        0, min(cfg.vocab_size, 32000), size=n_prefill + n_decode)
    t0 = time.monotonic()
    served = paged_step.served_logits(params, cfg, ids, n_prefill)
    positions = list(range(n_prefill - 1, n_prefill + n_decode))
    ref = reference.reference_logits(
        params, reference.hyper(cfg), ids, positions)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"])
    res["seconds"] = round(time.monotonic() - t0, 3)
    res["attention_backend"] = cfg.attention_backend
    return res


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--name", required=True)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal: JAX_PLATFORMS=cpu was set by the parent")
    args = p.parse_args()
    t_start = time.monotonic()

    sys.path.insert(0, ROOT)
    import jax

    platform = jax.default_backend()
    if not args.rehearse and (platform != "tpu"
                              or jax.device_count() < args.chips):
        print(f"serve.py: need {args.chips} TPU device(s), found "
              f"{jax.device_count()} x {platform}", file=sys.stderr, flush=True)
        raise SystemExit(EXIT_NO_CHIP)

    from aiohttp import web

    from kafka_tpu.logs import setup_logging
    from kafka_tpu.models import config as model_registry
    from kafka_tpu.server import app as app_module
    from kafka_tpu.server.config import ServingConfig

    with open(args.config) as f:
        spec = json.load(f)
    serving = dict(spec["serving"])
    serving["prefill_buckets"] = tuple(serving["prefill_buckets"])
    # config_from_hf_json names a model after its directory
    model_cfg = model_registry.config_from_hf_json(args.config).replace(
        name=args.name)
    model_registry.CONFIGS[args.name] = model_cfg
    cfg = ServingConfig.from_env(
        model_name=args.name, host="127.0.0.1", port=args.port,
        db_path=os.path.join(args.out, "threads.db"), **serving)
    setup_logging(cfg.log_format)
    # the program fixes /tmp/kafka_tpu_trace in code; a run keeps everything
    # inside its checkout (PERF.md, Open questions)
    app_module._PROFILE_DIR = os.path.join(args.out, "trace")

    info: dict = {}

    async def bench_info(request: web.Request) -> web.Response:
        stats = []
        for d in jax.local_devices():
            try:
                stats.append(d.memory_stats() or {})
            except Exception:  # a backend without memory_stats
                stats.append({})
        return web.json_response({**info, "memory_stats": stats})

    async def make_app() -> web.Application:
        app = await app_module.create_app(cfg)  # as run_server does
        t_built = time.monotonic()
        provider = app[app_module.STATE_KEY]["llm"]
        try:
            check = logit_check(provider)
        except Exception as e:  # the run goes on and reports correct: false
            import traceback

            traceback.print_exc()
            check = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        print("serve.py: logit check", json.dumps(check), flush=True)
        dev0 = jax.devices()[0]
        info.update({
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "visible": jax.device_count(),
            "logit_check": check,
            "boot_s": {"app": round(t_built - t_start, 3),
                       "logit_check": round(time.monotonic() - t_built, 3)},
        })
        app.router.add_get("/bench/info", bench_info)
        return app

    web.run_app(make_app(), host=cfg.host, port=cfg.port)


if __name__ == "__main__":
    main()
