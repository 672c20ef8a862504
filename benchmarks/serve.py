"""The one process of a benchmark run that holds the chip(s).

    python benchmarks/serve.py --config benchmarks/configs/<config>.json \
        --name <config> --port <p> --out <dir> --chips <n> [--rehearse]

Reads the configuration file, registers its model under its name in the
program's registry (the one place the benchmark reaches into the program's
state), builds `ServingConfig.from_env()` with the file's `serving` fields
replaced, and then does exactly what `kafka_tpu.server.app.run_server` does:
`build_tpu_provider` / `create_app` / `web.run_app`.  Between building the
provider and serving it runs the logit check and adds one read-only route,
`GET /bench/info`, that reports the device as JAX sees it, per-device
`memory_stats()` and the check's result.

What checks a configuration is named in its own file (`resolve_check`), so a
new architecture brings its reference and its cache driver as new files:
`check.reference` -> `references/<name>.py`, `check.driver` ->
`drivers/<name>.py`, each looked for under `--root` first and then beside
this file.  Without the keys the check is `reference.py` through
`paged_step.py`.  A named file that is not there stops the boot.

Exit code 3: no TPU, or fewer devices than the cell needs (never a fallback).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 3


CHECK_DEFAULTS = {"n_prefill": 64, "n_decode": 4, "pages_per_seq": 8}
CHECK_KEYS = set(CHECK_DEFAULTS) | {"reference", "driver"}


def resolve_check(spec: dict, root: str) -> dict:
    """The modules and sizes that check this configuration, from its file's
    optional `check` group.  `reference` names `references/<name>.py`:
    `hyper(model_cfg)`, `reference_logits(params, hp, token_ids,
    positions_out) -> {"logits", "router_gap"}` and `TOLERANCE = {"value",
    "why"}`.  `driver` names `drivers/<name>.py`: `served_logits(params, cfg,
    token_ids, n_prefill, *, page_size, pages_per_seq) -> [1 + n_decode, V]`.
    The comparison itself is always `reference.compare_logits`."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    import named

    check = dict(spec.get("check") or {})
    unknown = set(check) - CHECK_KEYS
    if unknown:
        raise ValueError(f"unknown keys in `check`: {sorted(unknown)}")
    out = {k: int(check.get(k, v)) for k, v in CHECK_DEFAULTS.items()}
    page = int(spec["serving"]["page_size"])
    if out["pages_per_seq"] * page < out["n_prefill"] + out["n_decode"]:
        raise ValueError(
            f"check: {out['pages_per_seq']} pages of {page} do not hold "
            f"{out['n_prefill']} + {out['n_decode']} tokens")
    out["page_size"] = page

    def pick(key: str, folder: str, default: str):
        if key in check:
            return (f"{folder}/{check[key]}",
                    named.load((root, HERE), folder, check[key]))
        return default, importlib.import_module(default)

    out["reference"], ref = pick("reference", "references", "reference")
    out["driver"], out["driver_mod"] = pick("driver", "drivers", "paged_step")
    out["reference_mod"] = ref
    # the default reference has none: compare_logits picks dense or routed
    out["tol"] = (float(ref.TOLERANCE["value"]) if "reference" in check
                  else None)
    return out


def logit_check(provider, check: dict, seed: int = 0) -> dict:
    """prefill(n_prefill) + n_decode decode steps of the program's `forward`
    on the served weights through a small paged cache (the configuration's
    driver), against its float32 reference.  Tokens are fixed (seed 0):
    weights are fixed by the program (PRNGKey(0)), so the check is the same
    comparison in every run."""
    import numpy as np

    import reference

    engine = provider.engine
    first = getattr(engine, "engines", [engine])[0]
    cfg, params = first.cfg, first.params
    n_prefill, n_decode = check["n_prefill"], check["n_decode"]
    ids = np.random.RandomState(seed).randint(
        0, min(cfg.vocab_size, 32000), size=n_prefill + n_decode)
    t0 = time.monotonic()
    served = check["driver_mod"].served_logits(
        params, cfg, ids, n_prefill, page_size=check["page_size"],
        pages_per_seq=check["pages_per_seq"])
    positions = list(range(n_prefill - 1, n_prefill + n_decode))
    ref_mod = check["reference_mod"]
    ref = ref_mod.reference_logits(params, ref_mod.hyper(cfg), ids, positions)
    res = reference.compare_logits(served, ref["logits"], ref["router_gap"],
                                   tol=check["tol"])
    res["seconds"] = round(time.monotonic() - t0, 3)
    res["attention_backend"] = cfg.attention_backend
    res["reference"], res["driver"] = check["reference"], check["driver"]
    return res


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--name", required=True)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--out", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--root", default=HERE,
                   help="data root searched first for references/, drivers/")
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
    check_spec = resolve_check(spec, os.path.abspath(args.root))
    # scope_reduce.py books an op under a listed scope as a component of its
    # own; a name the program does not register would never be found
    from kafka_tpu.tracing import DEVICE_SCOPES

    unknown = set(spec.get("scopes", ())) - set(DEVICE_SCOPES)
    if unknown:
        raise SystemExit(f"serve.py: `scopes` lists {sorted(unknown)}, which "
                         "kafka_tpu.tracing.DEVICE_SCOPES does not register")
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
            check = logit_check(provider, check_spec)
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
