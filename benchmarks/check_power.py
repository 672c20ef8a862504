"""What a configuration's logit check can tell, read on the chip (or, tiny,
on the CPU): the served program against its reference at EVERY compared
position with the reference's raw router gap beside it, the reference's own
`variants` (one mechanism taken out each) against the reference, and the
reference on int8 weights.  Prints JSON lines; PERF.md quotes them.

    python benchmarks/check_power.py <configuration file> [--no-int8]

Not part of a benchmark run: `serve.py` does the check itself.  This is how
the margin and the tolerance beside a new reference are set from readings.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def rel_rms(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (np.sqrt(np.mean((a - b) ** 2, axis=-1))
            / np.sqrt(np.mean(b ** 2, axis=-1)))


# the input (contracted) axes of each stacked matrix: one scale per output
# channel, as models/quant.py quantises weights
INPUT_AXES = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
              "router": (1,), "embed": (1,), "lm_head": (0,)}


def int8_weights(params):
    """Every matrix rounded to int8 per output channel (abs-max) and back to
    its own dtype, one leaf at a time and in place of the leaf it replaces.
    Expert leaves [L, E, in, out] contract axis 2, dense MLP leaves axis 1."""
    def fake_quant(w, axes):
        f = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(f), axis=axes, keepdims=True) / 127.0
        return (jnp.round(f / jnp.maximum(s, 1e-30)) * s).astype(w.dtype)

    fq = jax.jit(fake_quant, static_argnums=1, donate_argnums=0)
    layers = dict(params["layers"])
    for name in list(layers):
        w = layers[name]
        if w.ndim >= 3:
            layers[name] = fq(w, INPUT_AXES.get(name, (w.ndim - 2,)))
    out = dict(params, layers=layers)
    for name in ("embed", "lm_head"):
        if name in out:
            out[name] = fq(out[name], INPUT_AXES[name])
    return out


def main() -> None:
    import serve
    from kafka_tpu.models import config as model_registry
    from kafka_tpu.models.llama import init_params

    path = os.path.abspath(sys.argv[1])
    with open(path) as f:
        spec = json.load(f)
    check = serve.resolve_check(spec, os.path.dirname(os.path.dirname(path)))
    backend = spec["expect"]["attention_backend"]
    if jax.default_backend() != "tpu":
        backend = "xla"
    cfg = model_registry.config_from_hf_json(path).replace(
        dtype=spec["serving"]["dtype"], attention_backend=backend)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_prefill, n_decode = check["n_prefill"], check["n_decode"]
    ids = np.random.RandomState(0).randint(
        0, min(cfg.vocab_size, 32000), size=n_prefill + n_decode)
    positions = list(range(n_prefill - 1, n_prefill + n_decode))
    ref_mod = check["reference_mod"]
    hp = ref_mod.hyper(cfg)
    t0 = time.monotonic()
    served = check["driver_mod"].served_logits(
        params, cfg, ids, n_prefill, page_size=check["page_size"],
        pages_per_seq=check["pages_per_seq"])
    ref = ref_mod.reference_logits(params, hp, ids, positions)
    err = rel_rms(served, ref["logits"])
    raw = ref.get("raw_router_gap", ref["router_gap"])
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "backend": backend,
        "positions": positions, "seconds": round(time.monotonic() - t0, 1),
        "raw_router_gap": [round(float(g), 5) for g in raw],
        "served_rel_rms": [round(float(e), 5) for e in err]}), flush=True)
    for name, variant in getattr(ref_mod, "variants", lambda hp: {})(hp).items():
        got = ref_mod.reference_logits(params, variant, ids, positions)
        e = rel_rms(got["logits"], ref["logits"])
        print(json.dumps({"variant": name, "rel_rms_min": float(e.min()),
                          "rel_rms_median": float(np.median(e)),
                          "rel_rms_max": float(e.max())}), flush=True)
    if "--no-int8" not in sys.argv:
        got = ref_mod.reference_logits(int8_weights(params), hp, ids,
                                       positions)
        e = rel_rms(got["logits"], ref["logits"])
        print(json.dumps({
            "variant": "int8_weights", "rel_rms_min": float(e.min()),
            "rel_rms_median": float(np.median(e)),
            "rel_rms_max": float(e.max()),
            "rel_rms": [round(float(x), 5) for x in e],
            "raw_router_gap": [round(float(g), 5)
                               for g in got.get("raw_router_gap", [])]}),
            flush=True)


if __name__ == "__main__":
    main()
