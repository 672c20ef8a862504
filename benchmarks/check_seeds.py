"""A configuration's logit check on OTHER seeds than the one a run makes it
on, and its controls through the comparison that decides `correct`.

    python benchmarks/check_seeds.py <configuration file> [--seeds 0:0,1:1]
                                                          [--variants]

`serve.py` makes the check on one pair of seeds (weights from PRNGKey(0),
tokens from RandomState(0)): the same digits in every run.  This reads it on
each `weights:tokens` pair of `--seeds`, and beside the served program (which
must come out `ok`) what must NOT, each through `reference.compare_logits`
under the configuration's own tolerance and skip rule:

* the reference's variants whose names start `bf16_accumulate` (all of them
  with `--variants`, on the first pair of seeds, and there the served program
  on its OWN picks where the driver forces them), against the reference;
* `int8_reference`: the reference on int8 weights, against the reference on
  the weights as served;
* `int8_served`: the SERVED program, through the configuration's driver, on
  int8 weights, against the reference on the weights as served.

int8: every stacked matrix of the tree (mixers, lead, experts, routers, the
embedding) rounded per output channel (abs-max) and back to its dtype; a
depthwise conv's taps and the norms stay.  Where the reference reports the
experts its rows took (`picks`) and takes them back (`picks=`), the int8
passes are handed the unrounded tree's, as the driver hands them to the
served program.  Prints JSON lines; PERF.md quotes them.  Not part of a
benchmark run.
"""

from __future__ import annotations

import inspect
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

from check_power import INPUT_AXES  # noqa: E402

UNROUNDED = ("conv_w",)  # a depthwise conv's taps: weight-only int8 skips them


def int8_tree(tree):
    """`tree` with every stacked matrix rounded to int8 per output channel
    and back, one leaf at a time and IN PLACE of the leaf it replaces (two
    trees of the published widths do not fit a chip): `tree`'s own matrices
    are gone afterwards."""
    def fake_quant(w, axes):
        f = w.astype(jnp.float32)
        s = jnp.max(jnp.abs(f), axis=axes, keepdims=True) / 127.0
        return (jnp.round(f / jnp.maximum(s, 1e-30)) * s).astype(w.dtype)

    fq = jax.jit(fake_quant, static_argnums=1, donate_argnums=0)

    def walk(node, name=""):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if name in ("embed", "lm_head"):
            return fq(node, INPUT_AXES[name])
        if node.ndim < 3 or name in UNROUNDED:
            return node
        return fq(node, INPUT_AXES.get(name, (node.ndim - 2,)))

    return walk(tree)


def main() -> None:
    import reference
    import serve
    from kafka_tpu.models import config as model_registry
    from kafka_tpu.models.llama import init_params

    path = os.path.abspath(sys.argv[1])
    args = sys.argv[2:]
    seeds = [(0, 0), (1, 1)]
    if "--seeds" in args:
        seeds = [tuple(int(x) for x in pair.split(":"))
                 for pair in args[args.index("--seeds") + 1].split(",")]
    with open(path) as f:
        spec = json.load(f)
    check = serve.resolve_check(spec, os.path.dirname(os.path.dirname(path)))
    backend = spec["expect"]["attention_backend"]
    if jax.default_backend() != "tpu":
        backend = "xla"
    cfg = model_registry.config_from_hf_json(path).replace(
        dtype=spec["serving"]["dtype"], attention_backend=backend)
    n_prefill, n_decode = check["n_prefill"], check["n_decode"]
    positions = list(range(n_prefill - 1, n_prefill + n_decode))
    ref_mod, drive = check["reference_mod"], check["driver_mod"].served_logits
    hp = ref_mod.hyper(cfg)
    sizes = dict(page_size=check["page_size"],
                 pages_per_seq=check["pages_per_seq"])
    takes_picks = ("picks" in inspect.signature(drive).parameters and "picks"
                   in inspect.signature(ref_mod.reference_logits).parameters)

    def verdict(name, got, ref, **more):
        res = reference.compare_logits(got, ref["logits"], ref["router_gap"],
                                       tol=check["tol"])
        rel = res["rel_rms"] or [float("inf")]
        print(json.dumps({
            "what": name, "ok": res["ok"], "tol": res["tol"],
            "compared": res["compared"], "rel_rms_min": min(rel),
            "rel_rms_median": float(np.median(rel)), "rel_rms_max": max(rel),
            **more}), flush=True)

    for nth, (wseed, tseed) in enumerate(seeds):
        t0 = time.monotonic()
        params = init_params(cfg, jax.random.PRNGKey(wseed))
        ids = np.random.RandomState(tseed).randint(
            0, min(cfg.vocab_size, 32000), size=n_prefill + n_decode)
        tag = {"weights": wseed, "tokens": tseed}
        ref = ref_mod.reference_logits(params, hp, ids, positions)
        picks = {"picks": ref["picks"]} if takes_picks else {}
        verdict("served",
                drive(params, cfg, ids, n_prefill, **sizes, **picks), ref,
                device=jax.devices()[0].device_kind, backend=backend, **tag)
        if "--variants" in args and nth == 0 and (
                "force" in inspect.signature(drive).parameters):
            verdict("served_free_picks", drive(
                params, cfg, ids, n_prefill, **sizes, force=False), ref, **tag)
        for name, variant in getattr(ref_mod, "variants",
                                     lambda hp: {})(hp).items():
            if name.startswith("bf16_accumulate") or (
                    "--variants" in args and nth == 0):
                got = ref_mod.reference_logits(params, variant, ids,
                                               positions)
                verdict(name, got["logits"], ref, **tag)
        rounded = int8_tree(params)
        del params
        got = ref_mod.reference_logits(rounded, hp, ids, positions, **picks)
        verdict("int8_reference", got["logits"], ref, **tag)
        verdict("int8_served",
                drive(rounded, cfg, ids, n_prefill, **sizes, **picks), ref,
                seconds=round(time.monotonic() - t0, 1), **tag)
        del rounded


if __name__ == "__main__":
    main()
