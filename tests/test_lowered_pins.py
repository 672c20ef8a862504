"""The standing guard on the lowered programs: a PR that does not MEAN to
change what a model runs on the device changes none of it.  For every preset
of `models/config.CONFIGS`, every tiny twin under
`benchmarks/tests/*/configs/` and every configuration file under
`benchmarks/configs/`, the lowered text of the engine's decode step and of a
two-lane batched prefill launch (from shapes alone,
`tests/test_moe_dispatch.py`'s way; the full-width files too, which lower in
seconds and hold no array) is what the parent commit lowers:
`tests/recorded/lowered_pins.json` holds the digests, recorded AT THE PARENT
(48aecb9, PR 64, for PR 66) by running this file in a checkout of it with
`KAFKA_TPU_RECORD_PINS=<path>` (same conftest, same JAX).  Equal text = the
same executable and a warm compile cache across the two trees.

The lowered text carries no scope names, so the residual adds are held
apart: in the COMPILED decode step of three tiny models the adds under
`attn_out`, `mlp` and `moe_experts` are counted and compared with the
parent's counts, and no `hc_*` scope is in any of them.

A PR that MEANS to change a model's programs records the file again at its
own parent, names the programs it moves in `MOVED` (each must then DIFFER
from the parent's text, and nothing else may) and says so; the PR after it
empties `MOVED` and records again.  A `model_config` PR names its model's
files in `NEW` (the configuration's file under `benchmarks/configs/` and its
tiny twin: the parent cannot lower them), records the rest at its parent, and
the PR after it empties `NEW` and records again: `NEW` is empty on every tree
but a new model's own.
"""

import glob
import hashlib
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import init_params
from kafka_tpu.models.config import CONFIGS, config_from_hf_json
from kafka_tpu.runtime import step_programs
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINS = os.path.join(ROOT, "tests", "recorded", "lowered_pins.json")
RECORD = os.environ.get("KAFKA_TPU_RECORD_PINS")
# the files of a model this very PR adds (docstring): not pinned.  PR 66:
# Olmo-Hybrid-7B's configuration and its tiny twin.
NEW = ("olmo-hybrid-7b", "tiny-olmohybrid")
# the programs this very PR means to move (docstring).  PR 66 moves none: the
# shared delta block, the kernels' call sites, the residual form and the
# decode kernel's step size trace nothing new where the new fields are absent
# (PR 64's three moved decode steps are pinned as they stood at its end).
MOVED = frozenset()
PS, LANES, PAGES, BUCKET, WIDTH = 8, 4, 8, 16, 2


def _configs():
    out = {f"preset:{name}": cfg for name, cfg in sorted(CONFIGS.items())}
    files = sorted(
        glob.glob(os.path.join(ROOT, "benchmarks", "tests", "*", "configs",
                               "*.json"))
        + glob.glob(os.path.join(ROOT, "benchmarks", "configs", "*.json")))
    for path in files:
        name = os.path.basename(path)[:-len(".json")]
        if name not in NEW:
            out[f"file:{name}"] = path
    return out


CASES = _configs()
# the latent models' Pallas forms too: the block's scale is passed to the
# kernels as an argument
BACKENDS = {"file:tiny-kanana2": ("xla", "pallas"),
            "file:tiny-dots3": ("xla", "pallas"),
            "file:tiny-xing4": ("xla", "pallas")}
# the configurations whose state layers' tail goes through `_tail_conv_silu`,
# at their published widths, with every state kernel traced (interpreted)
STATE_PALLAS = ("solar-open2-250b", "nemotron-3-nano-30b-a3b",
                "falcon-h1-34b", "granite-4.0-h-small")
BACKENDS.update({f"file:{name}": ("xla", "pallas") for name in STATE_PALLAS})
KEYS = [f"{name}.{backend}.{program}" for name in CASES
        for backend in BACKENDS.get(name, ("xla",))
        for program in ("decode", "bprefill")]


def _cfg(name, backend):
    cfg = CASES[name]
    if isinstance(cfg, str):
        cfg = config_from_hf_json(cfg)
    return cfg.replace(attention_backend=backend)


def _args(cfg, program):
    """(fn, abstract args) of the program over a 64-page pool of 8-row
    pages, 4 lanes; the batched prefill 2 lanes of 16 rows."""
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    slots = default_state_slots(LANES) if cfg.has_state else 0
    pools = jax.eval_shape(lambda: make_kv_pool_arrays(
        cfg, 64, PS, state_slots=slots))

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    if program == "decode":
        lanes = step_programs.Lanes(
            page_table=of(i32, LANES, PAGES), last_tokens=of(i32, LANES),
            seq_lens=of(i32, LANES), active=of(jnp.bool_, LANES),
            temps=of(f32, LANES), top_ks=of(i32, LANES),
            top_ps=of(f32, LANES), seeds=of(u32, LANES))
        return (step_programs._decode_fn(cfg, None, PS),
                (params, *pools, lanes, None, None, None))
    extra = ()
    if cfg.has_state:
        extra = (of(i32, WIDTH), of(i32, WIDTH))
    elif cfg.vision is not None:
        extra = (of(cfg.activation_dtype, WIDTH, BUCKET, cfg.hidden_size),
                 of(jnp.bool_, WIDTH, BUCKET))
    args = (of(i32, WIDTH, PAGES), of(i32, WIDTH, BUCKET), of(i32, WIDTH),
            of(i32, WIDTH), of(f32, WIDTH), of(i32, WIDTH), of(f32, WIDTH),
            of(u32, WIDTH), of(jnp.bool_, WIDTH))
    return (step_programs._batched_prefill_fn(cfg, None, PS, BUCKET),
            (params, *pools, *args, *extra))


def _digest(key):
    name, backend, program = key.rsplit(".", 2)
    fn, args = _args(_cfg(name, backend), program)
    text = jax.jit(fn).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


ADDS = {"file:tiny-kanana2": ("attn_out", "mlp", "moe_experts"),
        "preset:tiny-gqa": ("attn_out", "mlp"),
        "preset:tiny-moe": ("attn_out", "moe_experts")}


def _residual_adds(name):
    """{scope: adds under it} in the compiled decode step, and whether any
    `hc_*` scope is in the program."""
    fn, args = _args(_cfg(name, "xla"), "decode")
    text = jax.jit(fn).lower(*args).compile().as_text()
    return ({scope: len(re.findall(rf'op_name="[^"]*/{scope}/add"', text))
             for scope in ADDS[name]}, "/hc_m" in text)


def _recorded():
    with open(PINS) as f:
        return json.load(f)


if RECORD:
    def test_record_the_pins():
        pins = {"texts": {key: _digest(key) for key in KEYS},
                "adds": {name: _residual_adds(name)[0] for name in ADDS}}
        with open(RECORD, "w") as f:
            json.dump(pins, f, indent=1, sort_keys=True)
            f.write("\n")
else:
    @pytest.mark.parametrize("key", KEYS)
    def test_every_other_model_lowers_to_the_parents_text(key):
        moved = _digest(key) != _recorded()["texts"][key]
        assert moved == (key in MOVED), key

    def test_every_configuration_but_the_new_one_is_pinned():
        assert set(_recorded()["texts"]) == set(KEYS) >= MOVED
        files = {os.path.basename(p)[:-5] for p in glob.glob(
            os.path.join(ROOT, "benchmarks", "configs", "*.json"))}
        pinned = {k.split(":", 1)[1] for k in CASES if k.startswith("file:")}
        assert files - pinned == files & set(NEW)

    @pytest.mark.parametrize("name", sorted(ADDS))
    def test_the_residual_adds_sit_where_they_sat(name):
        adds, widened = _residual_adds(name)
        assert adds == _recorded()["adds"][name]
        assert all(n > 0 for n in adds.values()), adds
        assert not widened
