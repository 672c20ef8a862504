"""Decode's tail convolution as ONE pass over the slot as stored (ISSUE 64;
`ops/pallas/tail_conv.tail_conv_step` behind `mixers/state._tail_conv_silu`).

(c) THROUGH THE ENGINE'S DECODE STEP, a delta-layout and a parallel-layout
model widened until their tails tile: two steps with the kernel are two steps
with the body, bit for bit.

(a) THE KERNEL, interpreted, float32, is EXACTLY the XLA body: the three
served tilings at reduced width with the same residues (C : W : C % W = 8 : 3
: 2 pieces; Solar-Open2's pieces are 24 lane tiles, Nemotron-H's 6,
Falcon-H1's 5), other tap counts, bias present and absent, a lane with no
real row (its slot written back bit for bit), three consecutive steps (the
shift composes), a layer past the first, lanes that fill a grid step and
lanes that do not.

(b) THE RULE is the shape's: `tiles` takes the three served geometries and
declines Granite's (8, 3168), a tail that is not laid over 8 rows and a piece
that is no whole lane tile; `_tail_conv_silu` hands the kernel decode's pass
alone (not S > 1, not the uncached call, not the XLA backend, not a launch
with slots of its own), and what it declines runs the body to the letter.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.cache import StatePlan
from kafka_tpu.models.config import (
    DELTA, GLOBAL, PARALLEL, _tail_layout, config_from_hf_json)
from kafka_tpu.models.mixers import state
from kafka_tpu.ops.pallas import tail_conv
from kafka_tpu.runtime import step_programs
from kafka_tpu.runtime.kv_cache import default_state_slots, make_kv_pool_arrays

F32 = jnp.float32


def _case(C, B, taps, bias, layers=2, spare=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    slot = _tail_layout(taps - 1, C)
    leaf = jax.random.normal(ks[0], (layers, B + spare) + slot, F32)
    w = jax.random.normal(ks[1], (taps, C), F32)
    b = jax.random.normal(ks[2], (C,), F32) if bias else None
    return leaf, w, b, ks[3]


def _body(x, w, b, leaf, layer, lens):
    """`_tail_conv_silu` as every pass ran it before the kernel."""
    return jax.jit(state._tail_conv_silu)(
        x, w, b, leaf, layer, StatePlan(lens=lens))


# channels a row (a piece = C / 8 at 4 taps), lanes, taps, bias, layer
GEOMETRIES = {
    "solar_1_tile_pieces": (1024, 4, 4, False, 1),
    "nemotron_2_tile_pieces": (2048, 16, 4, True, 0),
    "falcon_5_tile_pieces": (5120, 8, 4, True, 1),
    "two_lanes_a_step": (1024, 6, 4, True, 1),
    "one_lane_a_step": (1024, 3, 4, False, 0),
    "two_taps": (1024, 4, 2, True, 1),
    "five_taps": (512, 4, 5, False, 1),
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_three_steps_equal_the_body_bit_for_bit(name):
    C, B, taps, bias, layer = GEOMETRIES[name]
    leaf, w, b, key = _case(C, B, taps, bias)
    assert tail_conv.tiles(taps, C, leaf.shape[2:])
    lens = jnp.asarray([1] * (B - 1) + [0], jnp.int32)  # the last lane idles
    want_leaf = got_leaf = leaf
    for step in range(3):
        x = jax.random.normal(jax.random.fold_in(key, step), (B, 1, C), F32)
        want, want_leaf = _body(x, w, b, want_leaf, layer, lens)
        got, got_leaf = tail_conv.tail_conv_step(
            got_leaf, jnp.int32(layer), lens, x[:, 0], w, b, interpret=True)
        assert got.dtype == F32 and got_leaf.dtype == F32
        assert np.array_equal(got, want[:, 0]), step
        assert np.array_equal(got_leaf, want_leaf), step
    got_leaf, leaf = np.asarray(got_leaf), np.asarray(leaf)
    # the idle lane's slot, the slots past the lanes and the other layer hold
    # what they held; a decoding lane's slot moved
    assert np.array_equal(got_leaf[layer, B - 1:], leaf[layer, B - 1:])
    assert np.array_equal(got_leaf[1 - layer], leaf[1 - layer])
    assert not np.array_equal(got_leaf[layer, 0], leaf[layer, 0])
    # after taps - 1 steps a decoding lane's tail is the rows it was handed
    x_last = jax.random.normal(jax.random.fold_in(key, 2), (B, 1, C), F32)
    assert np.array_equal(
        got_leaf[layer, 0].reshape(taps - 1, C)[-1], x_last[0, 0])


def test_a_lane_with_no_real_row_still_gets_its_convolution():
    """`out` of an idle lane is the body's too (the caller drops it)."""
    leaf, w, b, key = _case(1024, 4, 4, True)
    lens = jnp.asarray([0, 1, 0, 0], jnp.int32)
    x = jax.random.normal(key, (4, 1, 1024), F32)
    want, want_leaf = _body(x, w, b, leaf, 1, lens)
    got, got_leaf = tail_conv.tail_conv_step(
        leaf, jnp.int32(1), lens, x[:, 0], w, b, interpret=True)
    assert np.array_equal(got, want[:, 0])
    assert np.array_equal(got_leaf, want_leaf)
    assert np.array_equal(np.asarray(got_leaf)[1, [0, 2, 3]],
                          np.asarray(leaf)[1, [0, 2, 3]])


SERVED = {  # taps, channels a row: the slot `state_shapes` gives
    "solar-open2-250b": (4, 24576, (8, 9216), True),
    "nemotron-3-nano-30b-a3b": (4, 6144, (8, 2304), True),
    "falcon-h1-34b": (4, 5120, (8, 1920), True),
    "granite-4.0-h-small": (4, 8448, (8, 3168), False),
}


@pytest.mark.parametrize("name", sorted(SERVED))
def test_the_rule_on_the_served_geometries(name):
    taps, C, slot, takes = SERVED[name]
    cfg = config_from_hf_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "configs", f"{name}.json"))
    assert dict(cfg.state_shapes())["conv"] == slot == _tail_layout(
        taps - 1, C)
    assert tail_conv.tiles(taps, C, slot) == takes
    # whole lane tiles: C, W and C % W are where a piece is
    assert takes == (C % 128 == 0 and slot[1] % 128 == 0
                     and (C % slot[1]) % 128 == 0)
    note = state.tail_step_note(cfg.replace(attention_backend="pallas"))
    assert ("tail_conv_step, pieces of" in note) == takes
    assert str(slot) in note and ("declines" in note) != takes
    assert "XLA body (xla backend)" in state.tail_step_note(
        cfg.replace(attention_backend="xla"))


@pytest.mark.parametrize("taps,C,slot", [
    (4, 1000, (3, 1000)),    # not laid over 8 rows (3 x 1000 / 8 is whole,
    (4, 1001, (3, 1001)),    # ... and is not)
    (4, 512, (8, 192)),      # pieces of 64: half a lane tile
    (1, 1024, (8, 0)),       # no tail at all
    (4, 1024, (8, 512)),     # a slot that is not this tail's
])
def test_the_rule_declines(taps, C, slot):
    assert not tail_conv.tiles(taps, C, slot)


def _spy(monkeypatch):
    calls = []
    real = tail_conv.tail_conv_step

    def step(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(state, "tail_conv_step", step)
    return calls


PASSES = {
    # what `_tail_conv_silu` is handed -> does the kernel run
    "decode": (dict(), True),
    "xla_backend": (dict(kernel=False), False),
    "two_rows": (dict(S=2), False),
    "uncached": (dict(leaf=None), False),
    "a_launch_with_slots": (dict(src=True), False),
    "granite": (dict(C=8448), False),
}


@pytest.mark.parametrize("name", sorted(PASSES))
def test_which_passes_take_the_kernel(monkeypatch, name):
    spec, takes = PASSES[name]
    C, B, S = spec.get("C", 1024), 4, spec.get("S", 1)
    calls = _spy(monkeypatch)
    leaf, w, b, key = _case(C, B, 4, True)
    if "leaf" in spec:
        leaf = None
    x = jax.random.normal(key, (B, S, C), F32)
    slots = jnp.arange(B, dtype=jnp.int32) if spec.get("src") else None
    plan = StatePlan(lens=jnp.asarray([S, S, 0, 1], jnp.int32), src=slots,
                     dst=slots, snap=slots)
    # (both jitted: XLA's CPU fusions contract a multiply and an add)
    got, got_leaf = jax.jit(functools.partial(
        state._tail_conv_silu, kernel=spec.get("kernel", True)))(
            x, w, b, leaf, 1, plan)
    want, want_leaf = jax.jit(state._tail_conv_silu)(x, w, b, leaf, 1, plan)
    assert len(calls) == takes
    if takes:
        assert calls[0] == {"interpret": True}  # (off the chip)
    assert np.array_equal(got, want)
    assert (leaf is None and got_leaf is None) or np.array_equal(
        got_leaf, want_leaf)


# ----------------------------------------------------------------------
# (c) through the engine's decode step
# ----------------------------------------------------------------------

WIDE = {
    # the delta layout, three convolutions side by side, no bias:
    # 3 x 8 x 128 = 3,072 channels, pieces of 3 lane tiles
    "delta": dict(
        vocab_size=300, hidden_size=64, intermediate_size=24, num_layers=4,
        num_heads=8, num_kv_heads=2, head_dim=16,
        layer_types=(GLOBAL, DELTA, DELTA, DELTA), delta_heads=8,
        delta_head_dim=128, delta_conv_kernel=4, delta_neg_eigval=True,
        attention_gate="elementwise", unrotated_kinds=(GLOBAL,),
        num_experts=4, num_experts_routed=8, expert_offset=4,
        num_experts_per_tok=3, moe_scoring="sigmoid",
        shared_intermediate_size=24),
    # the parallel layout, one convolution over [x | B | C] with a bias:
    # 4 x 128 + 2 x 2 x 128 = 1,024 channels, pieces of 1
    "parallel_ssd": dict(
        vocab_size=300, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=5, num_kv_heads=1, head_dim=16,
        layer_types=(PARALLEL,) * 2, ssd_heads=4, ssd_head_dim=128,
        ssd_d_state=128, ssd_groups=2, ssd_conv_kernel=4),
}
PS, LANES, PAGES = 8, 4, 4


@pytest.mark.parametrize("name", sorted(WIDE))
def test_two_decode_steps_with_the_kernel_equal_the_body(monkeypatch, name):
    """The engine's own decode program on the Pallas backend (every kernel
    interpreted), one lane idle: with the tail's kernel and with the rule
    made to decline, the tokens, the rows and every state leaf are equal bit
    for bit."""
    cfg = ModelConfig(name=f"wide-{name}", dtype="float32",
                      tie_word_embeddings=False, attention_backend="pallas",
                      **WIDE[name])
    assert "tail_conv_step" in state.tail_step_note(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    k_pool, v_pool = make_kv_pool_arrays(
        cfg, LANES * PAGES + 1, PS, state_slots=default_state_slots(LANES))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 4))
    v_pool = {leaf: (a if leaf == "v" else jax.random.normal(
        next(keys), a.shape, a.dtype)) for leaf, a in v_pool.items()}
    lanes = step_programs.Lanes(
        page_table=jnp.asarray(
            1 + np.arange(LANES * PAGES).reshape(LANES, PAGES), jnp.int32),
        last_tokens=jnp.asarray([5, 6, 7, 8], jnp.int32),
        seq_lens=jnp.asarray([3, 9, 0, 17], jnp.int32),
        active=jnp.asarray([True, True, False, True]),
        temps=jnp.zeros(LANES, F32), top_ks=jnp.zeros(LANES, jnp.int32),
        top_ps=jnp.ones(LANES, F32), seeds=jnp.zeros(LANES, jnp.uint32))

    def two_steps():
        fn = jax.jit(step_programs._decode_fn(cfg, None, PS))
        k, v, toks, lens, _ = fn(params, k_pool, v_pool, lanes, None)
        k, v, toks2, _, _ = fn(params, k, v, lanes._replace(
            last_tokens=toks, seq_lens=lens), None)
        return k, v, toks, toks2

    calls = _spy(monkeypatch)
    got = two_steps()
    traced = len(calls)  # (a site a body of the layer scan, one program)
    assert traced >= 1
    monkeypatch.setattr(state, "tail_tiles", lambda *a: False)
    want = two_steps()
    assert len(calls) == traced
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(a, b)
    conv = np.asarray(got[1]["conv"])
    before = np.asarray(v_pool["conv"])
    # a decoding lane's slot moved; the idle lane's and the slots past the
    # lanes hold what they held
    assert not np.array_equal(conv[:, 3], before[:, 3])
    assert np.array_equal(conv[:, 2], before[:, 2])
    assert np.array_equal(conv[:, LANES:], before[:, LANES:])
