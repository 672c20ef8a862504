"""Token dispatch at DECODE (models/ffn.py `_moe_block`, `_experts_token`;
the grouped matmul interpreted off the chip): a pass of few rows over many
experts fetches only the held experts some row picked, against the dense
einsums on the same inputs in float32; the rule that chooses the form from
the pass's shape, at every registered configuration's decode rows, prefill
buckets and logit-check launch; and the count of what a decode pass read, up
to `/metrics`."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import forward, init_params
from kafka_tpu.models import ffn
from kafka_tpu.models.config import ModelConfig, config_from_hf_json
from kafka_tpu.models.ffn import (
    _experts_token, _moe_block, moe_dispatch_form,
)
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import step_programs
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SOFTMAX = dict(name="routed", vocab_size=128, hidden_size=32,
               intermediate_size=48, num_layers=2, num_heads=4,
               num_kv_heads=2, head_dim=16, dtype="float32",
               tie_word_embeddings=False, num_experts=8,
               num_experts_per_tok=3)
SIGMOID = dict(SOFTMAX, moe_scoring="sigmoid", routed_scaling_factor=2.5,
               shared_intermediate_size=16)
# 16 lanes x top-2 of 64: (1 - 2/64) ** 16 = 0.60 of the experts expected
# unread, so the rule itself says "token" at 16 decode lanes
MANY = dict(SIGMOID, num_experts=64, num_experts_per_tok=2)
# (config, what is done to the layer's leaves)
ROUTING = {
    "softmax": (SOFTMAX, None),
    "sigmoid_shared": (SIGMOID, None),
    # experts 8..11 of the router's 16: most picks land on other chips
    "held_share_offset": (dict(SIGMOID, num_experts=4, num_experts_routed=16,
                               expert_offset=8), None),
    # top-1 and one expert's bias above all: ONE expert is picked
    "one_expert_picked": (dict(SIGMOID, num_experts_per_tok=1), "crowd"),
    "an_expert_nobody_chose": (SIGMOID, "starve"),
    # 3 of 4 a row: every expert is picked
    "every_expert_picked": (dict(SOFTMAX, num_experts=4,
                                 num_experts_per_tok=3), None),
}


def layer(cfg, how=None, key=0):
    lp = jax.tree.map(lambda a: a[0],
                      init_params(cfg, jax.random.PRNGKey(key))["layers"])
    if how == "starve":
        lp["router_bias"] = lp["router_bias"].at[2].set(-100.0)
    elif how == "crowd":
        lp["router_bias"] = lp["router_bias"].at[6].set(100.0)
    return lp


def weights(x, lp, cfg):
    """[T, held] routing weights of the block's own rule."""
    t = x.reshape(-1, x.shape[-1])
    if cfg.moe_scoring == "sigmoid":
        w = ffn._routing_weights_sigmoid(
            t, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
    else:
        w = ffn._routing_weights(t, lp["router"], cfg.num_experts_per_tok)
    if cfg.num_experts_routed:
        w = w[:, cfg.expert_offset:cfg.expert_offset + cfg.num_experts]
    return np.asarray(w)


def as_form(monkeypatch, form, *args, **kw):
    """`_moe_block` made to take `form` whatever the pass's shape."""
    monkeypatch.setattr(ffn, "TOKEN_DISPATCH_MIN_UNREAD",
                        0.0 if form == "token" else 2.0)
    monkeypatch.setattr(ffn, "TOKEN_DISPATCH_UNREAD_ROWS", 0)
    out = _moe_block(*args, **kw)
    monkeypatch.undo()
    return out


@pytest.mark.parametrize("rows", [16, 32])
@pytest.mark.parametrize("case", list(ROUTING))
def test_token_form_at_decode_rows_equals_dense_form(monkeypatch, case, rows):
    fields, how = ROUTING[case]
    cfg = ModelConfig(**fields)
    lp = layer(cfg, how)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 1, cfg.hidden_size))
    token, read = as_form(monkeypatch, "token", x, lp, cfg)
    dense, held = as_form(monkeypatch, "dense", x, lp, cfg)
    # float32 on both sides: they differ by the order of the additions
    np.testing.assert_allclose(np.asarray(token), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(dense)).max() > 0.1
    # the count is a NumPy count of the picks
    w = weights(x, lp, cfg)
    assert int(read) == int((w != 0).any(axis=0).sum()) <= held
    assert held == cfg.num_experts
    if case == "one_expert_picked":
        assert int(read) == 1
    if case == "every_expert_picked":
        assert int(read) == held
    if case == "an_expert_nobody_chose":
        assert int(read) < held and not (w[:, 2] != 0).any()
    if case == "held_share_offset":
        assert 0 < (w != 0).sum() < rows * cfg.num_experts_per_tok / 2


def stack_of(rng, layers, held, hidden, inter):
    return {"wg": rng.standard_normal((layers, held, hidden, inter)) * 0.2,
            "wu": rng.standard_normal((layers, held, hidden, inter)) * 0.2,
            "wd": rng.standard_normal((layers, held, inter, hidden)) * 0.2}


def dense_of(t, w, stack, at):
    g = np.einsum("th,ehf->tef", t, stack["wg"][at])
    u = np.einsum("th,ehf->tef", t, stack["wu"][at])
    y = np.einsum("tef,efh->teh", g / (1 + np.exp(-g)) * u, stack["wd"][at])
    return np.einsum("te,teh->th", w, y)


# the rows' picks among the router's 16 experts, of which 0..7 are held: ->
# experts [T, k] (one of 8..15: a pick held elsewhere)
def _lone_lane(rows, rng):
    idx = np.tile([1, 4, 12], (rows, 1))
    idx[rows - 3, 2] = 6  # expert 6: one lane's pick and nobody else's
    return idx


PICKS = {
    "every_expert": lambda rows, rng: np.tile(np.arange(8), (rows, 1)),
    "one_expert": lambda rows, rng: np.full((rows, 1), 5),
    "one_lane_alone_picks_an_expert": _lone_lane,
    "the_last_expert_only": lambda rows, rng: np.tile([7, 9], (rows, 1)),
    "nobody_picks": lambda rows, rng: np.tile([8, 15], (rows, 1)),
}


@pytest.mark.parametrize("rows", [16, 32])
@pytest.mark.parametrize("case", list(PICKS))
def test_an_unread_expert_cannot_reach_the_output(case, rows):
    """`_experts_token` on hand-made picks, the stacked leaves at layer 2 of
    3, and NaN in every expert NO row picked (and in every other layer):
    dense dispatch would multiply them by zero and return NaN; the grouped
    matmul visits no group without rows."""
    held, routed, hidden, inter, at = 8, 16, 128, 256, 2
    rng = np.random.default_rng(rows)
    stack = stack_of(rng, 3, held, hidden, inter)
    t = rng.standard_normal((rows, hidden))
    idx = PICKS[case](rows, rng)
    w_top = rng.random(idx.shape) + 0.1
    w = np.zeros((rows, routed))
    np.put_along_axis(w, idx, w_top, axis=1)
    w = w[:, :held]
    picked = (w != 0).any(axis=0)
    want = dense_of(t, w, stack, at)
    poisoned = {}
    for name, a in stack.items():
        a = a.copy()
        a[at][~picked] = np.nan
        a[:at] = np.nan
        poisoned[name] = jnp.asarray(a, jnp.float32)
    out, read = _experts_token(
        jnp.asarray(t, jnp.float32), jnp.asarray(idx, jnp.int32),
        jnp.asarray(w_top, jnp.float32), poisoned, jnp.int32(at), routed, 0)
    assert int(read) == int(picked.sum())
    # (float32 against NumPy float64, values of tens)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)
    if picked.any():
        assert np.abs(want).max() > 0.1


def test_idle_lanes_pick_nothing(monkeypatch):
    """3 lanes of which one is idle (`chunk_len` 0): its picks are read by
    nobody, its routed output is zero, and the other rows are the dense
    form's."""
    cfg = ModelConfig(**SIGMOID)
    lp = layer(cfg)
    # (a key at which the idle lane picks two experts the others do not)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 1, cfg.hidden_size))
    active = jnp.asarray([1, 0, 1], jnp.int32)
    token, read = as_form(monkeypatch, "token", x, lp, cfg, active)
    dense, _ = as_form(monkeypatch, "dense", x, lp, cfg, active)
    np.testing.assert_allclose(np.asarray(token)[[0, 2]],
                               np.asarray(dense)[[0, 2]], rtol=1e-5,
                               atol=1e-5)
    with jax.named_scope("shared"):
        shared = np.asarray(ffn._mlp_block(x, lp, ("ws_g", "ws_u", "ws_d")))
    np.testing.assert_allclose(np.asarray(token)[1], shared[1], rtol=1e-6,
                               atol=1e-6)
    w = weights(x, lp, cfg)
    assert int(read) == int((w[[0, 2]] != 0).any(axis=0).sum())
    assert int(read) < int((w != 0).any(axis=0).sum())


# ---------------------------------------------------------------------------
# which form runs
# ---------------------------------------------------------------------------

def _routed_configs():
    """name -> (ModelConfig, its `serving` block) of every registered
    configuration with a routed block."""
    found = {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        files = [c["file"] for c in json.load(f)["configs"]]
    for path in files:
        cfg = config_from_hf_json(os.path.join(ROOT, path))
        if cfg.is_moe:
            with open(os.path.join(ROOT, path)) as f:
                serving = json.load(f)["serving"]
            found[os.path.basename(path)[:-len(".json")]] = (cfg, serving)
    return found


ROUTED = _routed_configs()


def _registered():
    """(configuration, rows of a pass, what the pass is) of every launch a
    registered routed configuration's serving shape makes."""
    cases = []
    for name, (_, serving) in ROUTED.items():
        cases.append((name, serving["max_batch"], "decode"))
        for bucket in serving["prefill_buckets"]:
            cases.append((name, bucket, "prefill"))
            cases.append((name, 4 * bucket, "bprefill"))
    return cases


# the launches under TOKEN_DISPATCH_MIN_ROWS that dispatch by token (an
# expected unread share of TOKEN_DISPATCH_MIN_UNREAD or more: ffn.py's chip
# table); every other launch of a registered configuration keeps the form
# PR 45 gave it
UNREAD = {
    ("dots3-note-prev", 32, "decode"), ("dots3-note-prev", 64, "prefill"),
    ("kanana-2-30b-a3b", 32, "decode"), ("kanana-2-30b-a3b", 64, "prefill"),
    ("mellum2-12b-a2.5b", 16, "decode"),
    ("k-exaone-236b-a23b", 32, "decode"),
    ("solar-open2-250b", 32, "decode"), ("solar-open2-250b", 64, "prefill"),
    # (PR 56: 128 picks over 64 experts leave an expected 13% unread)
    ("xing4.0-29b-a4b", 32, "decode"),
    # (PR 60: 192 picks over 128 experts leave an expected 21.5% unread)
    ("nemotron-3-nano-30b-a3b", 32, "decode"),
    # (PR 63: 160 picks over 72 experts leave an expected 9.1% unread)
    ("granite-4.0-h-small", 16, "decode"),
}


@pytest.mark.parametrize("name,rows,what", _registered())
def test_the_rule_at_every_registered_launch(name, rows, what):
    cfg, _ = ROUTED[name]
    form = moe_dispatch_form(rows, cfg.num_experts, cfg.num_experts_per_tok,
                             False, cfg.num_router_experts)
    if (name, rows, what) in UNREAD:
        assert form == "token" and rows < ffn.TOKEN_DISPATCH_MIN_ROWS
    else:
        assert form == (
            "token" if rows >= ffn.TOKEN_DISPATCH_MIN_ROWS else "dense")
    # a mesh keeps the dense einsums, whatever the rows
    assert moe_dispatch_form(rows, cfg.num_experts, cfg.num_experts_per_tok,
                             True, cfg.num_router_experts) == "dense"


@pytest.mark.parametrize("name", list(ROUTED))
def test_the_logit_checks_own_launches(name):
    """The benchmark's `correct` (benchmarks/serve.py, a driver's
    `served_logits`) prefills `check.n_prefill` rows in ONE launch and then
    decodes with ONE lane.  Its decode steps run the dense einsums in every
    cell (what Mixtral's and LFM2's timed decode runs); its prefill runs the
    grouped matmul in the four cells whose timed decode does (1,536 or 3,072
    rows; Mixtral's 64 keep dense, as its whole timed window does)."""
    cfg, _ = ROUTED[name]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        n_prefill = json.load(f).get("check", {}).get("n_prefill", 64)
    shape = (cfg.num_experts, cfg.num_experts_per_tok, False,
             cfg.num_router_experts)
    assert moe_dispatch_form(1, *shape) == "dense"
    assert moe_dispatch_form(n_prefill, *shape) == (
        "dense" if name == "mixtral-8x7b" else "token")
    if (name, ROUTED[name][1]["max_batch"], "decode") in UNREAD:
        assert n_prefill >= ffn.TOKEN_DISPATCH_MIN_ROWS


def test_the_rule_counts_the_experts_the_router_knows():
    # 32 lanes x top-8: of 32 experts held WHOLE nearly all are picked; of
    # the same 32 as a share of 256, a third are not
    assert moe_dispatch_form(32, 32, 8, False) == "dense"
    assert moe_dispatch_form(32, 32, 8, False, 256) == "token"
    # under the rows the chip table timed: a single stream keeps dense
    assert moe_dispatch_form(15, 64, 2, False) == "dense"
    assert moe_dispatch_form(16, 64, 2, False) == "token"
    # every held expert takes every row
    assert moe_dispatch_form(16, 2, 2, False) == "dense"
    # int8 experts: by token only where dense dispatch is compute-bound
    assert moe_dispatch_form(16, 64, 2, False, None, True) == "dense"
    assert moe_dispatch_form(
        ffn.TOKEN_DISPATCH_MIN_ROWS, 64, 2, False, None, True) == "token"


def test_the_block_traces_the_grouped_matmul_where_the_rule_says_token():
    cfg = ModelConfig(**MANY)
    lp = layer(cfg)

    def kernel(rows, sharded=False):
        x = jnp.zeros((rows, 1, cfg.hidden_size))
        text = str(jax.make_jaxpr(
            lambda x: _moe_block(x, lp, cfg, None, sharded)[0])(x))
        return "name=gmm" in text
    assert kernel(16) and not kernel(16, sharded=True)
    assert not kernel(8)
    assert moe_dispatch_form(256, 64, 2, False) == "dense"
    assert not kernel(256)


def test_int8_experts_keep_the_dense_form_at_decode():
    from kafka_tpu.models.quant import quantize_params

    # (no shared branch: quantize_params has no table for one)
    cfg = ModelConfig(**dict(SOFTMAX, num_experts=64, num_experts_per_tok=2))
    params = quantize_params(init_params(cfg, jax.random.PRNGKey(0)), cfg)
    assert ffn.experts_int8(params["layers"])
    ids = jnp.arange(16, dtype=jnp.int32)[:, None]
    text = str(jax.make_jaxpr(lambda p: forward(
        p, cfg, ids, jnp.zeros_like(ids), expert_reads=True))(params))
    assert "name=gmm" not in text
    *_, read = forward(params, cfg, ids, jnp.zeros_like(ids),
                       expert_reads=True)
    # (the tally: experts read, picks held here, all picks; held whole)
    assert int(read[0]) == cfg.num_experts * cfg.num_layers
    assert int(read[1]) == int(read[2]) == 16 * 2 * cfg.num_layers
    # ... and the host counts launches by the same answer
    progs = [step_programs.StepPrograms(cfg, None, 8, 16, 8, int8)
             for int8 in (False, True)]
    assert [p.moe_dispatch(16) for p in progs] == ["token", "dense"]
    assert [p.moe_dispatch(512) for p in progs] == ["token", "token"]


@pytest.mark.parametrize("lanes,counted", [(16, True), (8, False)])
def test_decode_programs_return_their_count_last(lanes, counted):
    """One output shape: the single step and the fused steps end with the
    tally (experts read, picks held here, all picks) where the blocks
    dispatch by token, with None where they read every held expert and the
    experts are held whole (no leaf: the dense program's text is the
    parent's, tests/test_moe_dispatch.py)."""
    cfg = ModelConfig(**MANY)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pools = jax.eval_shape(
        lambda: make_kv_pool_arrays(cfg, 64, 8, jnp.float32))

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32, u32, n = jnp.int32, jnp.float32, jnp.uint32, lanes
    state = step_programs.Lanes(
        page_table=of(i32, n, 8), last_tokens=of(i32, n),
        seq_lens=of(i32, n), active=of(jnp.bool_, n), temps=of(f32, n),
        top_ks=of(i32, n), top_ps=of(f32, n), seeds=of(u32, n))
    one = jax.eval_shape(step_programs._decode_fn(cfg, None, 8),
                         params, *pools, state, None)
    fused = jax.eval_shape(step_programs._multi_decode_fn(cfg, None, 8, 4),
                           params, *pools, state)
    assert len(one) == 5 and len(fused) == 6
    if counted:
        assert (one[-1].shape, fused[-1].shape) == ((3,), (4, 3))
        assert one[-1].dtype == fused[-1].dtype == jnp.int32
    else:
        assert one[-1] is None and fused[-1] is None


# ---------------------------------------------------------------------------
# what a decode pass read
# ---------------------------------------------------------------------------

def test_forward_tallies_the_layers_counts():
    """Sixteen lanes of a 2-layer model: the tally is the two layers' counts,
    and the logits are those of the pass that does not count."""
    cfg = ModelConfig(**MANY)
    params = init_params(cfg, jax.random.PRNGKey(7))
    ids = jnp.arange(3, 19, dtype=jnp.int32)[:, None]
    logits, _, read = forward(params, cfg, ids, jnp.zeros_like(ids),
                              expert_reads=True)
    plain, _ = forward(params, cfg, ids, jnp.zeros_like(ids))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain))
    k = cfg.num_experts_per_tok
    assert cfg.num_layers * k <= int(read[0]) <= cfg.num_layers * 16 * k
    # every row is real and every expert held: all the picks are held picks
    assert int(read[1]) == int(read[2]) == cfg.num_layers * 16 * k


def run_engine(cfg, params, max_batch, prompts, new=7, multi_step=1):
    eng = InferenceEngine(
        cfg, params, EngineConfig(
            max_batch=max_batch, page_size=8, num_pages=64,
            max_pages_per_seq=8, prefill_buckets=(16, 32),
            multi_step=multi_step), kv_dtype=jnp.float32)
    for rid, p in prompts.items():
        eng.submit(GenRequest(request_id=rid, prompt_ids=p,
                              max_new_tokens=new))
    done = eng.run_to_completion()
    return ({rid: done[rid].output_ids for rid in prompts},
            eng.metrics.snapshot(eng)["engine"])


PROMPTS = {"a": [5, 9, 77, 3, 14, 15, 92, 6], "b": [2, 71, 8, 28, 18]}


@pytest.mark.parametrize("multi_step", [1, 4])
def test_engine_counts_the_picks_a_decode_pass_read(multi_step):
    """Two experts' bias above all: every lane of every routed layer picks
    exactly those, so a pass reads 2 of 64 a layer whatever the tokens and
    however many of its 16 lanes are idle, and the device's count is a count
    one can make by hand."""
    fields = dict(MANY, num_layers=3, first_k_dense=1,
                  dense_intermediate_size=48)
    cfg = ModelConfig(**fields)
    params = init_params(cfg, jax.random.PRNGKey(5))
    bias = np.zeros(cfg.num_experts, np.float32)
    bias[[1, 40]] = 100.0
    params["layers"]["router_bias"] = jnp.broadcast_to(
        jnp.asarray(bias), params["layers"]["router_bias"].shape)
    toks, eng = run_engine(cfg, params, 16, PROMPTS, multi_step=multi_step)
    counts = eng["moe_dispatch"]
    # (every launch: the 16- and 32-row prefill buckets take the form too)
    assert counts["token_launches"] > 0 == counts["dense_launches"]
    routed = cfg.num_layers - cfg.first_k_dense
    passes = eng["moe_experts_held"] // (routed * cfg.num_experts)
    assert passes >= 6
    assert eng["moe_experts_held"] == passes * routed * cfg.num_experts
    assert eng["moe_experts_read"] == passes * routed * 2
    # and the tokens are the dense engine's (8 lanes: the rule says dense)
    dense_toks, dense = run_engine(cfg, params, 8, PROMPTS,
                                   multi_step=multi_step)
    assert toks == dense_toks
    assert dense["moe_experts_read"] == dense["moe_experts_held"] > 0


def test_engine_reads_at_most_what_it_holds():
    cfg = ModelConfig(**MANY)
    params = init_params(cfg, jax.random.PRNGKey(9))
    toks, eng = run_engine(cfg, params, 16, PROMPTS)
    k, held = cfg.num_experts_per_tok, cfg.num_experts
    passes = eng["moe_experts_held"] // (cfg.num_layers * held)
    assert passes > 0
    assert eng["moe_experts_held"] == passes * cfg.num_layers * held
    # a pass with a busy lane reads at least its k picks a layer, and the
    # two busy lanes' picks at most: the 14 idle lanes cause no read
    assert (passes * cfg.num_layers * k <= eng["moe_experts_read"]
            <= passes * cfg.num_layers * 2 * k)
    dense_toks, _ = run_engine(cfg, params, 8, PROMPTS)
    assert toks == dense_toks


def test_engine_counts_no_expert_for_a_model_with_no_routed_block():
    from kafka_tpu.models.config import CONFIGS

    cfg = CONFIGS["tiny"].replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    _, eng = run_engine(cfg, params, 2, {"a": PROMPTS["a"]})
    assert eng["moe_experts_read"] == eng["moe_experts_held"] == 0
