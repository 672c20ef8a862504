"""The routed block's two forms (models/ffn.py `_moe_block`): token dispatch
(picks sorted by expert, one grouped matmul a projection) against dense
dispatch (every row through every held expert), row for row in float32; the
rule that chooses between them from the pass's shape; pad rows in no group;
the programs the rule leaves dense lower as the parent's; the engine's
`moe_dispatch` counters."""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import forward, init_params
from kafka_tpu.models import ffn
from kafka_tpu.models.cache import KVCache
from kafka_tpu.models.config import CONFIGS, ModelConfig, config_from_hf_json
from kafka_tpu.models.ffn import (
    TOKEN_DISPATCH_MIN_ROWS,
    _moe_block,
    moe_dispatch_form,
)
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import step_programs
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROUTED = ("mixtral-8x7b", "mellum2-12b-a2.5b", "kanana-2-30b-a3b",
          "k-exaone-236b-a23b", "dots3-note-prev")

SOFTMAX = dict(name="routed", vocab_size=128, hidden_size=32,
               intermediate_size=48, num_layers=2, num_heads=4,
               num_kv_heads=2, head_dim=16, dtype="float32",
               tie_word_embeddings=False, num_experts=8,
               num_experts_per_tok=3)
SIGMOID = dict(SOFTMAX, moe_scoring="sigmoid", routed_scaling_factor=2.5,
               shared_intermediate_size=16)
# rows of a pass: over the rule's row count, on no tile's boundary
N = TOKEN_DISPATCH_MIN_ROWS
ROWS = N + 44
# (config, rows, what is done to the layer's leaves)
CASES = {
    "softmax": (SOFTMAX, ROWS, None),
    "sigmoid_shared": (SIGMOID, ROWS, None),
    # experts 8..11 of the router's 16: most picks land on other chips
    "held_share_offset": (dict(SIGMOID, num_experts=4, num_experts_routed=16,
                               expert_offset=8), ROWS, None),
    # two pairs of experts score alike on every row: k-th place is a tie
    "ties": (SOFTMAX, ROWS, "ties"),
    "ties_sigmoid": (SIGMOID, ROWS, "ties"),
    "an_expert_nobody_chose": (SIGMOID, ROWS, "starve"),
    # top-1 and one expert's bias above all: one group holds every pick
    "every_row_one_expert": (dict(SIGMOID, num_experts_per_tok=1), ROWS,
                             "crowd"),
    # an odd count of rows x 3 picks: no multiple of any row tile
    "rows_off_the_tile": (SOFTMAX, N + 1, None),
    "two_lanes": (SIGMOID, 2 * (N // 2 + 32), None),
}


def layer(cfg, how=None, key=0):
    lp = jax.tree.map(lambda a: a[0],
                      init_params(cfg, jax.random.PRNGKey(key))["layers"])
    if how == "ties":
        router = lp["router"]
        lp["router"] = router.at[:, 1].set(router[:, 0]).at[:, 5].set(
            router[:, 4])
        if "router_bias" in lp:
            lp["router_bias"] = jnp.zeros_like(lp["router_bias"])
    elif how == "starve":
        lp["router_bias"] = lp["router_bias"].at[2].set(-100.0)
    elif how == "crowd":
        lp["router_bias"] = lp["router_bias"].at[6].set(100.0)
    return lp


def both_forms(monkeypatch, x, lp, cfg, chunk_len=None):
    assert moe_dispatch_form(x.shape[0] * x.shape[1], cfg.num_experts,
                             cfg.num_experts_per_tok, False) == "token"
    token, _ = _moe_block(x, lp, cfg, chunk_len)
    monkeypatch.setattr(ffn, "TOKEN_DISPATCH_MIN_ROWS", 1 << 30)
    monkeypatch.setattr(ffn, "TOKEN_DISPATCH_MIN_UNREAD", 2.0)
    dense, _ = _moe_block(x, lp, cfg, chunk_len)
    monkeypatch.undo()
    return np.asarray(token), np.asarray(dense)


@pytest.mark.parametrize("case", list(CASES))
def test_token_form_equals_dense_form_row_for_row(monkeypatch, case):
    fields, rows, how = CASES[case]
    cfg = ModelConfig(**fields)
    lp = layer(cfg, how)
    lanes = 2 if case == "two_lanes" else 1
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (lanes, rows // lanes, cfg.hidden_size))
    token, dense = both_forms(monkeypatch, x, lp, cfg)
    # float32 on both sides: they differ by the order of k additions
    np.testing.assert_allclose(token, dense, rtol=1e-5, atol=1e-5)
    assert np.abs(dense).max() > 0.1
    # what the case says of the groups
    picks = ffn._routing_weights_sigmoid(
        x.reshape(rows, -1), lp["router"], lp["router_bias"],
        cfg.num_experts_per_tok, cfg.routed_scaling_factor, True
    )[0] if cfg.moe_scoring == "sigmoid" else ffn._routing_weights(
        x.reshape(rows, -1), lp["router"], cfg.num_experts_per_tok, True)[0]
    counts = np.bincount(np.asarray(picks).reshape(-1),
                         minlength=cfg.num_router_experts)
    if how == "starve":
        assert counts[2] == 0
    if how == "crowd":
        assert counts[6] == rows
    if how == "ties":
        # the lower index of each tied pair wins k-th place, as lax.top_k
        assert counts[0] >= counts[1] and counts[4] >= counts[5]
        assert counts[0] > counts[1] or counts[4] > counts[5]
    if cfg.num_experts_routed:
        held = counts[cfg.expert_offset:cfg.expert_offset + cfg.num_experts]
        assert 0 < held.sum() < counts.sum() / 2


@pytest.mark.parametrize("chunk_len", [
    jnp.int32(N - 55), jnp.asarray([97, 0], jnp.int32)])
def test_pad_rows_fall_in_no_group(monkeypatch, chunk_len):
    cfg = ModelConfig(**SIGMOID)
    lp = layer(cfg)
    lanes = int(chunk_len.size)
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (lanes, (N + 64) // lanes, cfg.hidden_size))
    masked, dense = both_forms(monkeypatch, x, lp, cfg, chunk_len)
    real = np.arange(x.shape[1])[None, :] < np.asarray(chunk_len).reshape(-1, 1)
    np.testing.assert_allclose(masked[real], dense[real], rtol=1e-5,
                               atol=1e-5)
    # a pad row's routed output is zero: what is left is the shared expert's
    with jax.named_scope("shared"):
        shared = np.asarray(ffn._mlp_block(x, lp, ("ws_g", "ws_u", "ws_d")))
    np.testing.assert_allclose(masked[~real], shared[~real], rtol=1e-6,
                               atol=1e-6)
    assert np.abs(dense[~real] - shared[~real]).max() > 0.1


def paged_prefill_logits(cfg, params, ids, bucket, mask_pads):
    """Logits of one paged prefill launch of `bucket` rows holding `ids`,
    through `prefill_plan` as the engine's program builds it."""
    ps, pages = 8, bucket // 8 + 2
    pools = make_kv_pool_arrays(cfg, pages + 1, ps, jnp.float32)
    chunk = np.zeros(bucket, np.int32)
    chunk[:len(ids)] = ids

    @jax.jit
    def run(params, pools, chunk, n):
        positions, paged = step_programs.prefill_plan(
            jnp.arange(1, pages + 1), jnp.int32(0), n, bucket, ps)
        if not mask_pads:
            paged = paged._replace(chunk_len=None)
        return forward(params, cfg, chunk[None], positions,
                       kv_cache=KVCache(*pools), paged=paged)[0]
    return np.asarray(run(params, pools, jnp.asarray(chunk),
                          jnp.int32(len(ids))))[0, :len(ids)]


def test_real_rows_logits_are_equal_with_pad_rows_in_no_group():
    cfg = ModelConfig(**SIGMOID)
    params = init_params(cfg, jax.random.PRNGKey(4))
    ids = np.random.RandomState(0).randint(0, 128, size=150)
    masked = paged_prefill_logits(cfg, params, ids, N, True)
    every = paged_prefill_logits(cfg, params, ids, N, False)
    np.testing.assert_allclose(masked, every, rtol=2e-5, atol=2e-5)
    # and the launch is the plain forward pass over the real rows
    plain = forward(params, cfg, jnp.asarray(ids)[None],
                    jnp.arange(len(ids))[None])[0][0]
    np.testing.assert_allclose(masked, np.asarray(plain), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# which form runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ROUTED)
def test_the_rule_at_every_routed_configuration(name):
    cfg = config_from_hf_json(
        os.path.join(ROOT, "benchmarks", "configs", name + ".json"))
    held, k = cfg.num_experts, cfg.num_experts_per_tok
    # a single stream, and the benchmark's one-lane logit check (the decode
    # lanes: tests/test_decode_token_dispatch.py)
    assert moe_dispatch_form(1, held, k, False,
                             cfg.num_router_experts) == "dense"
    for rows in (512, 2048):  # the large prefill buckets, lanes x bucket
        assert moe_dispatch_form(rows, held, k, False) == "token"
        assert moe_dispatch_form(rows, held, k, True) == "dense"  # a mesh
    assert moe_dispatch_form(TOKEN_DISPATCH_MIN_ROWS - 1, held, k,
                             False) == "dense"
    # every held expert takes every row: nothing for the sort to save
    assert moe_dispatch_form(2048, k, k, False) == "dense"


def test_the_block_traces_the_form_the_rule_names():
    cfg = ModelConfig(**SOFTMAX)
    lp = layer(cfg)

    def prims(rows, sharded):
        x = jnp.zeros((1, rows, cfg.hidden_size))
        text = str(jax.make_jaxpr(
            lambda x: _moe_block(x, lp, cfg, None, sharded)[0])(x))
        return "name=gmm" in text  # the grouped matmul's jitted entry
    rows = TOKEN_DISPATCH_MIN_ROWS
    assert prims(rows, False) and not prims(rows - 1, False)
    assert not prims(rows, True)


# sha256[:16] of the lowered text as the PARENT commit (b9b2e45, dense
# dispatch alone) lowers it: recorded by running `_decode_text` below,
# unchanged, as a test in a checkout of that commit (same conftest, same JAX).
# The four decode digests were recorded again at PR 51, which split the XLA
# decode walk's loop in two (ops/attention.py paged_decode_walk) and touched
# nothing of the routed block: the batched prefill, which holds no walk, still
# lowers to b9b2e45's text.
PARENT_TEXTS = {
    "tiny-moe.decode": "01d1a24dc76875c0",
    "tiny-moe.multi_decode": "1ea2ec63bd9eb61c",
    "sigmoid.decode": "a756d47f875b7af2",
    "sigmoid.multi_decode": "cdfb139cfaf42ae8",
    "sigmoid.bprefill": "335b8cfb25dab145",
}


def _decode_text(name, program):
    """Lowered text of the engine's single decode step, its fused 4-step
    program or a 2-lane 16-row batched prefill (all under the rule's row
    count) over a 64-page pool of 8-row pages, 4 lanes, from shapes alone."""
    cfg = {"tiny-moe": CONFIGS["tiny-moe"].replace(dtype="float32"),
           "sigmoid": ModelConfig(**SIGMOID)}[name]
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pools = jax.eval_shape(
        lambda: make_kv_pool_arrays(cfg, 64, 8, jnp.float32))

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    if program == "bprefill":
        fn = step_programs._batched_prefill_fn(cfg, None, 8, 16)
        args = (of(i32, 2, 8), of(i32, 2, 16), of(i32, 2), of(i32, 2),
                of(f32, 2), of(i32, 2), of(f32, 2), of(u32, 2),
                of(jnp.bool_, 2))
        return jax.jit(fn).lower(params, *pools, *args).as_text()
    lanes = step_programs.Lanes(
        page_table=of(i32, 4, 8), last_tokens=of(i32, 4),
        seq_lens=of(i32, 4), active=of(jnp.bool_, 4), temps=of(f32, 4),
        top_ks=of(i32, 4), top_ps=of(f32, 4), seeds=of(u32, 4))
    if program == "decode":
        fn = step_programs._decode_fn(cfg, None, 8)
        return jax.jit(fn).lower(params, *pools, lanes, None, None,
                                 None).as_text()
    fn = step_programs._multi_decode_fn(cfg, None, 8, 4)
    return jax.jit(fn).lower(params, *pools, lanes, None).as_text()


@pytest.mark.parametrize("key", list(PARENT_TEXTS))
def test_programs_under_the_row_count_lower_to_the_parents_text(key):
    """Decode and fused decode keep the dense block, routing and all: not an
    op of their programs moved."""
    digest = hashlib.sha256(
        _decode_text(*key.split(".")).encode()).hexdigest()[:16]
    assert digest == PARENT_TEXTS[key], (key, digest)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def run_engine(cfg, params, buckets, prompts, new=6):
    eng = InferenceEngine(
        cfg, params, EngineConfig(
            max_batch=2, page_size=8, num_pages=256,
            max_pages_per_seq=(N + 64) // 8,
            prefill_buckets=buckets), kv_dtype=jnp.float32)
    for rid, p in prompts.items():
        eng.submit(GenRequest(request_id=rid, prompt_ids=p,
                              max_new_tokens=new))
    done = eng.run_to_completion()
    return ({rid: done[rid].output_ids for rid in prompts},
            eng.metrics.snapshot(eng)["engine"]["moe_dispatch"])


def test_engine_counts_launches_by_form_and_tokens_do_not_move():
    cfg = ModelConfig(**SIGMOID)
    params = init_params(cfg, jax.random.PRNGKey(5))
    ids = np.random.RandomState(1).randint(0, 128, size=N + 40)
    prompts = {"a": [int(t) for t in ids[:N + 34]],
               "b": [int(t) for t in ids[100:140]]}
    wide, counts = run_engine(cfg, params, (8, 64, N), prompts)
    narrow, small = run_engine(cfg, params, (8, 32, 64), prompts)
    assert wide == narrow
    # "a" takes one N-row launch: the one pass at the row count
    assert counts["token_launches"] == 1 and counts["token_rows"] == N
    assert counts["dense_launches"] > 0
    assert small["token_launches"] == 0 == small["token_rows"]
    assert small["dense_rows"] > counts["dense_rows"]


def test_engine_counts_nothing_for_a_model_with_no_routed_block():
    cfg = CONFIGS["tiny"].replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    _, counts = run_engine(cfg, params, (8, 64, N),
                           {"a": list(range(1, 100))})
    assert set(counts) == {"token_launches", "token_rows", "dense_launches",
                           "dense_rows"}
    assert not any(counts.values())
