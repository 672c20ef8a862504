"""Latent prefill's key walk, the Pallas fold against the XLA fold (PR 34).

`models/llama.py::_latent_prefill_walk` is one algorithm with two executors
of a trip's fold: XLA ops (the `xla` backend's form and the reference here)
and `ops/pallas/latent_prefill.latent_prefill_fold` (the `pallas` backend's,
interpreted on the CPU).  At the tiny dots3 preset's two geometries, float32
with matmuls at "highest":

* the chosen-keys mask, the window with the walk started past chunk 0, padded
  bucket rows, a lane with no live key, three and more trips (the carry) and
  a batched launch give the XLA fold's output;
* the engine counts the walk's trips on the host as the device loop bounds
  them, all of them kernel trips on `pallas`, none on `xla`, and is token
  exact after a prefix hit on both backends.

Tolerance 2e-6 absolute on outputs of magnitude ~1: both folds multiply the
same float32 operands and keep the same f32 max / sum / accumulator per
1,024-key (here 16-key) trip; the kernel adds a score's nope and rope parts
as two dots where XLA contracts one concatenated key, and sums a tile's
probabilities over sublanes first, so only the order of f32 additions
differs (a few ulp of the largest term).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kafka_tpu.models import llama
from kafka_tpu.models.config import GLOBAL, WINDOWED
from kafka_tpu.ops.pallas import latent_prefill
from kafka_tpu.runtime import GenRequest
from test_engine import assert_greedy_consistent
from test_sparse_latent_attention import (
    TOPK,
    WINDOW,
    make_engine,
    sparse_cfg,
)

PS, PAGES, TABLE = 4, 48, 32  # page size, pool pages, page-table width
TRIP = 16                     # keys a trip in these tests
TOL = 2e-6


@pytest.fixture(autouse=True)
def short_trips(monkeypatch):
    """16-key trips, so a 100-key context is a walk of seven."""
    monkeypatch.setattr(llama, "PREFILL_WALK_KEYS", TRIP)


def _case(kind, spans, rows, seed=0):
    """One layer's pools and a bucket of `rows` queries a lane; lane i holds
    `spans[i]` = (start, chunk_len) (chunk_len 0: an inactive lane).  Rows
    no lane has written hold NaN."""
    cfg = sparse_cfg()
    g = cfg.geometry_of(kind)
    n, dn, dr, dv = (g.num_heads, g.qk_nope_head_dim, g.qk_rope_head_dim,
                     g.v_head_dim)
    rng = np.random.RandomState(seed)
    b, C = len(spans), TABLE * PS
    k_pool = np.full((PAGES * PS, g.kv_lora_rank), np.nan, np.float32)
    v_pool = np.full((PAGES * PS, dr), np.nan, np.float32)
    table = np.zeros((b, TABLE), np.int32)
    free = list(rng.permutation(np.arange(1, PAGES)))
    starts = np.asarray([s for s, _ in spans])
    lens = np.asarray([n_ for _, n_ in spans])
    for i, live in enumerate(starts + lens):
        for p in range(-(-live // PS)):
            table[i, p] = free.pop()
            rows_ = slice(table[i, p] * PS, (table[i, p] + 1) * PS)
            k_pool[rows_] = rng.randn(PS, g.kv_lora_rank)
            v_pool[rows_] = rng.randn(PS, dr)
    positions = starts[:, None] + np.arange(rows)[None, :]
    kv_pos = np.broadcast_to(np.arange(C), (b, C))
    kv_valid = (kv_pos < (starts + lens)[:, None]) & (lens > 0)[:, None]
    paged = llama.PagedView(
        write_idx=None, read_idx=None,
        kv_positions=jnp.asarray(kv_pos, jnp.int32),
        kv_valid=jnp.asarray(kv_valid), page_table=jnp.asarray(table),
        page_size=PS)
    chosen = None
    if cfg.has_indexer(kind):
        scores = jnp.asarray(rng.randn(b, rows, C).astype(np.float32))
        causal = kv_valid[:, None] & (kv_pos[:, None] <= positions[..., None])
        chosen = llama._chosen_mask(scores, jnp.asarray(causal), TOPK)
    return dict(
        q_nope=jnp.asarray(rng.randn(b, rows, n, dn), jnp.float32),
        q_rope=jnp.asarray(rng.randn(b, rows, n, dr), jnp.float32),
        wkvb=jnp.asarray(rng.randn(n, g.kv_lora_rank, dn + dv)
                         * g.kv_lora_rank ** -0.5, jnp.float32),
        k_cache=jnp.asarray(k_pool), v_cache=jnp.asarray(v_pool),
        paged=paged, positions=jnp.asarray(positions, jnp.int32),
        scale=float((dn + dr) ** -0.5), dn=dn, dr=dr,
        window=cfg.window_of(kind), chosen_of=chosen)


def _both(case):
    with jax.default_matmul_precision("highest"):
        return [np.asarray(jax.jit(
            lambda: llama._latent_prefill_walk(**case, kernel=kernel))())
            for kernel in (False, True)]


# (start, chunk_len) a lane; the bucket's rows
LAUNCHES = {
    "one-trip": ([(0, 8)], 8),
    "three-trips-the-carry": ([(37, 8)], 8),
    "seven-trips-padded-rows": ([(97, 5)], 16),
    "window-starts-past-chunk-0": ([(70, 8)], 8),
    "a-lane-with-no-live-key": ([(50, 8), (0, 0)], 8),
    "batched-launch": ([(90, 16), (3, 9), (41, 16)], 16),
}


@pytest.mark.parametrize("kind", [GLOBAL, WINDOWED])
@pytest.mark.parametrize("spans, rows", LAUNCHES.values(), ids=LAUNCHES)
def test_kernel_fold_is_the_xla_fold(kind, spans, rows):
    case = _case(kind, spans, rows)
    want, got = _both(case)
    assert want.shape == got.shape == case["q_nope"].shape[:3] + (16,)
    for i, (_, n) in enumerate(spans):
        # rows that hold a token are finite and equal; a lane with no live
        # key attends nothing and gives exact zeros on both
        if n == 0:
            assert not want[i].any() and not got[i].any()
            continue
        assert np.isfinite(want[i, :n]).all() and np.abs(want[i, :n]).max() > 0
        np.testing.assert_allclose(got[i, :n], want[i, :n], rtol=0, atol=TOL)


def test_the_masks_are_exercised():
    """The cases above mean what their names say: the full layer's queries
    keep TOPK of more keys, the sliding walk starts past chunk 0."""
    full = _case(GLOBAL, [(97, 5)], 16)
    kept = np.asarray(full["chosen_of"]).sum(-1)[0, :5]
    assert (kept == TOPK).all()
    assert (70 - WINDOW + 1) // TRIP == 4 > 0


def test_blocks_come_from_the_shapes():
    """Heads a step are a power of two that divides the heads and fits the
    budget; rows a step are the bucket up to ROW_BLOCK."""
    hb, sb, vmem = latent_prefill.fold_blocks(128, 512, 1024, 128, 64, 128, 2)
    assert 128 % hb == 0 and sb == 512 and vmem <= latent_prefill.VMEM_BUDGET
    assert latent_prefill.fold_blocks(64, 512, 1024, 192, 64, 128, 2)[0] >= 2
    assert latent_prefill.fold_blocks(3, 128, 1024, 128, 64, 128, 2)[0] == 1
    assert latent_prefill.fold_blocks(128, 2048, 1024, 128, 64, 128, 2)[1] == 512
    assert latent_prefill.fold_blocks(128, 640, 1024, 128, 64, 128, 2)[1] == 128
    with pytest.raises(ValueError, match="whole lane tiles"):
        args = [jnp.zeros(s, jnp.float32) for s in (
            (1, 2, 16, 520), (1, 2, 8, 520), (1, 2, 16, 16), (1, 16, 8),
            (1, 2, 16, 16), (1, 16, 520), (1, 2, 520), (1, 2, 520),
            (1, 2, 16, 520))]
        latent_prefill.latent_prefill_fold(*args, scale=1.0, interpret=True)


@pytest.fixture(scope="module")
def model():
    cfg = sparse_cfg()
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(5))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_counts_the_trips_the_device_loops(model, backend):
    """`/metrics` `engine.prefill_walk_trips` is launches x layers x trips by
    the device loop's own bounds; `prefill_walk_kernel_trips` equals it where
    the fold runs in the kernel and stays 0 where it runs in XLA."""
    cfg, params = model
    eng = make_engine(cfg, params, attention_backend=backend,
                      max_pages_per_seq=16)
    assert eng._programs.prefill_walk_trips([], 1, 8) == (0, 0)
    # one launch, 16-key trips over a 128-key table: 3 full layers walk
    # ceil(45 / 16) = 3 trips, 3 sliding ones from chunk (37 - 5 + 1) // 16
    # = 2 on: 3 * 3 + 3 * 1
    kernel = backend == "pallas"
    want = 12
    if not kernel:
        # the XLA fold shrinks a trip at many rows; 8 rows do not
        assert llama.prefill_walk_pages(16, 8, 8, False) == 2
    assert eng._programs.prefill_walk_trips([(37, 8)], 1, 8) == (
        want, want if kernel else 0)
    # a batched launch: the longest lane bounds the trips, the lowest start
    # the sliding layers' first chunk
    assert eng._programs.prefill_walk_trips(
        [(37, 8), (3, 8)], 4, 8)[0] == 3 * 3 + 3 * 3
    eng.submit(GenRequest(request_id="a", prompt_ids=list(range(1, 38)),
                          max_new_tokens=3))
    eng.run_to_completion()
    e = eng.metrics.snapshot(eng)["engine"]
    # 37 tokens in buckets of 32 + 8 (or 32 + 5 padded): two launches
    assert e["prefill_walk_trips"] == eng.prefill_walk_trips > 0
    assert e["prefill_walk_kernel_trips"] == (
        e["prefill_walk_trips"] if kernel else 0)


def test_a_model_that_does_not_walk_counts_nothing():
    from test_latent_attention import latent_cfg

    cfg = latent_cfg()
    eng = make_engine(cfg, llama.init_params(cfg, jax.random.PRNGKey(1)),
                      max_pages_per_seq=16)
    eng.submit(GenRequest(request_id="a", prompt_ids=[3, 5, 7, 11, 13],
                          max_new_tokens=2))
    eng.run_to_completion()
    e = eng.metrics.snapshot(eng)["engine"]
    assert (e["prefill_walk_trips"], e["prefill_walk_kernel_trips"]) == (0, 0)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefix_hit_then_suffix_prefill_is_token_exact(model, backend):
    """test_sparse_latent_attention's case on both backends, with short
    trips: a suffix prefill over a cached prefix walks several chunks of
    another thread's pages through the fold."""
    cfg, params = model
    eng = make_engine(cfg, params, attention_backend=backend,
                      max_pages_per_seq=16)
    rng = np.random.RandomState(24)
    shared = list(rng.randint(1, 128, size=24))
    first = GenRequest(request_id="A", prompt_ids=shared + [3, 7, 11],
                       max_new_tokens=4, prefix_key="thread-A")
    eng.submit(first)
    eng.run_to_completion()
    prompt = shared + list(rng.randint(1, 128, size=13))
    second = GenRequest(request_id="B", prompt_ids=prompt, max_new_tokens=8,
                        prefix_key="thread-B")
    eng.submit(second)
    eng.run_to_completion()
    assert second.cached_tokens >= 8 and second.cache_source == "cross"
    assert_greedy_consistent(cfg, params, prompt, second.output_ids)
    assert (eng.prefill_walk_kernel_trips == eng.prefill_walk_trips) == (
        backend == "pallas")
