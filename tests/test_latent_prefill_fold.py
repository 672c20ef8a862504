"""Latent prefill's key walk, the Pallas fold against the XLA fold (PR 34).

`models/mixers/latent.py::_latent_prefill_walk` is one algorithm with two executors
of a trip's fold: XLA ops (the `xla` backend's form and the reference here)
and `ops/pallas/latent_prefill.latent_prefill_fold` (the `pallas` backend's,
interpreted on the CPU).  At the tiny dots3 preset's two geometries, float32
with matmuls at "highest":

* the chosen-keys mask, the window with the walk started past chunk 0, padded
  bucket rows, a lane with no live key, three and more trips (the carry) and
  a batched launch give the XLA fold's output;
* a uniform latent model's dense causal walk (PR 37: Kanana-2's 32 heads, no
  window, no chosen keys) at buckets of 64 / 256 / 512 rows, over a context
  that ends inside a trip and one that ends on a trip's last key;
* the engine counts the walk's trips on the host as the device loop bounds
  them, all of them kernel trips on `pallas`, none on `xla`, for both kinds
  of latent model and none for a model that is not latent, and is token
  exact after a prefix hit on both backends;
* the prefill programs of a GQA model and of the sparse by-kind model lower
  to the text the parent commit (fe610bc) lowered them to.

Tolerance 2e-6 absolute on outputs of magnitude ~1: both folds multiply the
same float32 operands and keep the same f32 max / sum / accumulator per
1,024-key (here 16-key) trip; the kernel adds a score's nope and rope parts
as two dots where XLA contracts one concatenated key, and sums a tile's
probabilities over sublanes first, so only the order of f32 additions
differs (a few ulp of the largest term).
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kafka_tpu.models import init_params
from kafka_tpu.models.cache import PagedView
from kafka_tpu.models.mixers import latent
from kafka_tpu.models.mixers.index import _chosen_mask
from kafka_tpu.models.config import CONFIGS, GLOBAL, WINDOWED
from kafka_tpu.ops.pallas import latent_prefill
from kafka_tpu.runtime import GenRequest, step_programs
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays
from test_engine import assert_greedy_consistent
from test_latent_attention import TRIP, latent_cfg, short_trips  # noqa: F401
from test_sparse_latent_attention import (
    TOPK,
    WINDOW,
    make_engine,
    sparse_cfg,
)

PS, PAGES, TABLE = 4, 48, 32  # page size, pool pages, page-table width
TOL = 2e-6

# every test here walks in 16-key trips: a 100-key context is a walk of seven
pytestmark = pytest.mark.usefixtures("short_trips")


def _case(kind, spans, rows, seed=0, cfg=None, table_pages=TABLE):
    """One layer's pools and a bucket of `rows` queries a lane; lane i holds
    `spans[i]` = (start, chunk_len) (chunk_len 0: an inactive lane).  Rows
    no lane has written hold NaN."""
    cfg = cfg or sparse_cfg()
    g = cfg.geometry_of(kind)
    n, dn, dr, dv = (g.num_heads, g.qk_nope_head_dim, g.qk_rope_head_dim,
                     g.v_head_dim)
    rng = np.random.RandomState(seed)
    pages = table_pages + PAGES - TABLE
    b, C = len(spans), table_pages * PS
    k_pool = np.full((pages * PS, g.kv_lora_rank), np.nan, np.float32)
    v_pool = np.full((pages * PS, dr), np.nan, np.float32)
    table = np.zeros((b, table_pages), np.int32)
    free = list(rng.permutation(np.arange(1, pages)))
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
    paged = PagedView(
        write_idx=None, read_idx=None,
        kv_positions=jnp.asarray(kv_pos, jnp.int32),
        kv_valid=jnp.asarray(kv_valid), page_table=jnp.asarray(table),
        page_size=PS)
    chosen = None
    if cfg.has_indexer(kind):
        scores = jnp.asarray(rng.randn(b, rows, C).astype(np.float32))
        causal = kv_valid[:, None] & (kv_pos[:, None] <= positions[..., None])
        chosen = _chosen_mask(scores, jnp.asarray(causal), TOPK)
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
            lambda: latent._latent_prefill_walk(**case, kernel=kernel))())
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


# Kanana-2's block at a tiny width: 32 heads of [16 nope | 8 rope] over a
# 32-value latent, one kind of layer, no window and no indexer.  (start,
# chunk_len) of the one lane: the context ends 5 keys into a trip, or on a
# trip's last key with the bucket full
UNIFORM = {
    "64-rows-mid-trip": (64, (37, 64)),
    "64-rows-trip-boundary": (64, (32, 64)),
    "256-rows-mid-trip": (256, (41, 252)),
    "256-rows-trip-boundary": (256, (64, 256)),
    "512-rows-mid-trip": (512, (60, 505)),
    "512-rows-trip-boundary": (512, (48, 512)),
}


@pytest.mark.parametrize("rows, span", UNIFORM.values(), ids=UNIFORM)
def test_kernel_fold_is_the_xla_fold_on_a_dense_causal_walk(request, rows,
                                                            span):
    """What every latent model's paged prefill runs since PR 37: the walk
    with `window=None, chosen_of=None` from key 0 to the lane's last valid
    key, the bucket padded to whole lane tiles (64 -> 128 rows)."""
    cfg = latent_cfg(num_heads=32, num_kv_heads=32)
    case = _case(GLOBAL, [span], rows, seed=rows, cfg=cfg, table_pages=160)
    assert case["window"] is None and case["chosen_of"] is None
    assert (sum(span) % TRIP == 0) == ("boundary" in request.node.name)
    want, got = _both(case)
    n = span[1]
    assert want.shape == got.shape == (1, rows, 32, 16)
    assert np.isfinite(want[0, :n]).all() and np.abs(want[0, :n]).max() > 0
    np.testing.assert_allclose(got[0, :n], want[0, :n], rtol=0, atol=2e-5)


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
    return cfg, init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def uniform_model():
    """Six latent layers of one kind (a dense one, then five routed)."""
    cfg = latent_cfg(num_layers=6)
    return cfg, init_params(cfg, jax.random.PRNGKey(5))


# one launch, 16-key trips over a 128-key table.  sparse: 3 full layers walk
# ceil(45 / 16) = 3 trips, 3 sliding ones from chunk (37 - 5 + 1) // 16 = 2
# on; batched, the longest lane bounds the trips and the lowest start the
# sliding layers' first chunk.  uniform: 6 layers x 3 trips, no sliding term
WALKS = {"sparse": (3 * 3 + 3 * 1, 3 * 3 + 3 * 3), "uniform": (6 * 3, 6 * 3)}


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("which", sorted(WALKS))
def test_engine_counts_the_trips_the_device_loops(request, which, backend):
    """`/metrics` `engine.prefill_walk_trips` is launches x layers x trips by
    the device loop's own bounds; `prefill_walk_kernel_trips` equals it where
    the fold runs in the kernel and stays 0 where it runs in XLA."""
    cfg, params = request.getfixturevalue(
        "model" if which == "sparse" else "uniform_model")
    eng = make_engine(cfg, params, attention_backend=backend,
                      max_pages_per_seq=16)
    assert eng._programs.prefill_walk_trips([], 1, 8) == (0, 0)
    kernel = backend == "pallas"
    want, batched = WALKS[which]
    if not kernel:
        # the XLA fold shrinks a trip at many rows; 8 rows do not
        assert latent.prefill_walk_pages(16, 8, 8, False) == 2
    assert eng._programs.prefill_walk_trips([(37, 8)], 1, 8) == (
        want, want if kernel else 0)
    assert eng._programs.prefill_walk_trips(
        [(37, 8), (3, 8)], 4, 8)[0] == batched
    eng.submit(GenRequest(request_id="a", prompt_ids=list(range(1, 38)),
                          max_new_tokens=3))
    eng.run_to_completion()
    e = eng.metrics.snapshot(eng)["engine"]
    # 37 tokens in buckets of 32 + 8 (or 32 + 5 padded): two launches
    assert e["prefill_walk_trips"] == eng.prefill_walk_trips > 0
    assert e["prefill_walk_kernel_trips"] == (
        e["prefill_walk_trips"] if kernel else 0)


def test_a_model_that_does_not_walk_counts_nothing():
    cfg = CONFIGS["tiny"].replace(dtype="float32")
    eng = make_engine(cfg, init_params(cfg, jax.random.PRNGKey(1)),
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


# ---------------------------------------------------------------------------
# what PR 37 leaves alone: prefill programs that never took the form it removed
# ---------------------------------------------------------------------------

# sha256[:16] of the lowered text as the PARENT commit (fe610bc, before every
# latent model walked) lowers it: recorded by running `_prefill_text` below,
# unchanged, as a test in a checkout of that commit (same conftest, same JAX)
PARENT_TEXTS = {
    "gqa.xla.prefill": "6c26c2dee962962f",
    "gqa.xla.bprefill": "e9677dad74f5b5fb",
    # (the one program of the eight that calls the flash-prefill kernel,
    # whose chunk step PR 44 rewrote: this digest is PR 44's text, recorded
    # the same way)
    "gqa.pallas.prefill": "b608355daa0eae32",
    "gqa.pallas.bprefill": "e9677dad74f5b5fb",
    # (the four that trace the indexer's selection, whose threshold search
    # PR 61 moved into `mixers/index._threshold` (one loop body for both
    # searches, `acc | (holds << shift)` for the parent's `where`): these
    # digests are PR 61's text, recorded the same way; PR 49's, with the
    # walk's scores held trip-major, were d7293a9eab2bdb2e,
    # 1bcc39c46b242d29, 59854056271026e8, 331da4948836c36a)
    "sparse.xla.prefill": "2dd0465094958bbb",
    "sparse.xla.bprefill": "5fff9374fb2d3fa6",
    "sparse.pallas.prefill": "7c6d53d7c9c299dd",
    "sparse.pallas.bprefill": "35cd5d1d02816fc1",
}


def _prefill_text(name, backend, program):
    """Lowered text of the engine's single (`prefill`) or batched
    (`bprefill`, 2 lanes) prefill program at a 16-row bucket over a 64-page
    pool of 8-row pages, from shapes alone."""
    cfg = {"gqa": CONFIGS["tiny"], "sparse": sparse_cfg()}[name].replace(
        dtype="float32", attention_backend=backend)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pools = jax.eval_shape(
        lambda: make_kv_pool_arrays(cfg, 64, 8, jnp.float32))

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    i32, f32 = jnp.int32, jnp.float32
    if program == "prefill":
        fn = step_programs._prefill_fn(cfg, None, 8, 16)
        args = (of(i32, 8), of(i32, 16), of(i32), of(i32), of(f32), of(i32),
                of(f32), of(jnp.uint32, 1), None)
    else:
        fn = step_programs._batched_prefill_fn(cfg, None, 8, 16)
        args = (of(i32, 2, 8), of(i32, 2, 16), of(i32, 2), of(i32, 2),
                of(f32, 2), of(i32, 2), of(f32, 2), of(jnp.uint32, 2),
                of(jnp.bool_, 2))
    return jax.jit(fn).lower(params, *pools, *args).as_text()


@pytest.mark.parametrize("key", [
    f"{name}.{backend}.{program}" for name in ("gqa", "sparse")
    for backend in ("xla", "pallas") for program in ("prefill", "bprefill")])
def test_prefill_programs_that_did_not_change_lower_to_the_parents_text(key):
    """A GQA model never traces the latent block and the sparse by-kind
    model already walked with the same arguments: the selection's new
    condition changes neither program."""
    digest = hashlib.sha256(
        _prefill_text(*key.split(".")).encode()).hexdigest()[:16]
    assert digest == PARENT_TEXTS[key], (key, digest)
