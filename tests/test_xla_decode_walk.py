"""The XLA decode read walks the live context (ISSUE 32): at s == 1 with a
page table `models/llama.py::_attention_core` no longer gathers each lane's
static window but folds chunks of `DECODE_WALK_KEYS` keys into a running
softmax, contracting on the pool row's merged Hkv*D axis.  Held here: the
walk against `causal_attention` over the materialised window, the lowered
decode step's temporaries, and the engine's two counters of what the walk
read (`decode_keys_walked`, `decode_keys_window`) with the benchmark's
reader of their window delta.

Since ISSUE 51 the walk splits where the lanes' page tables part: the trips
whose pages every active lane names read them once for all lanes.  Held
here too: the split walk against the per-lane walk (the same rows under
page ids no two lanes share) and the static-window read, the `[1, ck,
Hkv*D]` read in the lowered step, `decode_keys_shared` against the device's
own bound, and an engine whose threads hang off one prefix."""

import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.cache import _kv_read_pages
from kafka_tpu.models.mixers.gqa import _attention_core
from kafka_tpu.models.quant import quantize_array
from kafka_tpu.ops.attention import (
    DECODE_WALK_KEYS,
    causal_attention,
    common_pages,
    decode_walk_pages,
    decode_walk_trips,
    paged_decode_walk,
)
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import step_programs
from kafka_tpu.runtime.step_programs import decode_plan

PS = 16
CK = DECODE_WALK_KEYS
READER = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "benchmarks", "layer_metrics",
                      "decode_window_read_share.py")


def make_pool(rng, slots, hd, kind):
    """A flat one-layer pool [slots, hd] of the given kind and the dtype
    attention runs in."""
    rows = rng.randn(slots, hd).astype(np.float32)
    if kind == "int8":
        return quantize_array(jnp.asarray(rows), (1,)), jnp.float32
    dt = jnp.bfloat16 if kind == "bf16" else jnp.float32
    return jnp.asarray(rows, dt), dt


def both_reads(q, k_pool, v_pool, table, lens, active, hkv, window, dt):
    """(the walk through _attention_core, causal_attention over the
    materialised static window): [B, Hq, D] each."""
    b, hq, d = q.shape
    cfg = ModelConfig(name="walk", num_heads=hq, num_kv_heads=hkv,
                      head_dim=d, attention_backend="xla")
    positions, paged = decode_plan(table, lens, active, PS)
    k_new = jnp.zeros((b, 1, hkv, d), dt)  # shapes only: rows are in the pool
    walk, _, _ = jax.jit(
        lambda q, kp, vp: _attention_core(
            q[:, None], k_new, k_new, cfg, positions, kp, vp, None, None,
            paged, None, 0, window))(q, k_pool, v_pool)
    ref = causal_attention(
        q[:, None],
        _kv_read_pages(k_pool, table, PS, dt).reshape(b, -1, hkv, d),
        _kv_read_pages(v_pool, table, PS, dt).reshape(b, -1, hkv, d),
        q_positions=positions, kv_positions=paged.kv_positions,
        kv_valid=paged.kv_valid, window=window)
    return (np.asarray(walk[:, 0], np.float32),
            np.asarray(ref[:, 0], np.float32))


def make_table(rng, lens, P, num_pages):
    """Distinct pages for every slot a lane holds (its new row's too), 0
    (the trash page) past them, as the engine's tables read; the pool's
    last page is never handed out."""
    free = list(rng.permutation(np.arange(1, num_pages - 1)))
    table = np.zeros((len(lens), P), np.int32)
    for b, n in enumerate(lens):
        for i in range(-(-(int(n) + 1) // PS)):
            table[b, i] = free.pop()
    return jnp.asarray(table)


@pytest.mark.parametrize("window", [None, 1024])
@pytest.mark.parametrize("pool_kind", ["bf16", "f32", "int8"])
@pytest.mark.parametrize("hq, hkv, d", [(32, 8, 128), (32, 4, 128),
                                        (8, 8, 64)])
def test_walk_is_the_static_window_read(hq, hkv, d, pool_kind, window):
    """Lanes that end mid-chunk, on a chunk's last key, on the next chunk's
    first, at 0 and at the table's last slot, over a table whose width is
    not a multiple of the chunk, and an inactive lane."""
    P = 100                       # 1,600 keys: the last chunk is ragged
    assert P % decode_walk_pages(P, PS) and P * PS > CK
    lens = [500, CK - 1, CK, 0, P * PS - 1, 1300]
    active = jnp.asarray([True] * 5 + [False])
    rng = np.random.RandomState(hq + hkv + d)
    num_pages = 420
    k_pool, dt = make_pool(rng, num_pages * PS, hkv * d, pool_kind)
    v_pool, _ = make_pool(rng, num_pages * PS, hkv * d, pool_kind)
    q = jnp.asarray(rng.randn(len(lens), hq, d).astype(np.float32), dt)
    table = make_table(rng, lens, P, num_pages)
    walk, ref = both_reads(q, k_pool, v_pool, table,
                           jnp.asarray(lens, jnp.int32), active, hkv,
                           window, dt)
    tol = 2e-2 if pool_kind == "bf16" else 2e-5
    np.testing.assert_allclose(walk[:5], ref[:5], rtol=tol, atol=tol)
    assert np.isfinite(walk).all()
    assert not walk[5].any()      # nothing to attend: zeros, not NaN


SMALL = dict(hq=8, hkv=2, d=16)   # HD 32: many keys for little memory


@pytest.mark.parametrize("name, P, lens, active", [
    ("mid_chunk", 128, [500, 1500, 7], [1, 1, 1]),
    ("chunk_edges", 128, [CK - 1, CK, 2 * CK - 1], [1, 1, 1]),
    ("all_at_zero", 128, [0, 0], [1, 1]),
    ("last_slot", 128, [128 * PS - 1, 3], [1, 1]),
    ("table_not_a_multiple_of_the_chunk", 100, [100 * PS - 1, CK, 3],
     [1, 1, 1]),
    ("table_narrower_than_a_chunk", 5, [5 * PS - 1, 17], [1, 1]),
    ("inactive_lane_longer_than_any_active", 128, [10, 2000, 300],
     [1, 0, 1]),
    ("all_lanes_inactive", 128, [10, 2000], [0, 0]),
])
def test_walk_bounds_and_masks(name, P, lens, active):
    """The loop's bound comes from ACTIVE lanes only: every table entry a
    lane does not hold names a page of NaNs, so a trip past the bound (or a
    chunk read for an inactive lane's sake) would poison the active lanes
    that ride it."""
    rng = np.random.RandomState(len(name))
    hq, hkv, d = SMALL["hq"], SMALL["hkv"], SMALL["d"]
    num_pages = 300
    k_pool, dt = make_pool(rng, num_pages * PS, hkv * d, "f32")
    v_pool, _ = make_pool(rng, num_pages * PS, hkv * d, "f32")
    q = jnp.asarray(rng.randn(len(lens), hq, d).astype(np.float32))
    table = np.array(make_table(rng, lens, P, num_pages))
    lens_a = jnp.asarray(lens, jnp.int32)
    active_a = jnp.asarray(active, bool)
    walk, ref = both_reads(q, k_pool, v_pool, jnp.asarray(table), lens_a,
                           active_a, hkv, None, dt)
    live = np.asarray(active, bool)
    np.testing.assert_allclose(walk[live], ref[live], rtol=2e-5, atol=2e-5)
    assert not walk[~live].any()
    # poison: chunks past the bound point at a page of NaNs
    ck = decode_walk_pages(P, PS) * PS
    trips = int(decode_walk_trips(lens_a, active_a, ck))
    want = -(-(max([n for n, a in zip(lens, active) if a], default=-1) + 1)
             // ck)
    assert trips == want
    poison = num_pages - 1
    assert not (table == poison).any()
    table[:, trips * ck // PS:] = poison
    k_bad = k_pool.at[poison * PS:(poison + 1) * PS].set(jnp.nan)
    v_bad = v_pool.at[poison * PS:(poison + 1) * PS].set(jnp.nan)
    walk_bad, _ = both_reads(q, k_bad, v_bad, jnp.asarray(table), lens_a,
                             active_a, hkv, None, dt)
    np.testing.assert_array_equal(walk_bad, walk)


def test_windowed_walk_drops_keys_older_than_the_window():
    """A sliding-window layer on the walk attends exactly `window` keys:
    moving every older row of the pool changes nothing."""
    rng = np.random.RandomState(3)
    hq, hkv, d = SMALL["hq"], SMALL["hkv"], SMALL["d"]
    P, num_pages, window = 128, 300, 1024
    lens = [1500, 2047, 900]
    k_pool, dt = make_pool(rng, num_pages * PS, hkv * d, "f32")
    v_pool, _ = make_pool(rng, num_pages * PS, hkv * d, "f32")
    q = jnp.asarray(rng.randn(len(lens), hq, d).astype(np.float32))
    table = make_table(rng, lens, P, num_pages)
    lens_a = jnp.asarray(lens, jnp.int32)
    on = jnp.ones(len(lens), bool)
    walk, ref = both_reads(q, k_pool, v_pool, table, lens_a, on, hkv,
                           window, dt)
    np.testing.assert_allclose(walk, ref, rtol=2e-5, atol=2e-5)
    # lane 0 attends positions 477..1500: its first 29 pages are dead
    dead = np.asarray(table)[0, :29]
    for pg in dead:
        k_pool = k_pool.at[pg * PS:(pg + 1) * PS].add(5.0)
        v_pool = v_pool.at[pg * PS:(pg + 1) * PS].add(5.0)
    moved, _ = both_reads(q, k_pool, v_pool, table, lens_a, on, hkv,
                          window, dt)
    np.testing.assert_array_equal(moved[0], walk[0])


# ----------------------------------------------------------------------
# the split: trips whose pages every active lane names are read once
# ----------------------------------------------------------------------

CP = CK // PS          # pages a trip
IDLE = ("idle",)       # a lane on the trash row, not active


def shared_case(rng, P, shared_pages, lanes):
    """(table, lens, active) over a prefix of `shared_pages` pages attached
    to every lane that says so.  `lanes`: (tokens held, active, pages of
    the prefix the lane names in its leading columns), or IDLE.  The rest
    of what a lane holds (its new row's page too) are pages of its own."""
    free = iter(rng.permutation(np.arange(1, 1 << 12)))
    prefix = [next(free) for _ in range(shared_pages)]
    table = np.zeros((len(lanes), P), np.int32)
    lens, active = [], []
    for row, lane in zip(table, lanes):
        if lane == IDLE:
            lens.append(0)
            active.append(False)
            continue
        n, on, attached = lane
        held = -(-(n + 1) // PS)
        attached = min(attached, held)
        row[:attached] = prefix[:attached]
        row[attached:held] = [next(free) for _ in range(held - attached)]
        lens.append(n)
        active.append(on)
    return table, np.asarray(lens, np.int32), np.asarray(active, bool)


def privately(table, k_pool, v_pool):
    """The same rows under page ids no two lanes share: every page a lane
    names is copied to a page of its own past the pool's end (the per-lane
    walk, forced: no column is common)."""
    table = np.array(table)
    pages = sorted(set(table.ravel()) - {0})
    first = k_pool.shape[0] // PS
    rows = (np.asarray(pages)[:, None] * PS + np.arange(PS)).ravel()
    copies = [(k_pool, v_pool)]
    out = np.zeros_like(table)
    for b, row in enumerate(table):
        moved = {pg: first + b * len(pages) + i for i, pg in enumerate(pages)}
        out[b] = [moved.get(pg, 0) for pg in row]
        copies.append((k_pool[rows], v_pool[rows]))
    return (out, jnp.concatenate([k for k, _ in copies]),
            jnp.concatenate([v for _, v in copies]))


def walk_of(q, k_pool, v_pool, table, lens, active, hkv, window,
            heads_batched=False):
    """`paged_decode_walk` as `_decode_walk` calls it, and the trips it
    shared: (out [B, Hq, D], own)."""
    dt = q.dtype

    def read_pages(pages):
        return (_kv_read_pages(k_pool, pages, PS, dt),
                _kv_read_pages(v_pool, pages, PS, dt))

    table, lens, active = map(jnp.asarray, (table, lens, active))
    out = jax.jit(lambda q: paged_decode_walk(
        q, read_pages, table, lens, active, page_size=PS, num_kv_heads=hkv,
        window=window, heads_batched=heads_batched))(q)
    return np.asarray(out, np.float32), device_shared_trips(table, lens,
                                                            active)


def device_shared_trips(table, lens, active):
    """The walk's own bound: the table's common leading pages in whole
    trips, within the trips the longest active lane needs."""
    table, lens, active = map(jnp.asarray, (table, lens, active))
    P = table.shape[1]
    cp = decode_walk_pages(P, PS)
    return min(int(common_pages(table, active)[1]) // cp,
               int(decode_walk_trips(lens, active, cp * PS)), -(-P // cp))


SPLITS = {
    # name: (P, shared pages, lanes, shared trips)
    "whole_trips_and_ragged_tails": (
        128, 2 * CP, [(2 * CK + 5, True, 2 * CP), (2 * CK + 700, True, 2 * CP),
                      (3 * CK - 1, True, 2 * CP), (3 * CK, True, 2 * CP)], 2),
    "a_remainder_under_one_trip": (
        128, CP + 10, [(CK + 300, True, CP + 10), (CK + 170, True, CP + 10),
                       (2 * CK + 9, True, CP + 10)], 1),
    "less_than_one_trip_in_common": (
        128, CP - 1, [(CK + 300, True, CP - 1), (700, True, CP - 1)], 0),
    "no_sharing": (128, 0, [(CK + 300, True, 0), (2 * CK, True, 0)], 0),
    "an_idle_and_a_prefilling_lane_inside_the_run": (
        128, 2 * CP, [IDLE, (2 * CK + 40, True, 2 * CP), (900, False, 0),
                      (2 * CK + 400, True, 2 * CP), IDLE], 2),
    "a_lane_of_another_prefix": (
        128, 2 * CP, [(2 * CK + 40, True, 2 * CP), (2 * CK + 90, True, 0),
                      (2 * CK + 400, True, 2 * CP)], 0),
    "a_lane_shorter_than_the_shared_run": (
        128, 3 * CP, [(3 * CK + 40, True, 3 * CP), (CK + 130, True, 3 * CP),
                      (3 * CK + 400, True, 3 * CP)], 1),
    "the_first_lane_inactive": (
        128, 2 * CP, [(2 * CK + 7, False, 0), (2 * CK + 40, True, 2 * CP),
                      (2 * CK + 300, True, 2 * CP)], 2),
    "a_lone_lane": (128, 0, [(2 * CK + 77, True, 0)], 3),
    "the_table_ends_in_a_ragged_trip": (
        100, 3 * CP, [(100 * PS - 1, True, 3 * CP), (3 * CK + 1, True, 3 * CP)],
        3),
    # the table's last, ragged trip goes lane by lane even for a lone lane
    "a_lone_lane_to_the_tables_ragged_end": (
        100, 0, [(100 * PS - 1, True, 0)], 3),
}


FORMS = ["whole_trips_and_ragged_tails", "a_remainder_under_one_trip",
         "an_idle_and_a_prefilling_lane_inside_the_run", "a_lone_lane"]
SPLIT_CASES = (
    [(name, None, False, "f32") for name in SPLITS]
    + [(name, 600, False, "f32") for name in FORMS]      # a windowed layer
    + [(name, None, True, "f32") for name in FORMS]      # heads on a mesh
    + [(FORMS[0], 600, True, "f32")]
    + [(name, None, False, kind) for name in (FORMS[0], FORMS[3])
       for kind in ("bf16", "int8")])


@pytest.mark.parametrize("name, window, heads_batched, pool_kind",
                         SPLIT_CASES)
def test_split_walk_is_the_per_lane_walk(name, window, heads_batched,
                                         pool_kind):
    """The split walk over tables that share their leading pages equals the
    per-lane walk over the same rows (page ids no two lanes share) and the
    static-window read; it shares the trips the case says, and the per-lane
    form none but a lone lane's."""
    P, shared_pages, lanes, want = SPLITS[name]
    rng = np.random.RandomState(len(name))
    hq, hkv, d = SMALL["hq"], SMALL["hkv"], SMALL["d"]
    table, lens, active = shared_case(rng, P, shared_pages, lanes)
    k_pool, dt = make_pool(rng, (1 << 12) * PS, hkv * d, pool_kind)
    v_pool, _ = make_pool(rng, (1 << 12) * PS, hkv * d, pool_kind)
    q = jnp.asarray(rng.randn(len(lanes), hq, d).astype(np.float32), dt)
    split, own = walk_of(q, k_pool, v_pool, table, lens, active, hkv, window,
                         heads_batched)
    assert own == want
    _, ref = both_reads(q, k_pool, v_pool, jnp.asarray(table),
                        jnp.asarray(lens), jnp.asarray(active), hkv, window,
                        dt)
    tol = 2e-2 if pool_kind == "bf16" else 2e-5
    np.testing.assert_allclose(split[active], ref[active], rtol=tol, atol=tol)
    assert not split[~active].any()
    if pool_kind == "int8":   # rows and scales: not copied page by page
        return
    apart, k_apart, v_apart = privately(table, k_pool, v_pool)
    lane_by_lane, none = walk_of(q, k_apart, v_apart, apart, lens, active,
                                 hkv, window, heads_batched)
    assert none == (want if len(lanes) == 1 else 0)
    np.testing.assert_allclose(split, lane_by_lane, rtol=tol, atol=tol)


def test_shared_trips_mask_each_lane_by_its_own_length():
    """Nothing rests on the shared pages being full: a lone lane shares
    every trip with itself, its last one ragged, and moving the rows past
    its length (and the trash page's) changes nothing."""
    rng = np.random.RandomState(5)
    hq, hkv, d = SMALL["hq"], SMALL["hkv"], SMALL["d"]
    table, lens, active = shared_case(rng, 128, 0, [(CK + 201, True, 0)])
    k_pool, dt = make_pool(rng, (1 << 12) * PS, hkv * d, "f32")
    v_pool, _ = make_pool(rng, (1 << 12) * PS, hkv * d, "f32")
    q = jnp.asarray(rng.randn(1, hq, d).astype(np.float32))
    clean, own = walk_of(q, k_pool, v_pool, table, lens, active, hkv, None)
    assert own == 2
    last = int(table[0, (CK + 201) // PS]) * PS
    past = slice(last + (CK + 201) % PS + 1, last + PS)
    for rows in (past, slice(0, PS)):
        k_pool, v_pool = k_pool.at[rows].add(5.0), v_pool.at[rows].add(5.0)
    dirty, _ = walk_of(q, k_pool, v_pool, table, lens, active, hkv, None)
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("name", list(SPLITS))
def test_host_counts_the_devices_shared_trips(name, steps):
    """`StepPrograms.decode_keys_shared` from each active lane's page list
    and length, as `_book_dispatch` hands them over, is the device's
    `own x ck` for every lane of the program, step by step of a fused
    dispatch (a lane grows a token a step; its pages are held already)."""
    P, shared_pages, lanes, want = SPLITS[name]
    table, lens, active = shared_case(np.random.RandomState(len(name)), P,
                                      shared_pages, lanes)
    if lens.max() + steps > P * PS:
        lens = np.minimum(lens, P * PS - steps)
    programs = step_programs.StepPrograms(_tiny(), None, PS, len(lanes), P)
    held = [([int(pg) for pg in row[:-(-(int(n) + steps) // PS)]], int(n))
            for row, n, on in zip(table, lens, active) if on]
    device = sum(len(lanes) * CK * device_shared_trips(table, lens + i, active)
                 for i in range(steps))
    assert programs.decode_keys_shared(held, steps) == device
    assert (steps > 1 or device == len(lanes) * want * CK)
    walked, _ = programs.decode_keys(int(lens[active].max()), steps)
    assert device <= walked
    # a decode that does not walk in XLA shares nothing
    pallas = step_programs.StepPrograms(
        ModelConfig(name="k", attention_backend="pallas"), None, PS,
        len(lanes), P)
    assert pallas.decode_keys_shared(held, steps) == 0


# ----------------------------------------------------------------------
# the lowered decode step
# ----------------------------------------------------------------------

GEOM = dict(B=3, P=80, num_pages=200)   # window 1,280 keys > one chunk


def _tiny():
    return ModelConfig(name="walk-step", vocab_size=128, hidden_size=32,
                       intermediate_size=64, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=16, dtype="float32",
                       attention_backend="xla")


def _tensor_sizes(text):
    sizes = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text):
        sizes.add(int(np.prod([int(x) for x in dims.split("x") if x])))
    return sizes


def test_decode_step_holds_no_temporary_of_the_static_window():
    """The guard that the mechanism is still there on a CPU: the lowered
    single-device decode step has a chunk-sized K/V temporary and none with
    the static window's B x C x Hkv*D elements; the verify program of the
    same geometry (s > 1: the materialising read) has the window's."""
    cfg = _tiny()
    B, P = GEOM["B"], GEOM["P"]
    hd = cfg.num_kv_heads * cfg.head_dim
    window_elems = B * P * PS * hd
    chunk_elems = B * CK * hd
    assert P * PS > CK
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = jnp.zeros((cfg.num_layers, GEOM["num_pages"] * PS, hd),
                     jnp.float32)
    i32 = jnp.int32
    lanes = step_programs.Lanes(
        jnp.zeros((B, P), i32), jnp.zeros(B, i32), jnp.zeros(B, i32),
        jnp.ones(B, bool), jnp.zeros(B), jnp.zeros(B, i32), jnp.ones(B),
        jnp.zeros(B, i32))
    decode = jax.jit(step_programs._decode_fn(cfg, None, PS)).lower(
        params, pool, pool, lanes, None).as_text()
    sizes = _tensor_sizes(decode)
    assert chunk_elems in sizes
    assert window_elems not in sizes
    assert "stablehlo.while" in decode
    # the shared trips' read: one chunk for all lanes, [1, ck, Hkv*D]
    assert f"tensor<1x{CK}x{hd}xf32>" in decode
    assert f"tensor<{B}x{CK}x{hd}xf32>" in decode
    verify = jax.jit(step_programs._verify_fn(cfg, None, PS, 2)).lower(
        params, pool, pool, lanes, jnp.zeros((B, 2), i32),
        jnp.zeros(B, i32)).as_text()
    assert window_elems in _tensor_sizes(verify)


# ----------------------------------------------------------------------
# the engine's counters and the benchmark's reader
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="walk-count", vocab_size=128, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def read():
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.dirname(os.path.dirname(READER)))  # readers
        spec = importlib.util.spec_from_file_location(
            "decode_window_read_share", READER)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return mod.read


def make_engine(model, **kw):
    cfg, params = model
    defaults = dict(max_batch=2, page_size=PS, num_pages=200,
                    max_pages_per_seq=128, prefill_buckets=(16, 512))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32)


def spy_dispatches(eng, monkeypatch):
    """Record (active lanes' lengths, steps, active lanes' page-table rows)
    of every decode dispatch."""
    seen = []
    book = eng._book_dispatch

    def spy(toks, members, steps):
        seqs = [m.seq for m in members if m is not None]
        rows = np.zeros((len(seqs), eng.ecfg.max_pages_per_seq), np.int32)
        for row, seq in zip(rows, seqs):
            row[:len(seq.pages)] = seq.pages
        seen.append(([seq.length for seq in seqs], steps, rows))
        return book(toks, members, steps)

    monkeypatch.setattr(eng, "_book_dispatch", spy)
    return seen


@pytest.mark.parametrize("multi_step", [1, 4])
@pytest.mark.parametrize("prompts", [(5,), (CK - 9,), (CK - 9, 40)])
def test_counters_are_the_device_loops_bound(model, monkeypatch, prompts,
                                             multi_step):
    """walked / window over a run equals what the device loop's bound
    (decode_walk_trips over the dispatched lengths, step by step of a fused
    dispatch) implies: one chunk of the 2,048-key window while every lane
    holds less than a chunk, more once one has crossed."""
    eng = make_engine(model, multi_step=multi_step)
    assert eng.cfg.attention_backend == "xla"
    seen = spy_dispatches(eng, monkeypatch)
    rng = np.random.RandomState(sum(prompts))
    for i, n in enumerate(prompts):
        eng.submit(GenRequest(request_id=f"r{i}",
                              prompt_ids=list(rng.randint(1, 128, size=n)),
                              max_new_tokens=14))
    eng.run_to_completion()
    B, C = 2, 128 * PS
    walked = window = shared = 0
    for lens, steps, rows in seen:
        on = jnp.ones(len(lens), bool)
        common = int(common_pages(jnp.asarray(rows), on)[1])
        for i in range(steps):
            trips = int(decode_walk_trips(
                jnp.asarray(lens, jnp.int32) + i, on, CK))
            walked += B * trips * CK
            window += B * C
            shared += B * min(common // CP, trips) * CK
    assert seen and window > 0
    assert (eng.decode_keys_walked, eng.decode_keys_window,
            eng.decode_keys_shared) == (walked, window, shared)
    snap = eng.metrics.snapshot(eng)["engine"]
    assert snap["decode_keys_walked"] == walked
    assert snap["decode_keys_window"] == window
    assert snap["decode_keys_shared"] == shared
    # distinct prompts share no page: a lane shares its trips with itself
    # while it decodes alone, two lanes together share none
    assert (shared == walked) == (len(prompts) == 1)
    crossed = max(prompts) + 14 > CK
    assert (walked / window > CK / C) == crossed
    assert walked / window == CK / C or crossed


def test_counters_stay_zero_on_a_pallas_engine(model, read):
    eng = make_engine(model, attention_backend="pallas")
    assert eng.cfg.attention_backend == "pallas"
    eng.submit(GenRequest(request_id="a", prompt_ids=[3, 5, 7, 11],
                          max_new_tokens=4))
    before = eng.metrics.snapshot(eng)
    eng.run_to_completion()
    after = eng.metrics.snapshot(eng)
    assert (eng.decode_keys_walked, eng.decode_keys_window,
            eng.decode_keys_shared) == (0, 0, 0)
    assert after["engine"]["decode_keys_shared"] == 0
    assert read({"before": before, "after": after}) is None


def test_reader_gives_the_windows_share_or_nothing(model, read):
    eng = make_engine(model)

    def turn(rid, n):
        eng.submit(GenRequest(request_id=rid,
                              prompt_ids=list(range(1, n + 1)),
                              max_new_tokens=6))
        eng.run_to_completion()

    turn("warm", 9)   # before the window: must not count
    before = eng.metrics.snapshot(eng)
    assert read({"before": before, "after": before}) is None  # no decode
    turn("a", 12)
    after = eng.metrics.snapshot(eng)
    assert read({"before": before, "after": after}) == pytest.approx(
        100.0 * CK / (128 * PS))
    # the parent's /metrics has no such counters
    for snap in (before, after):
        snap = dict(snap, engine={k: v for k, v in snap["engine"].items()
                                  if not k.startswith("decode_keys")})
        assert read({"before": snap, "after": snap}) is None


# ----------------------------------------------------------------------
# an engine whose threads hang off one prefix
# ----------------------------------------------------------------------


def run_threads(model, threads, together, monkeypatch):
    """Tokens of `threads` {rid: prompt} behind a prefix a first request
    left in the cache, submitted all at once or one at a time; and the
    engine, with the dispatches it booked."""
    eng = make_engine(model, multi_step=4, max_batch=3)
    eng.submit(GenRequest(request_id="first",
                          prompt_ids=threads["t0"][:1000] + [3, 7],
                          max_new_tokens=2, prefix_key="thread-first"))
    eng.run_to_completion()
    seen = spy_dispatches(eng, monkeypatch)
    reqs = {rid: GenRequest(request_id=rid, prompt_ids=p, max_new_tokens=14,
                            prefix_key="thread-" + rid)
            for rid, p in threads.items()}
    for req in reqs.values():
        eng.submit(req)
        if not together:
            eng.run_to_completion()
    eng.run_to_completion()
    for req in reqs.values():
        assert req.cached_tokens >= 992 and req.cache_source == "cross"
    return {rid: list(req.output_ids) for rid, req in reqs.items()}, eng, seen


def test_threads_on_one_prefix_give_the_tokens_of_each_alone(model,
                                                             monkeypatch):
    """Three threads over one cached prefix of 1,000 tokens (62 whole
    pages: one shared trip and a remainder) decode together in fused
    launches of 4 steps and cross the walk's second trip boundary (1,024
    keys) mid-program: the first trip is read once for the three, the
    tokens are those of each thread run alone (a lone lane shares every
    trip with itself), and /metrics counts the shared trips by the device's
    arithmetic."""
    rng = np.random.RandomState(51)
    shared = [int(t) for t in rng.randint(1, 128, size=1000)]
    threads = {f"t{i}": shared + [int(t) for t in rng.randint(1, 128, size=n)]
               for i, n in enumerate((13, 19, 5))}
    with pytest.MonkeyPatch.context() as mp:
        alone, eng_alone, _ = run_threads(model, threads, False, mp)
    both, eng, seen = run_threads(model, threads, True, monkeypatch)
    assert both == alone
    assert all(len(out) == 14 for out in both.values())
    together = [d for d in seen if len(d[0]) == 3]
    assert any(steps == 4 and max(lens) < 2 * CK <= max(lens) + steps
               for lens, steps, _ in together)
    for _, _, rows in together:
        assert int(common_pages(jnp.asarray(rows), jnp.ones(3, bool))[1]) == 62
    # one trip of two or three is shared while all decode; alone, all are
    assert 0 < eng.decode_keys_shared < eng.decode_keys_walked
    assert eng_alone.decode_keys_shared == eng_alone.decode_keys_walked
    assert eng.decode_keys_shared >= sum(
        3 * CK * steps for _, steps, _ in together) > 0
