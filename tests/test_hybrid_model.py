"""A hybrid decoder on the served path (ISSUE 38; `phi4flash`,
Phi-4-mini-flash-reasoning): state-space layers whose per-thread state lives
in a state slot beside the pages, sliding and full differential attention,
gated memory units and cross attention over one full cache.

CPU, float32, tiny widths with the published layout (L = 8: three Mamba
layers, two sliding, one full, one gated memory unit, one cross layer),
seeded weights, against the plain reference `benchmarks/references/
phi4flash.py` (written from the equations, imports nothing of kafka_tpu).

TOLERANCES.  `forward` and the reference do the same float32 arithmetic in
another order (stacked einsums against per-layer loops, a kernel against a
scan): they agree to ~4e-6 relative RMS of the logits.  REF_TOL = 1e-4 leaves
25x room for the order of summation and is far under what any missing
mechanism costs at these sizes, which `test_reference_variants_exceed_tol`
holds: a state rounded to bfloat16 (>= 2.8e-3 at the median position), a
dropped D x (>= 0.79), no sub-layer norm (>= 0.39), a state zeroed at a chunk
boundary (0.15 at the median).  Engine tests compare TOKENS, greedy, against
the uncached forward: exact.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.models.config import (
    CROSS, GLOBAL, GMU, MAMBA, WINDOWED, UnsupportedConfigError,
    config_from_hf_json,
)
from kafka_tpu.models import hybrid
from kafka_tpu.models.cache import HybridPathError, KVCache
from kafka_tpu.models.llama import init_kv_cache
from kafka_tpu.ops.pallas import (
    paged_decode_attention, paged_decode_attention_window,
    paged_prefill_attention,
)
from kafka_tpu.ops.pallas.selective_scan import selective_scan
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.engine import RecurrentStateUnsupported
from kafka_tpu.runtime.kv_cache import (
    PagePool, StatePool, make_kv_pool_arrays,
)
from kafka_tpu.runtime.metrics import STATE_METRIC_KEYS
from kafka_tpu.runtime.prefix_cache import PrefixCache
from kafka_tpu.runtime.step_programs import StepPrograms, decode_plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF_TOL = 1e-4


def _load(folder, name):
    path = os.path.join(ROOT, "benchmarks", folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{folder}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "phi4flash")
drv = _load("drivers", "phi4flash_pool")


def layout(n):
    own = n // 2 + 2
    return tuple(
        (MAMBA if i % 2 == 0 else GLOBAL if i == own - 1 else WINDOWED)
        if i < own else (GMU if i % 2 == 0 else CROSS) for i in range(n))


def tiny_cfg(backend="xla", **kw):
    base = dict(
        name="tiny-phi4flash", vocab_size=300, hidden_size=64,
        intermediate_size=128, num_layers=8, num_heads=8, num_kv_heads=4,
        head_dim=8, layer_types=layout(8), sliding_window=8,
        mamba_d_state=16, mamba_dt_rank=4, dtype="float32",
        tie_word_embeddings=True, attention_backend=backend)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


_UNCACHED = {}


def assert_greedy_consistent(cfg, params, prompt, out, pad=192):
    """`out` is the greedy continuation of `prompt` under ONE uncached
    forward (test_engine's check), padded to a fixed length so that the
    module compiles it once: the model is causal, the padding comes after."""
    seq = list(prompt) + list(out)
    assert len(seq) <= pad
    fn = _UNCACHED.setdefault(cfg, jax.jit(lambda p, x: jnp.argmax(forward(
        p, cfg, x, jnp.arange(pad, dtype=jnp.int32)[None])[0][0], axis=-1)))
    preds = np.asarray(fn(params, jnp.asarray(
        [seq + [0] * (pad - len(seq))], jnp.int32)))
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert preds[i] == seq[i + 1], (
            f"divergence at position {i}: engine={seq[i + 1]} ref={preds[i]}")


def rel_rms(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (np.sqrt(np.mean((a - b) ** 2, axis=-1))
            / np.sqrt(np.mean(b ** 2, axis=-1)))


def tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 300, n)]


# ---------------------------------------------------------------------------
# the configuration
# ---------------------------------------------------------------------------

PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}


def _cfg_of(tmp_path, **over):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(PUBLISHED, **over)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json(tmp_path):
    cfg = _cfg_of(tmp_path)
    kinds = cfg.layer_types
    assert len(kinds) == 32 and kinds == layout(32)
    assert [kinds.count(k) for k in (MAMBA, WINDOWED, GLOBAL, GMU, CROSS)] \
        == [9, 8, 1, 7, 7]
    assert kinds[16] == MAMBA and kinds[17] == GLOBAL and kinds[18] == GMU
    assert cfg.has_state and cfg.state_layers == 9 and cfg.kv_layers == 9
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank) \
        == (5120, 16, 160)
    assert cfg.head_dim == 64 and cfg.sliding_window == 512
    assert cfg.rms_norm_eps == 1e-5 and cfg.tie_word_embeddings
    # 9 row-holding layers: 9 x (1280 + 1280) values a token
    assert cfg.kv_values_per_token == 9 * 2 * 1280
    # ~0.39 MB a layer, ~3.5 MB a thread (conv tail + h, float32)
    assert cfg.state_bytes_per_slot == 9 * 4 * (3 * 5120 + 16 * 5120)


@pytest.mark.parametrize("over", [
    {"hidden_act": "gelu"}, {"mb_per_layer": 4}, {"mlp_bias": True},
    {"lm_head_bias": True}, {"tie_word_embeddings": False},
    {"num_hidden_layers": 30}, {"sliding_window": [512, None]},
    {"rope_scaling": {"factor": 2.0}},
], ids=lambda o: next(iter(o)))
def test_config_refuses_by_key(tmp_path, over):
    with pytest.raises(UnsupportedConfigError, match=next(iter(over))):
        _cfg_of(tmp_path, **over)


def test_layout_is_checked():
    with pytest.raises(UnsupportedConfigError, match="unknown kinds"):
        tiny_cfg(mamba_d_state=0)  # mamba layers without the hybrid fields
    bad = list(layout(8))
    bad[1], bad[5] = bad[5], bad[1]  # the full layer among the sliding ones
    with pytest.raises(UnsupportedConfigError, match="hybrid decoder"):
        tiny_cfg(layer_types=tuple(bad))
    with pytest.raises(HybridPathError):
        init_kv_cache(tiny_cfg(), 1, 16)


def test_pool_holds_rows_for_the_attention_layers_only(model):
    cfg, _ = model
    k, v = make_kv_pool_arrays(cfg, 5, 16, state_slots=7)
    n = cfg.kv_layers
    assert n == 3 and cfg.num_layers == 8
    assert k.shape == v["v"].shape == (n, 80, 32)
    assert v["conv"].shape == (3, 7, 3, 128) and v["conv"].dtype == jnp.float32
    assert v["ssm"].shape == (3, 7, 16, 128) and v["ssm"].dtype == jnp.float32
    assert cfg.kv_row_widths(CROSS) == () and cfg.kv_row_widths(MAMBA) == ()


# ---------------------------------------------------------------------------
# forward against the reference
# ---------------------------------------------------------------------------

def test_full_forward_logits(model):
    cfg, params = model
    ids = tokens(40)
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(lambda p, t: forward(
            p, cfg, t, jnp.arange(t.shape[1])[None]))(
                params, jnp.asarray(ids)[None])
    want = ref.reference_logits(params, ref.hyper(cfg), ids, list(range(40)))
    assert cache is None
    assert rel_rms(logits[0], want["logits"]).max() < REF_TOL


def test_reference_variants_exceed_tol(model):
    """What the tolerance can tell: each mechanism taken out of the
    reference moves the logits by far more than REF_TOL."""
    cfg, params = model
    ids = tokens(40)
    hp = ref.hyper(cfg)
    base = ref.reference_logits(params, hp, ids, list(range(40)))["logits"]
    for name, variant in ref.variants(hp).items():
        if "zero_state_at" in variant:
            variant = dict(variant, zero_state_at=16)
        got = ref.reference_logits(params, variant, ids, list(range(40)))
        err = rel_rms(got["logits"], base)
        assert np.median(err) > 20 * REF_TOL, (name, float(np.median(err)))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefill_then_decode_through_pages_and_state(model, backend):
    """Two prefill launches (96 rows in a bucket of 128, then 48 in one of
    64, resumed from a SNAPSHOT slot), then decode in the lane's slot, across the window (8) and page
    (16) boundaries; Pallas: the decode and flash-prefill kernels with the
    differential pairing and the scan kernel, interpreted."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    ids = tokens(154, seed=1)
    want = ref.reference_logits(params, ref.hyper(cfg), ids,
                                list(range(143, 154)))
    with jax.default_matmul_precision("highest"):
        got = drv.served_logits(params, cfg, ids, 144, page_size=16,
                                pages_per_seq=12)
    assert rel_rms(got, want["logits"]).max() < REF_TOL


def _prefill(params, cfg, ids, sizes, pools=None, zero_at=None):
    """Prefill `ids` in launches of `sizes` rows (bucket 64), lane slot 0;
    `zero_at`: the launch that starts there reads a ZERO state."""
    k_pool, v_pool = pools or make_kv_pool_arrays(cfg, 13, 16, state_slots=3)
    page_row = jnp.arange(1, 13, dtype=jnp.int32)
    pre = jax.jit(drv.prefill_chunk, static_argnums=(1,),
                  static_argnames=("page_size",))
    start = 0
    for n in sizes:
        chunk = np.zeros(64, np.int32)
        chunk[:n] = ids[start:start + n]
        src = 2 if start == zero_at else 0  # slot 2 is never written
        logits, k_pool, v_pool = pre(
            params, cfg, k_pool, v_pool, page_row, jnp.asarray(chunk),
            jnp.int32(start), jnp.int32(n), jnp.int32(src), jnp.int32(0),
            jnp.int32(1), page_size=16)
        start += n
    return np.asarray(logits), k_pool, v_pool


def test_chunks_equal_one_chunk_and_padding_leaves_state(model):
    """A prompt prefilled 64 at once equals 40 + 24 and 7 + 33 + 24 (padded
    rows of a bucket pass the state through): same last-row logits, same
    state in the lane's slot."""
    cfg, params = model
    ids = tokens(64, seed=2)
    with jax.default_matmul_precision("highest"):
        one, _, v1 = _prefill(params, cfg, ids, [64])
        for sizes in ([40, 24], [7, 33, 24]):
            got, _, v = _prefill(params, cfg, ids, sizes)
            assert rel_rms(got, one) < REF_TOL
            for leaf in ("conv", "ssm"):
                np.testing.assert_allclose(
                    v[leaf][:, 0], v1[leaf][:, 0], rtol=1e-4, atol=1e-5)


def test_zeroed_state_at_a_chunk_boundary_fails(model):
    """The mutation: the second launch reads a zero state instead of what
    the first left.  The comparison that passes above must FAIL."""
    cfg, params = model
    ids = tokens(64, seed=2)
    want = ref.reference_logits(params, ref.hyper(cfg), ids, [63])["logits"][0]
    with jax.default_matmul_precision("highest"):
        good, _, _ = _prefill(params, cfg, ids, [40, 24])
        bad, _, _ = _prefill(params, cfg, ids, [40, 24], zero_at=40)
    assert rel_rms(good, want) < REF_TOL
    assert rel_rms(bad, want) > 100 * REF_TOL


def test_inactive_lanes_leave_state_untouched(model):
    """Decode with lane 1 inactive, and a batched prefill with lane 1
    inactive: every bit of lane 1's slot stays."""
    cfg, params = model
    k_pool, v_pool = make_kv_pool_arrays(cfg, 9, 16, state_slots=4)
    mark = jax.random.normal(jax.random.PRNGKey(3), v_pool["ssm"].shape)
    v_pool = dict(v_pool, ssm=mark, conv=v_pool["conv"] + 0.5)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    _, _, v_new = jax.jit(drv.decode_step, static_argnums=(1,),
                          static_argnames=("page_size",))(
        params, cfg, k_pool, v_pool, table, jnp.asarray([5, 6]),
        jnp.asarray([3, 9]), jnp.asarray([True, False]), page_size=16)
    for leaf in ("conv", "ssm"):
        assert np.array_equal(v_new[leaf][:, 1], v_pool[leaf][:, 1]), leaf
        assert not np.array_equal(v_new[leaf][:, 0], v_pool[leaf][:, 0]), leaf
        assert np.array_equal(v_new[leaf][:, 2:], v_pool[leaf][:, 2:]), leaf
    fn = StepPrograms(cfg, None, 16, 2, 4).batched_prefill(16, 2)
    z2 = jnp.zeros(2, jnp.int32)
    _, v_new, _ = fn(
        params, jnp.copy(k_pool), jax.tree.map(jnp.copy, v_pool), table,
        jnp.ones((2, 16), jnp.int32), z2, jnp.asarray([9, 7]),
        jnp.zeros(2), z2, jnp.ones(2), jnp.zeros(2, jnp.uint32),
        jnp.asarray([True, False]), jnp.asarray([0, 1]), jnp.asarray([3, 2]))
    for leaf in ("conv", "ssm"):
        # (the engine gives an inactive lane the trash slot for both; here
        # slot 2 takes lane 1's "snapshot": a copy of what it read)
        assert np.array_equal(v_new[leaf][:, 1], v_pool[leaf][:, 1]), leaf
        assert np.array_equal(v_new[leaf][:, 2], v_pool[leaf][:, 1]), leaf
        # lane 0's state went to its slot AND to its snapshot slot
        assert np.array_equal(v_new[leaf][:, 0], v_new[leaf][:, 3]), leaf
        assert not np.array_equal(v_new[leaf][:, 0], v_pool[leaf][:, 0]), leaf


def test_fused_multi_decode_equals_single_steps(model):
    cfg, params = model
    progs = StepPrograms(cfg, None, 16, 2, 4)
    from kafka_tpu.runtime.step_programs import Lanes

    def pools():
        k, v = make_kv_pool_arrays(cfg, 9, 16, state_slots=4)
        return k, dict(v, ssm=v["ssm"] + 0.25)

    lanes = Lanes(jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32),
                  jnp.asarray([5, 6]), jnp.asarray([3, 20]),
                  jnp.asarray([True, True]), jnp.zeros(2),
                  jnp.zeros(2, jnp.int32), jnp.ones(2),
                  jnp.zeros(2, jnp.uint32))
    k, v, toks, last, lens, _ = progs.multi_decode(4)(
        params, *pools(), lanes)
    k1, v1 = pools()
    step, seq = lanes, []
    for _ in range(4):
        k1, v1, t, n, _ = progs.decode()(params, k1, v1, step, None)
        seq.append(np.asarray(t))
        step = step._replace(last_tokens=t, seq_lens=n)
    assert np.array_equal(np.asarray(toks), np.stack(seq))
    assert np.array_equal(np.asarray(lens), np.asarray(n))
    for leaf in ("conv", "ssm"):
        np.testing.assert_allclose(v[leaf], v1[leaf], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(k, k1, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# differential attention
# ---------------------------------------------------------------------------

def _diff_loop(q, k, v, lp, layer, window):
    """Differential attention of one sequence as the equations say it, in
    numpy float64 loops.  q [S, Hq, D], k / v [S, Hkv, D] -> [S, Hq/2, 2D]."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    l0 = 0.8 - 0.6 * np.exp(-0.3 * layer)
    lam = (np.exp(np.dot(lp["lq1"], lp["lk1"]))
           - np.exp(np.dot(lp["lq2"], lp["lk2"])) + l0)
    out = np.zeros((s, hq // 2, 2 * d))
    for j in range(hq // 2):
        g = j // (hq // hkv)
        vg = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        for t in range(s):
            lo = 0 if not window else max(0, t - window + 1)
            p = []
            for half in (0, 1):
                sc = k[lo:t + 1, 2 * g + half] @ q[t, 2 * j + half] / np.sqrt(d)
                e = np.exp(sc - sc.max())
                p.append(e / e.sum())
            a = (p[0] - lam * p[1]) @ vg[lo:t + 1]
            a = a / np.sqrt(np.mean(a * a) + 1e-5) * lp["subln"]
            out[t, j] = a * (1 - l0)
    return out


@pytest.mark.parametrize("window", [None, 8])
def test_differential_attention_pairing_lambda_subln_window(window):
    """`_attend` + `_diff_combine` (uncached, no rotary anywhere) against
    the loop: the pairing of query and key-value heads, lambda from the
    absolute layer index, the sub-layer norm, the window's edge."""
    cfg = tiny_cfg()
    rng = np.random.RandomState(4)
    s, hq, hkv, d = 21, 8, 4, 8
    q, k, v = (rng.randn(s, h, d) for h in (hq, hkv, hkv))
    lp = {n: rng.randn(d) * 0.3 for n in ("lq1", "lk1", "lq2", "lk2")}
    lp["subln"] = 1 + 0.2 * rng.randn(2 * d)
    pos = jnp.arange(s)[None]
    f = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        o = hybrid._attend(f(q)[None], None, None, cfg, pos, None, window,
                           f(k)[None], f(v)[None])
        got = hybrid._diff_combine(o, {n: f(x) for n, x in lp.items()}, 5,
                                   1e-5)
    np.testing.assert_allclose(got[0], _diff_loop(q, k, v, lp, 5, window),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [None, 24])
def test_kernels_with_the_differential_pairing(window):
    """The existing merged-lane kernels with `diff=True` (another placement
    of q, another slice of the output) against the XLA form, decode and
    flash prefill, over scattered pages."""
    rng = np.random.RandomState(5)
    hq, hkv, d, ps, n_pages = 8, 4, 32, 16, 12
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    k_pool, v_pool = f(n_pages * ps, hkv * d), f(n_pages * ps, hkv * d)
    table = jnp.asarray([[3, 7, 1, 9, 4], [2, 8, 5, 6, 10]], jnp.int32)
    lens = jnp.asarray([70, 33], jnp.int32)
    q = f(2, hq, d)

    def xla(q, row, q_pos):
        rows = (row[:, None] * ps + jnp.arange(ps)[None]).reshape(-1)
        kw = k_pool[rows].reshape(1, -1, hkv, d)
        vw = v_pool[rows].reshape(1, -1, hkv, d)
        kv_pos = jnp.arange(rows.shape[0])[None, None, :]
        mask = kv_pos <= q_pos[None, :, None]
        if window:
            mask = mask & (kv_pos > q_pos[None, :, None] - window)
        return hybrid._diff_scores_xla(q, kw, vw, mask, d ** -0.5)[0]

    call = (paged_decode_attention if window is None else
            lambda *a, **kw: paged_decode_attention_window(
                *a, window=window, **kw))
    got = call(q, k_pool, v_pool, table, lens, page_size=ps, interpret=True,
               diff=True)
    assert got.shape == (2, hq, 2 * d)
    for b in range(2):
        want = xla(q[b][None, None], table[b], lens[b][None])
        np.testing.assert_allclose(got[b], want[0], rtol=2e-4, atol=2e-5)
    qs = f(64, hq, d)
    got = paged_prefill_attention(
        qs, k_pool, v_pool, table[0], jnp.int32(8), jnp.int32(50),
        page_size=ps, interpret=True, window=window, diff=True)
    want = xla(qs[None], table[0], 8 + jnp.arange(64))
    np.testing.assert_allclose(got[:50], want[:50], rtol=2e-4, atol=2e-5)


def test_scan_kernel_against_the_scan():
    rng = jax.random.split(jax.random.PRNGKey(6), 6)
    w, s, di, ds = 2, 32, 256, 16
    x = jax.random.normal(rng[0], (w, s, di))
    dt = jax.nn.softplus(jax.random.normal(rng[1], (w, s, di)) - 3)
    a = -jnp.exp(jnp.broadcast_to(
        jnp.log(jnp.arange(1, ds + 1.0))[:, None], (ds, di)))
    b, c = (jax.random.normal(r, (w, s, ds)) for r in rng[2:4])
    h0 = jax.random.normal(rng[4], (w, ds, di))
    lens = jnp.asarray([32, 20])
    y1, h1 = selective_scan(x, dt, a, b, c, jnp.ones(di), h0, lens,
                            kernel=True)
    y2, h2 = selective_scan(x, dt, a, b, c, jnp.ones(di), h0, lens,
                            kernel=False)
    np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h1, h2, rtol=1e-5, atol=1e-5)
    # the state after a lane's last REAL row: lane 1's 20 rows alone
    _, h3 = selective_scan(x[1:, :20], dt[1:, :20], a, b[1:, :20],
                           c[1:, :20], jnp.ones(di), h0[1:],
                           jnp.asarray([20]), kernel=False)
    np.testing.assert_allclose(h1[1:], h3, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# state slots and snapshots: the allocator and the radix tree
# ---------------------------------------------------------------------------

def test_state_pool_allocator():
    sp = StatePool(7, 3)  # lanes 0..2, trash 3, snapshots 4..6
    assert (sp.trash, sp.snapshot_slots, sp.free_slots) == (3, 3, 3)
    got = [sp.alloc() for _ in range(3)]
    assert sorted(got) == [4, 5, 6] and sp.alloc() is None
    assert sp.alloc_failures == 1 and sp.snapshots_live == 3
    sp.retain(got[0])
    sp.release(got[0])
    assert sp.free_slots == 0
    sp.release(got[0])
    assert sp.free_slots == 1 and sp.check_consistency() == []
    with pytest.raises(ValueError):
        StatePool(3, 3)


def _cache(n_slots=6):
    pool, sp = PagePool(64, 4), StatePool(n_slots, 1)
    return PrefixCache(pool, state_pool=sp), pool, sp


def test_lookup_returns_the_deepest_snapshot_under_the_match():
    pc, pool, sp = _cache()
    toks = list(range(100, 140))  # 10 pages of 4
    pages = pool.alloc(10)
    pc.store("a", toks[:16], pages[:4], snapshot=(16, pc.alloc_snapshot()))
    pc.store("a", toks, pages)
    # pages match 9 (one token is always left), the snapshot stands at 16
    hit = pc.lookup("b", toks)
    assert (hit.tokens, hit.matched_tokens, len(hit.pages)) == (16, 36, 4)
    assert hit.snapshot is not None and sp.refcount[hit.snapshot] == 2
    pc.release_snapshot(hit.snapshot)
    pool.release(hit.pages)
    # no snapshot under the match: nothing to share, the match is reported
    other = list(range(100, 108)) + list(range(500, 520))
    hit = pc.lookup("b", other)
    assert (hit.tokens, hit.pages, hit.snapshot) == (0, [], None)
    assert hit.matched_tokens == 8
    # a learnt boundary: the snapshot at 36 is now the deepest
    pc.store("b", toks[:36], pages[:9], snapshot=(36, pc.alloc_snapshot()))
    hit = pc.lookup("c", toks)
    assert (hit.tokens, hit.matched_tokens) == (36, 36)
    assert pc.snapshots_stored == 2
    # a second snapshot for a boundary that has one is given back
    free = sp.free_slots
    pc.store("c", toks[:36], pages[:9], snapshot=(36, pc.alloc_snapshot()))
    assert sp.free_slots == free and pc.snapshots_stored == 2


def test_split_keeps_the_snapshot_at_its_boundary():
    pc, pool, sp = _cache()
    toks = list(range(100, 124))  # 6 pages
    pages = pool.alloc(6)
    slot = pc.alloc_snapshot()
    pc.store("a", toks, pages, snapshot=(24, slot))
    # another thread diverges after 2 pages: the node splits at 8 tokens
    fork = toks[:8] + list(range(900, 912))
    pc.store("b", fork, pool.alloc(5))
    front = pc._root.children[tuple(toks[:4])]
    back = front.children[tuple(toks[8:12])]
    assert front.snapshot is None and back.snapshot == slot
    assert list(pc._snapshots) == [back]
    hit = pc.lookup("c", toks + [1, 2, 3, 4, 5])
    assert (hit.tokens, hit.snapshot) == (24, slot)
    pc.release_snapshot(hit.snapshot)
    # the fork has pages and no snapshot
    assert pc.lookup("c", fork + [1]).tokens == 0


def test_eviction_and_trim_free_the_snapshot():
    pc, pool, sp = _cache()
    toks = list(range(100, 124))
    pages = pool.alloc(6)
    pc.store("a", toks, pages, snapshot=(24, pc.alloc_snapshot()))
    pool.release(pages)  # the sequence that wrote them is gone
    assert sp.snapshots_live == 1
    assert pc.reclaim(63)  # evicts the leaf
    assert sp.snapshots_live == 0 and pc.snapshots_freed == 1
    assert sp.check_consistency() == [] and not pc._snapshots
    # a page budget that trims a run's tail moves its end: the snapshot goes
    pc2, pool2, sp2 = _cache()
    pc2.max_pages = 4
    pc2.store("a", toks, pool2.alloc(6), snapshot=(24, pc2.alloc_snapshot()))
    assert pc2.total_pages == 4 and sp2.snapshots_live == 0


def test_snapshot_slots_run_out_lru_goes():
    pc, pool, sp = _cache(n_slots=4)  # 2 snapshot slots
    runs = [list(range(b, b + 8)) for b in (100, 200, 300)]
    for i, toks in enumerate(runs[:2]):
        pc.store(str(i), toks, pool.alloc(2),
                 snapshot=(8, pc.alloc_snapshot()))
    held = pc.lookup("x", runs[0] + [1])  # holds the older one's snapshot
    slot = pc.alloc_snapshot()            # the other one goes
    assert slot is not None and pc.snapshots_evicted == 1
    pc.store("2", runs[2], pool.alloc(2), snapshot=(8, slot))
    assert pc.lookup("x", runs[1] + [1]).tokens == 0
    pc.release_snapshot(held.snapshot)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

ENGINE = dict(max_batch=4, page_size=16, num_pages=64, max_pages_per_seq=16,
              prefill_buckets=(16, 64), multi_step=4, attention_backend="xla")


def make_engine(model, **kw):
    cfg, params = model
    return InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)))


def run(eng, model, prompt, key, n=6):
    req = eng.generate(prompt, max_new_tokens=n, temperature=0.0,
                       prefix_key=key)
    assert_greedy_consistent(*model, prompt, req.output_ids)
    assert eng.self_check() == []
    return req


def test_engine_snapshot_hit_shortened_hit_and_learnt_boundary(model):
    eng = make_engine(model)
    shared = tokens(100, seed=7)
    # cold: chunks [0, 64) (a snapshot there) and the rest
    a = run(eng, model, shared + tokens(5, seed=8), "a")
    assert a.cached_tokens == 0 and eng.state_restores == 0
    # pages match 96 (a's whole prompt pages were stored as dispatched), the
    # deepest snapshot stands at 64: the hit is SHORTENED to it, the first
    # chunk is cut at 96 and leaves the snapshot there
    b = run(eng, model, shared + tokens(9, seed=9), "b")
    assert b.cached_tokens == 64
    assert eng.state_section()["state_tokens_matched"] == 96
    # the boundary was learnt: the next thread resumes from 96
    c = run(eng, model, shared + tokens(3, seed=10), "c")
    assert c.cached_tokens == 96 and eng.state_restores == 2
    sec = eng.state_section()
    assert set(sec) == set(STATE_METRIC_KEYS)
    assert sec["state_tokens_skipped"] == 64 + 96
    assert sec["state_snapshots"] >= 2 and sec["state_slots_total"] == 17
    assert eng.metrics.snapshot(engine=eng)["state"] == sec


def test_engine_second_turn_and_split(model):
    eng = make_engine(model)
    p1 = tokens(90, seed=11)
    t1 = run(eng, model, p1, "thread", n=8)
    # the thread's second turn: its stored run (97 tokens, 6 pages) is
    # longer than its prompt's last snapshot (64); the hit is shortened to
    # it, the first chunk is cut at 96, and the turn is token-exact
    p2 = p1 + t1.output_ids + tokens(20, seed=12)
    t2 = run(eng, model, p2, "thread", n=6)
    assert t2.cached_tokens == 64
    # the cut chunk left a snapshot at the end of the stored run's pages
    t3 = run(eng, model, p2 + t2.output_ids + tokens(4, seed=13), "thread")
    assert t3.cached_tokens == 96
    # another thread forks inside the first run: the node splits, and both
    # sides stay token-exact
    fork = run(eng, model, p1[:32] + tokens(40, seed=14), "fork")
    assert fork.cached_tokens == 0  # pages match 32, no snapshot stands there
    again = run(eng, model, p1 + tokens(6, seed=15), "other")
    assert again.cached_tokens == 64


def test_engine_eviction_frees_snapshots_and_stays_exact(model):
    eng = make_engine(model, num_pages=24)
    for i in range(8):  # each prompt's pages push the older runs out
        run(eng, model, tokens(90, seed=20 + i), f"t{i}")
    pc = eng.prefix_cache
    assert pc.evictions > 0 and pc.snapshots_freed > 0
    assert eng.state_pool.snapshots_live == len(pc._snapshots)
    first = run(eng, model, tokens(90, seed=20) + [1, 2, 3], "t0")
    assert first.cached_tokens == 0  # evicted: cold, and exact


def test_engine_preempt_and_readmit(model):
    eng = make_engine(model)
    cfg, params = model
    prompts = [tokens(40, seed=30), tokens(70, seed=31)]
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=p, max_new_tokens=40,
                       temperature=0.0, prefix_key=f"k{i}")
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    while any(len(r.output_ids) < 2 for r in reqs):
        eng.step()
    eng._drain(block=True)
    assert reqs[1].state == "active" and 2 <= len(reqs[1].output_ids) < 40
    eng._preempt(reqs[1])  # frees its pages; its state slot goes with its lane
    assert reqs[1].seq is None and reqs[1].slot == -1
    eng.run_to_completion()
    for r, p in zip(reqs, prompts):
        assert len(r.output_ids) == 40
        assert_greedy_consistent(cfg, params, p, r.output_ids)
    assert eng.self_check() == []
    assert eng.metrics.requests_preempted == 1


def test_engine_batched_prefill_and_fused_decode(model):
    """Three threads at once: same-bucket chunks fuse into the batched
    prefill program (per-lane slots and snapshots), decode fuses 4 steps."""
    eng = make_engine(model)
    cfg, params = model
    prompts = [tokens(30 + i, seed=40 + i) for i in range(3)]
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=p, max_new_tokens=9,
                       temperature=0.0, prefix_key=f"k{i}")
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    for r, p in zip(reqs, prompts):
        assert_greedy_consistent(cfg, params, p, r.output_ids)
    labels = {k[0] for k in eng._programs.built}
    assert "bprefill[64x4]" in labels and "multi_decode[4]" in labels


def test_engine_never_parks_a_lane_without_a_state_slot(model):
    eng = make_engine(model, max_batch=2, max_parked=8)
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=tokens(20, seed=50 + i),
                       max_new_tokens=4, temperature=0.0) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    assert not eng.parked and len(eng.waiting) == 2
    eng.run_to_completion()
    assert all(len(r.output_ids) == 4 for r in reqs)


# ---------------------------------------------------------------------------
# what cannot carry a state is refused by name
# ---------------------------------------------------------------------------

def _mesh(**axes):
    from kafka_tpu.parallel import MeshConfig, make_mesh

    return make_mesh(MeshConfig(**axes))


@pytest.mark.parametrize("path,kw,mesh", [
    ("speculative verify", dict(speculative_k=2), None),
    ("int8 pool", dict(kv_quantize="int8"), None),
    ("prefill_ring", {}, dict(sp=2)),
    ("pp / tp / ep mesh", {}, dict(tp=2)),
    ("pp / tp / ep mesh", {}, dict(pp=2)),
    ("KV tier", dict(kv_host_tier_mb=8), None),
    ("KV tier", dict(kv_object_dir="/tmp/nowhere"), None),
], ids=["speculative", "int8", "ring", "tp", "pp", "host_tier", "object"])
def test_engine_refuses_by_name(model, path, kw, mesh):
    cfg, params = model
    with pytest.raises(RecurrentStateUnsupported, match=path) as err:
        InferenceEngine(cfg, params, EngineConfig(**dict(ENGINE, **kw)),
                        mesh=None if mesh is None else _mesh(**mesh))
    assert path in err.value.path


def test_handoff_and_sleep_refused_at_admission(model):
    eng = make_engine(model)
    req = GenRequest(request_id="h", prompt_ids=[1, 2, 3], max_new_tokens=2)
    req.handoff = True
    with pytest.raises(RecurrentStateUnsupported, match="hand-off"):
        eng.submit(req)
    with pytest.raises(RecurrentStateUnsupported, match="sleep"):
        eng.sleep_to_object()
    assert not eng.waiting


def test_forward_backstops(model):
    cfg, params = model
    ids = jnp.zeros((1, 4), jnp.int32)
    pos = jnp.arange(4)[None]
    k, v = make_kv_pool_arrays(cfg, 3, 16, state_slots=2)
    _, paged = decode_plan(jnp.ones((1, 2), jnp.int32), jnp.zeros(1, jnp.int32),
                           jnp.ones(1, bool), 16)
    with pytest.raises(HybridPathError, match="StatePlan"):
        forward(params, cfg, ids[:, :1], pos[:, :1], kv_cache=KVCache(k, v),
                paged=paged)
    with pytest.raises(HybridPathError, match="one device"):
        forward(params, cfg, ids, pos, mesh=_mesh(tp=2))
    with pytest.raises(NotImplementedError, match="roll"):
        StepPrograms(cfg, None, 16, 2, 4).verify(2)


# ---------------------------------------------------------------------------
# the memory plan
# ---------------------------------------------------------------------------

def test_memory_plan_counts_the_tree_and_the_slots(tmp_path, model):
    from kafka_tpu.runtime import planner

    for cfg in (model[0], _cfg_of(tmp_path)):
        shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0)))
        held = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(shapes))
        assert planner.weight_bytes_per_device(cfg) == held
    cfg = _cfg_of(tmp_path)
    assert round(planner.weight_bytes_per_device(cfg) / 1e9, 2) == 7.71
    plan = planner.plan_memory(
        cfg, num_pages=5120, page_size=16, max_pages_per_seq=1024,
        max_batch=32, prefill_bucket=2048, state_slots=129,
        grammar_table_bytes=0)
    # 9 row-holding layers x 2 x 1280 values x 2 B x 81,920 slots
    assert plan.kv_pool_bytes == 9 * 2 * 1280 * 2 * 5120 * 16
    # as the device lays a slot out: the conv tail's 3 rows take 8
    assert plan.state_bytes == 129 * 9 * 4 * (8 + 16) * 5120
    assert plan.fits and plan.summary()["state_mib"] > 500
    assert planner.plan_memory(
        model[0].replace(mamba_d_state=0, layer_types=()), num_pages=8,
        page_size=16, max_pages_per_seq=4, max_batch=2,
        grammar_table_bytes=0).state_bytes == 0
