"""Latent attention per KIND of layer (dots3-note-prev, PR 33): full layers
that attend a learned top-k selection of keys (an indexer with a cache row of
its own), sliding-window latent layers of another geometry beside them, a
query low-rank, the latent rescale, the headwise gate, and one chip's share
of the routed experts.  At a tiny size on the CPU in float32:

* `forward` (uncached) against a plain numpy reference written from the
  description, and against the contiguous cache and prefill + paged decode on
  both backends, with contexts below, at and above `index_topk` (8) and the
  window (5);
* unwritten pool rows poisoned with NaN change nothing;
* a shared-prefix page reused by a second sequence holds the same k^I;
* the pool is a pair per kind under one page table, and the engine, the
  planner and /metrics report its bytes; the configuration's file plans the
  bytes its `reduced` states, the registered ones the parent's;
* the pattern's lone layer runs ahead of whole periods;
* the 8 shares' routed parts, the shared expert counted once, add up to the
  uncut layer;
* every new `UnsupportedConfigError` by key, every engine refusal by name.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from kafka_tpu.models import forward, init_params
from kafka_tpu.models.config import (
    GLOBAL,
    WINDOWED,
    LatentGeometry,
    ModelConfig,
    RopeParams,
    UnsupportedConfigError,
    config_from_hf_json,
)
from kafka_tpu.models.cache import INDEX
from kafka_tpu.models.ffn import _moe_block
from kafka_tpu.models.llama import init_kv_cache
from kafka_tpu.models.mixers import index
from kafka_tpu.models.mixers.index import _chosen_mask, _compact_chosen
from kafka_tpu.ops.pallas import paged_decode_attention_latent
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine, planner
from kafka_tpu.runtime.engine import (
    LatentAttentionUnsupported,
    WindowedAttentionUnsupported,
)
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays
from kafka_tpu.runtime.step_programs import decode_plan
from test_engine import assert_greedy_consistent
from test_latent_attention import PARENT_PLANS, _plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import paged_step  # noqa: E402

TOPK, WINDOW = 8, 5
KINDS = (GLOBAL, GLOBAL, WINDOWED, WINDOWED, WINDOWED, GLOBAL)


def sparse_cfg(backend="xla", dtype="float32", **kw):
    """dots3's first six layers, tiny: a dense full layer, a routed full
    layer, three sliding ones, a full one; 4 of 16 experts held."""
    base = dict(
        name="sparse-test", vocab_size=128, hidden_size=64, num_layers=6,
        num_heads=4, num_kv_heads=4, head_dim=8, intermediate_size=24,
        dtype=dtype, tie_word_embeddings=False, rms_norm_eps=1e-5,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_interleave=True, first_k_dense=1,
        dense_intermediate_size=96, shared_intermediate_size=24,
        moe_scoring="sigmoid", num_experts=4, num_experts_per_tok=3,
        num_experts_routed=16, expert_offset=4, q_lora_rank=24,
        latent_rescale=True, attention_gate="headwise", index_n_heads=4,
        index_head_dim=16, index_topk=TOPK,
        windowed_latent=LatentGeometry(2, 24, 48, 24, 8, 16),
        layer_types=KINDS, sliding_window=WINDOW, rope_theta=8e7,
        rope_by_kind=((GLOBAL, RopeParams(rope_theta=8e7)),
                      (WINDOWED, RopeParams(rope_theta=5e4))),
        attention_backend=backend)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = sparse_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(5))


IDS = np.random.RandomState(3).randint(1, 128, size=40)


# ---------------------------------------------------------------------------
# the plain reference: numpy float64, written from the description
# ---------------------------------------------------------------------------

def _np(x):
    return np.asarray(x, np.float64)


def _rms(x, w, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta, interleave):
    """x [S, ..., d] at positions 0..S-1."""
    d = x.shape[-1]
    if interleave:
        x = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(x.shape[0])[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _silu(x):
    return x / (1 + np.exp(-x))


def _swiglu(h, wg, wu, wd):
    return (_silu(h @ wg) * (h @ wu)) @ wd


def _attention(x, lp, cfg, kind):
    g = cfg.geometry_of(kind)
    r, dn, dr = g.kv_lora_rank, g.qk_nope_head_dim, g.qk_rope_head_dim
    theta = dict(cfg.rope_by_kind)[kind].rope_theta
    s, hid, eps = x.shape[0], cfg.hidden_size, cfg.rms_norm_eps
    h = _rms(x, lp["ln_attn"], eps)
    c_q = _rms(h @ lp["wqa"], lp["ln_q"], eps) * np.sqrt(hid / g.q_lora_rank)
    q = np.einsum("sr,rnd->snd", c_q, lp["wqb"])
    kva = h @ lp["wkva"]
    c = _rms(kva[:, :r], lp["ln_kv"], eps) * np.sqrt(hid / r)
    q_rope = _rope(q[..., dn:], theta, True)
    k_rope = _rope(kva[:, r:], theta, True)
    kv = np.einsum("tr,nrd->tnd", c, lp["wkvb"])
    pos = np.arange(s)
    allowed = pos[None, :] <= pos[:, None]
    if kind == WINDOWED:
        allowed &= pos[None, :] > pos[:, None] - cfg.sliding_window
    else:
        q_i = np.einsum("sr,rnd->snd", c_q, lp["wiq"])
        k_i = h @ lp["wik"]
        mu = k_i.mean(-1, keepdims=True)
        k_i = ((k_i - mu) / np.sqrt(((k_i - mu) ** 2).mean(-1, keepdims=True)
                                    + eps) * lp["ln_ik"] + lp["ln_ik_b"])
        q_i = np.concatenate([_rope(q_i[..., :dr], theta, False),
                              q_i[..., dr:]], -1)
        k_i = np.concatenate([_rope(k_i[:, :dr], theta, False),
                              k_i[:, dr:]], -1)
        w = (h @ lp["wiw"]) * (cfg.index_n_heads ** -0.5
                               * cfg.index_head_dim ** -0.5)
        score = np.einsum(
            "sn,snt->st", w,
            np.maximum(np.einsum("snd,td->snt", q_i, k_i), 0.0))
        chosen = np.zeros_like(allowed)
        for t in range(s):
            # the TOPK causal keys of largest score, ties to the lower one
            order = sorted(range(t + 1), key=lambda u: (-score[t, u], u))
            chosen[t, order[:cfg.index_topk]] = True
        allowed = chosen
    scores = (np.einsum("snd,tnd->nst", q[..., :dn], kv[..., :dn])
              + np.einsum("snd,td->nst", q_rope, k_rope)) / np.sqrt(dn + dr)
    scores = np.where(allowed[None], scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("nst,tnd->snd", p, kv[..., dn:])
    gate = 1 / (1 + np.exp(-(h @ lp["wgate"])))
    return x + np.einsum("snd,ndh->sh", out * gate[..., None], lp["wo"])


def _routing(h, lp, cfg):
    """[S, all experts] weights: chosen by sigma + b, renormalised over the
    chosen, zero elsewhere."""
    sigma = 1 / (1 + np.exp(-(h @ lp["router"])))
    w = np.zeros_like(sigma)
    for t in range(h.shape[0]):
        order = sorted(range(sigma.shape[1]),
                       key=lambda e: (-(sigma[t, e] + lp["router_bias"][e]),
                                      e))[:cfg.num_experts_per_tok]
        w[t, order] = sigma[t, order] / (sigma[t, order].sum() + 1e-20)
    return w * cfg.routed_scaling_factor


def reference_logits(params, cfg, ids):
    p = jax.tree.map(_np, params)
    x = p["embed"][ids]
    for l, kind in enumerate(cfg.layer_types):
        routed = l >= cfg.first_k_dense
        stack = p["layers" if routed else "dense_layers"]
        i = l - cfg.first_k_dense if routed else l
        lp = {k: v[i] for k, v in stack.items()}
        nth = cfg.layer_types[:l].count(kind)
        lp.update({k: v[nth] for k, v in p["attn"][kind].items()})
        x = _attention(x, lp, cfg, kind)
        h = _rms(x, lp["ln_mlp"], cfg.rms_norm_eps)
        if not routed:
            x = x + _swiglu(h, lp["wg"], lp["wu"], lp["wd"])
            continue
        w = _routing(h, lp, cfg)
        y = _swiglu(h, lp["ws_g"], lp["ws_u"], lp["ws_d"])
        for e in range(cfg.num_experts):
            y = y + w[:, cfg.expert_offset + e, None] * _swiglu(
                h, lp["wg"][e], lp["wu"][e], lp["wd"][e])
        x = x + y
    return _rms(x, p["final_norm"], cfg.rms_norm_eps) @ p["lm_head"]


def _forward(params, cfg, ids):
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, cfg, jnp.asarray(ids)[None],
                            jnp.arange(len(ids))[None])
    return np.asarray(logits[0])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def test_forward_matches_the_plain_reference(model):
    cfg, params = model
    np.testing.assert_allclose(_forward(params, cfg, IDS),
                               reference_logits(params, cfg, IDS),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("field, value", [
    ("index_topk", 0), ("sliding_window", 40), ("attention_gate", ""),
    ("latent_rescale", False), ("expert_offset", 0),
], ids=["no-selection", "no-window", "no-gate", "no-rescale", "other-share"])
def test_every_mechanism_moves_the_logits(model, field, value):
    """Power of the comparison above: with the selection, the window, the
    gate, the rescale or the share changed, the same weights give other
    logits."""
    cfg, params = model
    want = _forward(params, cfg, IDS)
    if field == "attention_gate":
        params = dict(params, attn={
            kind: {k: v for k, v in leaves.items() if k != "wgate"}
            for kind, leaves in params["attn"].items()})
    got = _forward(params, cfg.replace(**{field: value}), IDS)
    assert np.abs(got - want).max() > 1e-2


def test_contiguous_cache_matches_uncached(model):
    """Prefill 23 tokens into the contiguous cache, then decode one token at
    a time: positions below, at and above the window and index_topk."""
    cfg, params = model
    want = _forward(params, cfg, IDS)
    cache = init_kv_cache(cfg, 1, 48, jnp.float32)
    assert sorted(cache.k) == sorted(cfg.kinds)
    assert cache.v[INDEX].shape == (3, 1, 48, 1, 16)
    n = 23
    valid = (jnp.arange(48) < n)[None]
    with jax.default_matmul_precision("highest"):
        logits, cache = forward(params, cfg, jnp.asarray(IDS[:n])[None],
                                jnp.arange(n)[None], kv_cache=cache,
                                kv_valid=valid)
        np.testing.assert_allclose(np.asarray(logits[0]), want[:n],
                                   rtol=2e-4, atol=2e-4)
        for i in range(n, len(IDS)):
            valid = (jnp.arange(48) <= i)[None]
            logits, cache = forward(
                params, cfg, jnp.asarray(IDS[i:i + 1])[None],
                jnp.asarray([[i]]), kv_cache=cache, kv_valid=valid)
            np.testing.assert_allclose(np.asarray(logits[0, 0]), want[i],
                                       rtol=2e-4, atol=2e-4)


_STEPS = tuple(
    jax.jit(fn, static_argnums=(1,), static_argnames=("page_size",))
    for fn in (paged_step.prefill_chunk, paged_step.decode_step))


def _paged(params, cfg, ids, n_prefill, chunk, ps=4, pages=12, poison=False):
    """Prefill in chunks, then decode, through the engine's pool; logits at
    every position from the last prefilled one on."""
    k_pool, v_pool = make_kv_pool_arrays(cfg, pages + 1, ps, jnp.float32)
    if poison:
        # every row of every page, the trash page's included: what is read
        # before it is written shows
        k_pool, v_pool = jax.tree.map(
            lambda a: jnp.full_like(a, jnp.nan), (k_pool, v_pool))
    row = jnp.arange(1, pages + 1, dtype=jnp.int32)
    out = []
    pre, dec = _STEPS
    with jax.default_matmul_precision("highest"):
        for start in range(0, n_prefill, chunk):
            n = min(chunk, n_prefill - start)
            piece = np.zeros(chunk, np.int32)
            piece[:n] = ids[start:start + n]
            logits, k_pool, v_pool = pre(
                params, cfg, k_pool, v_pool, row, jnp.asarray(piece),
                jnp.int32(start), jnp.int32(n), page_size=ps)
        out.append(np.asarray(logits[n - 1]))
        for i in range(n_prefill, len(ids)):
            lg, k_pool, v_pool = dec(
                params, cfg, k_pool, v_pool, row[None],
                jnp.asarray(ids[i:i + 1]), jnp.asarray([i], jnp.int32),
                jnp.asarray([True]), page_size=ps)
            out.append(np.asarray(lg[0]))
    return np.stack(out), (k_pool, v_pool)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("n_prefill, chunk", [
    (3, 8), (5, 8), (8, 8), (9, 16), (23, 8), (32, 16),
], ids=["below", "at-window", "at-topk", "above", "chunks-of-8", "two-16s"])
def test_prefill_then_paged_decode_matches_uncached(model, backend,
                                                    n_prefill, chunk):
    """The served path: chunked prefill (the key walk, masked to the chosen
    keys or the window, across chunk boundaries) and paged decode (the
    chosen rows; the window's pages or the windowed latent kernel), with the
    pool's unwritten rows holding NaN."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    want = _forward(params, cfg, IDS)[n_prefill - 1:]
    got, _ = _paged(params, cfg, IDS, n_prefill, chunk, poison=True)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


def _scores_of(keys):
    """float32 scores whose order is that of the uint32 `keys`, the inverse
    of `_chosen_mask`'s own map (keys in [0x00800000, 0xFF7FFFFF]: finite)."""
    keys = np.asarray(keys, np.uint32)
    assert keys.min() >= 0x00800000 and keys.max() <= 0xFF7FFFFF
    return np.where(keys >> 31, keys ^ np.uint32(1 << 31), ~keys).view(
        np.float32)


def _drawn(k, allowed, ties, shape=(2, 3, 77)):
    def case(rng):
        scores = rng.randn(*shape).astype(np.float32)
        if ties:
            scores = np.round(scores * 2) / 2
            scores[0, 0, :5] = -0.0
        return scores, rng.rand(*shape) < allowed, k
    return case


def _kth_in(k, lo, hi, low_bits, shape=(2, 3, 77)):
    """Distinct keys in [lo, hi) sixteen apart, the k-th largest of each row
    with `low_bits` in its lowest four bits: a threshold whose top digit is
    the range's and whose last digit is all zeros or all ones at any r <= 4."""
    def case(rng):
        t, n = shape[-1], int(np.prod(shape[:-1]))
        step = (hi - lo) // 16 // t  # one key a stratum: no two alike
        rows = (rng.randint(0, step, (n, t)) + np.arange(t) * step) * 16 + lo
        rows = np.stack([rng.permutation(r) for r in rows]).astype(np.uint32)
        kth = np.sort(rows, axis=-1)[:, -k]
        rows[rows == kth[:, None]] += np.uint32(low_bits)
        return _scores_of(rows.reshape(shape)), np.ones(shape, bool), k
    return case


def _low_bits_only(rng):
    # every key the same but for its lowest 3 bits (r - 1 at r = 4): the
    # last pass alone tells them apart, then the tie search
    keys = np.uint32(0xC0490FD8) + rng.randint(0, 8, (2, 3, 77)).astype(
        np.uint32)
    return _scores_of(keys), rng.rand(2, 3, 77) < 0.8, 20


def _all_equal(rng):
    # the tie search carries the whole choice
    return (np.full((2, 3, 77), -2.5, np.float32), rng.rand(2, 3, 77) < 0.7,
            11)


def _exactly_k(rng):
    mask = np.zeros((2, 3, 77), bool)
    for row in mask.reshape(6, 77):
        row[rng.choice(77, 13, replace=False)] = True
    return rng.randn(2, 3, 77).astype(np.float32), mask, 13


def _around_zero(rng):
    values = np.asarray([-1.5, -1e-40, -0.0, 0.0, 1e-40, 2.0 ** -126, 0.25],
                        np.float32)
    return values[rng.randint(0, len(values), (2, 3, 77))], \
        rng.rand(2, 3, 77) < 0.9, 30


TOP_K_CASES = {
    "ties": _drawn(8, 0.9, True), "half-masked": _drawn(8, 0.5, False),
    "fewer-than-k": _drawn(20, 0.1, True), "k-1": _drawn(1, 0.9, False),
    "all-but-one": _drawn(76, 1.0, True), "none-allowed": _drawn(40, 0.0,
                                                                 False),
    # what a pass of several bits can get wrong and a bit a pass cannot
    "low-bits-only": _low_bits_only,
    "top-digit-zero": _kth_in(9, 0x00800000, 0x0FFFFFF0, 0),
    "top-digit-ones": _kth_in(9, 0xF0000000, 0xFF7FFFF0, 0),
    "last-digit-zero": _kth_in(30, 0x3F000000, 0xC1000000, 0),
    "last-digit-ones": _kth_in(30, 0x3F000000, 0xC1000000, 15),
    "all-equal": _all_equal, "exactly-k": _exactly_k,
    "around-zero": _around_zero,
    # T - 1 of 13 and of 15 bits (77 is 7): the position's top pass is short
    "t-4097": _drawn(300, 0.9, True, (1, 2, 4097)),
    "t-32768": _drawn(2048, 0.95, True, (1, 2, 32768)),
}


@pytest.mark.parametrize("bits", [index.PASS_BITS, 2, 3, 4],
                         ids=["installed", "r2", "r3", "r4"])
@pytest.mark.parametrize("case", list(TOP_K_CASES))
def test_the_chosen_set_is_exactly_top_k(case, bits, monkeypatch):
    """The selection without a sort picks the set `lax.top_k` picks: ties to
    the lower position (signed zeros are one value), masked keys never, all
    the allowed keys where there are no more than k; at the installed bits
    a trip of the search, and at 2, 3 (which does not divide a key's 32) and
    4."""
    monkeypatch.setattr(index, "PASS_BITS", bits)
    scores, mask, k = TOP_K_CASES[case](np.random.RandomState(
        list(TOP_K_CASES).index(case)))
    # (a function of its own a case: the jit cache does not see PASS_BITS)
    got = np.asarray(jax.jit(lambda s, m: _chosen_mask(s, m, k))(
        jnp.asarray(scores), jnp.asarray(mask)))
    # (the oracle orders -0.0 under 0.0, and a denormal apart from both where
    # the platform's `== 0` holds for it: it is shown the zeros as one value)
    one_zero = jnp.where(jnp.asarray(scores) == 0, 0.0, scores)
    vals, idx = jax.lax.top_k(jnp.where(mask, one_zero, -jnp.inf), k)
    want = np.zeros_like(mask)
    rows = np.indices(idx.shape)
    want[rows[0], rows[1], np.asarray(idx)] = np.asarray(vals) > -np.inf
    np.testing.assert_array_equal(got, want)
    # and the marked keys' values come out in order, without a sort
    t = mask.shape[-1]
    flat = want.reshape(-1, t)
    n = len(flat)
    values = jnp.asarray(1000 + 7 * np.arange(n * t).reshape(n, t))
    out, ok = _compact_chosen(jnp.asarray(flat), values, k)
    for row in range(n):
        where = np.nonzero(flat[row])[0]
        np.testing.assert_array_equal(
            np.asarray(out[row])[np.asarray(ok[row])],
            np.asarray(values[row])[where])
        assert int(ok[row].sum()) == len(where)
        if len(where):  # an entry past the last repeats the first value
            assert (np.asarray(out[row])[~np.asarray(ok[row])]
                    == int(values[row, where[0]])).all()


def test_windowed_latent_kernel_matches_the_xla_window_read():
    """The Pallas latent decode kernel with a window, against plain numpy,
    at lengths around the window and the kernel's chunk; it is named apart
    from the global latent call."""
    rng = np.random.RandomState(0)
    B, Hq, r, dr, ps, P, window = 3, 2, 128, 8, 16, 12, 37
    lens = np.asarray([5, 36, 150], np.int32)
    c = rng.randn((P * B + 1) * ps, r).astype(np.float32)
    kr = np.zeros(((P * B + 1) * ps, 128), np.float32)
    kr[:, :dr] = rng.randn(kr.shape[0], dr)
    table = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    q_lat = rng.randn(B, Hq, r).astype(np.float32)
    q_rope = rng.randn(B, Hq, dr).astype(np.float32)
    got = paged_decode_attention_latent(
        jnp.asarray(q_lat), jnp.asarray(q_rope), jnp.asarray(c),
        jnp.asarray(kr), jnp.asarray(table), jnp.asarray(lens), scale=0.2,
        page_size=ps, interpret=True, window=window)
    for b in range(B):
        pos = np.arange(max(lens[b] - window + 1, 0), lens[b] + 1)
        slots = table[b, pos // ps] * ps + pos % ps
        s = (q_lat[b] @ c[slots].T + q_rope[b] @ kr[slots, :dr].T) * 0.2
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ c[slots]
        np.testing.assert_allclose(np.asarray(got[b]), want, rtol=2e-4,
                                   atol=2e-4)
    text = str(jax.make_jaxpr(lambda *a: paged_decode_attention_latent(
        *a, scale=0.2, page_size=ps, interpret=True, window=window))(
        jnp.asarray(q_lat), jnp.asarray(q_rope), jnp.asarray(c),
        jnp.asarray(kr), jnp.asarray(table), jnp.asarray(lens)))
    assert "paged_decode_attention_latent_window" in text


# ---------------------------------------------------------------------------
# the pool, the plan, the pattern
# ---------------------------------------------------------------------------

def test_pool_is_a_pair_per_kind_under_one_page_table(model):
    cfg, _ = model
    assert cfg.by_kind and cfg.kinds == (GLOBAL, WINDOWED)
    assert cfg.kv_row_widths(GLOBAL) == (32, 128, 128)
    assert cfg.kv_row_widths(WINDOWED) == (48, 128)
    assert cfg.kv_values_per_token == 3 * 288 + 3 * 176
    k, v = make_kv_pool_arrays(cfg, 10, 4, jnp.float32)
    assert {n: a.shape for n, a in k.items()} == {
        GLOBAL: (3, 40, 32), WINDOWED: (3, 40, 48)}
    assert {n: a.shape for n, a in v.items()} == {
        GLOBAL: (3, 40, 128), WINDOWED: (3, 40, 128), INDEX: (3, 40, 128)}
    assert planner.kv_bytes_per_token(cfg, kv_dtype="float32") == \
        cfg.kv_values_per_token * 4


def test_a_shared_prefix_page_holds_the_same_index_keys(model):
    """The indexer key rides in the page: two sequences that share a prefix
    write the same k^I rows, so a prefix page reused by a second sequence
    needs no indexer pass over it."""
    cfg, params = model
    other = np.concatenate([IDS[:16], IDS[::-1][:8]])
    _, (_, v_a) = _paged(params, cfg, IDS[:24], 24, 8)
    _, (_, v_b) = _paged(params, cfg, other, 24, 8)
    rows = slice(4, 4 + 16)  # page 1 on: positions 0..15, the shared prefix
    for name in (INDEX, GLOBAL, WINDOWED):
        np.testing.assert_array_equal(np.asarray(v_a[name][:, rows]),
                                      np.asarray(v_b[name][:, rows]))
    assert not np.array_equal(np.asarray(v_a[INDEX][:, 20:28]),
                              np.asarray(v_b[INDEX][:, 20:28]))
    assert float(jnp.abs(v_a[INDEX][:, rows, :16]).min()) > 0


@pytest.mark.parametrize("kinds, dense, want", [
    (("f",) + ("f",) + ("s", "s", "s", "f") * 11, 1, (1, ("s", "s", "s", "f"))),
    (("s", "s", "s", "f") * 2, 0, (0, ("s", "s", "s", "f"))),
    (("f", "f", "s", "s", "s", "f", "s", "s", "s", "f"), 1,
     (1, ("s", "s", "s", "f"))),
    (("f", "f", "s", "s", "s", "f"), 1, (0, ("f", "s", "s", "s", "f"))),
    ((), 0, (0, ("f",))),
], ids=["dots3-uncut", "mellum2", "two-periods", "one-period", "no-pattern"])
def test_pattern_is_lead_layers_then_whole_periods(kinds, dense, want):
    name = {"f": GLOBAL, "s": WINDOWED}
    cfg = sparse_cfg(layer_types=tuple(name[k] for k in kinds),
                     num_layers=len(kinds) or 6, first_k_dense=dense,
                     **({} if kinds else {"windowed_latent": None,
                                          "sliding_window": None,
                                          "rope_by_kind": ()}))
    lead, period = cfg.pattern
    assert (lead, tuple(name[k] for k in want[1])) == (lead, period)
    assert lead == want[0]


def test_a_lone_layer_runs_ahead_of_two_whole_periods():
    """Ten layers: dense full, one routed full that stands alone, then two
    periods of (3 sliding, 1 full).  Uncached against the paged path, which
    indexes each kind's weights and pool by the layer's place in its kind."""
    kinds = (GLOBAL, GLOBAL) + (WINDOWED, WINDOWED, WINDOWED, GLOBAL) * 2
    cfg = sparse_cfg(layer_types=kinds, num_layers=10)
    assert cfg.pattern == (1, (WINDOWED, WINDOWED, WINDOWED, GLOBAL))
    params = init_params(cfg, jax.random.PRNGKey(2))
    assert params["attn"][GLOBAL]["wkvb"].shape[0] == 4
    assert params["attn"][WINDOWED]["wkvb"].shape[0] == 6
    want = _forward(params, cfg, IDS[:24])
    np.testing.assert_allclose(want, reference_logits(params, cfg, IDS[:24]),
                               rtol=3e-4, atol=3e-4)
    got, _ = _paged(params, cfg, IDS[:24], 12, 8, poison=True)
    np.testing.assert_allclose(got, want[11:], rtol=3e-4, atol=3e-4)


def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """One routed layer over all 16 experts equals the sum of four shares of
    4 (the tiny twin of 8 shares of 32), the shared expert counted once."""
    cfg, params = model
    whole = cfg.replace(num_experts=16, num_experts_routed=0, expert_offset=0)
    key = jax.random.PRNGKey(9)
    lp = {k: v[0] for k, v in params["layers"].items()}
    for i, name in enumerate(("wg", "wu", "wd")):
        shape = (16,) + lp[name].shape[1:]
        lp[name] = jax.random.normal(jax.random.fold_in(key, i), shape) * 0.1
    x = jax.random.normal(key, (2, 7, 64))
    with jax.default_matmul_precision("highest"):
        uncut, _ = _moe_block(x, lp, whole)
        shared = uncut - _moe_block(
            x, lp, whole.replace(shared_intermediate_size=0))[0]
        parts = 0
        for off in range(0, 16, 4):
            share = cfg.replace(expert_offset=off)
            held = dict(lp, **{n: lp[n][off:off + 4]
                               for n in ("wg", "wu", "wd")})
            parts = parts + _moe_block(x, held, share)[0] - shared
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(parts).max()) > 1e-2


# ---------------------------------------------------------------------------
# the configuration files
# ---------------------------------------------------------------------------

def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    for line in open(path):
        row = json.loads(line)
        if row["name"] == "dots3-note-prev":
            return row["config"]
    pytest.skip("no dots3-note-prev row")


def _write(tmp_path, hf):
    path = tmp_path / "m" / "config.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(hf))
    return str(path)


@pytest.mark.parametrize("stated, served", [
    (None, True), (True, True), (False, False)])
@pytest.mark.parametrize("model_type", ["dots3_note", "deepseek_v3"])
def test_rope_interleave_defaults_as_hf_does(tmp_path, stated, served,
                                             model_type):
    """HF DeepseekV3Config's `rope_interleave` defaults to true; a latent
    model's file that leaves the key out (dots3-note-prev's) publishes
    interleaved pairs, whatever the model is called."""
    with open(os.path.join(ROOT, "benchmarks", "tests", "dots3", "configs",
                           "tiny-dots3.json")) as f:
        hf = dict(json.load(f), model_type=model_type)
    hf.pop("rope_interleave", None)
    if stated is not None:
        hf["rope_interleave"] = stated
    assert config_from_hf_json(_write(tmp_path, hf)).rope_interleave is served


def test_the_uncut_published_config_builds(tmp_path):
    cfg = config_from_hf_json(_write(tmp_path, _catalog_row()))
    assert cfg.num_layers == 46 and cfg.by_kind
    assert cfg.layers_of(GLOBAL) == 13 and cfg.layers_of(WINDOWED) == 33
    assert cfg.pattern == (1, (WINDOWED,) * 3 + (GLOBAL,))
    assert cfg.first_k_dense == 1 and cfg.dense_intermediate_size == 13824
    assert cfg.geometry_of(GLOBAL) == LatentGeometry(128, 1024, 512, 128, 64,
                                                     128)
    assert cfg.geometry_of(WINDOWED) == LatentGeometry(64, 1024, 1024, 192,
                                                       64, 128)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        64, 128, 2048)
    assert cfg.sliding_window == 513 and cfg.rope_interleave
    assert cfg.latent_rescale and cfg.attention_gate == "headwise"
    ropes = dict(cfg.rope_by_kind)
    assert ropes[GLOBAL].rope_theta == 8e7 and ropes[WINDOWED].rope_theta == 5e4
    assert cfg.num_experts == cfg.num_router_experts == 256
    assert cfg.kv_row_widths(GLOBAL) == (512, 128, 128)
    assert cfg.kv_row_widths(WINDOWED) == (1024, 128)


def test_the_cut_file_plans_the_bytes_it_states():
    """benchmarks/configs/dots3-note-prev.json `reduced`: 10.02 GB of
    weights, 11,520 B a token, a 1.51 GB pool; 32 experts held of 256."""
    cfg, plan = _plan("dots3-note-prev")
    assert cfg.num_layers == 6 and cfg.layer_types == KINDS
    assert (cfg.num_experts, cfg.num_router_experts, cfg.expert_offset) == (
        32, 256, 0)
    assert cfg.vocab_size == 19008
    assert planner.kv_bytes_per_token(cfg) == 11520
    assert plan.kv_pool_bytes == 8192 * 16 * 11520
    assert plan.weight_bytes == pytest.approx(10.02e9, rel=2e-3)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert plan.weight_bytes == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert shapes["layers"]["wg"].shape == (5, 32, 5120, 1536)
    assert shapes["layers"]["router"].shape == (5, 5120, 256)
    assert shapes["attn"][GLOBAL]["wiq"].shape == (3, 1024, 64, 128)
    assert shapes["attn"][WINDOWED]["wkvb"].shape == (3, 64, 1024, 320)


@pytest.mark.parametrize("name", sorted(PARENT_PLANS) + ["kanana-2-30b-a3b"])
def test_registered_configurations_keep_one_pool_pair(name):
    """A configuration whose layers are of one geometry keeps the pool it
    had: two arrays, every layer, the widths it always planned.  (Their
    planned bytes and their programs' jaxprs are pinned in
    test_latent_attention.py and test_layer_pattern.py.)"""
    cfg, plan = _plan(name)
    assert not cfg.by_kind
    shapes = jax.eval_shape(lambda: make_kv_pool_arrays(cfg, 4, 16))
    assert [a.shape for a in shapes] == [
        (cfg.num_layers, 64, w) for w in cfg.kv_row_widths()]
    assert cfg.kv_values_per_token == cfg.num_layers * sum(cfg.kv_row_widths())
    assert planner.kv_bytes_per_token(cfg) * 8192 * 16 \
        == plan.kv_pool_bytes or name != "kanana-2-30b-a3b"


@pytest.mark.parametrize("edit, match", [
    ({"attention_gate_type": "elementwise",
      "swa_attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"swa_attention_gate_type": None}, "swa_attention_gate_type"),
    ({"swa_index_topk": 64}, "swa_index_topk"),
    ({"swa_rope_scaling": {"factor": 2.0}}, "swa_rope_scaling"),
    ({"rope_scaling": {"factor": 2.0}}, "rope_scaling"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"index_n_heads": 0}, "index_topk"),
    ({"n_routed_experts": 32, "n_routed_experts_published": 256,
      "expert_share_offset": 240}, "num_experts_routed"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_is_not_served_raises_by_key(tmp_path, edit, match):
    hf = dict(_catalog_row(), **edit)
    with pytest.raises(UnsupportedConfigError, match=match):
        config_from_hf_json(_write(tmp_path, hf))


def test_per_kind_keys_need_latent_attention():
    with pytest.raises(UnsupportedConfigError, match="latent"):
        ModelConfig(index_topk=8, index_n_heads=2, index_head_dim=8)
    with pytest.raises(UnsupportedConfigError, match="latent"):
        ModelConfig(attention_gate="headwise")

def _tables(case):
    """(page table [B, 32], tokens held [B], active [B], common) of one case
    of the walk's split: pages of 4 keys, trips of 8 pages (INDEX_WALK_KEYS
    patched to 32), page 0 the trash page, private pages from 100 on."""
    P = 32
    own = iter(range(100, 1000))
    prefix = list(range(1, 1 + P))

    def lane(shared, tokens, prefix=prefix):
        pages = prefix[:shared]
        pages += [next(own) for _ in range(tokens // 4 + 1 - shared)]
        return pages + [0] * (P - len(pages)), tokens

    idle = ([0] * P, 0)
    other = list(range(40, 40 + P))
    lanes, common, active = {
        "nothing in common": ([lane(0, 77), lane(0, 90), lane(0, 41)], 0, ()),
        "less than one trip": ([lane(5, 77), lane(5, 90), lane(5, 41)], 5, ()),
        "two trips and a remainder": (
            [lane(19, 100), lane(19, 81), lane(19, 126)], 19, ()),
        # (three lanes reading one sequence's pages at three lengths)
        "the whole table": (
            [(prefix, 126), (prefix, 90), (prefix, 127)], P, ()),
        "an idle lane on the trash page": (
            [idle, lane(19, 100), idle, lane(19, 126)], 19,
            [False, True, False, True]),
        "a lane shorter than the run": (
            [lane(19, 100), lane(10, 41), lane(19, 126)], 10, ()),
        "two prefixes": (
            [lane(19, 100), lane(19, 81), lane(19, 126, other),
             lane(19, 90, other)], 0, ()),
        "one lane": ([lane(19, 100)], P, ()),
    }[case]
    table = np.array([row for row, _ in lanes], np.int32)
    lens = np.array([n for _, n in lanes], np.int32)
    active = np.array(active or [True] * len(lanes))
    return table, lens, active, common


@pytest.mark.parametrize("case", [
    "nothing in common", "less than one trip", "two trips and a remainder",
    "the whole table", "an idle lane on the trash page",
    "a lane shorter than the run", "two prefixes", "one lane"])
def test_shared_trips_choose_what_per_lane_trips_choose(monkeypatch, case):
    """Decode's index walk scores the pages every lane holds in the same
    leading columns once for all lanes, whole trips of them: the same
    scores, chosen slots and `ok` as the walk that gathers every trip lane
    by lane (`_common_pages` answering 0).  The scores agree to the last
    bit: a score is a sum over a head's 16 values, then over the 4 heads,
    in one order whichever operand carries the lane axis."""
    table, lens, active, common = _tables(case)
    cfg = sparse_cfg(index_topk=TOPK)
    ps, di, hi = 4, cfg.index_head_dim, cfg.index_n_heads
    rng = np.random.RandomState(5)
    b = table.shape[0]
    pool = rng.standard_normal((1000 * ps, di)).astype(np.float32)
    pool[:ps] = np.nan  # the trash page holds anything
    pool = jnp.asarray(pool)
    q = jnp.asarray(rng.standard_normal((b, 1, hi, di)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, 1, hi)), jnp.float32)

    def fn(table, lens, active):
        positions, paged = decode_plan(table, lens, active, ps)
        scores = index._paged_index_scores(q, w, pool, paged, jnp.float32)
        slots, ok = index._paged_index_choice(
            q, w, pool, paged, positions, cfg, jnp.float32)
        return jnp.where(paged.kv_valid[:, None], scores, 0), slots, ok

    def run(split):
        with monkeypatch.context() as m:
            m.setattr(index, "INDEX_WALK_KEYS", 32)
            if not split:
                m.setattr(index, "_common_pages",
                          lambda paged: (jnp.int32(0), jnp.int32(0)))
            return [np.asarray(x) for x in jax.jit(fn)(table, lens, active)]

    lane, found = jax.jit(lambda *a: index._common_pages(
        decode_plan(*a, ps)[1]))(table, lens, active)
    assert (int(lane), int(found)) == (int(np.argmax(active)), common)
    scores, slots, ok = run(split=True)
    scores_own, slots_own, ok_own = run(split=False)
    np.testing.assert_array_equal(scores, scores_own)
    np.testing.assert_array_equal(ok, ok_own)
    np.testing.assert_array_equal(slots[ok[:, 0]], slots_own[ok[:, 0]])
    assert [int(n) for n in ok.sum(axis=(1, 2))] == [
        min(TOPK, n + 1) if on else 0 for n, on in zip(lens, active)]
    assert np.isfinite(scores).all() and scores[active].any(axis=-1).all()


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def make_engine(cfg, params, mesh=None, **kw):
    defaults = dict(max_batch=4, page_size=8, num_pages=64,
                    max_pages_per_seq=8, prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32, mesh=mesh)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_is_token_exact_and_counts_what_it_keeps(model, backend):
    """Admission, chunked prefill, batched and fused decode over the two
    kinds of row: greedy tokens are the cache-less forward's; /metrics says
    the bytes a token holds, the keys scored and kept, the experts held."""
    cfg, params = model
    eng = make_engine(cfg, params, attention_backend=backend)
    assert eng.cfg.attention_backend == backend
    assert eng.kv_bytes_per_token == cfg.kv_values_per_token * 4
    rng = np.random.RandomState(11)
    prompts = {"a": list(rng.randint(1, 128, size=37)),
               "b": list(rng.randint(1, 128, size=5)),
               "c": list(rng.randint(1, 128, size=20))}
    for rid, p in prompts.items():
        eng.submit(GenRequest(request_id=rid, prompt_ids=p,
                              max_new_tokens=10))
    done = eng.run_to_completion()
    for rid, p in prompts.items():
        assert len(done[rid].output_ids) == 10
        assert_greedy_consistent(cfg, params, p, done[rid].output_ids)
    e = eng.metrics.snapshot(eng)["engine"]
    assert e["kv_bytes_per_token"] == planner.kv_bytes_per_token(
        cfg, kv_dtype="float32")
    assert (e["experts_held"], e["experts_routed"]) == (4, 16)
    # a decode step scores a lane's whole context and keeps at most TOPK
    assert e["index_keys_scored"] > e["index_keys_kept"] > 0
    assert e["index_keys_kept"] <= TOPK * 3 * 10
    scored, kept = eng._programs.index_keys([5, 20], 2)
    assert (scored, kept) == (6 + 7 + 21 + 22, 6 + 7 + 8 + 8)
    # distinct prompts hold distinct pages: lanes that decode together
    # share no trip, a lane that decodes alone shares its one trip with
    # itself (8 pages of 8 keys: the whole table)
    shared = eng._programs.index_keys_shared
    assert shared([([3, 4], 5), ([5, 6, 7], 20)], 2) == 0
    assert shared([([5, 6, 7], 20)], 2) == 21 + 22
    assert 0 <= e["index_keys_shared"] < e["index_keys_scored"]


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_prefix_hit_then_suffix_prefill_is_token_exact(model, backend):
    """A prefix hit hands a second thread the first one's pages: latent rows
    of both kinds and the indexer keys; the suffix's queries choose among
    them, and the walk folds them in XLA or in the Pallas kernel."""
    cfg, params = model
    eng = make_engine(cfg, params, attention_backend=backend)
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


def _shared_keys(lanes, steps, P, ps, trip_keys=2048):
    """`index_keys_shared` the plain way: the page table as the device sees
    it, column by column."""
    table = np.zeros((len(lanes), P), np.int64)
    for row, (pages, _) in zip(table, lanes):
        row[:len(pages)] = pages
    same = np.cumprod((table == table[0]).all(axis=0))
    cp = min(trip_keys // ps, P)
    total = 0
    for i in range(steps):
        live = [n + i + 1 for _, n in lanes]
        trips = min(-(-max(live) // (cp * ps)), -(-P // cp))
        total += sum(min(n, min(int(same.sum()) // cp, trips) * cp * ps)
                     for n in live)
    return total


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_lanes_on_one_attached_prefix_share_its_index_keys(model, backend):
    """Three threads over one cached prefix of 2,080 tokens (260 pages, one
    whole trip of the index walk and a remainder) decode together: their
    tables name the prefix's pages in the same leading columns, the walk's
    first trip scores them once for the three lanes, and every token is the
    uncached forward's.  /metrics counts the keys scored that way, by the
    device's arithmetic; a batch of distinct prompts shares none."""
    cfg, params = model
    P, ps = 288, 8
    eng = make_engine(cfg, params, attention_backend=backend, num_pages=640,
                      max_pages_per_seq=P, prefill_buckets=(64, 512))
    dispatched, count = [], eng._programs.index_keys_shared

    def recorded(lanes, steps):
        dispatched.append(([(list(p), n) for p, n in lanes], steps))
        return count(lanes, steps)

    eng._programs.index_keys_shared = recorded
    rng = np.random.RandomState(31)
    shared = list(rng.randint(1, 128, size=2080))
    eng.submit(GenRequest(request_id="first", prompt_ids=shared + [3, 7],
                          max_new_tokens=2, prefix_key="thread-0"))
    eng.run_to_completion()
    alone = eng.index_keys_shared  # one lane shares its first trip
    assert alone == sum(_shared_keys(*d, P, ps) for d in dispatched) > 0
    # (one length: the uncached forward that checks them compiles once)
    threads = {f"t{i}": shared + list(rng.randint(1, 128, size=9))
               for i in range(3)}
    reqs = {rid: GenRequest(request_id=rid, prompt_ids=p, max_new_tokens=6,
                            prefix_key="thread-" + rid)
            for rid, p in threads.items()}
    for req in reqs.values():
        eng.submit(req)
    eng.run_to_completion()
    for rid, req in reqs.items():
        assert req.cached_tokens >= 2048 and req.cache_source == "cross"
        assert_greedy_consistent(cfg, params, threads[rid], req.output_ids)
    together = [d for d in dispatched if len(d[0]) == 3]
    assert together and all(
        _shared_keys(*d, P, ps) == 3 * 2048 * d[1] for d in together)
    assert eng.index_keys_shared == sum(
        _shared_keys(*d, P, ps) for d in dispatched)
    e = eng.metrics.snapshot(eng)["engine"]
    assert e["index_keys_shared"] == eng.index_keys_shared
    assert e["index_keys_scored"] > e["index_keys_shared"] > alone


@pytest.mark.parametrize("error, path, kw, mesh_axes", [
    (WindowedAttentionUnsupported, "speculative verify",
     {"speculative_k": 2}, None),
    (WindowedAttentionUnsupported, "kv_quantize int8",
     {"kv_quantize": "int8"}, None),
    (WindowedAttentionUnsupported, "prefill_ring", {}, {"sp": 2}),
    (WindowedAttentionUnsupported, "pp > 1", {}, {"pp": 2}),
    (LatentAttentionUnsupported, "a tp / ep mesh", {}, {"tp": 2}),
    (LatentAttentionUnsupported, "a tp / ep mesh", {}, {"ep": 2}),
    (LatentAttentionUnsupported, "a KV tier", {"kv_host_tier_mb": 8}, None),
])
def test_engine_refuses_what_is_not_built_by_name(model, error, path, kw,
                                                  mesh_axes):
    cfg, params = model
    mesh = None
    if mesh_axes:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), tuple(mesh_axes))
    with pytest.raises(error, match=path) as e:
        make_engine(cfg, params, mesh=mesh, **kw)
    assert path in e.value.path
