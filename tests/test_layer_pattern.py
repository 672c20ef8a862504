"""Layer patterns (ISSUE 27): windowed and global attention layers in one
model, one rotary table per kind, one uniform paged pool.

CPU, float32, tiny sizes, seeded weights.  The load-bearing checks:

* `forward` for a 2-period pattern agrees with a plain per-token reference
  written here (numpy loops, no cache) on every cache path: uncached,
  contiguous, paged-XLA and paged-Pallas (interpreted), with prefill chunks
  wider than the window and contexts several windows deep;
* the sliding mask keeps exactly `window` keys, the query's own included;
* a windowed Pallas decode call touches at most ceil((window + chunk) / chunk)
  KV chunks a lane whatever the context (counted, and shown by poisoning the
  pages below the window);
* YaRN against HF's closed form; `config_from_hf_json` on the catalog's
  Mellum2 keys and its typed errors;
* a period-1 config builds the jaxpr the parent commit built;
* the engine refuses every attention path that would ignore a window, and is
  token-exact for a windowed model through admission, prefix-cache hit and
  suffix prefill.
"""

import hashlib
import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.models.config import (
    CONFIGS, GLOBAL, WINDOWED, RopeParams, UnsupportedConfigError,
    config_from_hf_json,
)
from kafka_tpu.models.cache import KVCache, PagedView
from kafka_tpu.models.llama import init_kv_cache
from kafka_tpu.models.mixers.gqa import WindowedPathError
from kafka_tpu.ops.attention import causal_attention
from kafka_tpu.ops.pallas import (
    paged_decode_attention, paged_decode_attention_window,
)
from kafka_tpu.ops.pallas.paged_attention import decode_chunk_range
from kafka_tpu.ops.rope import kind_frequencies, yarn_frequencies
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime.engine import WindowedAttentionUnsupported

from test_engine import assert_greedy_consistent

WINDOW = 8
YARN = RopeParams("yarn", 10000.0, 4.0, 32, 8.0, 1.0, None)
ROPES = ((GLOBAL, YARN), (WINDOWED, RopeParams("default", 50000.0)))


def pattern_cfg(backend="xla", experts=4, **kw):
    """Two periods of (windowed, windowed, global): window 8, YaRN on the
    global layers, 4 experts top-2."""
    base = dict(
        name="pattern-test", vocab_size=128, hidden_size=64,
        intermediate_size=32, num_layers=6, num_heads=4, num_kv_heads=2,
        head_dim=16, dtype="float32", attention_backend=backend,
        layer_types=(WINDOWED, WINDOWED, GLOBAL) * 2, sliding_window=WINDOW,
        rope_by_kind=ROPES, num_experts=experts, num_experts_per_tok=2,
        tie_word_embeddings=False, rms_norm_eps=1e-6)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = pattern_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


# ---------------------------------------------------------------------------
# the plain reference: numpy, one query token at a time
# ---------------------------------------------------------------------------

def _np(x):
    return np.asarray(x, np.float64)


def _inv_freq(rp, dim):
    """HF _compute_default / _compute_yarn parameters in plain math."""
    idx = np.arange(0, dim, 2, dtype=np.float64) / dim
    if rp.rope_type == "default":
        return 1.0 / rp.rope_theta ** idx, 1.0

    def corr(rot):
        return (dim * math.log(rp.original_max_position / (rot * 2 * math.pi))
                / (2 * math.log(rp.rope_theta)))

    low = max(math.floor(corr(rp.beta_fast)), 0)
    high = min(math.ceil(corr(rp.beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    pos_freqs = rp.rope_theta ** idx
    inv = (1 / (rp.factor * pos_freqs)) * ramp + (1 / pos_freqs) * (1 - ramp)
    af = rp.attention_factor
    return inv, (0.1 * math.log(rp.factor) + 1.0 if af is None else af)


def _rope_np(x, pos, inv, af):
    ang = pos * inv
    cos, sin = np.cos(ang) * af, np.sin(ang) * af
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def reference_logits(params, cfg, ids):
    """[S, V] logits of one sequence, a query token at a time."""
    lay = {k: _np(v) for k, v in params["layers"].items()}
    x = _np(params["embed"])[np.asarray(ids)]
    S, d, rep = len(ids), cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
    for l, kind in enumerate(cfg.layer_types):
        inv, af = _inv_freq(dict(cfg.rope_by_kind)[kind], d)
        h = _rms(x, lay["ln_attn"][l], cfg.rms_norm_eps)
        q = np.einsum("sh,hnd->snd", h, lay["wq"][l])
        k = np.einsum("sh,hnd->snd", h, lay["wk"][l])
        v = np.einsum("sh,hnd->snd", h, lay["wv"][l])
        att = np.zeros_like(q)
        for t in range(S):
            qt = _rope_np(q[t], t, inv, af)
            first = max(0, t - cfg.sliding_window + 1) if kind == WINDOWED \
                else 0
            keys = np.stack([_rope_np(k[u], u, inv, af)
                             for u in range(first, t + 1)])
            for n in range(cfg.num_heads):
                sc = keys[:, n // rep] @ qt[n] / math.sqrt(d)
                p = np.exp(sc - sc.max())
                att[t, n] = (p / p.sum()) @ v[first:t + 1, n // rep]
        x = x + np.einsum("snd,ndh->sh", att, lay["wo"][l])
        h = _rms(x, lay["ln_mlp"][l], cfg.rms_norm_eps)
        y = np.zeros_like(h)
        for t in range(S):
            logit = h[t] @ lay["router"][l]
            top = np.argsort(-logit)[:cfg.num_experts_per_tok]
            w = np.exp(logit[top] - logit[top].max())
            for e, we in zip(top, w / w.sum()):
                g = h[t] @ lay["wg"][l, e]
                silu = g / (1 + np.exp(-g))
                y[t] += we * ((silu * (h[t] @ lay["wu"][l, e]))
                              @ lay["wd"][l, e])
        x = x + y
    return _rms(x, _np(params["final_norm"]), cfg.rms_norm_eps) \
        @ _np(params["lm_head"])


IDS = np.random.RandomState(5).randint(1, 128, size=44)  # 5.5 windows deep


@pytest.fixture(scope="module")
def ref(model):
    cfg, params = model
    return reference_logits(params, cfg, IDS)


def close(a, b):
    np.testing.assert_allclose(np.asarray(a, np.float64), b, rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# forward against the reference, by cache path
# ---------------------------------------------------------------------------

def test_uncached_matches_reference(model, ref):
    cfg, params = model
    pos = jnp.arange(len(IDS))[None]
    logits, _ = forward(params, cfg, jnp.asarray(IDS)[None], pos)
    close(logits[0], ref)


def test_contiguous_cache_matches_reference(model, ref):
    """Prefill 29 tokens (3.6 windows) in one call, then decode the rest."""
    cfg, params = model
    cap, n0 = 48, 29
    cache = init_kv_cache(cfg, 1, cap)
    slots = jnp.arange(cap)[None]
    logits, cache = forward(
        params, cfg, jnp.asarray(IDS[:n0])[None], jnp.arange(n0)[None],
        kv_cache=cache, kv_valid=slots < n0)
    close(logits[0], ref[:n0])
    for t in range(n0, len(IDS)):
        lg, cache = forward(
            params, cfg, jnp.asarray(IDS[t:t + 1])[None],
            jnp.asarray([[t]]), kv_cache=cache, kv_valid=slots <= t)
        close(lg[0, 0], ref[t])


PS, PAGES = 4, 12  # 48 slots a sequence; Pallas chunk = 8 pages = 32 slots


def _prefill(params, cfg, pools, page_row, chunk, start):
    """One prefill chunk of one sequence, the index plan as the engine
    builds it (benchmarks/paged_step.py has the same)."""
    S, C = len(chunk), PAGES * PS
    local = jnp.arange(S)
    write = (page_row[(start + local) // PS] * PS + (start + local) % PS)
    read = (page_row[:, None] * PS + jnp.arange(PS)[None]).reshape(1, C)
    kvp = jnp.arange(C)[None]
    view = PagedView(write[None], read, kvp, kvp < start + S,
                     page_table=page_row[None], page_size=PS,
                     start=jnp.int32(start), chunk_len=jnp.int32(S))
    logits, cache = forward(
        params, cfg, jnp.asarray(chunk)[None], (start + local)[None],
        kv_cache=KVCache(*pools), paged=view)
    return logits[0], (cache.k, cache.v)


def _decode(params, cfg, pools, page_row, token, n):
    C = PAGES * PS
    seq = jnp.asarray([n], jnp.int32)
    write = (page_row[n // PS] * PS + n % PS).reshape(1, 1)
    read = (page_row[:, None] * PS + jnp.arange(PS)[None]).reshape(1, C)
    kvp = jnp.arange(C)[None]
    view = PagedView(write, read, kvp, kvp <= n, page_table=page_row[None],
                     seq_lens=seq, page_size=PS)
    logits, cache = forward(
        params, cfg, jnp.asarray([[token]]), seq[:, None],
        kv_cache=KVCache(*pools), paged=view)
    return logits[0, 0], (cache.k, cache.v)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_prefill_and_decode_match_reference(model, ref, backend):
    """Chunked prefill with 16-token chunks (two windows wide) over 32
    tokens, then decode to 44: the Pallas leg runs the flash-prefill kernel
    and the windowed / global decode kernels interpreted, on scattered
    pages."""
    cfg, params = model
    cfg = cfg.replace(attention_backend=backend)
    shape = (cfg.num_layers, (PAGES + 1) * PS, cfg.num_kv_heads * cfg.head_dim)
    pools = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
    page_row = jnp.asarray(
        np.random.RandomState(1).permutation(np.arange(1, PAGES + 1)),
        jnp.int32)
    for start in (0, 16):
        lg, pools = _prefill(params, cfg, pools, page_row,
                             IDS[start:start + 16], start)
        close(lg, ref[start:start + 16])
    for t in range(32, len(IDS)):
        lg, pools = _decode(params, cfg, pools, page_row, int(IDS[t]), t)
        close(lg, ref[t])


# ---------------------------------------------------------------------------
# the mask's edge, and what the windowed decode kernel reads
# ---------------------------------------------------------------------------

def test_sliding_mask_keeps_window_keys_own_included():
    """With v = one-hot of the key's position the output IS the attention
    distribution: a query at p weighs exactly p - window + 1 .. p."""
    S, W = 12, 4
    q = jnp.zeros((1, S, 1, S))  # all scores equal: uniform over the mask
    v = jnp.eye(S)[None, :, None, :]
    pos = jnp.arange(S)[None]
    out = causal_attention(q, jnp.zeros((1, S, 1, S)), v.astype(jnp.float32),
                           q_positions=pos, kv_positions=pos, window=W)
    probs = np.asarray(out[0, :, 0])  # [query, key]
    for p in range(S):
        kept = np.nonzero(probs[p] > 0)[0]
        assert list(kept) == list(range(max(0, p - W + 1), p + 1))
        assert len(kept) == min(W, p + 1)


@pytest.mark.parametrize("seq_len", [3, 127, 128, 1023, 1024, 1100, 8300,
                                     16383])
def test_windowed_decode_chunk_count(seq_len):
    """ISSUE 27 acceptance: at most ceil((window + chunk) / chunk) chunks
    a lane, whatever the context; the global call reads them all."""
    window, ps, cp = 1024, 16, 8
    chunk = ps * cp
    first, end = decode_chunk_range(seq_len, window, ps, cp)
    assert end - first <= -(-(window + chunk) // chunk)
    assert first * chunk <= max(seq_len + 1 - window, 0)  # window covered
    g_first, g_end = decode_chunk_range(seq_len, None, ps, cp)
    assert (g_first, g_end) == (0, end) and end == -(-(seq_len + 1) // chunk)


def test_windowed_decode_kernel_never_uses_pages_below_the_window():
    """Pages of chunks wholly below the window hold NaN: the windowed
    kernel's output is finite and equals the XLA mask's; the global kernel's
    is poisoned.  Contexts from inside the first window to 6 chunks deep."""
    rng = np.random.RandomState(0)
    B, Hq, Hkv, D, ps, P, W = 4, 4, 2, 16, 4, 64, 8
    chunk = 8 * ps
    lens = np.asarray([5, 40, 100, 200], np.int32)
    k = rng.randn((P * B + 1) * ps, Hkv * D).astype(np.float32)
    v = rng.randn((P * B + 1) * ps, Hkv * D).astype(np.float32)
    table = 1 + np.arange(B * P, dtype=np.int32).reshape(B, P)
    k_bad, v_bad = k.copy(), v.copy()
    for b, n in enumerate(lens):
        first, _ = decode_chunk_range(int(n), W, ps)
        for page in table[b, :first * 8]:
            k_bad[page * ps:(page + 1) * ps] = np.nan
            v_bad[page * ps:(page + 1) * ps] = np.nan
    q = jnp.asarray(rng.randn(B, Hq, D), jnp.float32)
    args = (jnp.asarray(table), jnp.asarray(lens))
    got = paged_decode_attention_window(
        q, jnp.asarray(k_bad), jnp.asarray(v_bad), *args, window=W,
        page_size=ps, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    idx = (table[:, :, None] * ps + np.arange(ps)).reshape(B, P * ps)
    kvp = jnp.broadcast_to(jnp.arange(P * ps)[None], (B, P * ps))
    want = causal_attention(
        q[:, None], jnp.asarray(k[idx]).reshape(B, -1, Hkv, D),
        jnp.asarray(v[idx]).reshape(B, -1, Hkv, D),
        q_positions=jnp.asarray(lens)[:, None], kv_positions=kvp,
        kv_valid=kvp <= jnp.asarray(lens)[:, None], window=W)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    poisoned = paged_decode_attention(
        q, jnp.asarray(k_bad), jnp.asarray(v_bad), *args, page_size=ps,
        interpret=True)
    assert not np.isfinite(np.asarray(poisoned)[lens > chunk + W]).any()


def test_windowed_kernel_is_named_apart_from_the_global_one():
    """`decode_step_dev_ms` finds decode programs by `paged_decode`; the
    windowed calls must match it and still be told from the global ones."""
    q = jax.ShapeDtypeStruct((2, 4, 16), jnp.float32)
    pool = jax.ShapeDtypeStruct((36, 32), jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = str(jax.make_jaxpr(
        lambda *a: paged_decode_attention_window(
            *a, window=8, page_size=4, interpret=True)
    )(q, pool, pool, i32(2, 8), i32(2)))
    assert "paged_decode_attention_window" in text
    assert paged_decode_attention_window.__name__.startswith(
        paged_decode_attention.__name__)


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

def test_yarn_matches_closed_form_at_mellum_parameters():
    rp = RopeParams("yarn", 500000.0, 16.0, 8192, 32.0, 1.0,
                    1.2772588722239782)
    inv, af = yarn_frequencies(rp, 128)
    want, want_af = _inv_freq(rp, 128)
    np.testing.assert_allclose(np.asarray(inv), want, rtol=1e-6)
    assert af == want_af == 1.2772588722239782
    # the fastest pairs extrapolate (unscaled), the slowest interpolate
    base = 1.0 / 500000.0 ** (np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(np.asarray(inv[:8]), base[:8], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(inv[-8:]), base[-8:] / 16,
                               rtol=1e-6)
    # HF's default attention factor when the config leaves it out
    _, default_af = yarn_frequencies(
        RopeParams("yarn", 500000.0, 16.0, 8192, 32.0, 1.0, None), 128)
    assert default_af == pytest.approx(1.2772588722239782, rel=1e-12)


def test_each_kind_takes_its_own_table(model):
    cfg, _ = model
    g, g_af = kind_frequencies(cfg, GLOBAL)
    w, w_af = kind_frequencies(cfg, WINDOWED)
    assert g_af > 1.0 and w_af == 1.0
    assert not np.allclose(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# config_from_hf_json
# ---------------------------------------------------------------------------

MELLUM = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}


def _load(tmp_path, **changes):
    hf = dict(MELLUM, **changes)
    path = tmp_path / "mellum2" / "config.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(hf))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_reads_the_published_mellum2_keys(tmp_path):
    cfg = _load(tmp_path)
    assert cfg.name == "mellum2" and cfg.num_layers == 28
    assert cfg.layer_period == (WINDOWED,) * 3 + (GLOBAL,)
    assert cfg.sliding_window == 1024 and cfg.is_windowed
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    assert cfg.intermediate_size == 896  # the experts' width, not 7168
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 4, 128)
    assert cfg.rope_of(GLOBAL).rope_type == "yarn"
    assert cfg.rope_of(GLOBAL).attention_factor == 1.2772588722239782
    assert cfg.rope_of(WINDOWED) == RopeParams("default", 500000.0)
    assert not cfg.tie_word_embeddings and cfg.max_context == 131072
    hash(cfg)  # a static argument of every jitted step
    # a depth cut keeps whole periods of the published list
    cut = _load(tmp_path, num_hidden_layers=8)
    assert cut.layer_types == ((WINDOWED,) * 3 + (GLOBAL,)) * 2


@pytest.mark.parametrize("changes, word", [
    ({"mlp_layer_types": ["dense"] + ["sparse"] * 27}, "mlp_layer_types"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"layer_types": ["chunked_attention"] * 28}, "layer_types"),
    ({"use_sliding_window": False}, "use_sliding_window"),
    ({"sliding_window": None}, "sliding_window"),
    ({"layer_types": ["full_attention"] * 4}, "layer_types"),
    ({"rope_parameters": {"full_attention": {"rope_type": "yarn"}}},
     "rope_parameters"),
    ({"rope_parameters": dict(MELLUM["rope_parameters"], sliding_attention={
        "rope_type": "longrope"})}, "rope_type"),
])
def test_config_from_hf_json_refuses_what_it_cannot_honour(
        tmp_path, changes, word):
    with pytest.raises(UnsupportedConfigError, match=word):
        _load(tmp_path, **changes)


def test_a_config_without_a_pattern_is_what_it_was():
    for name in ("tiny", "tiny-moe", "mixtral-8x7b", "llama-3.1-8b"):
        cfg = CONFIGS[name]
        assert cfg.layer_types == () and cfg.sliding_window is None
        assert cfg.rope_by_kind == () and not cfg.is_windowed
        assert cfg.layer_period == (GLOBAL,)


# ---------------------------------------------------------------------------
# a period of one is the parent's program
# ---------------------------------------------------------------------------

# sha256[:16] of str(jax.make_jaxpr(...)) as the PARENT commit (41a1b4e, before
# patterns) builds it: recorded by running `_jaxprs` below, unchanged, as a
# test in a checkout of that commit (same conftest, same JAX).
PARENT_JAXPRS = {
    "tiny.plain": "b2124916576f66e8",
    "tiny.contiguous": "4a775050cc6e6e80",
    "tiny.paged": "ba9229535b5cf839",
    "tiny-moe.plain": "eda6f386a2a8e003",
    "tiny-moe.contiguous": "f94b1333782dd240",
    "tiny-moe.paged": "512805b0d62efd4d",
}


def _jaxprs(cfg):
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    ids, valid = i32(2, 8), jax.ShapeDtypeStruct((2, 32), jnp.bool_)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 32))
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, 64, cfg.num_kv_heads * cfg.head_dim), jnp.float32)

    def plain(p, i, q):
        return forward(p, cfg, i, q)[0]

    def contiguous(p, i, q, c, v):
        return forward(p, cfg, i, q, kv_cache=c, kv_valid=v)

    def paged(p, i, q, kp, vp, w, r, kvp, kvv, pt):
        view = PagedView(w, r, kvp, kvv, page_table=pt, page_size=4)
        return forward(p, cfg, i, q, kv_cache=KVCache(kp, vp), paged=view)

    return {
        "plain": jax.make_jaxpr(plain)(params, ids, ids),
        "contiguous": jax.make_jaxpr(contiguous)(params, ids, ids, cache,
                                                 valid),
        "paged": jax.make_jaxpr(paged)(params, ids, ids, pool, pool,
                                       i32(2, 8), i32(2, 32), i32(2, 32),
                                       valid, i32(2, 8)),
    }


@pytest.mark.parametrize("name", ["tiny", "tiny-moe"])
def test_period_one_config_builds_the_parents_jaxpr(name):
    cfg = CONFIGS[name].replace(dtype="float32")
    got = _jaxprs(cfg)
    for path, jaxpr in got.items():
        digest = hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
        assert digest == PARENT_JAXPRS[f"{name}.{path}"], path
    # and a pattern that SAYS every layer is global is that same program
    said = _jaxprs(cfg.replace(layer_types=(GLOBAL,) * cfg.num_layers))
    assert {k: str(v) for k, v in said.items()} == \
        {k: str(v) for k, v in got.items()}


def test_one_layer_scan_per_forward_pass(model):
    """`decode_step_dev_ms` divides device time by innermost `while` loops,
    one per forward pass: the scan over periods is still one loop."""
    cfg, params = model
    jaxpr = jax.make_jaxpr(
        lambda p, i, q: forward(p, cfg, i, q)[0]
    )(params, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None])
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 2  # 2 periods
    assert "scan" not in str(scans[0].params["jaxpr"])  # nothing nested


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def make_engine(cfg, params, mesh=None, **kw):
    defaults = dict(max_batch=4, page_size=8, num_pages=64,
                    max_pages_per_seq=8, prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32, mesh=mesh)


@pytest.mark.parametrize("path, kw, mesh_axes", [
    ("speculative verify", {"speculative_k": 2}, None),
    ("kv_quantize", {"kv_quantize": "int8"}, None),
    ("prefill_ring", {}, {"sp": 2}),
    ("pp > 1", {}, {"pp": 2}),
])
def test_engine_refuses_paths_that_would_ignore_the_window(
        model, path, kw, mesh_axes):
    cfg, params = model
    mesh = None
    if mesh_axes:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), tuple(mesh_axes))
    with pytest.raises(WindowedAttentionUnsupported, match=path) as e:
        make_engine(cfg, params, mesh=mesh, **kw)
    assert path in e.value.path
    # the same options serve a model without windowed layers
    if not mesh_axes:
        plain = ModelConfig(name="plain", vocab_size=128, dtype="float32")
        make_engine(plain, init_params(plain, jax.random.PRNGKey(0)), **kw)


def test_forward_backstops_raise_where_the_engine_is_bypassed(model):
    cfg, params = model
    shape = (cfg.num_layers, 64, cfg.num_kv_heads * cfg.head_dim)
    pools = KVCache(jnp.zeros(shape), jnp.zeros(shape))
    i = jnp.zeros((1, 3), jnp.int32)
    view = PagedView(i, jnp.zeros((1, 16), jnp.int32),
                     jnp.arange(16)[None], jnp.ones((1, 16), bool),
                     page_table=jnp.zeros((1, 4), jnp.int32),
                     seq_lens=jnp.zeros((1,), jnp.int32), page_size=4,
                     chunk_len=jnp.ones((1,), jnp.int32))
    with pytest.raises(WindowedPathError, match="verify"):
        forward(params, cfg.replace(attention_backend="pallas"), i, i,
                kv_cache=pools, paged=view)
    with pytest.raises(WindowedPathError, match="prefill_ring"):
        forward(params, cfg.replace(prefill_ring=True), i, i,
                kv_cache=pools, paged=view._replace(page_table=None))
    from kafka_tpu.parallel.pipeline import _check_pp_divisibility

    with pytest.raises(WindowedPathError, match="pp stage"):
        _check_pp_divisibility(cfg, 2, 1)


@pytest.mark.parametrize("backend, tp", [("xla", 1), ("pallas", 1),
                                         ("pallas", 2)])
def test_engine_is_token_exact_for_a_windowed_model(model, backend, tp):
    """Admission, chunked prefill with chunks wider than the window, batched
    decode several windows deep: greedy tokens are those of the cache-less
    forward.  tp=2: the per-shard windowed decode kernel under shard_map
    (prefill keeps the XLA mask on a mesh)."""
    cfg, params = model
    mesh = None
    if tp > 1:
        from kafka_tpu.parallel import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(tp=tp))
    eng = make_engine(cfg, params, mesh=mesh, attention_backend=backend)
    assert eng.cfg.attention_backend == backend
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
    info = eng.device_info
    assert info["layer_pattern"] == [WINDOWED, WINDOWED, GLOBAL]
    assert info["sliding_window"] == WINDOW


@pytest.mark.parametrize("common", [4, 8, 24],
                         ids=["hit-inside-first-window", "hit-at-the-window",
                              "hit-three-windows-deep"])
def test_prefix_hit_then_suffix_prefill_is_token_exact(model, common):
    """The uniform pool keeps every layer's every page, so a prefix hit
    serves windowed layers too: the suffix's first queries read keys on
    BOTH sides of the hit's end.  The hit ends before the window is full
    (4... the pages hold 8), exactly at it (8) and three windows past (24).
    """
    cfg, params = model
    eng = make_engine(cfg, params)
    rng = np.random.RandomState(common)
    shared = list(rng.randint(1, 128, size=max(common, 8)))
    first = GenRequest(request_id="A", prompt_ids=shared + [3, 7, 11],
                       max_new_tokens=4, prefix_key="thread-A")
    eng.submit(first)
    eng.run_to_completion()
    suffix = list(rng.randint(1, 128, size=13))
    prompt = shared[:max(common, 8)] + suffix
    second = GenRequest(request_id="B", prompt_ids=prompt, max_new_tokens=8,
                        prefix_key="thread-B")
    eng.submit(second)
    eng.run_to_completion()
    assert second.cached_tokens >= 8 and second.cache_source == "cross"
    assert_greedy_consistent(cfg, params, prompt, second.output_ids)
    ref = make_engine(cfg, params, prefix_cache_entries=0).generate(
        prompt, max_new_tokens=8)
    assert second.output_ids == ref.output_ids


def test_kv_window_dead_share_counts_rows_no_query_can_attend(model):
    cfg, params = model
    eng = make_engine(cfg, params)
    assert eng.kv_window_dead_share() == 0.0  # no live lane
    prompt = list(np.random.RandomState(2).randint(1, 128, size=30))
    eng.submit(GenRequest(request_id="a", prompt_ids=prompt,
                          max_new_tokens=20))
    for _ in range(6):
        eng.step()
    live = [s for s in eng.slots if s is not None]
    assert live
    n = live[0].seq.length
    # 4 of 6 layers are windowed; rows at positions <= n - window are dead
    want = 4 * (n - WINDOW + 1) / (6 * n)
    assert eng.kv_window_dead_share() == pytest.approx(want)
    snap = eng.metrics.snapshot(eng)
    assert snap["engine"]["kv_window_dead_share"] == pytest.approx(want,
                                                                   abs=1e-6)
    eng.run_to_completion()
    plain = ModelConfig(name="plain", vocab_size=128, dtype="float32")
    assert make_engine(plain, init_params(plain, jax.random.PRNGKey(0))
                       ).kv_window_dead_share() == 0.0
