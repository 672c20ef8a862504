"""Latent attention behind a leading dense layer, sigmoid routing with a
selection bias, shared experts (ISSUE 31; HF `deepseek_v3`, Kanana-2).

CPU, float32 and bfloat16, tiny sizes with the published ratios (rotary 8 of
24 query lanes as 64 of 192, one dense layer, a non-zero selection bias).
The load-bearing checks:

* `forward` agrees with a plain per-token reference written here (numpy
  float64 loops, expanded attention, no cache) on every cache path:
  uncached, contiguous, paged-XLA and paged-Pallas (interpreted), with
  prefill in chunks and decode across three of the kernel's softmax steps;
* the absorbed form (paged decode) equals the expanded one (every other
  path): to rounding in float32, and to a stated bound in bfloat16;
* the latent Pallas kernel against the XLA absorbed form on contexts around
  its 512-key step boundaries, and its name;
* the routing rule against a loop: choice by sigma + b, weight by sigma
  alone, the scale, ties to the lower index; the shared branch always on;
* `config_from_hf_json` on the catalog's keys and each typed error;
* the pool's row widths: one definition, and the three registered
  configurations' memory plans give the bytes the parent commit gave;
* the engine is token-exact through admission, prefix-cache hit and suffix
  prefill (since PR 37 a walk of the live keys, here in 16-key trips over
  another thread's pages, XLA fold and interpreted kernel), exports its
  bytes a token, and refuses by name every option with no latent form;
  `forward` backstops the same, a paged plan without a page table among them;
* a config without leading dense layers builds the parent's jaxpr.
"""

import functools
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, forward, init_params
from kafka_tpu.models.config import (
    CONFIGS, UnsupportedConfigError, config_from_hf_json,
)
from kafka_tpu.models.cache import KVCache, PagedView
from kafka_tpu.models.ffn import _moe_block, _routing_weights_sigmoid
from kafka_tpu.models.llama import init_kv_cache
from kafka_tpu.models.mixers import latent
from kafka_tpu.models.mixers.index import LatentPathError
from kafka_tpu.ops.pallas import paged_attention
from kafka_tpu.ops.pallas import paged_decode_attention_latent
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import planner
from kafka_tpu.runtime.engine import LatentAttentionUnsupported
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

from test_engine import assert_greedy_consistent
from test_layer_pattern import PARENT_JAXPRS, _jaxprs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALE = 2.448


def latent_cfg(backend="xla", dtype="float32", **kw):
    """One dense layer then two routed ones: 8 experts top-3 + a shared
    branch, 4 heads of [16 nope | 8 rope] over a 32-value latent."""
    base = dict(
        name="latent-test", vocab_size=128, hidden_size=64, num_layers=3,
        num_heads=4, num_kv_heads=4, head_dim=8, rope_theta=1e6,
        rms_norm_eps=1e-6, tie_word_embeddings=False, dtype=dtype,
        attention_backend=backend, num_experts=8, num_experts_per_tok=3,
        intermediate_size=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
        first_k_dense=1, dense_intermediate_size=96,
        shared_intermediate_size=48, moe_scoring="sigmoid",
        routed_scaling_factor=SCALE)
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = latent_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(5))


# ---------------------------------------------------------------------------
# the plain reference: numpy float64, one query token at a time
# ---------------------------------------------------------------------------

def _np(x):
    return np.asarray(x, np.float64)


def _rms(x, w, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [..., d] published interleaved: de-interleave, rotate half-split."""
    d = x.shape[-1]
    x = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    cos, sin = np.cos(pos * inv), np.sin(pos * inv)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, wg, wu, wd):
    g = h @ wg
    return (g / (1 + np.exp(-g)) * (h @ wu)) @ wd


def route_loop(sigma, bias, k, scale):
    """The rule, one token: the k largest sigma + b are chosen (ties to the
    lower index), a chosen expert weighs scale * sigma / sum of the chosen
    sigma."""
    order = sorted(range(len(sigma)), key=lambda e: (-(sigma[e] + bias[e]), e))
    chosen = order[:k]
    total = sum(sigma[e] for e in chosen) + 1e-20
    return {e: scale * sigma[e] / total for e in chosen}


def reference_logits(params, cfg, ids):
    p = jax.tree.map(_np, params)
    r, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps, n_tok = cfg.rms_norm_eps, len(ids)
    x = p["embed"][np.asarray(ids)]
    stacks = [(p["dense_layers"], False)] if "dense_layers" in p else []
    stacks.append((p["layers"], cfg.is_moe))
    for stack, routed in stacks:
        for i in range(stack["wq"].shape[0]):
            lp = {k: v[i] for k, v in stack.items()}
            h = _rms(x, lp["ln_attn"], eps)
            out = np.zeros((n_tok, cfg.num_heads, cfg.v_head_dim))
            kva = h @ lp["wkva"]
            c = _rms(kva[:, :r], lp["ln_kv"], eps)
            k_r = np.stack([_rope(kva[t, r:], t, cfg.rope_theta)
                            for t in range(n_tok)])
            for t in range(n_tok):
                q = np.einsum("h,hnd->nd", h[t], lp["wq"])
                for n in range(cfg.num_heads):
                    kv = c[: t + 1] @ lp["wkvb"][n]  # [t + 1, dn + dv]
                    q_r = _rope(q[n, dn:], t, cfg.rope_theta)
                    s = (kv[:, :dn] @ q[n, :dn] + k_r[: t + 1] @ q_r) \
                        / np.sqrt(dn + dr)
                    a = np.exp(s - s.max())
                    out[t, n] = (a / a.sum()) @ kv[:, dn:]
            x = x + np.einsum("tnd,ndh->th", out, lp["wo"])
            h = _rms(x, lp["ln_mlp"], eps)
            if not routed:
                x = x + _swiglu(h, lp["wg"], lp["wu"], lp["wd"])
                continue
            y = np.zeros_like(x)
            for t in range(n_tok):
                sigma = 1 / (1 + np.exp(-(h[t] @ lp["router"])))
                for e, g in route_loop(sigma, lp["router_bias"],
                                       cfg.num_experts_per_tok,
                                       cfg.routed_scaling_factor).items():
                    y[t] += g * _swiglu(h[t], lp["wg"][e], lp["wu"][e],
                                        lp["wd"][e])
            x = x + y + _swiglu(h, lp["ws_g"], lp["ws_u"], lp["ws_d"])
    return _rms(x, p["final_norm"], eps) @ p["lm_head"]


N_TOK = 90  # decode from 60 on: three 32-key kernel steps (STEP_ROWS below)
N_PRE = 60


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(1, 128, size=N_TOK)


@pytest.fixture(scope="module")
def ref(model, tokens):
    cfg, params = model
    return reference_logits(params, cfg, tokens)


def rel_rms(a, b):
    a, b = _np(a), _np(b)
    return float(np.sqrt(((a - b) ** 2).mean()) / np.sqrt((b ** 2).mean()))


# ---------------------------------------------------------------------------
# forward on every cache path
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 6))
def _prefill(params, cfg, pools, page_row, chunk, start, ps):
    s = len(chunk)
    local = jnp.arange(s)
    c = page_row.shape[0] * ps
    write = (page_row[(start + local) // ps] * ps + (start + local) % ps)[None]
    read = (page_row[:, None] * ps + jnp.arange(ps)[None]).reshape(1, c)
    kv_pos = jnp.arange(c)[None]
    view = PagedView(write, read, kv_pos, kv_pos < start + s,
                     page_table=page_row[None], page_size=ps,
                     start=start, chunk_len=jnp.int32(s))
    logits, cache = forward(params, cfg, jnp.asarray(chunk)[None],
                            (start + local)[None], kv_cache=KVCache(*pools),
                            paged=view)
    return logits[0], (cache.k, cache.v)


@functools.partial(jax.jit, static_argnums=(1, 6))
def _decode(params, cfg, pools, page_row, token, n, ps):
    c = page_row.shape[0] * ps
    write = (page_row[n // ps] * ps + n % ps).reshape(1, 1)
    read = (page_row[:, None] * ps + jnp.arange(ps)[None]).reshape(1, c)
    kv_pos = jnp.arange(c)[None]
    view = PagedView(write, read, kv_pos, kv_pos <= n,
                     page_table=page_row[None],
                     seq_lens=n.reshape(1), page_size=ps)
    logits, cache = forward(params, cfg, token.reshape(1, 1),
                            n.reshape(1, 1), kv_cache=KVCache(*pools),
                            paged=view)
    return logits[0, 0], (cache.k, cache.v)


@functools.partial(jax.jit, static_argnums=(1,))
def _contiguous(params, cfg, cache, ids, positions, valid):
    return forward(params, cfg, ids, positions, kv_cache=cache,
                   kv_valid=valid)


def run_path(params, cfg, tokens, path):
    """Logits [N_TOK, V] of `forward` over `tokens` through one cache path:
    prefill of N_PRE tokens in chunks of 24, 24 and 12, then decode."""
    ids = jnp.asarray(tokens)
    if path == "uncached":
        return jax.jit(forward, static_argnums=(1,))(
            params, cfg, ids[None], jnp.arange(N_TOK)[None])[0][0]
    chunks = [(0, 24), (24, 48), (48, N_PRE)]
    out = []
    if path == "contiguous":
        cache = init_kv_cache(cfg, 1, 96)
        for lo, hi in chunks:
            lg, cache = _contiguous(
                params, cfg, cache, ids[None, lo:hi],
                jnp.arange(lo, hi)[None], jnp.arange(96)[None] < hi)
            out.append(lg[0])
        for n in range(N_PRE, N_TOK):
            lg, cache = _contiguous(
                params, cfg, cache, ids[None, n:n + 1], jnp.asarray([[n]]),
                jnp.arange(96)[None] <= n)
            out.append(lg[0])
        return jnp.concatenate(out)
    ps = 4
    cfg = cfg.replace(attention_backend=path.split("-")[1])
    pools = make_kv_pool_arrays(cfg, 32, ps, jnp.dtype(cfg.dtype))
    # scattered, non-monotonic pages; page 0 is the trash page
    page_row = jnp.asarray(
        np.random.RandomState(2).permutation(np.arange(1, 32))[:24])
    for lo, hi in chunks:
        lg, pools = _prefill(params, cfg, pools, page_row, ids[lo:hi],
                             jnp.int32(lo), ps)
        out.append(lg)
    for n in range(N_PRE, N_TOK):
        lg, pools = _decode(params, cfg, pools, page_row, ids[n],
                            jnp.int32(n), ps)
        out.append(lg[None])
    return jnp.concatenate(out)


PATHS = ["uncached", "contiguous", "paged-xla", "paged-pallas"]


@pytest.fixture
def short_steps(monkeypatch):
    """32 keys a softmax step of the decode kernel instead of 512, so a
    90-token context crosses three (the wrapper reads the constant when it
    traces; these shapes are traced by this file alone)."""
    monkeypatch.setattr(paged_attention, "STEP_ROWS", 32)


@pytest.mark.parametrize("path", PATHS)
def test_forward_matches_the_per_token_reference(model, tokens, ref, path,
                                                 short_steps):
    cfg, params = model
    got = run_path(params, cfg, tokens, path)
    assert got.shape == ref.shape
    np.testing.assert_allclose(_np(got), ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("path", PATHS)
def test_forward_in_bfloat16_stays_near_the_reference(model, tokens, ref,
                                                      path, short_steps):
    """bf16 weights and activations through every path: a dropped term
    reads tens of percent at every position; rounding reads a few."""
    cfg, params = model
    cfg16 = cfg.replace(dtype="bfloat16")
    p16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    ref16 = reference_logits(p16, cfg16, tokens)
    got = _np(run_path(p16, cfg16, tokens, path))
    # per position: a position where bf16 rounding flips an expert reads far
    # more than one where it does not, so the median is what is held
    per_pos = (np.sqrt(((got - ref16) ** 2).mean(-1))
               / np.sqrt((ref16 ** 2).mean(-1)))
    assert np.median(per_pos) < 0.04


@pytest.mark.parametrize("dtype, bound", [("float32", 2e-5),
                                          ("bfloat16", 0.03)])
def test_absorbed_form_equals_expanded_form(model, tokens, dtype, bound,
                                            short_steps):
    """Paged decode multiplies W_kvb's key half into the query and its
    value half into the result; the contiguous cache expands every cached
    row.  Equal in exact arithmetic: float32 agrees to rounding, bf16 (q^
    and o^ rounded to bf16 on the way) to the stated bound."""
    cfg, params = model
    cfg = cfg.replace(dtype=dtype)
    if dtype == "bfloat16":
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    expanded = run_path(params, cfg, tokens, "contiguous")[N_PRE:]
    for path in ("paged-xla", "paged-pallas"):
        absorbed = run_path(params, cfg, tokens, path)[N_PRE:]
        assert rel_rms(absorbed, expanded) < bound, path


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_latent_kernel_matches_xla_around_its_step_boundaries(dtype, tol):
    """Contexts of 1, 511, 512, 513 and 1100 keys (the query's own
    included): inside one 512-key step, exactly one, one key into the second,
    and three steps; pages scattered; the pool's other pages poisoned with
    NaN (a never-attended row may hold anything)."""
    ps, n_pages, hq, r, dr, lanes = 16, 80, 4, 32, 8, 128
    rng = np.random.RandomState(0)
    lens = np.asarray([0, 510, 511, 512, 1099], np.int32)
    b = len(lens)
    tables = np.zeros((b, 72), np.int32)
    c_pool = np.full((b * n_pages * ps, r), np.nan, np.float32)
    r_pool = np.full((b * n_pages * ps, lanes), np.nan, np.float32)
    c_win = np.zeros((b, 72 * ps, r), np.float32)
    r_win = np.zeros((b, 72 * ps, dr), np.float32)
    for i, n in enumerate(lens):
        need = -(-(n + 1) // ps)
        pages = i * n_pages + 1 + rng.permutation(n_pages - 1)[:need]
        tables[i, :need] = pages
        c_win[i, : n + 1] = rng.randn(n + 1, r)
        r_win[i, : n + 1] = rng.randn(n + 1, dr)
        for j, pg in enumerate(pages):
            rows = slice(pg * ps, (pg + 1) * ps)
            c_pool[rows] = c_win[i, j * ps:(j + 1) * ps]
            r_pool[rows] = 0.0
            r_pool[rows, :dr] = r_win[i, j * ps:(j + 1) * ps]
    q_lat = rng.randn(b, hq, r).astype(np.float32)
    q_rope = rng.randn(b, hq, dr).astype(np.float32)
    cast = lambda a: jnp.asarray(a).astype(dtype)
    got = paged_decode_attention_latent(
        cast(q_lat), cast(q_rope), cast(c_pool), cast(r_pool),
        jnp.asarray(tables), jnp.asarray(lens), scale=24 ** -0.5,
        page_size=ps, interpret=True)
    c16, r16 = _np(cast(c_win)), _np(cast(r_win))
    s = (np.einsum("bnr,bkr->bnk", _np(cast(q_lat)), c16)
         + np.einsum("bnd,bkd->bnk", _np(cast(q_rope)), r16)) * 24 ** -0.5
    s = np.where(np.arange(72 * ps)[None, None] <= lens[:, None, None], s,
                 -np.inf)
    a = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bnk,bkr->bnr", a / a.sum(-1, keepdims=True), c16)
    assert got.shape == (b, hq, r) and got.dtype == dtype
    assert rel_rms(got, want) < tol


def test_latent_kernel_has_its_own_name_and_no_value_pool():
    """A device trace tells the latent calls from the GQA ones by the
    kernel's name, and the kernel is handed two pools only: the latent rows
    (keys and values both) and the rotary rows."""
    ps, r, lanes = 16, 32, 128
    args = (jnp.zeros((1, 4, r)), jnp.zeros((1, 4, 8)),
            jnp.zeros((4 * ps, r)), jnp.zeros((4 * ps, lanes)),
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32))
    jaxpr = jax.make_jaxpr(lambda *a: paged_decode_attention_latent(
        *a, scale=1.0, page_size=ps, interpret=False))(*args)
    text = str(jaxpr)
    assert "paged_decode_attention_latent" in text
    call = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
            if e.primitive.name == "pallas_call"]
    assert len(call) == 1
    # page table, lengths, [q^ | q_rope], latent rows, rotary rows: the
    # pools as they lie (a step whose pages are one run is one slice of them)
    shapes = [tuple(v.aval.shape) for v in call[0].invars]
    assert shapes == [(1, 2), (1,), (1, 4, r + lanes), (4 * ps, r),
                      (4 * ps, lanes)]


# ---------------------------------------------------------------------------
# routing, shared branch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", ["random", "bias-decides", "ties"])
def test_sigmoid_routing_against_a_loop(case):
    rng = np.random.RandomState(3)
    t_n, h, e, k = 6, 16, 8, 3
    t = rng.randn(t_n, h).astype(np.float32)
    router = rng.randn(h, e).astype(np.float32)
    bias = (0.1 * rng.randn(e)).astype(np.float32)
    if case == "bias-decides":
        # a bias large enough to choose an expert the scores would not
        bias = np.zeros(e, np.float32)
        bias[5] = 10.0
    if case == "ties":
        # identical router columns and no bias: every sigma + b ties
        router = np.repeat(router[:, :1], e, axis=1)
        bias = np.zeros(e, np.float32)
    got = np.asarray(_routing_weights_sigmoid(
        jnp.asarray(t), jnp.asarray(router), jnp.asarray(bias), k, SCALE))
    sigma = 1 / (1 + np.exp(-(t.astype(np.float64) @ router)))
    for i in range(t_n):
        want = route_loop(sigma[i], bias.astype(np.float64), k, SCALE)
        assert set(np.nonzero(got[i])[0]) == set(want)
        for ex, g in want.items():
            assert got[i, ex] == pytest.approx(g, rel=1e-5)
        assert got[i].sum() == pytest.approx(SCALE, rel=1e-5)
    if case == "bias-decides":
        assert (got[:, 5] > 0).all()
        # it chose; it does not weigh: expert 5's weight is its sigma's share
        assert (got[:, 5] < SCALE).all()
    if case == "ties":
        assert (np.nonzero(got[0])[0] == np.arange(k)).all()


def test_shared_branch_is_always_on(model):
    """Whatever the router chooses, every token gets the shared SwiGLU: the
    block's output less the same block without its shared down-projection
    is exactly the shared branch."""
    cfg, params = model
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.hidden_size))
    full, _ = _moe_block(x, lp, cfg)
    without, _ = _moe_block(
        x, dict(lp, ws_d=jnp.zeros_like(lp["ws_d"])), cfg)
    shared = _swiglu(_np(x), _np(lp["ws_g"]), _np(lp["ws_u"]), _np(lp["ws_d"]))
    np.testing.assert_allclose(_np(full - without), shared, atol=1e-5)
    assert np.abs(shared).min(axis=-1).max() > 0  # every token, non-zero


# ---------------------------------------------------------------------------
# config.json
# ---------------------------------------------------------------------------

KANANA = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "kanana-2-30b-a3b.json")))
CATALOG_KEYS = {k: v for k, v in KANANA.items() if k not in (
    "source", "reduced", "assumed", "deployment", "expect", "scopes", "check",
    "serving", "torch_dtype")}


def _load(tmp_path, **changes):
    path = tmp_path / "kanana2" / "config.json"
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(dict(CATALOG_KEYS, **changes)))
    return config_from_hf_json(str(path))


def test_config_from_hf_json_reads_the_catalogs_keys(tmp_path):
    cfg = _load(tmp_path, num_hidden_layers=48)
    assert cfg.name == "kanana2" and cfg.num_layers == 48 and cfg.is_latent
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert cfg.head_dim == 64 and cfg.rope_theta == 1e6 and cfg.rope_interleave
    assert (cfg.first_k_dense, cfg.dense_intermediate_size) == (1, 6144)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (128, 6)
    assert cfg.intermediate_size == 768  # the experts' width
    assert cfg.shared_intermediate_size == 2 * 768
    assert cfg.moe_scoring == "sigmoid"
    assert cfg.routed_scaling_factor == 2.448
    assert cfg.layer_types == () and not cfg.is_windowed
    assert not cfg.tie_word_embeddings and cfg.vocab_size == 128256
    # 512 latent values, and the 64 rotary ones padded to a lane tile
    assert cfg.kv_row_widths() == (512, 128)
    hash(cfg)  # a static argument of every jitted step
    assert _load(tmp_path).num_layers == 6  # the benchmark's cut


@pytest.mark.parametrize("changes, word", [
    # (a query low-rank is served since PR 33: tests/
    # test_sparse_latent_attention.py)
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"n_group": 8}, "n_group"),
    ({"topk_group": 4}, "topk_group"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"attention_bias": True}, "attention_bias"),
    ({"first_k_dense_replace": 6}, "first_k_dense"),
])
def test_config_from_hf_json_refuses_what_is_not_served(tmp_path, changes,
                                                        word):
    with pytest.raises(UnsupportedConfigError, match=word):
        _load(tmp_path, **changes)


# ---------------------------------------------------------------------------
# the pool's rows: one definition
# ---------------------------------------------------------------------------

def test_pool_arrays_and_plan_follow_the_row_widths(model):
    cfg, _ = model
    assert cfg.kv_row_widths() == (32, 128)
    k, v = make_kv_pool_arrays(cfg, 8, 4)
    assert k.shape == (3, 32, 32) and v.shape == (3, 32, 128)
    per_token = 3 * (32 + 128) * 4  # layers x values x float32
    assert planner.kv_bytes_per_token(cfg, kv_dtype="float32") == per_token
    assert planner.kv_pool_bytes_per_device(
        cfg, num_pages=8, page_size=4, kv_dtype="float32") == 32 * per_token
    gqa = CONFIGS["tiny-gqa"]
    assert gqa.kv_row_widths() == (32, 32)
    assert [a.shape for a in make_kv_pool_arrays(gqa, 8, 4)] == \
        [(2, 32, 32)] * 2


# (weights, pool, activations, bytes a token, the cost model's bytes a token
# and flops a key) as the parent commit 13433d9 plans them, from
# `planner.plan_memory` / `dispatch_cost_model` at each file's `serving`
PARENT_PLANS = {
    "yi-1.5-9b": (7969513472, 3355443200, 664797184, 40960, 40960, 327680.0),
    "mixtral-8x7b": (6329376768, 671088640, 1079885824, 8192, 8192, 32768.0),
    "mellum2-12b-a2.5b": (7589933568, 1342177280, 855638016, 16384, 16384,
                          131072.0),
}


def _plan(name):
    path = os.path.join(ROOT, "benchmarks", "configs", name + ".json")
    srv = json.load(open(path))["serving"]
    cfg = config_from_hf_json(path)
    plan = planner.plan_memory(
        cfg, tp=1, num_pages=srv["num_pages"], page_size=srv["page_size"],
        max_pages_per_seq=srv["max_pages_per_seq"],
        max_batch=srv["max_batch"], prefill_bucket=max(srv["prefill_buckets"]),
        hbm_bytes=16 * 2 ** 30)
    return cfg, plan


@pytest.mark.parametrize("name", sorted(PARENT_PLANS))
def test_registered_configurations_plan_the_bytes_they_did(name):
    cfg, plan = _plan(name)
    cost = planner.dispatch_cost_model(cfg)
    assert (plan.weight_bytes, plan.kv_pool_bytes, plan.activation_bytes,
            planner.kv_bytes_per_token(cfg), cost.kv_bytes_per_token,
            cost.attn_flops_per_kv) == PARENT_PLANS[name]


def test_the_new_configuration_plans_its_stated_bytes():
    """benchmarks/configs/kanana-2-30b-a3b.json `reduced`: 7.58 GB of
    weights, 1,280 B a token a layer, a 1.0 GB pool."""
    cfg, plan = _plan("kanana-2-30b-a3b")
    assert plan.weight_bytes == pytest.approx(7.58e9, rel=2e-3)
    assert planner.kv_bytes_per_token(cfg) == 6 * 1280
    assert plan.kv_pool_bytes == 8192 * 16 * 6 * 1280
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert plan.weight_bytes == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert shapes["layers"]["wg"].shape == (5, 128, 2048, 768)
    assert shapes["dense_layers"]["wg"].shape == (1, 2048, 6144)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def make_engine(cfg, params, mesh=None, **kw):
    defaults = dict(max_batch=4, page_size=8, num_pages=64,
                    max_pages_per_seq=8, prefill_buckets=(8, 16, 32, 64))
    defaults.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**defaults),
                           kv_dtype=jnp.float32, mesh=mesh)


TRIP = 16  # keys a trip of prefill's key walk under `short_trips`


@pytest.fixture
def short_trips(monkeypatch):
    """16-key trips of prefill's key walk instead of 1,024, so a 64-key
    window is a walk of up to four and a 100-key context one of seven (read
    when a program traces; tests/test_latent_prefill_fold.py runs under it)."""
    monkeypatch.setattr(latent, "PREFILL_WALK_KEYS", TRIP)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_engine_is_token_exact_for_a_latent_model(model, backend,
                                                  short_trips):
    """Admission, chunked prefill (a walk of the live keys, three trips for
    the longest prompt), batched decode: greedy tokens are those of the
    cache-less forward; the pool holds latent rows only and the engine says
    how many bytes a token they take."""
    cfg, params = model
    eng = make_engine(cfg, params, attention_backend=backend)
    assert eng.cfg.attention_backend == backend
    assert eng.k_pool.shape == (3, 512, 32)
    assert eng.v_pool.shape == (3, 512, 128)
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
    snap = eng.metrics.snapshot(eng)
    assert snap["engine"]["prefill_walk_trips"] >= 3 * 3
    assert snap["engine"]["prefill_walk_kernel_trips"] == (
        snap["engine"]["prefill_walk_trips"] if backend == "pallas" else 0)
    assert snap["engine"]["kv_bytes_per_token"] == 3 * (32 + 128) * 4
    assert snap["engine"]["kv_bytes_per_token"] == \
        planner.kv_bytes_per_token(cfg, kv_dtype="float32")
    from kafka_tpu.server.prometheus import render_prometheus

    assert "kafka_tpu_kv_bytes_per_token 1920" in render_prometheus(snap)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("common", [8, 24], ids=["one-page", "three-pages"])
def test_prefix_hit_then_suffix_prefill_is_token_exact(model, common, backend,
                                                       short_trips):
    """A prefix hit hands a second thread the first one's latent rows; the
    suffix's queries expand them through their own layer's W_kvb, trip by
    trip: the three-page prefix is a trip and a half of another thread's
    pages ahead of the suffix's own."""
    cfg, params = model
    eng = make_engine(cfg, params, attention_backend=backend)
    rng = np.random.RandomState(common)
    shared = list(rng.randint(1, 128, size=common))
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
    assert eng.prefill_walk_trips >= 3 * -(-(common + 13) // TRIP)
    assert_greedy_consistent(cfg, params, prompt, second.output_ids)
    ref = make_engine(cfg, params, prefix_cache_entries=0).generate(
        prompt, max_new_tokens=8)
    assert second.output_ids == ref.output_ids


@pytest.mark.parametrize("path, kw, mesh_axes", [
    ("speculative verify", {"speculative_k": 2}, None),
    ("kv_quantize int8 pool", {"kv_quantize": "int8"}, None),
    ("prefill_ring", {}, {"sp": 2}),
    ("pp > 1", {}, {"pp": 2}),
    ("a tp / ep mesh", {}, {"tp": 2}),
    ("a tp / ep mesh", {}, {"ep": 2}),
])
def test_engine_refuses_options_with_no_latent_form(model, path, kw,
                                                    mesh_axes):
    cfg, params = model
    mesh = None
    if mesh_axes:
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:2]), tuple(mesh_axes))
    with pytest.raises(LatentAttentionUnsupported, match=path) as e:
        make_engine(cfg, params, mesh=mesh, **kw)
    assert path in e.value.path


def test_a_one_device_mesh_serves_a_latent_model(model):
    """What a dp replica is: a 1-device mesh that pins the replica."""
    from jax.sharding import Mesh

    cfg, params = model
    eng = make_engine(cfg, params,
                      mesh=Mesh(np.asarray(jax.devices()[:1]), ("tp",)))
    prompt = [5, 9, 2, 77, 31]
    out = eng.generate(prompt, max_new_tokens=6)
    assert_greedy_consistent(cfg, params, prompt, out.output_ids)


def test_forward_backstops_raise_where_the_engine_is_bypassed(model):
    cfg, params = model
    pools = KVCache(*make_kv_pool_arrays(cfg, 16, 4, jnp.float32))
    i = jnp.zeros((1, 3), jnp.int32)
    view = PagedView(i, jnp.zeros((1, 16), jnp.int32),
                     jnp.arange(16)[None], jnp.ones((1, 16), bool),
                     page_table=jnp.zeros((1, 4), jnp.int32),
                     seq_lens=jnp.zeros((1,), jnp.int32), page_size=4,
                     chunk_len=jnp.ones((1,), jnp.int32))
    with pytest.raises(LatentPathError, match="verify"):
        forward(params, cfg, i, i, kv_cache=pools, paged=view)
    with pytest.raises(LatentPathError, match="page table"):
        forward(params, cfg, i, i, kv_cache=pools,
                paged=view._replace(seq_lens=None, page_table=None))
    with pytest.raises(LatentPathError, match="prefill_ring"):
        forward(params, cfg.replace(prefill_ring=True), i, i,
                kv_cache=pools, paged=view._replace(seq_lens=None))
    int8 = KVCache(*make_kv_pool_arrays(cfg, 16, 4, quantize="int8"))
    with pytest.raises(LatentPathError, match="int8"):
        forward(params, cfg, i, i, kv_cache=int8,
                paged=view._replace(seq_lens=None))
    from jax.sharding import Mesh

    with pytest.raises(LatentPathError, match="mesh"):
        forward(params, cfg, i, i,
                mesh=Mesh(np.asarray(jax.devices()[:2]), ("tp",)))
    from kafka_tpu.models import quantize_params

    with pytest.raises(NotImplementedError, match="latent"):
        quantize_params(params, cfg)


# ---------------------------------------------------------------------------
# a config without leading dense layers is the parent's program
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _digests(name):
    cfg = CONFIGS[name].replace(dtype="float32")
    return {path: hashlib.sha256(str(jaxpr).encode()).hexdigest()[:16]
            for path, jaxpr in _jaxprs(cfg).items()}


@pytest.mark.parametrize("key", sorted(PARENT_JAXPRS))
def test_a_config_without_dense_layers_builds_the_parents_jaxpr(key):
    """Digests recorded at commit 41a1b4e (tests/test_layer_pattern.py): the
    stack that now runs dense layers ahead of the scan, picks a routing rule
    and may add a shared branch traces, for a config with none of them, what
    it traced before any of them existed."""
    name, path = key.split(".")
    assert _digests(name)[path] == PARENT_JAXPRS[key]


def test_one_layer_scan_per_forward_pass(model):
    """`decode_step_dev_ms` divides device time by innermost `while` loops,
    one per forward pass: the leading dense layer runs unrolled ahead of the
    one scan over the routed layers."""
    cfg, params = model
    jaxpr = jax.make_jaxpr(
        lambda p, i, q: forward(p, cfg, i, q)[0]
    )(params, jnp.zeros((1, 4), jnp.int32), jnp.arange(4)[None])
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [s.params["length"] for s in scans] == [2]
    assert "scan" not in str(scans[0].params["jaxpr"])
