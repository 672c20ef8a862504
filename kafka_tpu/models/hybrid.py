"""ONE decoder with a per-thread recurrent state, whole: `phi4flash`
(Phi-4-mini-flash-reasoning), state-space mixers whose per-thread state lives
in a STATE SLOT beside the pages, sliding and full differential attention
with K/V rows of their own, and a second half of gated memory units and cross
attention that reads ONE full cache.  What every such decoder shares
(`StatePlan`, the slot read and write, `HybridPathError`) is models/cache.py's;
the others (the conv, linear-attention and parallel layouts) run through
models/llama.forward with their mixers taken from its table.

Layout (`ModelConfig._check_hybrid`): n x [mamba, sliding attention], then
[mamba, full attention], then m x [gmu, cross attention]; every layer is
`h += mixer(LN(h)); h += MLP(LN(h))` with LayerNorm (weight and bias), no
positional encoding anywhere.  `models/llama.forward` hands a config with
`cfg.has_state` to `forward` here: same entry, same `PagedView`, same step
programs.

What a thread holds:

* ROWS, in the paged pool, for the n + 1 attention layers with K/V of their
  own (`cfg.kv_layers`): `k_pool` [n + 1, SLOTS, Hkv*D] and `v_pool["v"]`
  alike.  The m cross layers read the full layer's slice and write nothing.
* STATE, in a slot of `v_pool["conv"]` [n + 1, n_slots, d_conv - 1, inner]
  and `v_pool["ssm"]` [n + 1, n_slots, d_state, inner], float32: the last
  conv inputs and h of each Mamba layer.  The state rides in the v pool's
  pytree so that every step program donates, carries and returns it without
  a signature of its own; `PagedView.state` (a `StatePlan`) says which slot
  each lane reads and writes.  A state is mutated in place, pages are not:
  a prefix hit COPIES a snapshot slot into the lane's slot
  (runtime/engine.py).

The last Mamba layer also hands its scan output (with the D * x term, ahead
of the gate) to the gated memory units, for the same token, this pass only.

Prefill (s > 1 over the paged pool) runs the second half on each lane's LAST
REAL ROW only: nothing in those layers holds per-token state, so their other
rows are read by nobody.  The logits of a paged prefill are therefore
[B, 1, V].

Differential attention: q heads in pairs (2j, 2j + 1), k / v heads in pairs
(2g, 2g + 1), g = j // (Hq / Hkv); `o_j = (1 - l0) * RMSNorm_2D((P_1 -
lam * P_2) [v_2g | v_2g+1])`.  On the Pallas backend the two softmaxes of a
pair are two query rows of the existing merged-lane kernels
(`paged_decode_attention(..., diff=True)`: another placement of q on the
merged row, another slice of the output); the combine and the sub-layer norm
are XLA under `attn_diff`.
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.pallas.selective_scan import selective_scan
from .cache import (
    HybridPathError, KVCache, StatePlan, _flat_pool, _kv_read_pages,
    _kv_write, _layer_view, _read_state, _stacked_pool, _write_state,
)
from .config import CROSS, GLOBAL, WINDOWED, ModelConfig
from .ffn import _mlp_block
from .quant import Params

# (benchmarks/ imports `StatePlan` from here and swaps `_write_state` here)

NEG_INF = -1e30


def lambda_init(layer) -> jnp.ndarray:
    """Differential attention's l0 of (absolute) layer `layer`."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
# parameters
# ----------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights of a hybrid decoder.  "layers" holds what every layer
    has (two LayerNorms, the MLP) stacked [L, ...]; "mamba", "attn", "cross"
    and "gmu" hold each kind's mixers stacked in layer order ("attn": the
    sliding layers, then the full one).  Where stability depends on it the
    initialiser is the published one (A_log = log(1..d_state), D = 1, the dt
    bias the inverse softplus of a log-uniform 1e-3..1e-1), with the dt
    projection a tenth of its published scale so that dt stays near its
    bias: the state then lives tens to hundreds of tokens and a check on
    the logits can SEE it.  Norm weights, biases and lambda vectors are
    spread (not 1 / 0), so that a program that skips one fails the check."""
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    r = cfg.mamba_dt_rank
    n_m = cfg.state_layers
    n_x = cfg.layers_of(CROSS)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def norm01(k, shape, fan_in, out_dtype=dtype):
        # one program a leaf: no float32 copy of a 0.8G-element leaf is held
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(out_dtype)

    def spread(k, shape, mean=1.0, sd=0.2, out_dtype=dtype):
        return (mean + sd * jax.random.normal(k, shape, jnp.float32)
                ).astype(out_dtype)

    def diff_leaves(k, n):
        ks = jax.random.split(k, 5)
        return {
            **{name: spread(ks[i], (n, d), 0.0, 0.1, jnp.float32)
               for i, name in enumerate(("lq1", "lk1", "lq2", "lk2"))},
            "subln": spread(ks[4], (n, 2 * d)),
        }

    keys = jax.random.split(key, 12)
    kl = jax.random.split(keys[1], 7)
    layers = {
        "ln_attn": spread(kl[0], (L, h)),
        "ln_attn_b": spread(kl[1], (L, h), 0.0, 0.1),
        "ln_mlp": spread(kl[2], (L, h)),
        "ln_mlp_b": spread(kl[3], (L, h), 0.0, 0.1),
        "wg": norm01(kl[4], (L, h, f), h),
        "wu": norm01(kl[5], (L, h, f), h),
        "wd": norm01(kl[6], (L, f, h), f),
    }
    km = jax.random.split(keys[2], 8)
    dt = jnp.exp(jax.random.uniform(km[5], (n_m, di), jnp.float32)
                 * (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
    mamba = {
        "in_proj": norm01(km[0], (n_m, h, 2 * di), h),
        "conv_w": norm01(km[1], (n_m, dc, di), dc, jnp.float32),
        "conv_b": spread(km[2], (n_m, di), 0.0, 0.1, jnp.float32),
        "x_proj": norm01(km[3], (n_m, di, r + 2 * ds), di),
        "dt_w": norm01(km[4], (n_m, r, di), 100 * r),
        "dt_b": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1(dt), float32
        # [d_state, inner]: the state's own layout (the wide axis in lanes)
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, ds + 1, dtype=jnp.float32))[None, :, None],
            (n_m, ds, di)),
        "D": jnp.ones((n_m, di), jnp.float32),
        "out_proj": norm01(km[6], (n_m, di, h), di),
    }
    ka = jax.random.split(keys[3], 9)
    attn = {
        "wq": norm01(ka[0], (n_m, h, hq, d), h),
        "wk": norm01(ka[1], (n_m, h, hkv, d), h),
        "wv": norm01(ka[2], (n_m, h, hkv, d), h),
        "bq": spread(ka[3], (n_m, hq, d), 0.0, 0.1),
        "bk": spread(ka[4], (n_m, hkv, d), 0.0, 0.1),
        "bv": spread(ka[5], (n_m, hkv, d), 0.0, 0.1),
        # the hq / 2 pairs' 2d-wide outputs, concatenated
        "wo": norm01(ka[6], (n_m, hq // 2, 2 * d, h), hq * d),
        "bo": spread(ka[7], (n_m, h), 0.0, 0.1),
        **diff_leaves(ka[8], n_m),
    }
    kx = jax.random.split(keys[4], 5)
    cross = {
        "wq": norm01(kx[0], (n_x, h, hq, d), h),
        "bq": spread(kx[1], (n_x, hq, d), 0.0, 0.1),
        "wo": norm01(kx[2], (n_x, hq // 2, 2 * d, h), hq * d),
        "bo": spread(kx[3], (n_x, h), 0.0, 0.1),
        **diff_leaves(kx[4], n_x),
    }
    kg = jax.random.split(keys[5], 2)
    gmu = {
        "w1": norm01(kg[0], (n_x, h, di), h),
        "w2": norm01(kg[1], (n_x, di, h), di),
    }
    kf = jax.random.split(keys[6], 2)
    return {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": spread(kf[0], (h,)),
        "final_norm_b": spread(kf[1], (h,), 0.0, 0.1),
        "layers": layers, "mamba": mamba, "attn": attn, "cross": cross,
        "gmu": gmu,
    }


# ----------------------------------------------------------------------
# the mixers
# ----------------------------------------------------------------------


def _mamba_block(u, lp, cfg: ModelConfig, conv0, h0, lens):
    """One Mamba-1 mixer over u [B, S, H] from (conv0 [B, dc - 1, di], h0
    [B, ds, di]) float32.  Returns (out [B, S, H], memory [B, S, di] = the
    scan's output WITH its D * x term and ahead of the gate, conv', h'): the
    state after each lane's last real row (`lens`; a lane of 0 rows returns
    its state as it came)."""
    dt_ = u.dtype
    f32 = jnp.float32
    di, ds, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    dc = cfg.mamba_d_conv
    B, S = u.shape[:2]
    with jax.named_scope("ssm_proj"):
        xz = jnp.einsum("bsh,hf->bsf", u, lp["in_proj"].astype(dt_))
        x_in, z = xz[..., :di], xz[..., di:]
    with jax.named_scope("ssm_conv"):
        # causal depthwise conv over [the dc - 1 inputs before | the chunk]
        seq = jnp.concatenate([conv0, x_in.astype(f32)], axis=1)
        w = lp["conv_w"].astype(f32)  # [dc, di]; tap dc - 1 is the row's own
        x = lp["conv_b"].astype(f32) + sum(
            w[i] * seq[:, i:i + S] for i in range(dc))
        x = jax.nn.silu(x)
        # the last dc - 1 REAL inputs: rows lens - dc + 1 .. lens - 1 of the
        # chunk are rows lens .. lens + dc - 2 of `seq`
        conv_new = jax.vmap(
            lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, dc - 1, axis=0)
        )(seq, lens)
    with jax.named_scope("ssm_proj"):
        dbc = jnp.einsum("bsd,df->bsf", x.astype(dt_),
                         lp["x_proj"].astype(dt_))
        dt = jax.nn.softplus(
            jnp.einsum("bsr,rd->bsd", dbc[..., :r], lp["dt_w"].astype(dt_),
                       preferred_element_type=f32) + lp["dt_b"].astype(f32))
        b = dbc[..., r:r + ds].astype(f32)
        c = dbc[..., r + ds:].astype(f32)
    with jax.named_scope("ssm_scan"):
        y, h_new = selective_scan(
            x, dt, -jnp.exp(lp["A_log"].astype(f32)), b, c,
            lp["D"].astype(f32), h0, lens,
            kernel=cfg.attention_backend == "pallas")
    with jax.named_scope("ssm_proj"):
        memory = y.astype(dt_)
        out = jnp.einsum("bsd,dh->bsh", memory * jax.nn.silu(z),
                         lp["out_proj"].astype(dt_))
    return out, memory, conv_new, h_new


def _gmu_block(u, memory, lp):
    """Gated memory unit: W_2 (m * silu(W_1 u))."""
    with jax.named_scope("gmu"):
        g = jnp.einsum("bsh,hd->bsd", u, lp["w1"].astype(u.dtype))
        return jnp.einsum("bsd,dh->bsh", memory * jax.nn.silu(g),
                          lp["w2"].astype(u.dtype))


def _diff_scores_xla(q, k, v, mask, scale):
    """Both softmaxes of every pair over both value heads, the XLA form.
    q [B, S, Hq, D], k / v [B, T, Hkv, D], mask [B, S, T] -> [B, S, Hq, 2D]:
    row n is P_(n % 2 + 1) of pair n // 2 times [v_2g | v_2g+1]."""
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g, rr = hkv // 2, hq // hkv
    qg = q.reshape(b, s, g, rr, 2, d)
    kg = k.reshape(b, t, g, 2, d)
    logits = jnp.einsum("bqgrtd,bkgtd->bgrtqk", qg, kg,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    vg = v.reshape(b, t, g, 2 * d)
    out = jnp.einsum("bgrtqk,bkge->bqgrte", probs.astype(v.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, hq, 2 * d).astype(q.dtype)


def _diff_combine(o, lp, layer, eps):
    """(1 - l0) * RMSNorm((P_1 - lam * P_2) V) of every pair: o [B, S, Hq,
    2D] (row 2j = P_1 V, row 2j + 1 = P_2 V) -> [B, S, Hq / 2, 2D]."""
    with jax.named_scope("attn_diff"):
        f32 = jnp.float32
        l0 = lambda_init(layer)
        lam = (jnp.exp(jnp.sum(lp["lq1"].astype(f32) * lp["lk1"].astype(f32)))
               - jnp.exp(jnp.sum(lp["lq2"].astype(f32)
                                 * lp["lk2"].astype(f32))) + l0)
        b, s, hq, e = o.shape
        pairs = o.astype(f32).reshape(b, s, hq // 2, 2, e)
        a = pairs[..., 0, :] - lam * pairs[..., 1, :]
        a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)
        return (a * lp["subln"].astype(f32) * (1.0 - l0)).astype(o.dtype)


def _attend(q, k_rows, v_rows, cfg: ModelConfig, positions, paged, window,
            k_new=None, v_new=None):
    """Differential attention's two softmaxes a pair -> [B, S, Hq, 2D].

    Paged (`paged` addresses the layer's slice of the flat pools `k_rows` /
    `v_rows`, the pass's own rows already written): the Pallas decode kernel
    at s == 1, flash prefill for one sequence's chunk, else the page gather
    of the static window in XLA.  Uncached (`paged` None): over the pass's
    own k_new / v_new."""
    d = cfg.head_dim
    scale = d ** -0.5
    if paged is None:
        mask = positions[:, :, None] >= positions[:, None, :]
        if window is not None:
            mask = mask & (positions[:, None, :]
                           > positions[:, :, None] - window)
        return _diff_scores_xla(q, k_new, v_new, mask, scale)
    b, s = q.shape[:2]
    interp = jax.default_backend() != "tpu"
    pallas = cfg.attention_backend == "pallas"
    if pallas and s == 1:
        from ..ops.pallas import (
            paged_decode_attention,
            paged_decode_attention_window,
        )

        if window is None:
            return paged_decode_attention(
                q[:, 0], k_rows, v_rows, paged.page_table, positions[:, 0],
                page_size=paged.page_size, interpret=interp, diff=True)[:, None]
        return paged_decode_attention_window(
            q[:, 0], k_rows, v_rows, paged.page_table, positions[:, 0],
            window=window, page_size=paged.page_size, interpret=interp,
            diff=True)[:, None]
    if pallas and b == 1 and paged.start is not None:
        from ..ops.pallas import paged_prefill_attention

        return paged_prefill_attention(
            q[0], k_rows, v_rows, paged.page_table[0], paged.start,
            paged.chunk_len, page_size=paged.page_size, interpret=interp,
            window=window, diff=True)[None]
    hkv = cfg.num_kv_heads
    k_win = _kv_read_pages(k_rows, paged.page_table, paged.page_size,
                           q.dtype).reshape(b, -1, hkv, d)
    v_win = _kv_read_pages(v_rows, paged.page_table, paged.page_size,
                           q.dtype).reshape(b, -1, hkv, d)
    kv_pos = paged.kv_positions
    mask = (positions[:, :, None] >= kv_pos[:, None, :]) \
        & paged.kv_valid[:, None, :]
    if window is not None:
        mask = mask & (kv_pos[:, None, :] > positions[:, :, None] - window)
    # (a masked row may hold anything: a page never written)
    keep = jnp.any(mask, axis=1)[..., None, None]
    return _diff_scores_xla(q, jnp.where(keep, k_win, 0),
                            jnp.where(keep, v_win, 0), mask, scale)


def _project_out(a, lp, dt_):
    with jax.named_scope("attn_out"):
        return (jnp.einsum("bsne,neh->bsh", a, lp["wo"].astype(dt_))
                + lp["bo"].astype(dt_))


def _self_attention(u, lp, cfg, positions, layer, pool_layer, k_pool, v_rows,
                    paged, window):
    """A differential attention layer with K/V of its own.  Paged: its rows
    are written into layer `pool_layer` of the stacked pools (returned);
    uncached: returns its own k, v in their place."""
    dt_ = u.dtype
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bsh,hnd->bsnd", u, lp["wq"].astype(dt_)) + lp["bq"].astype(dt_)
        k = jnp.einsum("bsh,hnd->bsnd", u, lp["wk"].astype(dt_)) + lp["bk"].astype(dt_)
        v = jnp.einsum("bsh,hnd->bsnd", u, lp["wv"].astype(dt_)) + lp["bv"].astype(dt_)
    view = None
    if paged is not None:
        b, s, hkv, d = k.shape
        n_layers, slots = k_pool.shape[:2]
        view = _layer_view(paged, pool_layer, slots)
        k_pool = _kv_write(_flat_pool(k_pool), view.write_idx,
                           k.reshape(b, s, hkv * d))
        v_rows = _kv_write(_flat_pool(v_rows), view.write_idx,
                           v.reshape(b, s, hkv * d))
    with jax.named_scope("attn_core"), (
            nullcontext() if window is None
            else jax.named_scope("attn_window")):
        o = _attend(q, k_pool, v_rows, cfg, positions, view, window, k, v)
    if paged is not None:
        k_pool = _stacked_pool(k_pool, n_layers)
        v_rows = _stacked_pool(v_rows, n_layers)
    else:
        k_pool, v_rows = k, v
    out = _project_out(_diff_combine(o, lp, layer, cfg.rms_norm_eps), lp, dt_)
    return out, k_pool, v_rows


def _cross_attention(u, lp, cfg, positions, layer, pool_layer, k_pool, v_rows,
                     paged):
    """Differential attention of q = W_q u over the FULL layer's rows (layer
    `pool_layer` of the pools; uncached: that layer's own k, v).  Writes
    nothing."""
    dt_ = u.dtype
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bsh,hnd->bsnd", u, lp["wq"].astype(dt_)) + lp["bq"].astype(dt_)
    with jax.named_scope("attn_core"), jax.named_scope("attn_cross"):
        if paged is None:
            o = _attend(q, None, None, cfg, positions, None, None,
                        k_pool, v_rows)
        else:
            view = _layer_view(paged, pool_layer, k_pool.shape[1])
            o = _attend(q, _flat_pool(k_pool), _flat_pool(v_rows), cfg,
                        positions, view, None)
    return _project_out(_diff_combine(o, lp, layer, cfg.rms_norm_eps), lp, dt_)


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _at(stacked, i):
    """Layer i's leaves of a stacked tree (i static or traced)."""
    if isinstance(i, int):
        return jax.tree.map(lambda a: a[i], stacked)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False),
        stacked)


def forward(params: Params, cfg: ModelConfig, token_ids, positions,
            kv_cache=None, paged=None):
    """The hybrid decoder.  token_ids, positions [B, S].

    Uncached (`kv_cache` None): every row from a zero state; logits
    [B, S, V].  Paged (`kv_cache` = KVCache(k_pool, v_pool) with the state
    in `v_pool`, `paged.state` a StatePlan): logits [B, S, V] at s == 1,
    [B, 1, V] (each lane's last real row) at s > 1.  Returns (logits f32,
    the new KVCache or None)."""
    eps = cfg.rms_norm_eps
    n_m = cfg.state_layers      # mamba layers = attention layers w/ K/V
    n_x = cfg.layers_of(CROSS)  # cross layers = gated memory units
    full = n_m - 1                  # the full layer's place in the pool
    window = cfg.sliding_window
    B, S = token_ids.shape
    if kv_cache is not None and paged is None:
        raise HybridPathError(
            "a hybrid decoder has no contiguous cache: rows go through the "
            "paged pool, the recurrent state through its slots")
    if paged is not None and (paged.page_table is None
                              or paged.state is None):
        raise HybridPathError(
            "a paged plan without a page table or a StatePlan (pp, a direct "
            "caller that built the view without `state`)")
    with jax.named_scope("embed"):
        x = params["embed"][token_ids].astype(cfg.activation_dtype)

    if paged is not None:
        k_pool, v_pool = kv_cache
        v_rows, conv, ssm = v_pool["v"], v_pool["conv"], v_pool["ssm"]
        plan = paged.state
    else:
        k_pool = v_rows = conv = ssm = None
        plan = StatePlan(lens=jnp.full((B,), S, jnp.int32))
    di, ds, dc = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv

    def mlp(h, lp):
        with jax.named_scope("mlp_norm"):
            y = layer_norm(h, lp["ln_mlp"], lp["ln_mlp_b"], eps)
        with jax.named_scope("mlp"):
            return h + _mlp_block(y, lp)

    def normed(h, lp):
        with jax.named_scope("attn_norm"):
            return layer_norm(h, lp["ln_attn"], lp["ln_attn_b"], eps)

    def self_period(carry, i, kind):
        """[mamba, attention of `kind`]: Mamba layer i (absolute 2i), then
        attention layer i of the pool (absolute 2i + 1)."""
        h, kp, vr, cv, sm, _ = carry
        lp = _at(params["layers"], 2 * i)
        if cv is None:
            conv0 = jnp.zeros((B, dc - 1, di), jnp.float32)
            h0 = jnp.zeros((B, ds, di), jnp.float32)
        else:
            conv0 = _read_state(cv, i, plan, B)
            h0 = _read_state(sm, i, plan, B)
        out, memory, conv1, h1 = _mamba_block(
            normed(h, lp), _at(params["mamba"], i), cfg, conv0, h0, plan.lens)
        if cv is not None:
            cv = _write_state(cv, i, plan, conv1, conv0)
            sm = _write_state(sm, i, plan, h1, h0)
        with jax.named_scope("ssm_proj"):
            h = h + out
        h = mlp(h, lp)
        lp = _at(params["layers"], 2 * i + 1)
        out, k_new, v_new = _self_attention(
            normed(h, lp), _at(params["attn"], i), cfg, positions, 2 * i + 1,
            i, kp, vr, paged, window if kind == WINDOWED else None)
        with jax.named_scope("attn_out"):
            h = h + out
        if paged is not None:
            kp, vr = k_new, v_new
        # (uncached: the layer's own k, v leave beside the carry)
        return (mlp(h, lp), kp, vr, cv, sm, memory), (k_new, v_new)

    def cross_period(carry, j):
        """[gmu, cross attention]: absolute layers 2 (n_m + j), + 1."""
        h, memory, kp, vr = carry
        first = 2 * (n_m + j)
        lp = _at(params["layers"], first)
        out = _gmu_block(normed(h, lp), memory, _at(params["gmu"], j))
        with jax.named_scope("gmu"):
            h = h + out
        h = mlp(h, lp)
        lp = _at(params["layers"], first + 1)
        out = _cross_attention(
            normed(h, lp), _at(params["cross"], j), cfg, pos_x, first + 1,
            full, kp, vr, paged_x)
        with jax.named_scope("attn_out"):
            h = h + out
        return (mlp(h, lp), memory, kp, vr)

    with jax.named_scope("layers"):
        carry = (x, k_pool, v_rows, conv, ssm,
                 jnp.zeros((B, S, di), x.dtype))
        if full > 0:
            carry, _ = jax.lax.scan(
                lambda c, i: (self_period(c, i, WINDOWED)[0], None), carry,
                jnp.arange(full))
        (x, k_pool, v_rows, conv, ssm, memory), kv_full = self_period(
            carry, full, GLOBAL)
        pos_x, paged_x = positions, paged
        if paged is not None and S > 1:
            # the second half holds no per-token state: each lane's last
            # real row is all anybody reads of a prefill launch
            last = jnp.clip(plan.lens - 1, 0, S - 1)[:, None]
            x = jnp.take_along_axis(x, last[..., None], axis=1)
            memory = jnp.take_along_axis(memory, last[..., None], axis=1)
            pos_x = jnp.take_along_axis(positions, last, axis=1)
            # one query a lane over the full layer's rows: decode's read
            paged_x = paged._replace(start=None, chunk_len=None)
        # (uncached: the "pool" the cross layers read is the full layer's
        # own k, v of this pass)
        carry = (x, memory) + (kv_full if paged is None
                               else (k_pool, v_rows))
        if n_x > 0:
            carry, _ = jax.lax.scan(
                lambda c, j: (cross_period(c, j), None), carry,
                jnp.arange(n_x))
        x = carry[0]
    with jax.named_scope("head"):
        x = layer_norm(x, params["final_norm"], params["final_norm_b"], eps)
        logits = jnp.einsum("bsh,vh->bsv", x, params["embed"],
                            preferred_element_type=jnp.float32)
    if paged is None:
        return logits, None
    return logits, KVCache(
        k=k_pool, v={"v": v_rows, "conv": conv, "ssm": ssm})
