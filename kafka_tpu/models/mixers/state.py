"""The mixers that carry a recurrent per-thread STATE in a slot beside the
pages (models/cache.py), through slot accessors that are ARGUMENTS: the
forward pass hands a mixer the ones its own module names (`ctx`), so a check
that swaps one there reaches every write.

* the gated short convolution (`lfm2_moe`: LFM2-8B-A1B).  In the conv layout
  (`cfg.conv_L_cache`) mixer leaves are stacked per kind under
  `params["attn"][kind]`; only the attention layers hold rows, and the v pool
  is a dict {"v": rows, "conv": [conv layers, n_slots, L - 1, H] float32};
* gated delta-rule linear attention (`solar_open2`: Solar-Open2-250B; Kimi
  Delta Attention): the conv layout's mechanism with a second state leaf;
* the Mamba-2 (SSD) mixer of the parallel layout (`falcon_h1`): attention
  (mixers/gqa.py) and this mixer read one normed input and both add into the
  residual; every layer holds rows in the paged pool AND a state slot;
* the same mixer standing ALONE in a layer (`nemotron_h`'s one-sublayer
  layout): the conv layout's mechanism (leaves per kind, a state and no
  rows) with the parallel layout's two state leaves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.norms import rms_norm
from ...ops.pallas import gated_delta as kda_kernels
from ...ops.pallas import gdn as gdn_kernels
from ...ops.pallas import ssd as ssd_kernels
from ...ops.pallas.gated_delta import gated_delta
from ...ops.pallas.gdn import gdn
from ...ops.pallas.selective_scan import kernel_ok as scan_kernel_ok
from ...ops.pallas.ssd import ssd
from ...ops.pallas.tail_conv import piece, tail_conv_step, tiles as tail_tiles
from ..cache import StatePlan, _read_state, _write_state
from ..config import ModelConfig
from ..quant import Params, _w
from . import gqa


def _tail_conv_silu(rows: jnp.ndarray, w: jnp.ndarray, bias, leaf, layer,
                    plan: StatePlan, read_state=_read_state,
                    write_state=_write_state, kernel: bool = False):
    """SiLU of a depthwise causal convolution whose tail is a layer's STATE
    (the delta layout's three convolutions side by side, the parallel
    layout's one over [x | B | C]).  rows [B, S, C]; w [taps, C] float32 (tap
    taps - 1 multiplies the row's own value); bias [C] float32 or None.  The
    taps - 1 rows before the pass come from `leaf`, the stacked state array
    (laid out in the slot as `cfg.state_shapes` says; None: uncached, zeros)
    at `layer`, and the last taps - 1 REAL rows (`plan.lens`; as
    `_short_conv_block`'s tail) go back to it (`read_state` / `write_state`:
    models/cache.py's, as the forward pass names them).  `kernel` (the Pallas
    backend): decode's one row a lane (S == 1, lane i in slot i) is ONE pass
    over the slots as they are stored, in place, where the tail's sizes tile
    (ops/pallas/tail_conv.py: the same sums in the same order, interpreted
    off the chip); every other pass is the body below.  Returns (float32 [B,
    S, C], leaf')."""
    f32 = jnp.float32
    b, s, c = rows.shape
    taps = w.shape[0]
    if leaf is not None and tail_form(
            kernel, s, plan.src is not None, taps, c,
            leaf.shape[2:]) == "kernel":
        out, leaf = tail_conv_step(
            leaf, jnp.asarray(layer, jnp.int32), plan.lens,
            rows[:, 0].astype(f32), w, bias,
            interpret=jax.default_backend() != "tpu")
        return out[:, None], leaf
    tail = (jnp.zeros((b, taps - 1, c), f32) if leaf is None
            else read_state(leaf, layer, plan, b).reshape(b, taps - 1, c))
    seq = jnp.concatenate([tail, rows.astype(f32)], axis=1)
    out = sum(w[j] * seq[:, j:j + s] for j in range(taps))
    out = jax.nn.silu(out if bias is None else out + bias)
    if leaf is not None:
        new = jax.vmap(
            lambda rows, n: jax.lax.dynamic_slice_in_dim(
                rows, n, taps - 1, axis=0))(seq, plan.lens)
        slot = (b,) + leaf.shape[2:]
        leaf = write_state(leaf, layer, plan, new.reshape(slot),
                            tail.reshape(slot))
    return out, leaf


def tail_form(kernel: bool, S: int, own_slots: bool, taps: int, C: int,
              slot) -> str:
    """Which form a CACHED pass of S rows a lane takes of the tail
    convolution, "kernel" or "xla": `_tail_conv_silu`'s own rule, from what
    is known when the program is traced (`own_slots`: a launch that names
    its lanes' slots, a prefill)."""
    if kernel and S == 1 and not own_slots and tail_tiles(taps, C, slot):
        return "kernel"
    return "xla"


@functools.lru_cache(maxsize=None)
def state_launch_forms(cfg: ModelConfig, S: int, own_slots: bool):
    """{"recurrence" | "tail": "kernel" | "xla"} of ONE cached pass of S rows
    a lane through this model's state layers: which form each op of a layer
    takes, decided by shapes when the program is traced and otherwise
    visible in a device capture only (Granite's tail runs the XLA body and
    nothing said so).  Each mixer's own rule (`form`, `tail_form`, the scan
    kernel's `kernel_ok`), so the two cannot part.  An op the model's state
    layers do not have (a short convolution has no recurrence) is absent; {}
    for a model without a state.  (Asked once a dispatch by the engine:
    remembered, and the dict is the callers' to read only.)"""
    kernel = cfg.attention_backend == "pallas"
    if not cfg.has_state:
        return {}
    slot = dict(cfg.state_shapes())["conv"]
    if cfg.ssd_heads:
        return {
            "recurrence": ssd_kernels.form(
                kernel, True, S, own_slots, cfg.ssd_heads, cfg.ssd_groups,
                cfg.ssd_head_dim, cfg.ssd_d_state),
            "tail": tail_form(kernel, S, own_slots, cfg.ssd_conv_kernel,
                              cfg.ssd_conv_dim, slot)}
    if cfg.delta_heads:
        args = (kernel, True, S, own_slots)
        return {
            "recurrence": gdn_kernels.form(
                *args, cfg.delta_heads, cfg.delta_head_dim, cfg.delta_v_dim)
            if cfg.delta_gate == "head" else kda_kernels.form(
                *args, cfg.delta_head_dim, cfg.delta_v_dim),
            "tail": tail_form(kernel, S, own_slots, cfg.delta_conv_kernel,
                              cfg.delta_conv_dim, slot)}
    if cfg.hybrid_decoder:
        # (models/hybrid.py: the scan kernel where it tiles a chunk, decode's
        # closed form and the convolution in XLA)
        scan = kernel and S > 1 and scan_kernel_ok(S, cfg.mamba_d_inner)
        return {"recurrence": "kernel" if scan else "xla", "tail": "xla"}
    return {"tail": "xla"}  # a gated short convolution: its tail alone


def tail_step_note(cfg: ModelConfig):
    """For the engine's start-up log: what decode's tail convolution of this
    model's state layers runs as (`_tail_conv_silu`'s rule on the configured
    sizes), or None where no layer has such a tail."""
    if cfg.ssd_heads:
        taps, c = cfg.ssd_conv_kernel, cfg.ssd_conv_dim
    elif cfg.delta_heads:
        taps, c = cfg.delta_conv_kernel, cfg.delta_conv_dim
    else:
        return None
    slot = dict(cfg.state_shapes())["conv"]
    what = f"{cfg.state_layers} state layers' tail ({taps - 1} x {c} as {slot})"
    if cfg.attention_backend != "pallas":
        return f"{what}: the XLA body ({cfg.attention_backend} backend)"
    if tail_tiles(taps, c, slot):
        return f"{what}: tail_conv_step, pieces of {piece(c, slot)}"
    return (f"{what}: the XLA body, tail_conv_step declines a piece of "
            f"{piece(c, slot)} values (no whole 128-lane tiles)")


def _short_conv_block(x: jnp.ndarray, lp: Params, leaf, layer,
                      plan: StatePlan, read_state=_read_state,
                      write_state=_write_state):
    """One gated short convolution (`lfm2_moe`'s conv mixer).  x: [B, S, H].
    [B | C | u] = x W_in; z = B * u; c_t = sum_j w_j * z_(t - L + 1 + j)
    over the L taps (depthwise, causal: tap L - 1 is the row's own); the
    block is (C * c) W_out.  The L - 1 products z before the pass are the
    layer's STATE: `leaf` is the stacked state array [conv layers, n_slots,
    L - 1, H] float32 (None: uncached, a zero tail) and `layer` this layer's
    place in it; a lane's tail comes from its `plan.src` slot and the tail
    after its last real row (`plan.lens`) goes to `dst` and `snap`, an
    inactive lane's passing through (models/cache._read_state /
    _write_state, one implementation for every decoder with a state).  z is
    the EXACT product of the two gates' values, taken in float32 (two
    bfloat16 values multiply into 16 significant bits), which the float32
    slot holds as it is and a slot of the activations' dtype would round: on
    the chip XLA computes the bfloat16 product unrounded anyway (excess
    precision; my chip run A, PR 47: 97% of a slot's values needed more than
    bfloat16), so saying float32 makes every backend and every fusion agree
    on what a tail row is.  The taps accumulate in float32.  At S == 1 this
    is decode's closed-form step.
    Returns (out [B, S, H] ahead of the residual add, leaf')."""
    dt, f32 = x.dtype, jnp.float32
    b, s, h = x.shape
    with jax.named_scope("conv_proj"):
        bcu = jnp.einsum("bsh,hf->bsf", x, _w(lp, "w_in", dt))
    with jax.named_scope("conv_mix"):
        gate_b, gate_c, u = bcu[..., :h], bcu[..., h:2 * h], bcu[..., 2 * h:]
        w = lp["conv_w"].astype(f32)  # [L, H]
        taps = w.shape[0]
        tail = (jnp.zeros((b, taps - 1, h), f32) if leaf is None
                else read_state(leaf, layer, plan, b))
        seq = jnp.concatenate([tail, gate_b.astype(f32) * u.astype(f32)],
                              axis=1)
        c = sum(w[j] * seq[:, j:j + s] for j in range(taps))
        if leaf is not None:
            # the last L - 1 REAL products: rows lens - L + 1 .. lens - 1 of
            # the pass are rows lens .. lens + L - 2 of `seq`
            new = jax.vmap(
                lambda rows, n: jax.lax.dynamic_slice_in_dim(
                    rows, n, taps - 1, axis=0))(seq, plan.lens)
            leaf = write_state(leaf, layer, plan, new, tail)
        y = gate_c * c.astype(dt)
    with jax.named_scope("conv_proj"):
        out = jnp.einsum("bsh,hk->bsk", y, _w(lp, "w_out", dt))
    return out, leaf



def mix_conv(x, lp: Params, ctx, kc, vc, layer, kind):
    """`MIXERS["conv"]`: `layer` counts the conv layers, its place in the
    state array under "conv" of the v pool's dict."""
    out, tail = _short_conv_block(
        x, lp, None if vc is None else vc["conv"], layer, ctx.plan,
        ctx.read_state, ctx.write_state)
    if vc is not None:
        vc = {**vc, "conv": tail}
    return out, kc, vc


def _delta_attention_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
                           leaves, layer, plan: StatePlan,
                           read_state=_read_state, write_state=_write_state):
    """One gated delta-rule linear-attention layer (`solar_open2`'s mixer;
    Kimi Delta Attention).  x: [B, S, H].  With W = heads x head size D:

        q~, k~, v~ = x W_q, x W_k, x W_v;  q, k, v = SiLU(conv(.)), a
        depthwise causal convolution of `delta_conv_kernel` taps a channel;
        q <- q / ||q|| D^-1/2, k <- k / ||k|| a head
        g = -exp(A_log) softplus(x W_f1 W_f2 + dt_bias)   a key CHANNEL
        beta = sigmoid(x W_beta) (x 2 with `delta_neg_eigval`)   a head
        S_t = (I - beta k k^T) Diag(exp g) S_(t-1) + beta k v^T;  o = S_t^T q
        y = (RMSNorm_head(o) * sigmoid(x W_g1 W_g2)) W_o

    The layer's STATE is two leaves of `leaves` (the v pool's dict; None:
    uncached, from zeros), `layer` this layer's place in both: "conv", the
    last taps - 1 rows of [q~ | k~ | v~] in float32 (laid out in the slot as
    `cfg.state_shapes` says), read and written as a short convolution's tail
    is (`read_state` / `write_state`: models/cache.py), and "delta", S
    transposed a head, float32, which ops/pallas/gated_delta updates IN PLACE
    on the Pallas backend (the chunk kernel at S > 1, the step kernel in
    decode) and through the same slot read and write under a row-by-row scan
    elsewhere.  Everything between the projections and W_o is float32.

    `cfg.delta_gate` "head" is Gated DeltaNet's form of the same layer
    (`olmo_hybrid`): key heads of D and value heads of `cfg.delta_v_dim`
    values, ONE log-decay a head, g = -exp(A_log) softplus(x W_a + dt_bias)
    with W_a [H, heads], a full-rank gate under SiLU, y = (RMSNorm_head(o) *
    SiLU(x W_go)) W_o, and "delta" S itself, [D, heads x d_v]
    (ops/pallas/gdn.py).
    Returns (out [B, S, H] ahead of the residual add, leaves')."""
    dt, f32 = x.dtype, jnp.float32
    b, s, _ = x.shape
    H, D, Dv = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_v_dim
    a_head = cfg.delta_gate == "head"
    with jax.named_scope("kda_proj"):
        qkv = jnp.concatenate(
            [jnp.einsum("bsh,hw->bsw", x, _w(lp, n, dt))
             for n in ("wq", "wk", "wv")], axis=-1)
        if a_head:
            decay, gate = (jnp.einsum("bsh,hw->bsw", x, _w(lp, n, dt))
                           for n in ("wa", "wgo"))
        else:
            decay, gate = (
                jnp.einsum("bsr,rw->bsw",
                           jnp.einsum("bsh,hr->bsr", x, _w(lp, a, dt)),
                           _w(lp, c, dt))
                for a, c in (("wf1", "wf2"), ("wg1", "wg2")))
        beta = jnp.einsum("bsh,hn->bsn", x, _w(lp, "wbeta", dt))
    conv_leaf, delta_leaf = (None, None) if leaves is None else (
        leaves["conv"], leaves["delta"])
    with jax.named_scope("kda_conv"):
        qkv, conv_leaf = _tail_conv_silu(
            qkv, lp["conv_w"].astype(f32), None, conv_leaf, layer, plan,
            read_state, write_state,
            kernel=cfg.attention_backend == "pallas")
    with jax.named_scope("kda_gate"):
        # (thirds where the heads are square, as it always was)
        q, k, v = (a.reshape(b, s, H, -1) for a in jnp.split(
            qkv, 3 if Dv == D else (H * D, 2 * H * D), axis=-1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q, k = unit(q) * D**-0.5, unit(k)
        if a_head:
            g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(
                decay.astype(f32) + lp["dt_bias"].astype(f32))
        else:
            g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
                decay.astype(f32) + lp["dt_bias"].astype(f32)
            ).reshape(b, s, H, D)
        beta = jax.nn.sigmoid(beta.astype(f32)) * (
            2.0 if cfg.delta_neg_eigval else 1.0)
    with jax.named_scope("kda_delta"):
        o, delta_leaf = (gdn if a_head else gated_delta)(
            delta_leaf, layer, plan, q, k, v, g, beta,
            kernel=cfg.attention_backend == "pallas",
            read_state=read_state, write_state=write_state)
    with jax.named_scope("kda_gate"):
        o = rms_norm(o, lp["ln_o"].astype(f32), cfg.rms_norm_eps) \
            * (jax.nn.silu if a_head else jax.nn.sigmoid)(
                gate.astype(f32)).reshape(b, s, H, Dv)
    with jax.named_scope("kda_proj"):
        out = jnp.einsum("bsw,wh->bsh", o.astype(dt).reshape(b, s, H * Dv),
                         _w(lp, "w_out", dt))
    if leaves is not None:
        leaves = {**leaves, "conv": conv_leaf, "delta": delta_leaf}
    return out, leaves



def mix_delta(x, lp: Params, ctx, kc, vc, layer, kind):
    """`MIXERS["delta"]`: `layer` counts the linear layers, its place in
    both state leaves of the v pool's dict."""
    out, vc = _delta_attention_block(
        x, lp, ctx.cfg, vc, layer, ctx.plan, ctx.read_state, ctx.write_state)
    return out, kc, vc


def ssd_mup_vector(cfg: ModelConfig):
    """`ssm_multipliers` spread over the columns of the SSD mixer's input
    projection, [z | x | B | C | dt], as a float32 vector (None: the config
    has none)."""
    if not cfg.ssm_multipliers:
        return None
    d_ssm = cfg.ssd_heads * cfg.ssd_head_dim
    gw = cfg.ssd_groups * cfg.ssd_d_state
    return np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                     (d_ssm, d_ssm, gw, gw, cfg.ssd_heads))


def _ssd_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig, leaves, layer,
               plan: StatePlan, read_state=_read_state,
               write_state=_write_state):
    """One Mamba-2 (SSD) mixer (`falcon_h1`'s, beside attention on the same
    normed input).  x: [B, S, H].  With d = heads x head size P, N the state
    size and G groups:

        p = ((x ssm_in_multiplier) W_in) * m, m the muP vector over the
        column ranges; [z | xBC | dt] = p (d | d + 2 G N | heads)
        xBC <- SiLU(conv(xBC) + b), a depthwise causal convolution of
        `ssd_conv_kernel` taps a channel; [x | B | C] = xBC
        dt = softplus(dt + dt_bias), g = -exp(A_log) dt   a SCALAR a head
        S_t = exp(g_t) S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        (head h reads group h // (heads / G)'s B and C)
        y <- RMSNorm_grouped(y * SiLU(z)), each group's d / G channels
        normalised apart under one learned weight of d
        out = (y W_out) ssm_out_multiplier

    The multipliers are applied in the activations' dtype where the equations
    put them; none is folded into a weight.  The layer's STATE is two leaves
    of `leaves` (the v pool's dict, whose "v" is the SAME layer's attention
    rows; None: uncached, from zeros), `layer` this layer's place in both:
    "conv", the last taps - 1 rows of xBC ahead of the convolution in float32
    (laid out in the slot as `cfg.state_shapes` says; `read_state` /
    `write_state`: models/cache.py), and "ssd", S a head, float32, which
    ops/pallas/ssd updates IN PLACE on the Pallas backend (the chunk kernel
    at S > 1, the step kernel in decode) and through the same slot read and
    write under a row-by-row scan elsewhere.  Everything between the
    projections is float32.  Returns (out [B, S, H] ahead of the residual
    add, leaves')."""
    dt_, f32 = x.dtype, jnp.float32
    b, s, _ = x.shape
    H, P, N, G = (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_d_state,
                  cfg.ssd_groups)
    d, cw = H * P, cfg.ssd_conv_dim
    with jax.named_scope("ssd_proj"):
        if cfg.ssm_in_multiplier != 1.0:
            x = x * jnp.asarray(cfg.ssm_in_multiplier, dt_)
        p = jnp.einsum("bsh,hw->bsw", x, _w(lp, "w_in", dt_))
        mup = ssd_mup_vector(cfg)
        if mup is not None:
            p = p * jnp.asarray(mup, dt_)
        z, xbc, step = p[..., :d], p[..., d:d + cw], p[..., d + cw:]
    conv_leaf, ssd_leaf = (None, None) if leaves is None else (
        leaves["conv"], leaves["ssd"])
    with jax.named_scope("ssd_conv"):
        xbc, conv_leaf = _tail_conv_silu(
            xbc, lp["conv_w"].astype(f32), lp["conv_b"].astype(f32),
            conv_leaf, layer, plan, read_state, write_state,
            kernel=cfg.attention_backend == "pallas")
    with jax.named_scope("ssd_gate"):
        xs = xbc[..., :d].reshape(b, s, H, P)
        Bm = xbc[..., d:d + G * N].reshape(b, s, G, N)
        Cm = xbc[..., d + G * N:].reshape(b, s, G, N)
        step = jax.nn.softplus(step.astype(f32) + lp["dt_bias"].astype(f32))
        g = -jnp.exp(lp["A_log"].astype(f32)) * step
    with jax.named_scope("ssd_scan"):
        y, ssd_leaf = ssd(
            ssd_leaf, layer, plan, xs * step[..., None], Bm, Cm, g,
            kernel=cfg.attention_backend == "pallas",
            read_state=read_state, write_state=write_state)
    with jax.named_scope("ssd_gate"):
        y = y + lp["D"].astype(f32)[:, None] * xs
        y = (y.reshape(b, s, d) * jax.nn.silu(z.astype(f32))).reshape(
            b, s, G, d // G)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = y.reshape(b, s, d) * lp["ln_ssd"].astype(f32)
    with jax.named_scope("ssd_proj"):
        out = jnp.einsum("bsw,wh->bsh", y.astype(dt_), _w(lp, "w_out", dt_))
        if cfg.ssm_out_multiplier != 1.0:
            out = out * jnp.asarray(cfg.ssm_out_multiplier, dt_)
    if leaves is not None:
        leaves = {**leaves, "conv": conv_leaf, "ssd": ssd_leaf}
    return out, leaves



def mix_ssd(x, lp: Params, ctx, kc, vc, layer, kind):
    """`MIXERS["ssd"]`: attention, then the layer's SECOND mixer on the same
    normed input; ONE `layer` indexes the page pool and both state leaves."""
    attn_out, kc, vc = gqa.mix(x, lp, ctx, kc, vc, layer, kind)
    ssd_out, vc = _ssd_block(x, lp, ctx.cfg, vc, layer, ctx.plan,
                             ctx.read_state, ctx.write_state)
    with jax.named_scope("ssd_proj"):
        return ssd_out + attn_out, kc, vc


def mix_mamba2(x, lp: Params, ctx, kc, vc, layer, kind):
    """`MIXERS["mamba2"]`: the SSD mixer ALONE in its layer; `layer` counts
    the layers of its kind, its place in both state leaves of the v pool's
    dict, and no page is touched."""
    out, vc = _ssd_block(x, lp, ctx.cfg, vc, layer, ctx.plan,
                         ctx.read_state, ctx.write_state)
    return out, kc, vc
