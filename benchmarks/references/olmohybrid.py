"""Plain float32 reference of the Olmo-Hybrid-7B decoder
(allenai/Olmo-Hybrid-7B, `model_type` "olmo_hybrid"), written from its
published config.json and ISSUE 66's equations.  Imports nothing of
`kafka_tpu` (a test scans for it); `tests/test_olmo_hybrid.py` holds
`kafka_tpu.models.forward` to it at a tiny size in float32.

The decoder, per token x (what the config has no key for is marked A1-A9 and
listed under `assumed` in the configuration's file, each with where it is
recalled from):

* block, both kinds (A8: Olmo 2 / Olmo 3's reordered norm, on each
  sublayer's OUTPUT and none on its input): h <- h + RMSNorm(Mixer_l(h)),
  h <- h + RMSNorm(W_down(SiLU(W_gate h) * W_up h)), eps `rms_norm_eps`;
  Mixer_l is by `layer_types[l]`;
* linear attention (Gated DeltaNet, arXiv:2412.06464, flash-linear-attention
  `gated_deltanet.py`, whose arguments the `linear_*` keys are), per head of
  `linear_num_key_heads` = `linear_num_value_heads` 30, d_k
  `linear_key_head_dim` 96, d_v `linear_value_head_dim` 192:
  q~, k~, v~ = x W_q, x W_k, x W_v; q, k, v = SiLU(conv4(.)), a depthwise
  causal convolution of `linear_conv_kernel_dim` taps a channel, no bias,
  zero before the sequence starts (A1); q <- q / sqrt(|q|^2 + 1e-6) d_k^-1/2,
  k <- k / sqrt(|k|^2 + 1e-6) a head (A2); beta = 2 sigmoid(x W_b) a head
  (`linear_allow_neg_eigval`: the factor 2; A3); g = -exp(A_log)
  softplus(x W_a + dt_bias), ONE log-decay a head, A_log and dt_bias [30]
  (A4);
      S_t = e^(g_t) S_(t-1) + beta_t k_t (v_t - e^(g_t) S_(t-1)^T k_t)^T
      o_t = S_t^T q_t
  with S in R^(96 x 192) a head, float32, zero before the sequence starts;
  y = (RMSNorm_192(o_t) * SiLU(x W_g)) W_o, the norm's weight one vector of
  d_v a layer (A5);
* full attention: 30 query and 30 key / value heads of `hidden_size` / 30 =
  128 (`head_dim` absent); q = RMSNorm_3840(x W_q), k = RMSNorm_3840(x W_k),
  each over the WHOLE projection ahead of the split into heads (A6); v = x
  W_v; no bias, NO rotation (`rope_parameters.rope_theta` null: a theta of
  null cannot be rotated by; A7); scores / sqrt(128), causal, softmax,
  values, W_o;
* final RMSNorm, an untied head.

The tree is the program's (`kafka_tpu/models/init_params._init_lead_tree_params`,
the linear-attention layout, dense): "layers" holds the two norms and the
feed-forward leaves stacked over the layers, `attn["linear_attention"]` and
`attn["full_attention"]` each kind's mixers stacked in layer order (the
linear mixer's output gate is "wgo": "wg" is the feed-forward's).

Float32 under `default_matmul_precision("highest")`, the recurrence written
token by token as the equation above (no chunking), no cache, no kernels, no
batching; the stacked bf16 weights are upcast one layer at a time, the
feed-forward and the head in BLOCKS of their wide axis, so it fits at the
published widths beside the served model (the head alone is 1.5 GB in
float32).

Departures from the published model: weights are random (the check compares
programs, not models); nothing else.  No routing, so no position is skipped
(`router_gap` is +inf everywhere) and nothing is forced.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

DELTA, GLOBAL = "linear_attention", "full_attention"

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary, at ALL 48 positions.  Readings on the v5e at
# the published widths, 16 layers, Pallas, seeded weights: launches of 512,
# 512, 496 (of 512, leaving a snapshot) and 16 (of 64, resumed from it) and 47
# decode steps through pages and state slots, the 48 positions 1535..1582
# (my chip runs 1 and 2, PR 66; `benchmarks/check_power.py` on the pair of
# seeds every run of the cell checks, `benchmarks/check_seeds.py` on the pairs
# (1, 1) and (2, 7), every control through `compare_logits`): the served
# program (bf16 weights and activations, float32 state, `gdn_chunk` and
# `gdn_step`, flash prefill and the Pallas decode kernel at 30 / 30 x 128)
# reads 0.0912-0.1084, 0.1008-0.1178 and 0.0935-0.1124, 144 readings; this
# reference in the nearest precision below, a bfloat16 accumulator rounded
# after every 256 of the contraction (`bf16_accumulate_256`), 0.2180-0.2610,
# 0.2391-0.2784 and 0.2320-0.2788 (after every 128: 0.2972-0.3879); with the
# delta state rounded to bfloat16 after every token (`bf16_state`)
# 0.1556-0.1818; on int8 weights 0.4536-0.5307, and int8 weights through the
# SERVED program 0.4575-0.5387.  0.14 is 1.19x the largest served reading of
# the 144 (1.29x the largest of the pair a run checks, which is the same
# digits in every run) and 0.64x the smallest of the 256-deep accumulator's,
# which fails it at every position of every pair, as a bfloat16 state does
# (0.90x its smallest).  Why the served band is 2.7x Solar-Open2's: A_log is
# drawn log U(0, 16) as the layer's own initialiser draws it, so a fifteenth
# of the heads forget nothing over 1,536 rows, their 96 key dimensions fill,
# and u = v - S^T k is the small difference of two large terms; every
# precision below reads 2.7x higher too.  One mechanism out each
# (`variants`), smallest - median - largest over the 48: beta in (0, 1) 1.22
# - 1.26 - 1.31, the q scale dropped 1.23 - 1.27 - 1.30, q unnormalised 1.35
# - 1.39 - 1.41, k unnormalised NaN (beta k k^T with |k|^2 ~ 100 diverges),
# a sigmoid output gate 1.36 - 1.39 - 1.42, the decays dealt over channels
# 1.33 - 1.36 - 1.40, A_log dropped 1.31 - 1.33 - 1.37, the norms ahead of
# their sublayers 1.37 - 1.41 - 1.43, a QK-norm a head 0.18 - 0.20 - 0.22, a
# rotation at theta 500,000 0.72 - 0.76 - 0.79, the conv tail zeroed at the
# snapshot 0.90 - 1.01 - 1.12 and at decode's take-over 0.00 - 1.06 - 1.28,
# the state lost there 1.19 - 1.24 - 1.30 and 0.00 - 1.27 - 1.31 (the
# `_at_decode` ones leave the one prefill position alone and fail by all 47
# others), the 96 x 192 state read transposed 1.23 - 1.28 - 1.32: all fail.
# What it cannot fail: a bfloat16 decay (0.0082 - 0.0089 - 0.0098) and a
# bfloat16 QK-norm (0.0060 - 0.0065 - 0.0071), a twelfth of the served
# error; the float32 CPU tests hold both.
TOLERANCE = {
    "value": 0.14,
    "why": "served bf16 0.0912-0.1178 over 144 readings on three pairs of "
           "seeds, a bf16 accumulator 0.2180-0.2788 (256 deep; 128 deep "
           "0.2972-0.3879), a bf16 state 0.1556-0.1818 and int8 weights "
           "0.4536-0.5387 there (my chip runs 1 and 2, PR 66; PERF.md 6)",
}

# The check's last prefill launch: LAST rows (a page) resumed from the
# snapshot the launch before it left (`drivers/olmohybrid_pool.py` has the
# same number); the variants' `_at_snapshot` mistakes happen at its first row.
LAST = 16
# columns a block of the feed-forward's and the head's wide axis holds
BLOCK = 4096


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _round_bf16(x):
    """x rounded to bfloat16's 8 bits and back (a convert pair would be
    dropped: XLA allows excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(a, b, bf16_acc: int = 0):
    """a [M, K] @ b [K, N] in float32.  `bf16_acc` (the `bf16_accumulate`
    variants): operands rounded to bfloat16 and the running sum rounded to
    bfloat16 after every block of that many of the contracted axis."""
    b = _f32(b)
    if not bf16_acc:
        return a @ b
    k = a.shape[1]
    c = bf16_acc if k % bf16_acc == 0 else k
    a, b = _round_bf16(a), _round_bf16(b)

    def step(i, acc):
        pa = jax.lax.dynamic_slice_in_dim(a, i * c, c, 1)
        pb = jax.lax.dynamic_slice_in_dim(b, i * c, c, 0)
        return _round_bf16(acc + _round_bf16(pa @ pb))

    return jax.lax.fori_loop(
        0, k // c, step, jnp.zeros((a.shape[0], b.shape[1]), jnp.float32))


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def _rope(x, theta: float):
    """x [S, N, D] at positions 0..S-1, all D values, pairs (i, i + D/2)
    (the `rotation_on` variant only: the model does not rotate)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


def _conv_silu(z, w, hp):
    """SiLU of the depthwise causal convolution of z [S, C] with taps w [L,
    C] (tap L - 1 is the row's own; zero before the sequence starts)."""
    s = z.shape[0]
    taps = w.shape[0]
    rows = jnp.arange(s)[:, None]
    c = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        zj = jnp.pad(z, ((back, 0), (0, 0)))[:s]  # z_{t - back}
        cut = hp.get("zero_tail_at")
        if cut is not None:
            # the mistake: the rows before `cut` are lost to the rows from it
            zj = jnp.where((rows >= cut) & (rows - back < cut), 0.0, zj)
        c = c + w[j] * zj
    return jax.nn.silu(c)


def _linear_attention(h, mp, hp):
    """The Gated DeltaNet mixer over the rows h [S, H]."""
    acc = hp.get("bf16_accumulate", 0)
    s = h.shape[0]
    n, dk, dv = hp["delta_heads"], hp["delta_head_dim"], hp["delta_value_dim"]
    taps = _f32(mp["conv_w"])  # [L, n (2 dk + dv)]: q | k | v
    cuts = np.cumsum([0, n * dk, n * dk, n * dv])
    q, k, v = (
        _conv_silu(_mm(h, mp[name], acc), taps[:, cuts[i]:cuts[i + 1]], hp
                   ).reshape(s, n, -1)
        for i, name in enumerate(("wq", "wk", "wv")))
    if not hp.get("q_unnormalised"):
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    if not hp.get("k_unnormalised"):
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    if not hp.get("q_scale_dropped"):
        q = q * dk ** -0.5
    rate = 1.0 if hp.get("a_log_dropped") else jnp.exp(_f32(mp["A_log"]))
    g = -rate * jax.nn.softplus(_mm(h, mp["wa"], acc) + _f32(mp["dt_bias"]))
    if hp.get("bf16_decay"):
        g = _round_bf16(g)
    # [S, n, dk]: one decay a head over all its key channels ...
    g = jnp.broadcast_to(g[:, :, None], (s, n, dk))
    if hp.get("decay_per_channel"):
        # ... or, the mistake, the heads' decays dealt out over the channels
        g = g.reshape(s, dk, n).swapaxes(1, 2)
    beta = jax.nn.sigmoid(_mm(h, mp["wbeta"], acc)) * (
        2.0 if hp["delta_neg_eigval"] and not hp.get("beta_unit") else 1.0)
    lost = hp.get("zero_state_at", -1)
    turned = hp.get("transpose_state_at", -1)

    def token(S, row):
        """S [heads, d_k, d_v]: the equation, one token."""
        q_t, k_t, v_t, g_t, b_t, t = row
        S = jnp.where(t == lost, 0.0, S)
        # (the mistake: a slot laid [d_k, d_v] read as if laid [d_v, d_k])
        S = jnp.where(t == turned,
                      S.reshape(n, dv, dk).swapaxes(1, 2), S)
        S = jnp.exp(g_t)[:, :, None] * S                   # e^g S
        kS = jnp.einsum("nk,nkv->nv", k_t, S)              # S^T k
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - kS)[:, None, :]
        if hp.get("bf16_state"):
            S = _round_bf16(S)
        return S, jnp.einsum("nkv,nk->nv", S, q_t)         # S^T q

    _, o = jax.lax.scan(token, jnp.zeros((n, dk, dv), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(s)))
    o = _rms_norm(o, mp["ln_o"], hp["rms_norm_eps"])
    gate = _mm(h, mp["wgo"], acc)
    o = o * (jax.nn.sigmoid(gate) if hp.get("sigmoid_gate")
             else jax.nn.silu(gate)).reshape(s, n, dv)
    return _mm(o.reshape(s, n * dv), mp["w_out"], acc)


def _attention(h, mp, hp):
    """The softmax mixer over the rows h [S, H], one head at a time."""
    s, acc = h.shape[0], hp.get("bf16_accumulate", 0)
    hq, d = mp["wq"].shape[-2:]
    hkv = mp["wk"].shape[-2]
    rep = hq // hkv
    q = _mm(h, mp["wq"].reshape(-1, hq * d), acc)
    k = _mm(h, mp["wk"].reshape(-1, hkv * d), acc)
    v = _mm(h, mp["wv"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    eps = hp["rms_norm_eps"]

    def normed(a, w, heads):
        if hp.get("bf16_qk_norm"):
            a = _round_bf16(a)
            var = _round_bf16(jnp.mean(_round_bf16(a * a), -1, keepdims=True))
            return _round_bf16(_round_bf16(a * jax.lax.rsqrt(var + eps))
                               * _round_bf16(_f32(w))).reshape(s, heads, d)
        if hp["qk_norm"] == "head":
            # the mistake: a norm a head (under the head's part of the weight)
            return _rms_norm(a.reshape(s, heads, d),
                             _f32(w).reshape(heads, d), eps)
        return _rms_norm(a, w, eps).reshape(s, heads, d)

    q, k = normed(q, mp["ln_q"], hq), normed(k, mp["ln_k"], hkv)
    if hp.get("rotation_on"):
        q, k = (_rope(a, hp["rotation_theta"]) for a in (q, k))
    allowed = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def group(g, out):
        qg = jax.lax.dynamic_slice_in_dim(q, g * rep, rep, 1)  # [S, rep, D]
        kg = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        scores = jnp.einsum("snd,td->nst", qg, kg) / np.sqrt(d)
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        og = jnp.einsum("nst,td->snd", jax.nn.softmax(scores, axis=-1), vg)
        return jax.lax.dynamic_update_slice_in_dim(out, og, g * rep, 1)

    out = jax.lax.fori_loop(0, hkv, group, jnp.zeros_like(q)).reshape(s, -1)
    return _mm(out, mp["wo"].reshape(hq * d, -1), acc)


def _swiglu(h, wg, wu, wd, acc=0):
    """W_down(SiLU(W_gate h) * W_up h), BLOCK columns of the wide axis at a
    time (a float32 copy of one whole matrix at the published widths is 169
    MB, three of them beside the served model too many)."""
    f = wg.shape[1]
    out = jnp.zeros_like(h)
    for lo in range(0, f, BLOCK):
        cols = slice(lo, min(lo + BLOCK, f))
        mid = jax.nn.silu(_mm(h, wg[:, cols], acc)) * _mm(h, wu[:, cols], acc)
        out = out + _mm(mid, wd[cols], acc)
    return out


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in hp.items()
                        if not isinstance(v, (list, dict))))


@partial(jax.jit, static_argnames=("hp", "kind"))
def _layer(x, stack, mixers, l, nth, *, hp, kind: str):
    """Layer `l` of `stack` (norms and feed-forward leaves), its mixer the
    `nth` of its kind's."""
    hp = dict(hp)
    lp = {name: _at(w, l) for name, w in stack.items()}
    mp = {name: _at(w, nth) for name, w in mixers.items()}
    mixer = _linear_attention if kind == DELTA else _attention
    eps, acc = hp["rms_norm_eps"], hp.get("bf16_accumulate", 0)

    def ffn(h):
        return _swiglu(h, lp["wg"], lp["wu"], lp["wd"], acc)

    if hp["norm_position"] == "pre":
        # the mistake: the norms ahead of their sublayers
        x = x + mixer(_rms_norm(x, lp["ln_attn"], eps), mp, hp)
        return x + ffn(_rms_norm(x, lp["ln_mlp"], eps))
    x = x + _rms_norm(mixer(x, mp, hp), lp["ln_attn"], eps)
    return x + _rms_norm(ffn(x), lp["ln_mlp"], eps)


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, positions_out, *, eps: float):
    x = _rms_norm(x, final_norm, eps)[positions_out]
    return jnp.concatenate(
        [x @ _f32(head[:, lo:lo + 4 * BLOCK])
         for lo in range(0, head.shape[1], 4 * BLOCK)], axis=-1)


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    kinds = list(model_cfg.layer_types)
    if DELTA not in kinds or set(kinds) - {DELTA, GLOBAL}:
        raise ValueError(
            "olmo_hybrid: linear_attention and full_attention layers")
    if (model_cfg.tie_word_embeddings or model_cfg.num_experts
            or model_cfg.delta_gate != "head"):
        raise ValueError("olmo_hybrid: an untied head, dense layers, one "
                         "decay a head")
    return {
        "layer_types": kinds,
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "delta_heads": int(model_cfg.delta_heads),
        "delta_head_dim": int(model_cfg.delta_head_dim),
        "delta_value_dim": int(model_cfg.delta_v_dim),
        "delta_neg_eigval": bool(model_cfg.delta_neg_eigval),
        "norm_position": str(model_cfg.norm_position),
        "qk_norm": "whole" if model_cfg.qk_norm_whole else "head",
        "rotation_on": GLOBAL not in model_cfg.unrotated_kinds,
        "rotation_theta": float(model_cfg.rope_theta),
    }


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `router_gap` +inf (nothing routes: no position is
    skipped)."""
    ids = jnp.asarray(token_ids, jnp.int32)
    first = int(positions_out[0])
    for lost, at in (("tail_lost_behind", "zero_tail_at"),
                     ("state_lost_behind", "zero_state_at"),
                     ("state_turned_behind", "transpose_state_at")):
        if lost in hp:
            hp = dict(hp, **{at: first + hp[lost]})
    frozen = _freeze(hp)
    seen: Dict[str, int] = {}
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], ids)
        for l, kind in enumerate(hp["layer_types"]):
            nth = seen.get(kind, 0)
            seen[kind] = nth + 1
            x = _layer(x, params["layers"], params["attn"][kind],
                       jnp.int32(l), jnp.int32(nth), hp=frozen, kind=kind)
        logits = _head(x, params["final_norm"], params["lm_head"],
                       jnp.asarray(positions_out, jnp.int32),
                       eps=hp["rms_norm_eps"])
    return {"logits": np.asarray(logits),
            "router_gap": np.full((len(positions_out),), np.inf)}


def variants(hp: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The reference with one mechanism taken out or got wrong, or computed
    in a lower precision, for the check's POWER (`check_power.py`,
    `check_seeds.py`): were the served program to make this mistake, would
    the logits at the compared positions move by more than the tolerance?
    `..._at_snapshot` mistakes happen at the first row of the check's last
    prefill launch, which resumes from the snapshot the launch before it
    left (a snapshot that was not restored, or read as another layout);
    `..._at_decode` ahead of the first decode step."""
    def wrong(**mistake):
        return dict(hp, **mistake)

    return {
        "bf16_accumulate": wrong(bf16_accumulate=128),
        "bf16_accumulate_256": wrong(bf16_accumulate=256),
        "bf16_state": wrong(bf16_state=True),
        "bf16_decay": wrong(bf16_decay=True),
        "bf16_qk_norm": wrong(bf16_qk_norm=True),
        "beta_in_0_1": wrong(beta_unit=True),
        "q_scale_dropped": wrong(q_scale_dropped=True),
        "q_unnormalised": wrong(q_unnormalised=True),
        "k_unnormalised": wrong(k_unnormalised=True),
        "sigmoid_output_gate": wrong(sigmoid_gate=True),
        "decay_per_channel": wrong(decay_per_channel=True),
        "a_log_dropped": wrong(a_log_dropped=True),
        "norm_position_pre": wrong(norm_position="pre"),
        "qk_norm_per_head": wrong(qk_norm="head"),
        "rotation_on": wrong(rotation_on=True, rotation_theta=500000.0),
        "conv_tail_zeroed_at_snapshot": wrong(tail_lost_behind=1 - LAST),
        "conv_tail_zeroed_at_decode": wrong(tail_lost_behind=1),
        "state_lost_at_snapshot": wrong(state_lost_behind=1 - LAST),
        "state_lost_at_decode": wrong(state_lost_behind=1),
        "state_transposed_at_snapshot": wrong(state_turned_behind=1 - LAST),
    }
