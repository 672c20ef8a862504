"""Plain float32 reference of the Falcon-H1 decoder
(tiiuae/Falcon-H1-34B-Instruct, `model_type` "falcon_h1"), written from its
published config.json and ISSUE 54's equations.  Imports nothing of
`kafka_tpu` (a test scans for it); `tests/test_falcon_h1.py` holds
`kafka_tpu.models.forward` to it at a tiny size in float32.

The decoder, per token at position p, h the residual stream (what the config
has no key for is marked A1-A8 and listed under `assumed` in the
configuration's file, each with where it is recalled from: the `falcon_h1`
modeling code of `transformers` and `mamba_ssm`'s Mamba-2):

* embedding and head: h_0 = E[token] x `embedding_multiplier`; logits =
  RMSNorm(h_L) W_head x `lm_head_multiplier` (an untied head);
* block: u = RMSNorm_in(h);
      h <- h + (`ssm_out_multiplier` x SSM(u)
                + `attention_out_multiplier` x Attn(u x
                  `attention_in_multiplier`))
  then h <- h + MLP(RMSNorm_ff(h)), eps `rms_norm_eps` (A7: pre-norm, the
  two mixers summed ahead of the residual add);
* attention: q = u W_q (20 heads x 128), k = (u W_k) x `key_multiplier`, v =
  u W_v (4 x 128 each); rotary over the whole head, theta `rope_theta`, the
  half-split form (A6: pairs (i, i + 64)); causal softmax at scale 128^-1/2;
  W_o.  No bias, no QK-norm, no gate.  Query head n reads kv head n // 5;
* the Mamba-2 mixer, d = `mamba_d_ssm` = heads x head size P, N =
  `mamba_d_state`, G = `mamba_n_groups`:
  p = ((u x `ssm_in_multiplier`) W_in) * m, m the muP vector:
  `ssm_multipliers[0..4]` over the column ranges z (d) | x (d) | B (G N) | C
  (G N) | dt (heads) (A2); split p = [z | xBC (d + 2 G N) | dt] (A1: gate,
  conv input, dt); xBC <- SiLU(conv4(xBC) + b_conv), a depthwise causal
  convolution of `mamba_d_conv` taps a channel, zero before the sequence (A4:
  SiLU after the convolution); x in R^(heads x P), B, C in R^(G x N), head h
  reads group h // (heads / G); dt_h = softplus(dt_h + dt_bias_h) (A5: no
  clamp), a_h = exp(-exp(A_log_h) dt_h), a SCALAR a head;
      S_t = a_t S_(t-1) + dt_t x_t B_t^T,   S in R^(P x N) a head, float32
      y_t = S_t C_t + D_h x_t
  y <- RMSNorm_grouped(y * SiLU(z)): the gate BEFORE the norm
  (`mamba_norm_before_gate` false), each group's d / G channels normalised
  apart under one learned weight of d (A3: `mamba_ssm`'s
  RMSNormGated(group_size = d / G)); SSM(u) = y W_out;
* MLP(x) = ((x W_up) * SiLU((x W_gate) x `mlp_multipliers[0]`)) W_down x
  `mlp_multipliers[1]`.

The tree is the program's (`kafka_tpu/models/llama._init_parallel_params`):
"layers" holds every leaf stacked over the layers (wq [L, H, heads, 128], wo
[L, heads, 128, H], w_in, conv_w [L, taps, d + 2 G N] whose LAST tap is the
row's own, conv_b, A_log, D, dt_bias, ln_ssd, w_out, wg, wu, wd, the two
norms), beside "embed", "final_norm" and "lm_head".  A8, the seeded
initialiser: every leaf a multiplier scales is drawn at its fan-in standard
deviation divided by that multiplier (the embedding at 1 / its multiplier), so
that scores, both mixers' outputs, the MLP's and the logits are of order 1.

Float32 under `default_matmul_precision("highest")`, the recurrence written
token by token as the equation above (no chunking), no cache, no kernels, no
batching; the stacked bf16 weights are upcast one layer at a time, so it fits
at the published widths beside the served model.

Departures from the published model: weights are random (the check compares
programs, not models); nothing else.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary slice, at ALL 48 positions (1535..1582; there
# is no router, so none is skipped).  The logits' scale: rms 1.00 over the
# slice (the head is drawn at 1 / sqrt(H) / `lm_head_multiplier`, so the
# multiplier times the product is of order 1; A8).  Readings on the v5e at the
# published widths, 7 layers, Pallas, seeded weights: a first launch of 1,520
# rows in a bucket of 1,536, 16 rows from its snapshot in a bucket of 128 and
# 47 decode steps through pages and state slots in every layer, on the pair of
# seeds every run of the cell checks (my chip run 1, PR 54;
# `benchmarks/check_power.py`) and on the pairs (1, 1) and (2, 7) with every
# control through `compare_logits` (my chip run 2, `benchmarks/check_seeds.py
# --variants`): the served program (bf16 weights and activations with every
# muP multiplier applied in bf16, float32 state, `ssd_chunk` and flash prefill
# in the same layer, `ssd_step` and the Pallas decode kernel at 20 / 4 x 128)
# reads 0.0118-0.0151 over the 144, median 0.0136; this reference in the
# nearest precisions below: with a bfloat16 accumulator rounded after every
# 256 of the contraction (`bf16_accumulate_256`) 0.0237-0.0323, after every
# 128 (`bf16_accumulate`) 0.0304-0.0435; on int8 weights 0.0374-0.0529 (the
# SERVED program on int8 weights 0.0388-0.0527); with the SSD state rounded to
# bfloat16 after every token (`bf16_state`) 0.0106-0.0224, median 0.0151,
# growing over the decode steps.  0.019 is 1.26x the largest served reading of
# the 144 and 0.80x the smallest of the 256-deep accumulator's, which fails it
# at every position of every pair, as the 128-deep one and int8 weights do with
# more room; the bfloat16 state fails it by its worst positions (0.0224) and
# not by its median, so the driver ALSO reads the slot and fails by name
# (`SsdStateError`).  One mechanism out each (`variants`), smallest - median -
# largest over the 48: no SSM branch 1.20 - 1.22 - 1.25, no attention branch
# 0.28 - 0.30 - 0.32, `key_multiplier` 1 1.03 - 1.08 - 1.12, the other
# multipliers at 1 0.40 (`ssm_multipliers[0]`) to 127 (`lm_head_multiplier`),
# decay 1 1.12 - 1.17 - 1.22, no D skip 0.71 - 0.79 - 0.89, no conv bias 0.27 -
# 0.31 - 0.34, groups swapped 0.45 - 0.56 - 0.78, the norm ungrouped 0.26 -
# 0.37 - 0.53, the norm before the gate 0.54 - 0.60 - 0.69, the conv tail
# zeroed where the second launch resumes 0.027 - 0.046 - 0.155, the state lost
# there 0.28 - 0.44 - 0.60, rotation off 0.052 - 0.060 - 0.069: all fail, at
# every position.
TOLERANCE = {
    "value": 0.019,
    "why": "served bf16 0.0118-0.0151 over 48 positions and three pairs of "
           "seeds; a bf16 accumulator 0.0237-0.0323 (256 deep; 128 deep "
           "0.0304-0.0435), int8 weights 0.0374-0.0529, a bf16 SSD state "
           "0.0106-0.0224 (my chip runs 1 and 2, PR 54; PERF.md 6)",
}

# The check prefills n_prefill rows in two launches: all but the last TAIL,
# which leaves a snapshot on a page boundary, then those from the snapshot
# (`drivers/falconh1_pool.py` has the same number).
TAIL = 16


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
    a = _round_bf16(a)

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
    """x [S, N, D] at positions 0..S-1, all D values, pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(stacked, i):
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=0, keepdims=False)


def _conv_silu(z, w, bias, hp):
    """SiLU of the depthwise causal convolution of z [S, C] with taps w [L,
    C] (tap L - 1 is the row's own; zero before the sequence starts) + b."""
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
    if not hp.get("no_conv_bias"):
        c = c + bias
    return jax.nn.silu(c)


def _ssm(u, lp, hp):
    """The Mamba-2 mixer over the normed rows u [S, H]."""
    acc = hp.get("bf16_accumulate", 0)
    s = u.shape[0]
    H, P, N, G = (hp["ssd_heads"], hp["ssd_head_dim"], hp["ssd_d_state"],
                  hp["ssd_groups"])
    d, gw = H * P, G * N
    p = _mm(u * hp["ssm_in_multiplier"], lp["w_in"], acc)
    m = hp["ssm_multipliers"]
    if m:
        p = p * jnp.concatenate([
            jnp.full((n,), v, jnp.float32)
            for v, n in zip(m, (d, d, gw, gw, H))])
    z, xbc, dt = p[:, :d], p[:, d:2 * d + 2 * gw], p[:, 2 * d + 2 * gw:]
    xbc = _conv_silu(xbc, _f32(lp["conv_w"]), _f32(lp["conv_b"]), hp)
    x = xbc[:, :d].reshape(s, H, P)
    Bm = xbc[:, d:d + gw].reshape(s, G, N)
    Cm = xbc[:, d + gw:].reshape(s, G, N)
    group = np.arange(H) // (H // G)
    if hp.get("groups_swapped"):
        group = G - 1 - group
    Bh, Ch = Bm[:, group], Cm[:, group]                    # [S, H, N]
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))         # [S, H]
    a = jnp.exp(-jnp.exp(_f32(lp["A_log"])) * dt)
    if hp.get("decay_one"):
        a = jnp.ones_like(a)
    lost = hp.get("zero_state_at", -1)

    def token(S, row):
        """S [heads, P, N]: the equation, one token."""
        x_t, b_t, c_t, dt_t, a_t, t = row
        S = jnp.where(t == lost, 0.0, S)
        S = (a_t[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if hp.get("bf16_state"):
            S = _round_bf16(S)
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32),
                        (x, Bh, Ch, dt, a, jnp.arange(s)))
    if not hp.get("no_d_skip"):
        y = y + _f32(lp["D"])[:, None] * x
    y = y.reshape(s, d)
    w, eps = _f32(lp["ln_ssd"]), hp["rms_norm_eps"]
    gate = jax.nn.silu(z)

    def norm(v):
        v = v.reshape(s, 1 if hp.get("norm_ungrouped") else G, -1)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        return v.reshape(s, d) * w

    y = norm(y) * gate if hp.get("norm_before_gate") else norm(y * gate)
    return _mm(y, lp["w_out"], acc)


def _attention(u, lp, hp):
    """The softmax mixer over the normed rows u [S, H], one group of query
    heads (one kv head) at a time: [rep, S, S] scores."""
    s, acc = u.shape[0], hp.get("bf16_accumulate", 0)
    hq, d = lp["wq"].shape[-2:]
    hkv = lp["wk"].shape[-2]
    rep = hq // hkv
    q = _mm(u, lp["wq"].reshape(-1, hq * d), acc).reshape(s, hq, d)
    k = _mm(u, lp["wk"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    k = k * hp["key_multiplier"]
    v = _mm(u, lp["wv"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    if not hp.get("rotation_off"):
        q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
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
    return _mm(out, lp["wo"].reshape(hq * d, -1), acc)


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in hp.items()))


@partial(jax.jit, static_argnames=("hp",))
def _layer(x, stack, l, *, hp):
    """Layer `l` of `stack`: both mixers on one normed input, then the MLP."""
    hp = dict(hp)
    acc = hp.get("bf16_accumulate", 0)
    lp = {name: _at(w, l) for name, w in stack.items()}
    u = _rms_norm(x, lp["ln_attn"], hp["rms_norm_eps"])
    mixed = jnp.zeros_like(x)
    if not hp.get("no_ssm_branch"):
        mixed = mixed + hp["ssm_out_multiplier"] * _ssm(u, lp, hp)
    if not hp.get("no_attention_branch"):
        mixed = mixed + hp["attention_out_multiplier"] * _attention(
            u * hp["attention_in_multiplier"], lp, hp)
    x = x + mixed
    u = _rms_norm(x, lp["ln_mlp"], hp["rms_norm_eps"])
    gate_m, down_m = hp["mlp_multipliers"] or (1.0, 1.0)
    y = _mm(_mm(u, lp["wu"], acc)
            * jax.nn.silu(_mm(u, lp["wg"], acc) * gate_m), lp["wd"], acc)
    return x + y * down_m


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, positions_out, *, eps: float):
    return _rms_norm(x, final_norm, eps)[positions_out] @ _f32(head)


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


SCALARS = ("embedding_multiplier", "lm_head_multiplier",
           "attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    if not model_cfg.ssd_heads or model_cfg.tie_word_embeddings:
        raise ValueError("falcon_h1: an SSD mixer in every layer and an "
                         "untied head")
    hp = {
        "num_layers": int(model_cfg.num_layers),
        "rope_theta": float(model_cfg.rope_theta),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "ssd_heads": int(model_cfg.ssd_heads),
        "ssd_head_dim": int(model_cfg.ssd_head_dim),
        "ssd_d_state": int(model_cfg.ssd_d_state),
        "ssd_groups": int(model_cfg.ssd_groups),
        "ssm_multipliers": tuple(float(m)
                                 for m in model_cfg.ssm_multipliers),
        "mlp_multipliers": tuple(float(m)
                                 for m in model_cfg.mlp_multipliers),
    }
    for name in SCALARS:
        hp[name] = float(getattr(model_cfg, name))
    return hp


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `router_gap` +inf (there is no router: no position is
    skipped)."""
    ids = jnp.asarray(token_ids, jnp.int32)
    first = int(positions_out[0])
    hp_in = hp
    for lost, at in (("tail_lost_behind", "zero_tail_at"),
                     ("state_lost_behind", "zero_state_at")):
        if lost in hp:
            hp = {k: v for k, v in hp.items() if k != lost}
            hp[at] = first + hp_in[lost]
    frozen = _freeze({k: v for k, v in hp.items() if k != "num_layers"})
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], ids) * hp["embedding_multiplier"]
        for l in range(hp["num_layers"]):
            x = _layer(x, params["layers"], jnp.int32(l), hp=frozen)
        logits = _head(x, params["final_norm"], params["lm_head"],
                       jnp.asarray(positions_out, jnp.int32),
                       eps=hp["rms_norm_eps"]) * hp["lm_head_multiplier"]
    logits = np.asarray(logits)
    return {"logits": logits,
            "router_gap": np.full((len(positions_out),), np.inf)}


def variants(hp: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The reference with one mechanism taken out or got wrong, or computed
    in a lower precision, for the check's POWER (`check_power.py`,
    `check_seeds.py`): were the served program to make this mistake, would
    the logits at the compared positions move by more than the tolerance?
    Every multiplier that is not 1 in `hp` is set to 1 in a variant of its
    own (the five entries of `ssm_multipliers` and the two of
    `mlp_multipliers` one by one).  `conv_tail_zeroed_at_chunk` zeroes the
    convolution's tail, and `state_lost_at_chunk` every head's S, ahead of
    the second launch's first row, which resumes from the first launch's
    snapshot (a snapshot that was not restored)."""
    out = {
        "bf16_accumulate": dict(hp, bf16_accumulate=128),
        "bf16_accumulate_256": dict(hp, bf16_accumulate=256),
        "bf16_state": dict(hp, bf16_state=True),
        "no_ssm_branch": dict(hp, no_ssm_branch=True),
        "no_attention_branch": dict(hp, no_attention_branch=True),
    }
    for name in SCALARS:
        if hp[name] != 1.0:
            out[f"{name}_1"] = dict(hp, **{name: 1.0})
    for name in ("ssm_multipliers", "mlp_multipliers"):
        for i, m in enumerate(hp[name]):
            if m != 1.0:
                out[f"{name}_{i}_1"] = dict(hp, **{name: tuple(
                    1.0 if j == i else v for j, v in enumerate(hp[name]))})
    out.update({
        "decay_one": dict(hp, decay_one=True),
        "no_d_skip": dict(hp, no_d_skip=True),
        "no_conv_bias": dict(hp, no_conv_bias=True),
        "groups_swapped": dict(hp, groups_swapped=True),
        "norm_ungrouped": dict(hp, norm_ungrouped=True),
        "norm_before_gate": dict(hp, norm_before_gate=True),
        "conv_tail_zeroed_at_chunk": dict(hp, tail_lost_behind=1 - TAIL),
        "state_lost_at_chunk": dict(hp, state_lost_behind=1 - TAIL),
        "rotation_off": dict(hp, rotation_off=True),
    })
    return out
