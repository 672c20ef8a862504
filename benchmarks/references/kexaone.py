"""Plain float32 reference of the K-EXAONE decoder (LGAI-EXAONE/K-EXAONE-236B-
A23B, `model_type` "exaone_moe"), written from its published config.json and
the building blocks that config names.  Imports nothing of `kafka_tpu` (a
test scans for it); `tests/test_exaone_moe.py` and
`benchmarks/tests/test_kexaone.py` hold it to `kafka_tpu.models.forward` at a
tiny size in float32.

The decoder, per token x at position p (what the config has no key for is
marked A and listed under `assumed` in the configuration's file):

* residual form (A1): pre-norm, x += Attn(RMSNorm(x)), x += FFN(RMSNorm(x)),
  eps `rms_norm_eps`, as the `deepseek_v3`-style decoder whose keys the config
  carries;
* attention: q = x W_q (64 heads x 128), k = x W_k, v = x W_v (8 heads x 128),
  no bias; (A2, EXAONE-4's QK-norm) q and k RMS-normed per head over the 128
  values with a learned weight a layer; (A3, EXAONE-4's hybrid rule, the model
  card's "global attention uses no rotary embedding") a `sliding_attention`
  layer rotates q and k with default RoPE (theta `rope_parameters.rope_theta`,
  all 128 values, half-split pairs) and a `full_attention` layer does not
  rotate; scores / sqrt(128); a sliding layer's query at p attends keys
  p - W < j <= p (W = `sliding_window` keys, its own included), a full layer
  every j <= p; softmax, values, W_o.  Query head n reads kv head n // 8;
* FFN: the first `first_k_dense_replace` layers W_d (silu(x W_g) * x W_u) of
  width `intermediate_size`; the others s = sigmoid(x W_r) in float32 over ALL
  the published experts; the top-k by s + b are chosen (A4: b the selection
  bias `e_score_correction_bias` of `deepseek_v3`'s router; `n_group` =
  `topk_group` = 1, so no group step); a chosen expert weighs
  scale * s_e / (sum of the chosen s + 1e-20) (the bias chooses, it does not
  weigh), `routed_scaling_factor` the scale; plus one always-on shared SwiGLU.
  THE HELD SHARE: the expert leaves are experts `expert_offset` ..
  `expert_offset` + E_held of the published ones (one chip of an
  expert-parallel layer); a token's weights are chosen and renormalised over
  all the router knows, and what the absent experts would add is left out
  (the other chips' part of the combine).  The shares of all the chips, with
  the shared expert counted once, add up to the uncut layer
  (`tests/test_exaone_moe.py`);
* final RMSNorm, untied head.

Float32 under `default_matmul_precision("highest")`, no cache, no kernels, no
batching, no scan; the stacked bf16 weights are upcast one layer, one expert,
one block of the dense width and one group of heads at a time, so it fits at
the published widths beside the served model.

Departures from the published model, each deliberate: the multi-token
prediction module (`num_nextn_predict_layers` 1) is not built, as a loader
that drops `mtp.*` weights serves the model (the configuration's `assumed`
says why); weights are random (the check compares programs, not models).

ROUTER TIES.  `reference.compare_logits` skips a position whose reported
`router_gap` is under 0.05 and needs 3 compared.  Here the gap is the k-th
minus the (k+1)-th of s + b, in s's units, the smallest over the routed
layers; with 128 experts the 8th and 9th lie a few thousandths apart, and a
flip swaps an expert that weighs ~scale / k = 0.31 of the routed sum where it
is held and moves the renormalisation where it is not.  So, as
`references/kanana2.py` does, this file reports the gap RESCALED so that
compare_logits' fixed 0.05 falls on `ROUTER_FLIP_MARGIN`, and the
configuration compares enough positions (`check.n_decode`) that three or more
are settled.  What the check can tell: a dropped or wrong term at any layer
(`variants` below, one each), bf16-accumulated matmuls and int8 weights.
What it cannot: a fault that shows only at positions whose routing is within
the margin of a tie, and anything in the engine's own jitted step programs
(PERF.md section 7).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

WINDOWED, GLOBAL = "sliding_attention", "full_attention"

# compare_logits skips a position whose reported gap is under this (its own
# constant, copied: this file imports nothing but jax and numpy)
COMPARE_SKIPS_UNDER = 0.05

# A position is compared only where every routed layer's raw gap (k-th minus
# (k+1)-th of s + b) is at least this.  Measured on the v5e at the published
# widths, 6 layers, Pallas, the 48 positions 1535..1582 (my chip run 2, PR 43;
# `benchmarks/check_power.py`, deterministic: fixed tokens, PRNGKey(0)
# weights, the same digits in the cell's own boots): the served error is
# bimodal, 0.0193-0.0229 at 40 positions and 0.1047-0.2506 at 8, and every one
# of the 8 has a layer whose raw gap is under 0.0025 (the largest: position
# 1567, gap 0.00241, error 0.2123), while the twelve positions with gaps from
# 0.00297 up all read 0.0198-0.0229.  0.004 leaves 1.66x room over the
# largest flipping gap and seven compared positions (gaps 0.00448-0.00968);
# 41 of 48 are skipped.
ROUTER_FLIP_MARGIN = 0.004

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary.  Two readings, as PERF.md section 2 asks (my
# chip run 2, PR 43): the served program (bf16 weights and activations, flash
# prefill at a q block of 8 and both Pallas decode kernels through the paged
# pool) reads 0.0198-0.0222 at the seven compared positions (0.0229 the
# largest at any position whose routing held); this reference in the nearest
# precisions below: with a bfloat16 accumulator (`bf16_accumulate`, rounded
# every 256 of the contraction) 0.0355-0.2835 over all 48 positions, median
# 0.0408; on int8 weights (per-output-channel abs-max, dequantised, float32
# math) 0.0448-0.2903, median 0.0496.  0.029 is 1.27x the largest served
# reading and 0.82x the smallest bf16-accumulated one, so both fail at EVERY
# position, and a flipped expert (>= 0.1047) fails too: the margin above, not
# the tolerance, is what carries ties.  One mechanism out each (`variants`)
# reads, smallest over the 48: no QK-norm 0.548, full layers rotated 0.073,
# no window 1.334, a window of 127 / 129 keys 0.126 / 0.130, chosen without
# the bias 0.154, no scale 0.118, no shared expert 0.958, absent experts
# renormalised away 0.585: all fail everywhere.  What it cannot fail: weights
# taken from the BIASED scores (0.0123-0.118, median 0.0291: b is N(0, 0.1^2)
# beside scores near 0.9, renormalised, and one of a token's eight experts is
# held here); the float32 tests on the CPU hold that rule
# (`tests/test_exaone_moe.py`).
TOLERANCE = {
    "value": 0.029,
    "why": "served bf16 0.0198-0.0222 at the compared positions (0.0229 at "
           "any settled one), a bf16 accumulator 0.0355-0.2835 and int8 "
           "weights 0.0448-0.2903 over all 48 (my chip run 2, PR 43; "
           "PERF.md 6)",
}

# the dense width is walked in blocks of at most this many columns
DENSE_BLOCK = 4608


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _round_bf16(x):
    """x rounded to bfloat16's 8 bits and back (a convert pair would be
    dropped: XLA allows excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(a, b, bf16_acc: bool = False):
    """a [M, K] @ b [K, N] in float32.  `bf16_acc` (the `bf16_accumulate`
    variant): operands rounded to bfloat16 and the running sum rounded to
    bfloat16 after every block of 256 of the contracted axis - fewer
    roundings than an accumulator that is bfloat16 at every add, so the real
    thing is no closer to float32 than this."""
    b = _f32(b)
    if not bf16_acc:
        return a @ b
    k = a.shape[1]
    c = 256 if k % 256 == 0 else k
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


def _attention(x, lp, hp, kind: str):
    """x + Attn(RMSNorm(x)) of a layer of `kind`, one group of query heads
    (one kv head) at a time: [rep, S, S] scores, never [Hq, S, S]."""
    s, acc = x.shape[0], hp.get("bf16_accumulate", False)
    eps = hp["rms_norm_eps"]
    h = _rms_norm(x, lp["ln_attn"], eps)
    hq, d = lp["wq"].shape[-2:]
    hkv = lp["wk"].shape[-2]
    rep = hq // hkv
    q = _mm(h, lp["wq"].reshape(-1, hq * d), acc).reshape(s, hq, d)
    k = _mm(h, lp["wk"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    v = _mm(h, lp["wv"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    if not hp.get("skip_qk_norm"):
        q = _rms_norm(q, lp["ln_q"], eps)
        k = _rms_norm(k, lp["ln_k"], eps)
    if kind == WINDOWED or hp.get("rotate_full_layers"):
        q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
    qp, kp = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    allowed = kp <= qp
    if kind == WINDOWED and not hp.get("ignore_window"):
        allowed = allowed & (kp > qp - (hp["sliding_window"]
                                        + hp.get("window_delta", 0)))

    def group(g, out):
        """kv head g and the `rep` query heads that read it."""
        qg = jax.lax.dynamic_slice_in_dim(q, g * rep, rep, 1)  # [S, rep, D]
        kg = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        scores = jnp.einsum("snd,td->nst", qg, kg) / np.sqrt(d)
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        og = jnp.einsum("nst,td->snd", jax.nn.softmax(scores, axis=-1), vg)
        return jax.lax.dynamic_update_slice_in_dim(out, og, g * rep, 1)

    out = jax.lax.fori_loop(0, hkv, group, jnp.zeros_like(q))
    return x + _mm(out.reshape(s, hq * d), lp["wo"].reshape(hq * d, -1), acc)


def _swiglu(h, wg, wu, wd, acc=False):
    return _mm(jax.nn.silu(_mm(h, wg, acc)) * _mm(h, wu, acc), wd, acc)


def _dense(h, lp, hp):
    """The lead layer's SwiGLU, a block of the dense width at a time."""
    f = lp["wg"].shape[-1]
    blk = DENSE_BLOCK if f % DENSE_BLOCK == 0 else f
    acc = hp.get("bf16_accumulate", False)

    def block(i, out):
        cut = partial(jax.lax.dynamic_slice_in_dim, start_index=i * blk,
                      slice_size=blk)
        return out + _swiglu(h, cut(lp["wg"], axis=1), cut(lp["wu"], axis=1),
                             cut(lp["wd"], axis=0), acc)

    return jax.lax.fori_loop(0, f // blk, block, jnp.zeros_like(h))


def _moe(h, lp, hp):
    """The routed FFN's part that the HELD experts give, plus the shared
    expert.  Returns (out [S, H], gap [S]): the k-th minus the (k+1)-th of
    s + b over ALL the router's experts."""
    k, scale = hp["num_experts_per_tok"], hp["routed_scaling_factor"]
    acc = hp.get("bf16_accumulate", False)
    sigma = jax.nn.sigmoid(_mm(h, lp["router"], acc))  # [S, E_published]
    biased = sigma + (0.0 if hp.get("skip_selection_bias")
                      else _f32(lp["router_bias"]))
    order = jnp.argsort(-biased, axis=-1)  # stable: ties to the lower index
    srt = jnp.take_along_axis(biased, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]
    top = order[:, :k]
    held = lp["wg"].shape[0]
    lo = hp["expert_offset"]
    weigh = biased if hp.get("weigh_by_biased") else sigma
    chosen = jnp.take_along_axis(weigh, top, axis=-1)
    if hp.get("renormalise_over_held"):
        # the mistake: absent experts' weights renormalised away
        mine = (top >= lo) & (top < lo + held)
        total = jnp.sum(jnp.where(mine, chosen, 0.0), -1, keepdims=True)
    else:
        total = jnp.sum(chosen, axis=-1, keepdims=True)
    w_top = (1.0 if hp.get("skip_scale") else scale) * chosen / (total + 1e-20)

    def add_expert(i, out):
        w_e = jnp.sum(jnp.where(top == lo + i, w_top, 0.0), axis=-1)  # [S]
        y = _swiglu(h, _at(lp["wg"], i), _at(lp["wu"], i), _at(lp["wd"], i),
                    acc)
        return out + w_e[:, None] * y

    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    if not hp.get("skip_shared"):
        out = out + _swiglu(h, lp["ws_g"], lp["ws_u"], lp["ws_d"], acc)
    return out, gap


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in hp.items()
                        if not isinstance(v, (list, dict))))


@partial(jax.jit, static_argnames=("hp", "kind", "routed"))
def _layer(x, stack, l, *, hp, kind: str, routed: bool):
    hp = dict(hp)
    lp = {name: _at(w, l) for name, w in stack.items()}
    x = _attention(x, lp, hp, kind)
    h = _rms_norm(x, lp["ln_mlp"], hp["rms_norm_eps"])
    if routed:
        y, gap = _moe(h, lp, hp)
    else:
        y, gap = _dense(h, lp, hp), jnp.full((x.shape[0],), jnp.inf)
    return x + y, gap


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, positions_out, *, eps: float):
    return _rms_norm(x, final_norm, eps)[positions_out] @ _f32(head)


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    if model_cfg.tie_word_embeddings or not model_cfg.num_experts:
        raise ValueError("K-EXAONE: untied head, routed layers after the lead")
    if not model_cfg.qk_norm or tuple(model_cfg.unrotated_kinds) != (GLOBAL,):
        raise ValueError("K-EXAONE: QK-norm, full layers without rotation")
    return {
        "layer_types": list(model_cfg.layer_types),
        "sliding_window": int(model_cfg.sliding_window),
        "rope_theta": float(model_cfg.rope_theta),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "first_k_dense": int(model_cfg.first_k_dense),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
        "routed_scaling_factor": float(model_cfg.routed_scaling_factor),
        "expert_offset": int(model_cfg.expert_offset),
    }


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int]) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `router_gap` as the module docstring says (rescaled),
    and `raw_router_gap`, the smallest raw gap over the routed layers."""
    kinds = hp["layer_types"]
    n_dense = hp["first_k_dense"]
    frozen = _freeze(hp)
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(token_ids, jnp.int32)
        x = _embed(params["embed"], ids)
        min_gap = jnp.full((ids.shape[0],), jnp.inf)
        for l, kind in enumerate(kinds):
            routed = l >= n_dense
            x, gap = _layer(
                x, params["layers" if routed else "dense_layers"],
                jnp.int32(l - n_dense if routed else l), hp=frozen,
                kind=kind, routed=routed)
            min_gap = jnp.minimum(min_gap, gap)
        logits = _head(x, params["final_norm"], params["lm_head"],
                       jnp.asarray(positions_out, jnp.int32),
                       eps=hp["rms_norm_eps"])
        raw = np.asarray(min_gap)[np.asarray(positions_out)]
        return {"logits": np.asarray(logits),
                "router_gap": raw * (COMPARE_SKIPS_UNDER / ROUTER_FLIP_MARGIN),
                "raw_router_gap": raw}


def variants(hp: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The reference with one mechanism taken out or got wrong, or computed
    in a lower precision, for the check's POWER (`check_power.py`): were the
    served program to make this mistake, would the logits at the compared
    positions move by more than the tolerance?"""
    return {
        "bf16_accumulate": dict(hp, bf16_accumulate=True),
        "no_qk_norm": dict(hp, skip_qk_norm=True),
        "full_layers_rotate": dict(hp, rotate_full_layers=True),
        "all_global": dict(hp, ignore_window=True),
        "window_127": dict(hp, window_delta=-1),
        "window_129": dict(hp, window_delta=1),
        "chosen_without_bias": dict(hp, skip_selection_bias=True),
        "weighed_by_biased_scores": dict(hp, weigh_by_biased=True),
        "no_routed_scale": dict(hp, skip_scale=True),
        "no_shared_expert": dict(hp, skip_shared=True),
        "absent_experts_renormalised_away": dict(
            hp, renormalise_over_held=True),
    }
