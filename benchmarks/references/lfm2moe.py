"""Plain float32 reference of the LFM2-8B-A1B decoder (LiquidAI/LFM2-8B-A1B,
`model_type` "lfm2_moe"), written from its published config.json and ISSUE
47's equations, which recall the `lfm2_moe` modeling code of the
`transformers` library (not fetched: no network).  Imports nothing of
`kafka_tpu` (a test scans for it); `tests/test_lfm2_moe.py` holds
`kafka_tpu.models.forward` to it at a tiny size in float32, on the PUBLISHED
24-entry `layer_types`.

The decoder, per token x at position p (what the config has no key for is
marked A1-A6 and listed under `assumed` in the configuration's file):

* residual form: x += Op_l(RMSNorm(x)), x += FFN_l(RMSNorm(x)), eps
  `norm_eps`; Op_l is the short convolution where `layer_types[l]` is "conv"
  and attention where it is "full_attention";
* short convolution (A1: the chunk order B | C | u, no nonlinearity,
  depthwise, causal): [B | C | u] = x W_in (2048 x 6144, `conv_bias` false);
  z_t = B_t * u_t; c_t = sum_{j=0..L-1} w_j * z_{t-L+1+j} (`conv_L_cache` L =
  3 taps, a weight a channel, z zero before the sequence starts);
  Op = (C_t * c_t) W_out.  What a thread carries from token to token is the
  L - 1 rows z_{t-1}, z_{t-2} a conv layer; nothing accumulates;
* attention: q = x W_q (32 heads x 64), k = x W_k, v = x W_v (8 x 64), no
  bias; (A2) head_dim = hidden_size / heads = 64; (A3) q and k RMS-normed per
  head over the 64 values with a learned weight a layer, ahead of the
  rotation (the family's `q_layernorm` / `k_layernorm`); default RoPE, theta
  `rope_theta`, half-split pairs over all 64 values; scores / sqrt(64);
  causal; softmax, values, W_o.  Query head n reads kv head n // 4;
* FFN: the first `num_dense_layers` layers W_d (silu(x W_g) * x W_u) of width
  `intermediate_size`; the others s = sigmoid(x W_r) in float32 over the 32
  experts; the top-4 by s + b are chosen (`use_expert_bias`; A4: the bias
  chooses, it does not weigh); a chosen expert weighs s_e / (sum of the
  chosen s + 1e-6) (`norm_topk_prob`; A5 the 1e-6) times
  `routed_scaling_factor`; no shared expert;
* final RMSNorm, (A6) a head TIED to the embedding.

The tree is the program's (`kafka_tpu/models/llama._init_lead_tree_params`,
the conv layout), read here leaf by leaf: "dense_layers" and "layers" hold
the norms and the feed-forward leaves stacked over the lead and the routed
layers, `attn["conv"]` and `attn["full_attention"]` each kind's mixers
stacked in layer order.

Float32 under `default_matmul_precision("highest")`, no cache, no kernels, no
batching, no scan; the stacked bf16 weights are upcast one layer, one expert,
one block of the dense width and one group of heads at a time, so it fits at
the published widths beside the served model.

Departures from the published model: weights are random (the check compares
programs, not models); nothing else.

TEACHER-FORCED PICKS.  The served bfloat16 program and this reference take
another fourth expert at ~6% of the (row, routed layer) pairs, and a conv
layer hands a swapped row's difference on to the rows behind it, so with
free picks every compared position sits behind several swaps, its own router
gap says nothing about its logits, and no precision can be told from another
(my chip runs B and I, PR 47: the served program 0.05-0.40 at EVERY position).
So the check holds the picks still where it compares: from RUN_IN rows ahead
of the first compared position on, the driver runs every row as a launch of
its own and hands it, through the selection bias (which chooses and does not
weigh), the experts THIS reference takes there (`picks` in what
`reference_logits` returns; `drivers/lfm2_pool.py`).  The scores, the
weights and all the arithmetic stay the served program's; the first launch's
rows keep their own picks.  A variant below is `forced` the same way: it
reads its own mistake, not the experts the mistake swapped.  No position is
skipped (`router_gap` is +inf everywhere; `raw_router_gap`, the smallest k-th
minus (k+1)-th of s + b over the routed layers, is reported for
`check_power.py`).  What the check can tell: a dropped or wrong term at any
layer (`variants`, one each), which experts a row takes where the program
does not read the bias, weights taken from the biased scores, a conv tail
lost where the last prefill launch resumes (a snapshot not restored) or
where decode takes over, a bfloat16 accumulator and int8 weights.  What it
cannot: a wrong choice between two experts whose biased scores lie within
bfloat16's noise of each other at a compared row (that is what forcing
removes), a missing QK-norm (0.030-0.040 at these widths, inside the served
band: near-uniform attention over random keys does not feel the scale; the
float32 tests on the CPU hold it), a tail rounded to bfloat16 on its way into
its slot (`bf16_tail` moves the logits by 0.009-0.012: the driver reads the
slot instead, `tail_f32_share`), and anything in the engine's own jitted step
programs (PERF.md section 7).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

CONV, GLOBAL = "conv", "full_attention"

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary, at ALL 48 positions (module docstring: none is
# skipped).  Readings on the v5e at the published widths, 14 layers, Pallas,
# independent seeded experts, picks forced: a first launch of 1,504 rows, 17
# launches of one row and 47 decode steps through pages and state slots, the
# 48 positions 1520..1567, on THREE pairs of (weight, token) seeds, (0, 0)
# being the pair every run of the cell checks (my chip run I, PR 47;
# `benchmarks/check_seeds.py`, each line through `compare_logits`): the served
# program (bf16 weights and activations, flash prefill and the Pallas decode
# kernel at 32 / 8 x 64, dense and token dispatch) reads 0.0304-0.0399 |
# 0.0301-0.0452 | 0.0325-0.0427, medians 0.036-0.038; this reference in the
# nearest precisions below: with a bfloat16 accumulator rounded after every
# 128 of the contraction, the depth of one pass of the chip's 128 x 128 MXU
# (`bf16_accumulate`) 0.0677-0.0864 | 0.0709-0.0936 | 0.0674-0.0928, and
# after every 256, as the other references round (`bf16_accumulate_256`)
# 0.0507-0.0679 | 0.0541-0.0741 | 0.0540-0.0715; on int8 weights (every
# stacked matrix of the tree per output channel, the reference's picks kept)
# 0.1135-0.1627 | 0.1234-0.1658 | 0.1251-0.1787 as this reference computes
# them and 0.1181-0.1671 | 0.1301-0.1725 | 0.1296-0.1813 through the SERVED
# program.  0.055 is 1.22x the largest served reading of the 144 and 0.82x
# the smallest of the 128-deep accumulator's, which fails it at every
# position of every pair, as int8 weights do twice over; the 256-deep
# accumulator's bands stand clear of the served ones on every pair (0.0507
# over 0.0452) and it fails by most of its positions (medians 0.059-0.062).
# Not the 3x a control should read: 14 bfloat16 layers amplify the served
# rounding and the control's alike (K-EXAONE's six read 0.02 | 0.036).  With
# FREE picks the same launches read 0.0475-0.3967 (run I; run B's 0.0507-
# 0.3951 on the two-launch check).  One mechanism out each (`variants`, pair
# (0, 0)), smallest - median - largest over the 48: a tail lost where the last
# prefill launch resumes 0.0042 - 0.0055 - 1.3732 and at decode's take-over
# 0.0000 - 0.0051 - 1.3555 (both fail by the row right behind the loss and
# fade within a few), no conv tail at all 1.31-1.41, the gates swapped
# 1.35-1.43, no output gate 1.38-1.45, no rotation 0.0801-0.1037, chosen
# without the bias (not forced: the bias is what forces) 0.55-0.76, weighed
# by the biased scores 0.0698-0.0972, weights not renormalised 0.95-1.06: all
# fail, each but the lost tails at every position.  What it cannot fail:
# module docstring (no QK-norm 0.0301-0.0395, a bfloat16 tail 0.0093-0.0122).
TOLERANCE = {
    "value": 0.055,
    "why": "served bf16 0.0301-0.0452 over 48 positions x 3 seed pairs, a "
           "bf16 accumulator 0.0674-0.0936 (128 deep; 256 deep 0.0507-"
           "0.0741) and int8 weights 0.1135-0.1813 there (my chip run I, "
           "PR 47; PERF.md 6)",
}

# The check prefills n_prefill = 1521 rows: a first launch of all but RUN_IN
# + TAIL of them, then those a row a launch (`drivers/lfm2_pool.py` has the
# same numbers), then decodes.  A conv tail reaches L - 1 = 2 rows back a
# layer and what it carries fades within a few rows (lost 15 rows ahead of
# the first compared position it moved the logits by 0.004-0.034, under the
# served error: my chip run C, PR 47), and so does what a swapped expert
# leaves in it: RUN_IN = one page of rows on forced picks stands between the
# first launch's free rows and the first compared position, which is the
# LAST prefill launch's one row (TAIL), resumed on a page boundary from a
# snapshot, with the prefill-to-decode boundary right behind it.
TAIL = 1
RUN_IN = 16
N_PREFILL = 1521

# the dense width is walked in blocks of at most this many columns
DENSE_BLOCK = 3584


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _round_bf16(x):
    """x rounded to bfloat16's 8 bits and back (a convert pair would be
    dropped: XLA allows excess precision)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(a, b, bf16_acc: int = 0):
    """a [M, K] @ b [K, N] in float32.  `bf16_acc` (the `bf16_accumulate`
    variants): operands rounded to bfloat16 and the running sum rounded to
    bfloat16 after every block of that many of the contracted axis - fewer
    roundings than an accumulator that is bfloat16 at every add, so the real
    thing is no closer to float32 than this."""
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


def _short_conv(h, mp, hp):
    """Op of a conv layer over the normed rows h [S, H]."""
    acc = hp.get("bf16_accumulate", 0)
    s, width = h.shape
    bcu = _mm(h, mp["w_in"], acc)
    order = (1, 0, 2) if hp.get("swap_gates") else (0, 1, 2)
    gate_b, gate_c, u = (bcu[:, i * width:(i + 1) * width] for i in order)
    z = gate_b * u
    if hp.get("bf16_tail"):
        z = _round_bf16(z)
    w = _f32(mp["conv_w"])  # [L, H]; tap L - 1 is the row's own
    taps = w.shape[0]
    rows = jnp.arange(s)[:, None]
    c = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j
        zj = jnp.pad(z, ((back, 0), (0, 0)))[:s]  # z_{t - back}, 0 before 0
        for cut in hp.get("zero_tail_at", ()):
            # the mistake: the rows before `cut` are lost to the rows from it
            zj = jnp.where((rows >= cut) & (rows - back < cut), 0.0, zj)
        if back and hp.get("no_tail"):
            continue
        c = c + w[j] * zj
    y = c if hp.get("no_out_gate") else gate_c * c
    return _mm(y, mp["w_out"], acc)


def _attention(h, mp, hp):
    """Attn of a full-attention layer over the normed rows h [S, H], one
    group of query heads (one kv head) at a time: [rep, S, S] scores."""
    s, acc = h.shape[0], hp.get("bf16_accumulate", 0)
    eps = hp["rms_norm_eps"]
    hq, d = mp["wq"].shape[-2:]
    hkv = mp["wk"].shape[-2]
    rep = hq // hkv
    q = _mm(h, mp["wq"].reshape(-1, hq * d), acc).reshape(s, hq, d)
    k = _mm(h, mp["wk"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    v = _mm(h, mp["wv"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    if not hp.get("skip_qk_norm"):
        q = _rms_norm(q, mp["ln_q"], eps)
        k = _rms_norm(k, mp["ln_k"], eps)
    if not hp.get("skip_rope"):
        q, k = _rope(q, hp["rope_theta"]), _rope(k, hp["rope_theta"])
    allowed = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

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
    return _mm(out.reshape(s, hq * d), mp["wo"].reshape(hq * d, -1), acc)


def _swiglu(h, wg, wu, wd, acc=0):
    return _mm(jax.nn.silu(_mm(h, wg, acc)) * _mm(h, wu, acc), wd, acc)


def _dense(h, lp, hp):
    """A lead layer's SwiGLU, a block of the dense width at a time."""
    f = lp["wg"].shape[-1]
    blk = DENSE_BLOCK if f % DENSE_BLOCK == 0 else f
    acc = hp.get("bf16_accumulate", 0)

    def block(i, out):
        cut = partial(jax.lax.dynamic_slice_in_dim, start_index=i * blk,
                      slice_size=blk)
        return out + _swiglu(h, cut(lp["wg"], axis=1), cut(lp["wu"], axis=1),
                             cut(lp["wd"], axis=0), acc)

    return jax.lax.fori_loop(0, f // blk, block, jnp.zeros_like(h))


def _moe(h, lp, hp, forced, forced_from):
    """The routed FFN.  Returns (out [S, H], gap [S], top [S, k]): gap is the
    k-th minus the (k+1)-th of s + b, top the experts taken.  Rows from
    `forced_from` on take the experts `forced` [S, k] names in place of their
    own choice (module docstring, TEACHER-FORCED PICKS) and weigh them by
    their own s."""
    k, scale = hp["num_experts_per_tok"], hp["routed_scaling_factor"]
    acc = hp.get("bf16_accumulate", 0)
    sigma = jax.nn.sigmoid(_mm(h, lp["router"], acc))  # [S, E]
    biased = sigma + (0.0 if hp.get("skip_selection_bias")
                      else _f32(lp["router_bias"]))
    order = jnp.argsort(-biased, axis=-1)  # stable: ties to the lower index
    srt = jnp.take_along_axis(biased, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]
    rows = jnp.arange(h.shape[0])[:, None]
    top = jnp.where(rows >= forced_from, forced, order[:, :k])
    weigh = biased if hp.get("weigh_by_biased") else sigma
    chosen = jnp.take_along_axis(weigh, top, axis=-1)
    w_top = chosen if hp.get("skip_renormalise") else (
        chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6))
    w_top = scale * w_top

    def add_expert(i, out):
        w_e = jnp.sum(jnp.where(top == i, w_top, 0.0), axis=-1)  # [S]
        y = _swiglu(h, _at(lp["wg"], i), _at(lp["wu"], i), _at(lp["wd"], i),
                    acc)
        return out + w_e[:, None] * y

    out = jax.lax.fori_loop(0, lp["wg"].shape[0], add_expert,
                            jnp.zeros_like(h))
    return out, gap, top


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in hp.items()
                        if not isinstance(v, (list, dict))))


@partial(jax.jit, static_argnames=("hp", "kind", "routed"))
def _layer(x, stack, mixers, l, nth, forced, forced_from, *, hp, kind: str,
           routed: bool):
    """Layer `l` of `stack` (the lead's or the routed layers' norms and
    feed-forward leaves), its mixer the `nth` of its kind's.  Returns the
    stream, the router's gap a row and the experts a row took (`forced`
    itself for a lead layer)."""
    hp = dict(hp)
    lp = {name: _at(w, l) for name, w in stack.items()}
    mp = {name: _at(w, nth) for name, w in mixers.items()}
    h = _rms_norm(x, lp["ln_attn"], hp["rms_norm_eps"])
    x = x + (_short_conv(h, mp, hp) if kind == CONV
             else _attention(h, mp, hp))
    h = _rms_norm(x, lp["ln_mlp"], hp["rms_norm_eps"])
    if routed:
        y, gap, top = _moe(h, lp, hp, forced, forced_from)
    else:
        y, gap, top = (_dense(h, lp, hp), jnp.full((x.shape[0],), jnp.inf),
                       forced)
    return x + y, gap, top


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, table, positions_out, *, eps: float):
    return _rms_norm(x, final_norm, eps)[positions_out] @ _f32(table).T


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


# what `hyper` gives: a variant's further keys name its mistake
PLAIN_KEYS = ("layer_types", "rope_theta", "rms_norm_eps", "first_k_dense",
              "num_experts_per_tok", "routed_scaling_factor")


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    kinds = list(model_cfg.layer_types)
    if CONV not in kinds or set(kinds) - {CONV, GLOBAL}:
        raise ValueError("lfm2_moe: conv and full_attention layers")
    if not model_cfg.tie_word_embeddings or not model_cfg.num_experts:
        raise ValueError("lfm2_moe: a tied head, routed layers after the lead")
    return {
        "layer_types": kinds,
        "rope_theta": float(model_cfg.rope_theta),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "first_k_dense": int(model_cfg.first_k_dense),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
        "routed_scaling_factor": float(model_cfg.routed_scaling_factor),
    }


def _pass(params, hp, ids, positions_out, picks, forced_from: int):
    """One causal forward over `ids` [S]: the logits at `positions_out`, each
    row's smallest router gap over the routed layers, and the experts every
    routed layer's rows took [routed layers, S, k].  Rows from `forced_from`
    on take `picks`' experts."""
    kinds = hp["layer_types"]
    n_dense = hp["first_k_dense"]
    frozen = _freeze({k: v for k, v in hp.items() if k != "forced"})
    seen: Dict[str, int] = {}
    took = []
    x = _embed(params["embed"], ids)
    min_gap = jnp.full((ids.shape[0],), jnp.inf)
    for l, kind in enumerate(kinds):
        routed = l >= n_dense
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        x, gap, top = _layer(
            x, params["layers" if routed else "dense_layers"],
            params["attn"][kind], jnp.int32(l - n_dense if routed else l),
            jnp.int32(nth), picks[max(l - n_dense, 0)],
            jnp.int32(forced_from), hp=frozen, kind=kind, routed=routed)
        min_gap = jnp.minimum(min_gap, gap)
        if routed:
            took.append(top)
    logits = _head(x, params["final_norm"], params["embed"],
                   jnp.asarray(positions_out, jnp.int32),
                   eps=hp["rms_norm_eps"])
    return np.asarray(logits), np.asarray(min_gap), jnp.stack(took)


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int], picks=None) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `picks` [routed layers, S, k], the experts every row
    took; `router_gap` +inf (module docstring: no position is skipped) and
    `raw_router_gap`, the smallest raw gap over the routed layers.

    A variant that `variants` marks `forced` is handed the picks of the plain
    pass over the same weights (or `picks`, where the caller has another
    tree's: int8 weights under the original tree's picks) from the first
    compared position on, as the driver hands them to the served program."""
    ids = jnp.asarray(token_ids, jnp.int32)
    if "tail_lost_behind" in hp:
        hp = dict(hp, zero_tail_at=(
            int(positions_out[0]) + hp["tail_lost_behind"],))
    plain = {k: v for k, v in hp.items() if k in PLAIN_KEYS}
    none = jnp.zeros((len(plain["layer_types"]) - plain["first_k_dense"],
                      ids.shape[0], plain["num_experts_per_tok"]), jnp.int32)
    with jax.default_matmul_precision("highest"):
        forced_from = int(ids.shape[0])  # nothing is forced
        if hp.get("forced") or picks is not None:
            forced_from = max(int(positions_out[0]) - RUN_IN, 0)
            if picks is None:
                picks = _pass(params, plain, ids, positions_out, none,
                              int(ids.shape[0]))[2]
        logits, gap, took = _pass(
            params, hp, ids, positions_out,
            none if picks is None else jnp.asarray(picks, jnp.int32),
            forced_from)
    raw = gap[np.asarray(positions_out)]
    return {"logits": logits, "picks": np.asarray(took),
            "router_gap": np.full(raw.shape, np.inf), "raw_router_gap": raw}


def variants(hp: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The reference with one mechanism taken out or got wrong, or computed
    in a lower precision, for the check's POWER (`check_power.py`,
    `check_seeds.py`): were the served program to make this mistake, would
    the logits at the compared positions move by more than the tolerance?
    Each is `forced` as the served program is: it takes the plain pass's
    experts from the first compared position on, so what it reads is its
    mistake and not the experts the mistake swapped.  But
    `chosen_without_bias`: the picks are forced THROUGH the bias, and a
    program that does not read it is not reached.
    `tail_lost_between_launches` zeroes every conv layer's tail ahead of the
    first compared row, the one the last prefill launch holds (a snapshot
    that was not restored); `tail_lost_at_decode` ahead of the row behind it,
    where decode takes over from the lane's slot."""
    def forced(**mistake):
        return dict(hp, forced=True, **mistake)

    return {
        "bf16_accumulate": forced(bf16_accumulate=128),
        "bf16_accumulate_256": forced(bf16_accumulate=256),
        "tail_lost_between_launches": forced(tail_lost_behind=0),
        "tail_lost_at_decode": forced(tail_lost_behind=TAIL),
        "bf16_tail": forced(bf16_tail=True),
        "no_conv_tail": forced(no_tail=True),
        "conv_gates_swapped": forced(swap_gates=True),
        "no_conv_out_gate": forced(no_out_gate=True),
        "no_qk_norm": forced(skip_qk_norm=True),
        "no_rotation": forced(skip_rope=True),
        "chosen_without_bias": dict(hp, skip_selection_bias=True),
        "weighed_by_biased_scores": forced(weigh_by_biased=True),
        "weights_not_renormalised": forced(skip_renormalise=True),
    }
