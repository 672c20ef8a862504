"""Plain float32 reference of the Xing4.0-29B-A4B decoder (XingChen-AGI/
Xing4.0-29B-A4B, `model_type` "xing4_0"), written from its published
config.json and the public descriptions of what that config names.  Imports
nothing of `kafka_tpu` (a test scans for it); `benchmarks/tests/test_xing4.py`
and `tests/test_xing4.py` hold it to `kafka_tpu.models.forward` at a tiny size
in float32.

n = `hc_mult`, C = `hidden_size`.  Per token at position p:

* STREAM.  The state is X in R^(n x C).  X_0 = the embedding row in every one
  of the n rows.  After the last layer h = the sum of X's rows; logits =
  RMSNorm(h) W_head (untied).
* EVERY SUBLAYER F (attention, feed-forward: two a layer) has mappings of its
  own.  x~ = RMSNorm over all nC values of vec(X) with a weight of nC.
  H~_pre = a_pre (x~ Phi_pre) + b_pre; H~_post = a_post (x~ Phi_post) + b_post;
  H~_res = a_res mat_(n x n)(x~ Phi_res) + B_res (row-major).  H_pre =
  sigmoid(H~_pre); H_post = 2 sigmoid(H~_post); H_res = Sinkhorn(H~_res): M =
  exp(clip(H~_res, `mhc_h_res_clamp_min`, `mhc_h_res_clamp_max`)), then
  `hc_sinkhorn_iters` rounds of (each row by its sum + `hc_eps`; each column
  by its sum + `hc_eps`).  u = H_pre X in R^C; y = F(RMSNorm_C(u)) with the
  layer's own norm weight; X <- H_res X + H_post^T y (row i gets sum_j
  H_res[i, j] X_j + H_post[i] y).
* ATTENTION (latent, a query low-rank): c_q = RMSNorm(x W_qa); q = c_q W_qb,
  per head [q_nope | q_rope]; [c | k_r] = x W_kva; c~ = RMSNorm(c); per head
  [k_nope | v] = c~ W_kvb,h.  Rotary on q_rope and k_r (ONE vector a token),
  published interleaved pairs de-interleaved then rotated half-split
  (`rope_interleave` absent: the `deepseek_v3` default, true), with YaRN
  inverse frequencies: pair i of d/2 has the plain theta^(-2i/d) where it
  turns more than `beta_fast` times over the original context, that over
  `factor` where fewer than `beta_slow`, a linear ramp over the pair index in
  between (HF `_compute_yarn_parameters`, ramp ends rounded outwards); cos and
  sin times get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
  (1 here).  Scores (q_nope . k_nope + q_rope . k_r) * (nope + rope)^-1/2 *
  m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1 (HF `DeepseekV3Attention`),
  causal softmax, values, W_o.  The EXPANDED form, keys and values per head,
  no cache; the served program's paged decode runs the absorbed form.
* FEED-FORWARD.  The first `first_k_dense` layers a SwiGLU; the others sigma =
  sigmoid(x W_r), the k with the largest sigma + b chosen (`noaux_tc`, one
  group), each weighs scale * sigma_e / (sum of the chosen sigma + 1e-20),
  plus one always-on shared SwiGLU.

ASSUMED (the config has no key for these; the configuration's file lists them
with where each is recalled from): the widening by repetition and the collapse
by sum ("Hyper-Connections", arXiv:2409.19606); the stream norm's learned
weight of nC, the order of a Sinkhorn round (rows, then columns) and `hc_eps`
in every divisor, mappings in float32 ("mHC: Manifold-Constrained
Hyper-Connections", arXiv:2512.24880); attention, rotation and routing as the
`deepseek_v3` modeling code of `transformers`.  The multi-token-prediction
module (`num_nextn_predict_layers` 1) is no part of the pass that gives a
token's logits and is not here.

Float32 under `default_matmul_precision("highest")`, token-parallel, no
cache, no kernels, no scan; attention runs one head at a time (scores [S, S]:
87 MB at the check's 4,655 tokens) and the experts one at a time, upcast from
the stacked bf16 weights, so it fits beside the served model.

TEACHER-FORCED PICKS (`references/lfm2moe.py`'s rule, and why).  With 64
experts top-4 the 4th and 5th of sigma + b lie a few thousandths apart at
almost every row, the served bfloat16 hidden state is 6% off after sixteen
mixes under a softmax scale of twice the usual, and a flipped expert moves
the logits by 0.2-0.8: on the v5e at the published widths, free picks, 30 of
the 48 compared positions sit behind a flip, one of them with a raw gap of
0.0148, and only TWO have a wider one (my chip run 1, PR 56), so no margin
leaves three positions to compare.  So the check holds the picks still where
it compares: from RUN_IN rows ahead of the first compared position on, the
driver runs every row as a launch of its own and hands it, through the
selection bias (which chooses and does not weigh), the experts THIS reference
takes there (`picks` in what `reference_logits` returns;
`drivers/xing4_pool.py`).  The scores, the weights and all the arithmetic stay
the served program's; the rows ahead keep their own picks (their swaps reach
the compared rows through attention, one key of 4,600).  A variant below is
`forced` the same way: it reads its own mistake, not the experts the mistake
swapped.  No position is skipped (`router_gap` is +inf everywhere;
`raw_router_gap`, the smallest k-th minus (k+1)-th of sigma + b over the
routed layers, is reported for `check_power.py`).  What the check cannot
tell: a wrong choice between two experts whose biased scores lie within
bfloat16's noise of each other at a compared row (that is what forcing
removes; the float32 tests on the CPU hold the choice), and anything in the
engine's own jitted step programs (PERF.md section 7).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# Rows on forced picks ahead of the first compared position: with it, one
# page of 16 (`drivers/xing4_pool.py` has the same number).
RUN_IN = 15

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary, at ALL 48 positions (module docstring: none is
# skipped).  Readings on the v5e at the published widths, 8 layers, Pallas,
# picks forced: 4,592 rows in nine launches of 512, 16 launches of one row and
# 47 decode steps through the latent pool, the 48 positions 4607..4654, on
# THREE pairs of (weight, token) seeds, (0, 0) being the pair every run of the
# cell checks (my chip run 2, PR 56; `benchmarks/check_seeds.py --variants`,
# each line through `compare_logits`; deterministic: the cell's own check
# printed (0, 0)'s digits again in each of its runs): the served program (bf16
# weights, activations and stream; float32 mappings; the fold kernel in
# prefill and the absorbed Pallas decode kernel behind the query low-rank;
# token dispatch) reads 0.0562-0.1150 | 0.0578-0.1056 | 0.0523-0.1064,
# medians 0.074-0.081; in the nearest precision below, int8 weights (every
# stacked matrix of the tree per output channel, the reference's picks kept),
# this reference reads 0.1353-0.2121 | 0.1410-0.2405 | 0.1398-0.2737 and the
# SERVED program 0.1403-0.2232 | 0.1441-0.2537 | 0.1414-0.2791.  0.125 is
# 1.09x the largest served reading of the 144 and 0.92x the smallest int8
# reading of the 288, so int8 weights fail at every position of every pair.
# Not the 3x a control should read: three times Kanana-2's served error
# (0.021-0.025 at 6 layers), because sixteen mixes carry the stream's
# bfloat16 rounding through sigmoids and a softmax scale of twice the usual;
# the control moves with it (Kanana-2's int8 0.045-0.054).  With FREE picks
# the same launches read 0.0561-0.7690, 30 of 48 positions behind a flipped
# expert.  One mechanism out each (`variants`, pair (0, 0)), smallest -
# median - largest over the 48: static mappings 0.303 - 0.459 - 0.765, one
# row-softmax for Sinkhorn 0.180 - 0.314 - 0.809, ONE Sinkhorn round 0.082 -
# 0.139 - 0.244 (fails by more than half its positions), H_post not doubled 0.265-0.598,
# H_pre not squashed 1.31-1.43, the embedding in row 0 alone 0.252-0.829, the
# attention site's mappings at the feed-forward site 0.485-1.098, m^2 out of
# the softmax scale 1.13-1.36, YaRN's blend off 1.20-1.35, the query latent
# not normed 0.696-1.132, the selection bias ignored (not forced: the bias is
# what forces) 0.586-1.190, `routed_scaling_factor` 1 0.452-0.667: all fail,
# all but one at every position.  The collapse reading row 0 alone 0.029 -
# 0.107 - 0.312 fails by fewer than half of them.  What it CANNOT fail:
# bfloat16 mappings 0.0166 - 0.0233 - 0.0487, under the served error at every
# position (one more rounding among the many the served program makes; ISSUE
# 56 asked that it fail: it cannot, by any tolerance above the served
# reading; the float32 tests on the CPU hold the mappings' precision), and the
# clamp, which nothing reaches as seeded (0.0; the tiny test shifts B_res by
# 40 for it).
TOLERANCE = {
    "value": 0.125,
    "why": "served bf16 0.0523-0.1150 over all 48 positions of three seed "
           "pairs, int8 weights 0.1353-0.2791 there (my chip run 2, PR 56; "
           "PERF.md 6)",
}


def _f32(x) -> jnp.ndarray:
    return jnp.asarray(x).astype(jnp.float32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(w)


def yarn_inv_freq(hp) -> np.ndarray:
    """Inverse frequencies of the d/2 rotary pairs (float64 here, float32
    where used): YaRN's blend, or plain theta where `yarn_off`."""
    d, base = hp["qk_rope_head_dim"], hp["rope_theta"]
    plain = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if hp.get("yarn_off") or not hp["rope_factor"]:
        return plain
    orig = hp["rope_original_max_position"]

    def correction_dim(rotations):
        return (d * math.log(orig / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(base)))

    low = max(math.floor(correction_dim(hp["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(hp["rope_beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return plain / hp["rope_factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(hp) -> float:
    scale = (hp["qk_nope_head_dim"] + hp["qk_rope_head_dim"]) ** -0.5
    if hp["rope_factor"] and hp["rope_mscale_all_dim"] \
            and not hp.get("skip_mscale"):
        m = 0.1 * hp["rope_mscale_all_dim"] * math.log(hp["rope_factor"]) + 1
        scale *= m * m
    return scale


def _rope(x, hp):
    """x [S, ..., d] at positions 0..S-1: de-interleave the published pairs,
    then rotate with pairs (i, i + d/2)."""
    d = x.shape[-1]
    if hp["rope_interleave"]:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(hp), jnp.float32)[None, :])
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(stacked, i, axis: int = 0):
    return jax.lax.dynamic_index_in_dim(stacked, i, axis=axis, keepdims=False)


def sinkhorn(logits, hp):
    """[S, n, n] logits -> doubly stochastic [S, n, n] (module docstring)."""
    if hp.get("softmax_not_sinkhorn"):
        return jax.nn.softmax(logits, axis=-1)
    if not hp.get("skip_clamp"):
        logits = jnp.clip(logits, hp["hc_clamp_min"], hp["hc_clamp_max"])
    m = jnp.exp(logits)
    rounds = 1 if hp.get("one_round") else hp["hc_sinkhorn_iters"]
    for _ in range(rounds):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hp["hc_eps"])
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hp["hc_eps"])
    return m


def _mappings(X, lp, site: str, hp):
    """X [S, n, C] -> (H_pre [S, n], H_post [S, n], H_res [S, n, n])."""
    n = hp["hc_mult"]
    s = X.shape[0]
    if hp.get("attn_maps_at_mlp"):
        site = "attn"
    dt = jnp.bfloat16 if hp.get("bf16_mappings") else jnp.float32
    x = _rms_norm(X.reshape(s, -1), lp[f"hc_{site}_norm"],
                  hp["rms_norm_eps"]).astype(dt)
    t = (x @ _f32(lp[f"hc_{site}_phi"]).astype(dt)).astype(dt)
    alpha = _f32(lp[f"hc_{site}_alpha"])
    if hp.get("static_maps"):
        alpha = jnp.zeros_like(alpha)
    bias = _f32(lp[f"hc_{site}_bias"])
    pre = (alpha[0].astype(dt) * t[:, :n] + bias[:n].astype(dt))
    post = (alpha[1].astype(dt) * t[:, n:2 * n] + bias[n:2 * n].astype(dt))
    res = (alpha[2].astype(dt) * t[:, 2 * n:] + bias[2 * n:].astype(dt))
    h_pre = pre if hp.get("pre_not_squashed") else jax.nn.sigmoid(pre)
    h_post = jax.nn.sigmoid(post) * (1.0 if hp.get("post_not_doubled")
                                     else 2.0)
    h_res = sinkhorn(res.reshape(s, n, n), hp)
    return _f32(h_pre), _f32(h_post), _f32(h_res)


def _sublayer(X, lp, site: str, hp, fn):
    """X <- H_res X + H_post^T F(RMSNorm(H_pre X)); returns (X, F's aux)."""
    h_pre, h_post, h_res = _mappings(X, lp, site, hp)
    u = jnp.einsum("sn,snc->sc", h_pre, X)
    ln = "ln_attn" if site == "attn" else "ln_mlp"
    y, aux = fn(_rms_norm(u, lp[ln], hp["rms_norm_eps"]))
    return (jnp.einsum("sij,sjc->sic", h_res, X)
            + h_post[:, :, None] * y[:, None, :]), aux


def _attention(h, lp, hp):
    """attention(h), expanded as published, one head at a time."""
    r, dn = hp["kv_lora_rank"], hp["qk_nope_head_dim"]
    eps = hp["rms_norm_eps"]
    s = h.shape[0]
    c_q = h @ _f32(lp["wqa"])
    if not hp.get("skip_query_norm"):
        c_q = _rms_norm(c_q, lp["ln_q"], eps)
    kva = h @ _f32(lp["wkva"])
    c = _rms_norm(kva[:, :r], lp["ln_kv"], eps)
    k_rope = _rope(kva[:, r:], hp)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scale = softmax_scale(hp)

    def head(n, out):
        q = c_q @ _f32(_at(lp["wqb"], n, 1))  # [S, dn + dr]
        kv = c @ _f32(_at(lp["wkvb"], n, 0))  # [S, dn + dv]: [k_nope | v]
        scores = (q[:, :dn] @ kv[:, :dn].T
                  + _rope(q[:, dn:], hp) @ k_rope.T) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return out + (probs @ kv[:, dn:]) @ _f32(_at(lp["wo"], n, 0))

    return jax.lax.fori_loop(0, lp["wo"].shape[0], head,
                             jnp.zeros_like(h)), None


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ _f32(wg)) * (h @ _f32(wu))) @ _f32(wd)


def _moe(h, lp, hp, picks, forced_from):
    """The routed experts and the shared branch; one expert upcast at a
    time.  Rows from `forced_from` on take the experts `picks` [S, k] names.
    Returns (out [S, C], gap [S], top [S, k]): the k-th minus the (k+1)-th of
    what the choice is made by, and the experts each row took."""
    k = hp["num_experts_per_tok"]
    scale = 1.0 if hp.get("skip_scale") else hp["routed_scaling_factor"]
    logits = h @ _f32(lp["router"])  # [S, E]
    sigma = jax.nn.sigmoid(logits)
    choose_by = sigma if hp.get("ignore_bias") \
        else sigma + _f32(lp["router_bias"])[None, :]
    order = jnp.argsort(-choose_by, axis=-1)  # stable: ties to the lower id
    top = jnp.where(jnp.arange(h.shape[0])[:, None] >= forced_from, picks,
                    order[:, :k])
    w_top = jnp.take_along_axis(sigma, top, axis=-1)
    w_top = w_top / (jnp.sum(w_top, axis=-1, keepdims=True) + 1e-20) * scale
    srt = jnp.take_along_axis(choose_by, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]

    def add_expert(e, out):
        w_e = jnp.sum(jnp.where(top == e, w_top, 0.0), axis=-1)  # [S]
        return out + w_e[:, None] * _swiglu(
            h, _at(lp["wg"], e), _at(lp["wu"], e), _at(lp["wd"], e))

    out = jax.lax.fori_loop(0, logits.shape[-1], add_expert,
                            jnp.zeros_like(h))
    return (out + _swiglu(h, lp["ws_g"], lp["ws_u"], lp["ws_d"]),
            (gap, top))


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted(hp.items()))


@partial(jax.jit, static_argnames=("hp", "routed"))
def _layer(X, stack, attn, l, a, picks, forced_from, *, hp, routed: bool):
    """Layer `l` of its feed-forward stack, `a` of the attention stack.
    Returns (X, gap [S], top [S, k])."""
    hp = dict(hp)
    lp = {**{name: _at(w, l) for name, w in stack.items()},
          **{name: _at(w, a) for name, w in attn.items()}}
    X, _ = _sublayer(X, lp, "attn", hp, lambda h: _attention(h, lp, hp))
    if routed:
        X, (gap, top) = _sublayer(
            X, lp, "mlp", hp, lambda h: _moe(h, lp, hp, picks, forced_from))
        return X, gap, top
    X, _ = _sublayer(
        X, lp, "mlp", hp,
        lambda h: (_swiglu(h, lp["wg"], lp["wu"], lp["wd"]), None))
    return X, jnp.full((X.shape[0],), jnp.inf), picks


@partial(jax.jit, static_argnames=("eps", "row0"))
def _head(X, final_norm, head, positions_out, *, eps: float, row0: bool):
    h = X[:, 0] if row0 else jnp.sum(X, axis=1)
    return _rms_norm(h, final_norm, eps)[positions_out] @ _f32(head)


@partial(jax.jit, static_argnames=("n", "row0"))
def _embed(table, ids, *, n: int, row0: bool):
    x = _f32(table[ids])
    if row0:
        return jnp.concatenate(
            [x[:, None], jnp.zeros((x.shape[0], n - 1, x.shape[1]))], axis=1)
    return jnp.tile(x[:, None], (1, n, 1))


# the keys `hyper` gives: the plain pass's (a variant adds its own)
PLAIN_KEYS = (
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "hc_clamp_min", "hc_clamp_max",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "rope_theta",
    "rope_interleave", "rope_factor", "rope_original_max_position",
    "rope_beta_fast", "rope_beta_slow", "rope_mscale_all_dim",
    "rms_norm_eps", "num_experts_per_tok", "routed_scaling_factor")


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    if model_cfg.tie_word_embeddings or not model_cfg.kv_lora_rank \
            or not model_cfg.q_lora_rank:
        raise ValueError("Xing4.0: latent attention with a query low-rank, "
                         "untied head")
    if model_cfg.moe_scoring != "sigmoid" or model_cfg.hc_mult < 1:
        raise ValueError("Xing4.0: sigmoid-scored routing, hc_mult >= 1")
    rope = dict(model_cfg.rope_by_kind).get("full_attention")
    return {
        "hc_mult": int(model_cfg.hc_mult),
        "hc_sinkhorn_iters": int(model_cfg.hc_sinkhorn_iters),
        "hc_eps": float(model_cfg.hc_eps),
        "hc_clamp_min": float(model_cfg.hc_res_clamp_min),
        "hc_clamp_max": float(model_cfg.hc_res_clamp_max),
        "kv_lora_rank": int(model_cfg.kv_lora_rank),
        "qk_nope_head_dim": int(model_cfg.qk_nope_head_dim),
        "qk_rope_head_dim": int(model_cfg.qk_rope_head_dim),
        "rope_theta": float(model_cfg.rope_theta),
        "rope_interleave": bool(model_cfg.rope_interleave),
        "rope_factor": float(rope.factor) if rope else 0.0,
        "rope_original_max_position":
            int(rope.original_max_position) if rope else 0,
        "rope_beta_fast": float(rope.beta_fast) if rope else 0.0,
        "rope_beta_slow": float(rope.beta_slow) if rope else 0.0,
        "rope_mscale_all_dim": float(rope.mscale_all_dim) if rope else 0.0,
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
        "routed_scaling_factor": float(model_cfg.routed_scaling_factor),
    }


def _pass(params, hp, ids, positions_out, picks, forced_from: int):
    """One causal forward over `ids` [S]: the logits at `positions_out`, each
    row's smallest router gap over the routed layers, and the experts every
    routed layer's rows took [routed layers, S, k].  Rows from `forced_from`
    on take `picks`' experts."""
    frozen = _freeze({k: v for k, v in hp.items() if k != "forced"})
    (attn,) = params["attn"].values()  # one kind of layer
    X = _embed(params["embed"], ids, n=hp["hc_mult"],
               row0=bool(hp.get("widen_row0")))
    min_gap = jnp.full((ids.shape[0],), jnp.inf)
    a, took = 0, []
    for name, routed in (("dense_layers", False), ("layers", True)):
        stack = params.get(name)
        if stack is None:
            continue
        for l in range(stack["ln_attn"].shape[0]):
            X, gap, top = _layer(
                X, stack, attn, jnp.int32(l), jnp.int32(a),
                picks[l if routed else 0], jnp.int32(forced_from),
                hp=frozen, routed=routed)
            min_gap = jnp.minimum(min_gap, gap)
            if routed:
                took.append(top)
            a += 1
    logits = _head(X, params["final_norm"], params["lm_head"],
                   jnp.asarray(positions_out, jnp.int32),
                   eps=hp["rms_norm_eps"],
                   row0=bool(hp.get("collapse_row0")))
    return np.asarray(logits), np.asarray(min_gap), jnp.stack(took)


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int], picks=None) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `picks` [routed layers, S, k], the experts every row
    took; `router_gap` +inf (module docstring: no position is skipped) and
    `raw_router_gap`, the smallest raw gap over the routed layers.

    A variant that `variants` marks `forced` is handed the picks of the plain
    pass over the same weights (or `picks`, where the caller has another
    tree's: int8 weights under the original tree's picks) from RUN_IN rows
    ahead of the first compared position on, as the driver hands them to the
    served program."""
    ids = jnp.asarray(token_ids, jnp.int32)
    plain = {k: v for k, v in hp.items() if k in PLAIN_KEYS}
    none = jnp.zeros((params["layers"]["ln_attn"].shape[0], ids.shape[0],
                      plain["num_experts_per_tok"]), jnp.int32)
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
    """The reference with one mechanism taken out, or computed in a lower
    precision, for the check's POWER: were the served program to make this
    mistake, would the logits at the compared positions move by more than the
    tolerance?  Each is `forced` as the served program is: it takes the plain
    pass's experts from RUN_IN rows ahead of the first compared position on,
    so what it reads is its mistake and not the experts the mistake swapped.
    But `bias_ignored_in_choice`: the picks are forced THROUGH the bias, and
    a program that does not read it is not reached.  (`no_clamp` binds only
    where some H~_res passes a clamp: the tiny test shifts B_res by 40 for
    it; at the seeded widths it never does.)"""
    def forced(**mistake):
        return dict(hp, forced=True, **mistake)

    return {
        "static_mappings": forced(static_maps=True),
        "softmax_not_sinkhorn": forced(softmax_not_sinkhorn=True),
        "one_sinkhorn_round": forced(one_round=True),
        "no_clamp": forced(skip_clamp=True),
        "post_not_doubled": forced(post_not_doubled=True),
        "pre_not_squashed": forced(pre_not_squashed=True),
        "widen_row0": forced(widen_row0=True),
        "collapse_row0": forced(collapse_row0=True),
        "attn_maps_at_mlp": forced(attn_maps_at_mlp=True),
        "no_mscale_in_scale": forced(skip_mscale=True),
        "yarn_off": forced(yarn_off=True),
        "query_latent_not_normed": forced(skip_query_norm=True),
        "bias_ignored_in_choice": dict(hp, ignore_bias=True),
        "scale_one": forced(skip_scale=True),
        "bf16_mappings": forced(bf16_mappings=True),
    }
