"""Plain float32 reference of the Solar-Open2-250B decoder
(upstage/Solar-Open2-250B, `model_type` "solar_open2"), written from its
published config.json and ISSUE 50's equations.  Imports nothing of
`kafka_tpu` (a test scans for it); `tests/test_solar_open2.py` holds
`kafka_tpu.models.forward` to it at a tiny size in float32.

The decoder, per token x at position p (what the config has no key for is
marked A1-A8 and listed under `assumed` in the configuration's file, each
with where it is recalled from):

* residual form: x += Mixer_l(RMSNorm(x)), x += FFN_l(RMSNorm(x)), eps
  `rms_norm_eps`; Mixer_l is softmax attention where l is in `gqa_layers`
  and linear attention elsewhere;
* linear attention (the gated delta rule with a decay per key channel: Kimi
  Delta Attention, arXiv:2510.26692, which the keys `kda_*` and
  `short_conv_kernel_size` name), per head h of `linear_attn_config`'s 64,
  d_k = d_v = 128 (`num_kv_heads` null: k and v have 64 heads too):
  q~, k~, v~ = x W_q, x W_k, x W_v; q, k, v = SiLU(conv4(.)), a depthwise
  causal convolution of `short_conv_kernel_size` taps a channel, each of the
  three its own, zero before the sequence starts (A1: SiLU after the
  convolution, no bias); q <- q / sqrt(|q|^2 + 1e-6) d_k^-1/2, k <- k /
  sqrt(|k|^2 + 1e-6) a head (A2: the L2 norm's 1e-6 under the root);
  g_t = -exp(A_log_h) softplus(x W_f1 W_f2 + dt_bias), alpha_t = exp(g_t) a
  key CHANNEL (`kda_use_full_proj` false: W_f1 [H, 128], W_f2 [128, 8192];
  A3: the low rank is the head size); beta_t = 2 sigmoid(x W_beta) a head
  (`kda_allow_neg_eigval`);
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_(t-1) + beta_t k_t v_t^T
      o_t = S_t^T q_t
  with S in R^(128 x 128) a head, float32, zero before the sequence starts;
  y = (RMSNorm_head(o_t) * sigmoid(x W_g1 W_g2)) W_o (A4: the output gate
  low-rank as the decay's, the head norm's weight one vector a layer);
* softmax attention: q = x W_q (64 heads x 128), k = x W_k, v = x W_v (8 x
  128), no bias, NO rotation (`use_rope` false), scores / sqrt(128), causal,
  softmax, values; o <- o * sigmoid(x W_gate), W_gate [H, 64 x 128],
  elementwise ahead of W_o (`use_gqa_gate`; A5: the G1 position of "Gated
  Attention for LLMs", arXiv:2505.06708).  Query head n reads kv head n // 8;
* FFN of every layer (`first_k_dense_replace` 0): s = sigmoid(x W_r) in
  float32 over the 320 experts (A6: sigmoid scores, the `solar_open` family's
  modeling code derives from `glm4_moe`; the config has no `scoring_func`);
  the top-8 by s + b are chosen (A7: the selection bias chooses, it does not
  weigh); a chosen expert weighs s_e / (sum of the chosen s + 1e-20)
  (`norm_topk_prob`) times `routed_scaling_factor`; plus one shared SwiGLU of
  `moe_intermediate_size` always on (`n_shared_experts` 1);
* final RMSNorm, an untied head (A8: nothing between them).

THE SHARE.  The tree holds experts `expert_offset` .. `expert_offset` + E of
the router's 320 (one chip's part of an expert-parallel layer) and a slice of
the vocabulary.  A row's eight experts are chosen and its weights
renormalised over all 320; what the absent experts would add is left out,
here as in the program, and that partial result goes on to the next layer.

The tree is the program's (`kafka_tpu/models/llama._init_lead_tree_params`,
the linear-attention layout), read here leaf by leaf: "layers" holds the
norms and the feed-forward leaves stacked over the layers,
`attn["linear_attention"]` and `attn["full_attention"]` each kind's mixers
stacked in layer order.

Float32 under `default_matmul_precision("highest")`, the recurrence written
token by token as the equation above (no chunking), no cache, no kernels, no
batching; the stacked bf16 weights are upcast one layer, one expert and one
group of heads at a time, so it fits at the published widths beside the
served model.

Departures from the published model: weights are random (the check compares
programs, not models); nothing else.

TEACHER-FORCED PICKS.  As `references/lfm2moe.py`: an expert swapped under
bfloat16's noise is handed on by every state-carrying layer behind it, so
the check holds the picks still where it compares.  From RUN_IN rows ahead of
the first decode step on, the driver runs every row as a launch of its own
(and every decode step is one row wide) and hands it, through the selection
bias (which chooses and does not weigh), the experts THIS reference takes
there (`picks` in what `reference_logits` returns;
`drivers/solaropen2_pool.py`).  The scores, the weights and all the
arithmetic stay the served program's; the first launch's rows keep their own
picks.  With the run-in unforced (two launches, the second a page of 16 rows
with its own picks) the same check read 0.111 at the last prefill row and
0.063 falling to 0.037 over the 47 decode steps (my chip run 1, PR 50): what
the swapped rows left in the delta state, fading at the state's own rate.  A
variant below is `forced` the same way: it reads its own mistake, not the
experts the mistake swapped.  No position is skipped (`router_gap` is +inf
everywhere, so this file sets no ROUTER_FLIP_MARGIN; `raw_router_gap`, the
smallest k-th minus (k+1)-th of s + b over the layers, is reported for
`check_power.py`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

DELTA, GLOBAL = "linear_attention", "full_attention"

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary slice, at ALL 48 positions (module docstring:
# none is skipped).  Readings on the v5e at the published widths, 8 layers,
# Pallas, seeded weights, picks forced from the run-in on: a first launch of
# 1,520 rows, 16 launches of one row and 47 decode steps through pages and
# state slots, the 48 positions 1535..1582, on the pair of seeds every run of
# the cell checks (my chip run 2, PR 50; `benchmarks/check_power.py`; on the
# pairs (1, 1) and (2, 7) too and every control through `compare_logits`, my
# chip run A, `benchmarks/check_seeds.py`: the served program 0.0287-0.0344
# and 0.0306-0.0374 there, the 256-deep accumulator 0.0656-0.0791 and
# 0.0700-0.0860, int8 weights through the SERVED program 0.124-0.159 over the
# three pairs, the served program on its OWN picks 0.038-0.111): the
# served program (bf16 weights and activations, float32 state, the chunk and
# step kernels, flash prefill and the Pallas decode kernel at 64 / 8 x 128,
# dense and token dispatch) reads 0.0344-0.0431, median 0.0378; this
# reference in the nearest precisions below: with a bfloat16 accumulator
# rounded after every 128 of the contraction (`bf16_accumulate`)
# 0.0915-0.1053, after every 256 (`bf16_accumulate_256`) 0.0727-0.0928; on
# int8 weights 0.0983-0.1315; with the delta state rounded to bfloat16 after
# every token (`bf16_state`) 0.0133-0.0157, which no tolerance over the served
# band can fail: the driver reads the slot instead and fails by name
# (`DeltaStateError`).  0.056 is 1.30x the largest served reading of the 144
# and 0.85x the smallest of the 256-deep accumulator's, which fails it at
# every position of every pair, as the 128-deep one and int8 weights do with more room.  One
# mechanism out each (`variants`), smallest - median - largest over the 48:
# decay per head 0.83 - 0.88 - 0.93, beta in (0, 1) 0.48 - 0.52 - 0.56, no
# output gate 0.91 - 0.95 - 1.00, no GQA gate 1.04 - 1.11 - 1.14, rotation on
# 1.23 - 1.27 - 1.31, q / k unnormalised NaN (beta k k^T with |k|^2 ~ 100
# diverges), the conv tail zeroed where the run-in resumes 0.20 - 0.29 - 0.44
# and at decode's take-over 0.00 - 0.34 - 1.10, the state lost there 0.50 -
# 0.63 - 0.86 and 0.00 - 0.74 - 1.08 (the `_at_decode` ones leave the one
# prefill position alone and fail by all 47 others), chosen without the bias
# 0.33 - 0.40 - 0.47, no shared expert 1.28 - 1.31 - 1.34: all fail.  What it
# cannot fail: experts weighed by the biased scores (0.026 - 0.030 - 0.035:
# the seeded bias is N(0, 0.1^2) on scores of ~0.5 renormalised over eight)
# and the bfloat16 state above; the float32 CPU tests hold both.
TOLERANCE = {
    "value": 0.056,
    "why": "served bf16 0.0344-0.0431 over 48 positions, a bf16 accumulator "
           "0.0727-0.0928 (256 deep; 128 deep 0.0915-0.1053) and int8 "
           "weights 0.0983-0.1315 there (my chip run 2, PR 50; PERF.md 6)",
}

# The check prefills n_prefill rows: a first launch of all but the last
# RUN_IN of them, which leaves a snapshot on a page boundary, then those a row
# a launch on forced picks (`drivers/solaropen2_pool.py` has the same number):
# a page of rows stands between the first launch's free rows and the first
# compared position, which is the last one-row launch.
RUN_IN = 16


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
    """The linear-attention mixer over the normed rows h [S, H]."""
    acc = hp.get("bf16_accumulate", 0)
    s = h.shape[0]
    n, d = hp["delta_heads"], hp["delta_head_dim"]
    w = n * d
    taps = _f32(mp["conv_w"])  # [L, 3W]: q | k | v
    q, k, v = (
        _conv_silu(_mm(h, mp[name], acc), taps[:, i * w:(i + 1) * w], hp
                   ).reshape(s, n, d)
        for i, name in enumerate(("wq", "wk", "wv")))
    if not hp.get("qk_unnormalised"):
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * d ** -0.5
    g = -jnp.exp(_f32(mp["A_log"]))[:, None] * jax.nn.softplus(
        _mm(_mm(h, mp["wf1"], acc), mp["wf2"], acc) + _f32(mp["dt_bias"])
    ).reshape(s, n, d)
    if hp.get("decay_per_head"):
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm(h, mp["wbeta"], acc)) * (
        2.0 if hp["delta_neg_eigval"] and not hp.get("beta_unit") else 1.0)
    lost = hp.get("zero_state_at", -1)

    def token(S, row):
        """S [heads, d_k, d_v]: the equation, one token."""
        q_t, k_t, v_t, g_t, b_t, t = row
        S = jnp.where(t == lost, 0.0, S)
        S = jnp.exp(g_t)[:, :, None] * S                   # Diag(alpha) S
        kS = jnp.einsum("nk,nkv->nv", k_t, S)              # k^T S
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - kS)[:, None, :]
        if hp.get("bf16_state"):
            S = _round_bf16(S)
        return S, jnp.einsum("nkv,nk->nv", S, q_t)         # S^T q

    _, o = jax.lax.scan(token, jnp.zeros((n, d, d), jnp.float32),
                        (q, k, v, g, beta, jnp.arange(s)))
    o = _rms_norm(o, mp["ln_o"], hp["rms_norm_eps"])
    if not hp.get("no_output_gate"):
        o = o * jax.nn.sigmoid(
            _mm(_mm(h, mp["wg1"], acc), mp["wg2"], acc)).reshape(s, n, d)
    return _mm(o.reshape(s, w), mp["w_out"], acc)


def _attention(h, mp, hp):
    """The softmax mixer over the normed rows h [S, H], one group of query
    heads (one kv head) at a time: [rep, S, S] scores."""
    s, acc = h.shape[0], hp.get("bf16_accumulate", 0)
    hq, d = mp["wq"].shape[-2:]
    hkv = mp["wk"].shape[-2]
    rep = hq // hkv
    q = _mm(h, mp["wq"].reshape(-1, hq * d), acc).reshape(s, hq, d)
    k = _mm(h, mp["wk"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    v = _mm(h, mp["wv"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    if hp.get("rotation_on"):
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
    if hp["gqa_gate"] and not hp.get("no_gqa_gate"):
        out = out * jax.nn.sigmoid(_mm(h, mp["wgate"], acc))
    return _mm(out, mp["wo"].reshape(hq * d, -1), acc)


def _swiglu(h, wg, wu, wd, acc=0):
    return _mm(jax.nn.silu(_mm(h, wg, acc)) * _mm(h, wu, acc), wd, acc)


def _moe(h, lp, hp, forced, forced_from):
    """The routed FFN over the HELD experts plus the shared one.  Returns
    (out [S, H], gap [S], top [S, k]): gap is the k-th minus the (k+1)-th of
    s + b over all the router's experts, top the experts taken (the router's
    numbering).  Rows from `forced_from` on take `forced` [S, k]."""
    k, scale = hp["num_experts_per_tok"], hp["routed_scaling_factor"]
    acc = hp.get("bf16_accumulate", 0)
    sigma = jax.nn.sigmoid(_mm(h, lp["router"], acc))  # [S, routed]
    biased = sigma + (0.0 if hp.get("skip_selection_bias")
                      else _f32(lp["router_bias"]))
    order = jnp.argsort(-biased, axis=-1)  # stable: ties to the lower index
    srt = jnp.take_along_axis(biased, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]
    rows = jnp.arange(h.shape[0])[:, None]
    top = jnp.where(rows >= forced_from, forced, order[:, :k])
    weigh = biased if hp.get("weigh_by_biased") else sigma
    chosen = jnp.take_along_axis(weigh, top, axis=-1)
    w_top = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    first = hp["expert_offset"]

    def add_expert(i, out):
        w_e = jnp.sum(jnp.where(top == first + i, w_top, 0.0), axis=-1)  # [S]
        y = _swiglu(h, _at(lp["wg"], i), _at(lp["wu"], i), _at(lp["wd"], i),
                    acc)
        return out + w_e[:, None] * y

    out = jax.lax.fori_loop(0, lp["wg"].shape[0], add_expert,
                            jnp.zeros_like(h))
    if not hp.get("no_shared_expert"):
        out = out + _swiglu(h, lp["ws_g"], lp["ws_u"], lp["ws_d"], acc)
    return out, gap, top


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in hp.items()
                        if not isinstance(v, (list, dict))))


@partial(jax.jit, static_argnames=("hp", "kind"))
def _layer(x, stack, mixers, l, nth, forced, forced_from, *, hp, kind: str):
    """Layer `l` of `stack` (norms and feed-forward leaves), its mixer the
    `nth` of its kind's.  Returns the stream, the router's gap a row and the
    experts a row took."""
    hp = dict(hp)
    lp = {name: _at(w, l) for name, w in stack.items()}
    mp = {name: _at(w, nth) for name, w in mixers.items()}
    h = _rms_norm(x, lp["ln_attn"], hp["rms_norm_eps"])
    x = x + (_linear_attention(h, mp, hp) if kind == DELTA
             else _attention(h, mp, hp))
    h = _rms_norm(x, lp["ln_mlp"], hp["rms_norm_eps"])
    y, gap, top = _moe(h, lp, hp, forced, forced_from)
    return x + y, gap, top


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, positions_out, *, eps: float):
    return _rms_norm(x, final_norm, eps)[positions_out] @ _f32(head)


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


# what `hyper` gives: a variant's further keys name its mistake
PLAIN_KEYS = ("layer_types", "rope_theta", "rms_norm_eps",
              "num_experts_per_tok", "routed_scaling_factor", "expert_offset",
              "delta_heads", "delta_head_dim", "delta_neg_eigval", "gqa_gate")


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    kinds = list(model_cfg.layer_types)
    if DELTA not in kinds or set(kinds) - {DELTA, GLOBAL}:
        raise ValueError(
            "solar_open2: linear_attention and full_attention layers")
    if (model_cfg.tie_word_embeddings or not model_cfg.num_experts
            or model_cfg.first_k_dense):
        raise ValueError("solar_open2: an untied head, every layer routed")
    if GLOBAL not in model_cfg.unrotated_kinds:
        raise ValueError("solar_open2: attention that does not rotate")
    return {
        "layer_types": kinds,
        "rope_theta": float(model_cfg.rope_theta),
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
        "routed_scaling_factor": float(model_cfg.routed_scaling_factor),
        "expert_offset": int(model_cfg.expert_offset),
        "delta_heads": int(model_cfg.delta_heads),
        "delta_head_dim": int(model_cfg.delta_head_dim),
        "delta_neg_eigval": bool(model_cfg.delta_neg_eigval),
        "gqa_gate": model_cfg.attention_gate == "elementwise",
    }


def _pass(params, hp, ids, positions_out, picks, forced_from: int):
    """One causal forward over `ids` [S]: the logits at `positions_out`, each
    row's smallest router gap over the layers, and the experts every layer's
    rows took [layers, S, k].  Rows from `forced_from` on take `picks`'."""
    frozen = _freeze({k: v for k, v in hp.items() if k != "forced"})
    seen: Dict[str, int] = {}
    took = []
    x = _embed(params["embed"], ids)
    min_gap = jnp.full((ids.shape[0],), jnp.inf)
    for l, kind in enumerate(hp["layer_types"]):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        x, gap, top = _layer(
            x, params["layers"], params["attn"][kind], jnp.int32(l),
            jnp.int32(nth), picks[l], jnp.int32(forced_from), hp=frozen,
            kind=kind)
        min_gap = jnp.minimum(min_gap, gap)
        took.append(top)
    logits = _head(x, params["final_norm"], params["lm_head"],
                   jnp.asarray(positions_out, jnp.int32),
                   eps=hp["rms_norm_eps"])
    return np.asarray(logits), np.asarray(min_gap), jnp.stack(took)


def reference_logits(params, hp: Dict[str, Any], token_ids,
                     positions_out: List[int], picks=None) -> Dict[str, Any]:
    """Full causal forward over `token_ids` [S]; float32 logits [n, V] at
    `positions_out`; `picks` [layers, S, k], the experts every row took;
    `router_gap` +inf (module docstring: no position is skipped) and
    `raw_router_gap`, the smallest raw gap over the layers.

    A variant that `variants` marks `forced` is handed the picks of the plain
    pass over the same weights (or `picks`, where the caller has another
    tree's) from RUN_IN - 1 rows ahead of the first compared position on, as
    the driver hands them to the served program."""
    ids = jnp.asarray(token_ids, jnp.int32)
    first = int(positions_out[0])
    for lost, at in (("tail_lost_behind", "zero_tail_at"),
                     ("state_lost_behind", "zero_state_at")):
        if lost in hp:
            hp = dict(hp, **{at: first + hp[lost]})
    plain = {k: v for k, v in hp.items() if k in PLAIN_KEYS}
    none = jnp.zeros((len(plain["layer_types"]), ids.shape[0],
                      plain["num_experts_per_tok"]), jnp.int32)
    with jax.default_matmul_precision("highest"):
        forced_from = int(ids.shape[0])  # nothing is forced
        if hp.get("forced") or picks is not None:
            forced_from = max(first + 1 - RUN_IN, 0)
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
    Each is `forced` as the served program is (but `chosen_without_bias`: the
    picks are forced THROUGH the bias).  `conv_tail_zeroed_at_chunk` zeroes
    every convolution's tail, and `state_lost_at_chunk` every head's S, ahead
    of the run-in's first row, which resumes from the first launch's snapshot
    (a snapshot that was not restored); `..._at_decode` ahead of the first
    decode step."""
    def forced(**mistake):
        return dict(hp, forced=True, **mistake)

    return {
        "bf16_accumulate": forced(bf16_accumulate=128),
        "bf16_accumulate_256": forced(bf16_accumulate=256),
        "bf16_state": forced(bf16_state=True),
        "decay_per_head": forced(decay_per_head=True),
        "beta_in_0_1": forced(beta_unit=True),
        "no_output_gate": forced(no_output_gate=True),
        "no_gqa_gate": forced(no_gqa_gate=True),
        "rotation_on": forced(rotation_on=True),
        "qk_unnormalised": forced(qk_unnormalised=True),
        "conv_tail_zeroed_at_chunk": forced(tail_lost_behind=1 - RUN_IN),
        "conv_tail_zeroed_at_decode": forced(tail_lost_behind=1),
        "state_lost_at_chunk": forced(state_lost_behind=1 - RUN_IN),
        "state_lost_at_decode": forced(state_lost_behind=1),
        "chosen_without_bias": dict(hp, skip_selection_bias=True),
        "weighed_by_biased_scores": forced(weigh_by_biased=True),
        "no_shared_expert": forced(no_shared_expert=True),
    }
