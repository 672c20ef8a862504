"""Plain float32 reference of the Granite-4.0-H decoder
(ibm-granite/granite-4.0-h-small, `model_type` "granitemoehybrid"), written
from its published config.json and ISSUE 63's equations.  Imports nothing of
`kafka_tpu` (a test scans for it); `tests/test_granite_moe_hybrid.py` holds
`kafka_tpu.models.forward` to it at a tiny size in float32.

The decoder, per token, h the residual stream (what the config has no key for
is marked A1-A8 and listed under `assumed` in the configuration's file, each
with where it is recalled from: the `granitemoehybrid` modeling code of
`transformers`, whose mixer is Bamba's / `mamba_ssm`'s Mamba2, and Mamba-2,
arXiv:2405.21060):

* h0 = `embedding_multiplier` x E[token]; E is tied with the head;
* every layer is TWO sublayers, each under a norm of its own and each added
  at `residual_multiplier` r (A7):
      h <- h + r M_l(RMSNorm(h; w_in_l)),   M_l the Mamba-2 mixer or attention
                                            by the layer's `layer_types` word
      h <- h + r F_l(RMSNorm(h; w_post_l)), F_l the routed feed-forward
  eps `rms_norm_eps`; a final RMSNorm; logits = h E^T / `logits_scaling`;
* "mamba", the Mamba-2 mixer, d = heads x head size P, N = `mamba_d_state`, G
  = `mamba_n_groups` (1): [z | xBC | dt] = u W_in (d | d + 2 G N | heads)
  (A1); xBC <- SiLU(conv4(xBC) + b), a depthwise causal convolution of
  `mamba_d_conv` taps a channel, zero before the sequence (A6); x in R^(heads
  x P), B, C in R^(G x N), head h reads group h // (heads / G); dt_h =
  softplus(dt_h + dt_bias_h), no clamp (A2); a_h = exp(-exp(A_log_h) dt_h), a
  SCALAR a head;
      S_t = a_t S_(t-1) + dt_t x_t B_t^T,   S in R^(P x N) a head, float32
      y_t = S_t C_t + D_h x_t
  y <- RMSNorm(y * SiLU(z)): the gate BEFORE the norm, over each group's d / G
  channels (one group: all d) under one learned weight of d (A3); out = y
  W_out;
* "attention": q = u W_q (heads x 128), k, v = u W_k, u W_v (KV heads x 128),
  NO rotation and no other position signal (`position_embedding_type` "nope",
  A4), causal softmax at scale `attention_multiplier` (0.0078125 = 1 / 128,
  NOT 128^-1/2), W_o; query head n reads KV head n // (heads / KV heads);
* the routed feed-forward (A5): s = v W_r over all the router's experts in
  float32; the `num_experts_per_tok` largest are chosen; p = softmax over
  those logits ALONE; f = sum_e p_e W_d,e (SiLU(W_g,e v) * W_u,e v) + W_d,s
  (SiLU(W_g,s v) * W_u,s v): gated SiLU experts, one shared expert of
  `shared_intermediate_size` the same way, added unweighted; no bias
  anywhere, no selection bias, no scaling factor.
  THE HELD SHARE: the expert leaves are experts `expert_offset` ..
  `expert_offset` + E_held of the published ones (one chip of an
  expert-parallel layer); a token's experts are chosen and its softmax taken
  over all ten of its picks, held here or not, and what the absent experts
  would add is left out (the other chip's part of the combine).  The shares
  of the chips, with the shared expert and the mixers counted once, add up to
  the uncut layer (`tests/test_granite_moe_hybrid.py`).

The tree is the program's (`kafka_tpu/models/init_params.
_init_lead_tree_params`): "layers" holds every layer's two norms ("ln_attn",
"ln_mlp" [L, H]) and its routed block ("router" [L, H, routed], "wg", "wu"
[L, E_held, H, f], "wd" [L, E_held, f, H], "ws_g", "ws_u" [L, H, fs], "ws_d");
"attn" the mixers' leaves stacked per kind in layer order ("mamba2": w_in,
conv_w [n, taps, d + 2 G N] whose LAST tap is the row's own, conv_b, A_log, D,
dt_bias, ln_ssd, w_out; "full_attention": wq [n, H, heads, 128], wk, wv, wo
[n, heads, 128, H]); beside "embed" and "final_norm".  The published
checkpoint fuses an expert's gate and up matrices into one input matrix whose
FIRST half is gated; the tree holds the halves apart as "wg" and "wu".  A8,
the seeded initialiser, draws every leaf a multiplier scales at its fan-in
deviation divided by it, so that each scalar matters to the logits.

Float32 under `default_matmul_precision("highest")`, token-parallel, the
recurrence written token by token as the equation above (no chunking), no
cache, no kernels, no batching; the stacked bf16 weights are upcast one layer
and one expert at a time, so it fits at the published widths beside the
served model.

TEACHER-FORCED PICKS, as `references/nemotronh.py`: a state carries a swapped
expert's difference to every row behind it, so from RUN_IN - 1 rows ahead of
the first compared position on the driver hands the served program the experts
THIS pass takes (`picks`), and a variant marked `forced` takes the plain
pass's likewise: it reads its own mistake, not the experts the mistake
swapped.  The softmax rule has no selection bias to force through: the driver
hands the program a CHOICE leaf ("router_choice", which no published tree
holds) that is added to the logits for the choice alone; the weights stay the
softmax over the chosen experts' own logits.  No position is skipped
(`router_gap` is +inf everywhere; `raw_router_gap`, the smallest k-th minus
(k+1)-th logit over the layers, is reported for `check_power.py`).

Departures from the published model: weights are random (the check compares
programs, not models); nothing else.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

MAMBA2, GLOBAL = "mamba2", "full_attention"

# Relative RMS error allowed per compared position, rms(served - ref) /
# rms(ref) over the vocabulary slice, at ALL 48 positions (1535..1582; picks
# are forced, so none is skipped).  The logits' scale: rms ~1 / 16 over the
# slice (a tied head over a normed stream, divided by `logits_scaling`).
# Readings on the v5e at the published widths, 10 layers, Pallas, seeded
# weights: a first launch of 1,488 rows in a bucket of 1,536, 48 rows a row a
# launch from its snapshot and 47 decode steps through pages and state slots,
# on the pair of seeds every run of the cell checks, (0, 0), and on (1, 1),
# (2, 7) and (3, 5) (my chip run B, PR 63; `benchmarks/check_seeds.py
# --variants`, `chiprun_out/pr63/seeds.jsonl`): the served program (bf16
# weights and activations, float32 state, `ssd_chunk`, flash prefill and the
# grouped matmul in the first launch, `ssd_step` and the Pallas decode kernel
# at 32 / 8 x 128 under scale 1 / 128) reads 0.0180-0.0254 over the 192
# (0.0191-0.0227 on the cell's own pair); this reference in the nearest
# precisions below: with a bfloat16 accumulator rounded after every 256 of the
# contraction (`bf16_accumulate_256`) 0.0407-0.0554, after every 128
# (`bf16_accumulate`) 0.0545-0.0711, on int8 weights 0.0663-0.0897 (the SERVED
# program on int8 weights 0.0676-0.0937).  0.031 is 1.22x the largest served
# reading of the 192 and 0.76x the smallest of the 256-deep accumulator's,
# which fails it at every position of every pair, as the 128-deep one and int8
# weights do.  One mechanism out each (`variants`), smallest - median -
# largest over the 48 on the pair (0, 0): `residual_multiplier` dropped at the
# mixer's add 0.92 - 0.97 - 1.03, at the feed-forward's 1.20 - 1.24 - 1.28,
# 128^-1/2 for `attention_multiplier` 0.36 - 0.39 - 0.42, a rotation on (theta
# 10,000) 0.081 - 0.096 - 0.115, the weights renormalised over the HELD picks
# 0.38 - 0.47 - 0.85, a softmax over all 72 0.19 - 0.23 - 0.27,
# `logits_scaling` dropped 15.0, `embedding_multiplier` dropped 0.44 - 0.47 -
# 0.52, no shared expert 1.01 - 1.07 - 1.12, the up half gated 0.89 - 0.97 -
# 1.04, no D skip 1.12 - 1.23 - 1.34, no dt_bias 1.10 - 1.19 - 1.43, no conv
# bias 0.40 - 0.46 - 0.54, the norm a head 0.96 - 1.07 - 1.14, the norm before
# the gate 0.69 - 0.79 - 0.86, the state lost where the run-in resumes 0.29 -
# 0.38 - 0.52: all fail, at every position; the conv tail zeroed there 0.023 -
# 0.038 - 0.071 fails by its worst positions (48 rows wash a 3-row tail out;
# the tier-1 tests hold the tail across launches exactly); the served program
# on its OWN picks 0.023 - 0.039 - 0.079 (why the picks are forced).  WHAT THE
# TOLERANCE CANNOT FAIL: the state rounded to bfloat16 after every token
# (`bf16_state`) reads 0.0204 - 0.0243 - 0.0282, inside the served error
# (twenty rounded sublayers weigh as much as a rounded state), so the driver
# reads the slot itself and fails by name (`SsdStateError`: float32 leaves, S
# unrounded); router logits rounded to bfloat16 read 0.0018 - 0.0022 - 0.0032
# (ten softmax weights move by 2^-9 once the picks are forced): nothing on the
# chip catches that.  With W_k drawn for scores of deviation 1 (my chip run 2)
# the served program read 0.0176-0.0252 and a rotation 0.021-0.025, NOT told
# apart: the seeded initialiser now draws W_k for deviation 2 (A8).
TOLERANCE = {
    "value": 0.031,
    "why": "served bf16 0.0180-0.0254 over 48 positions and four pairs of "
           "seeds; a bf16 accumulator 256 deep 0.0407-0.0554, 128 deep "
           "0.0545-0.0711, int8 weights 0.0663-0.0897; residual_multiplier "
           "dropped at either add, 128^-1/2 for attention_multiplier, a "
           "rotation (0.081-0.115), the weights renormalised over the held "
           "picks, logits_scaling or embedding_multiplier dropped all fail "
           "at every position; a bf16 SSD state (0.0204-0.0282) and bf16 "
           "router logits are NOT told apart by logits: the driver reads "
           "the slot (my chip run B, PR 63; PERF.md 6)",
}

# Rows ahead of the compared positions that run a row a launch on forced
# picks: three pages, `references/nemotronh.py`'s number for the same Mamba-2
# state (at one page a free pick swapped in the first launch's last rows still
# showed in the compared rows through S: my chip run 3, PR 60).
RUN_IN = 48


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
    """x [S, N, D] at positions 0..S-1, all D values, pairs (i, i + D/2):
    the `rotation_on` variant's (the model itself does not rotate)."""
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
    p = _mm(u, lp["w_in"], acc)
    z, xbc, dt = p[:, :d], p[:, d:2 * d + 2 * gw], p[:, 2 * d + 2 * gw:]
    xbc = _conv_silu(xbc, _f32(lp["conv_w"]), _f32(lp["conv_b"]), hp)
    x = xbc[:, :d].reshape(s, H, P)
    Bm = xbc[:, d:d + gw].reshape(s, G, N)
    Cm = xbc[:, d + gw:].reshape(s, G, N)
    group = np.arange(H) // (H // G)
    Bh, Ch = Bm[:, group], Cm[:, group]                    # [S, H, N]
    if not hp.get("no_dt_bias"):
        dt = dt + _f32(lp["dt_bias"])
    dt = jax.nn.softplus(dt)                               # [S, H]
    a = jnp.exp(-jnp.exp(_f32(lp["A_log"])) * dt)
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
        v = v.reshape(s, H if hp.get("norm_per_head") else G, -1)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        return v.reshape(s, d) * w

    y = norm(y) * gate if hp.get("norm_before_gate") else norm(y * gate)
    return _mm(y, lp["w_out"], acc)


def _attention(u, lp, hp):
    """The softmax mixer over the normed rows u [S, H], one group of query
    heads (one KV head) at a time: [rep, S, S] scores.  No rotation; the
    scale is `attention_multiplier`."""
    s, acc = u.shape[0], hp.get("bf16_accumulate", 0)
    hq, d = lp["wq"].shape[-2:]
    hkv = lp["wk"].shape[-2]
    rep = hq // hkv
    q = _mm(u, lp["wq"].reshape(-1, hq * d), acc).reshape(s, hq, d)
    k = _mm(u, lp["wk"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    v = _mm(u, lp["wv"].reshape(-1, hkv * d), acc).reshape(s, hkv, d)
    if hp.get("rotation_on"):
        q, k = _rope(q, hp["rotation_on"]), _rope(k, hp["rotation_on"])
    scale = (d ** -0.5 if hp.get("scale_rsqrt_head_dim")
             else hp["attention_multiplier"])
    allowed = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def group(g, out):
        qg = jax.lax.dynamic_slice_in_dim(q, g * rep, rep, 1)  # [S, rep, D]
        kg = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vg = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        scores = jnp.einsum("snd,td->nst", qg, kg) * scale
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        og = jnp.einsum("nst,td->snd", jax.nn.softmax(scores, axis=-1), vg)
        return jax.lax.dynamic_update_slice_in_dim(out, og, g * rep, 1)

    out = jax.lax.fori_loop(0, hkv, group, jnp.zeros_like(q)).reshape(s, -1)
    return _mm(out, lp["wo"].reshape(hq * d, -1), acc)


def _moe(h, lp, hp, forced, forced_from):
    """The routed feed-forward over the HELD experts plus the shared one.
    Returns (out [S, H], gap [S], top [S, k]): gap is the k-th minus the
    (k+1)-th logit over all the router's experts, top the experts taken (the
    router's numbering).  Rows from `forced_from` on take `forced`."""
    k = hp["num_experts_per_tok"]
    acc = hp.get("bf16_accumulate", 0)
    logits = _mm(h, lp["router"], acc)  # [S, routed]
    if hp.get("bf16_router_logits"):
        logits = _round_bf16(logits)
    order = jnp.argsort(-logits, axis=-1)  # stable: ties to the lower index
    srt = jnp.take_along_axis(logits, order, axis=-1)
    gap = srt[:, k - 1] - srt[:, k]
    rows = jnp.arange(h.shape[0])[:, None]
    top = jnp.where(rows >= forced_from, forced, order[:, :k])
    chosen = jnp.take_along_axis(logits, top, axis=-1)
    first, held = hp["expert_offset"], lp["wu"].shape[0]
    if hp.get("softmax_over_all"):
        # the mistake: the top-k of a softmax over ALL the router's experts
        w_top = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top,
                                    axis=-1)
    elif hp.get("renormalised_over_held"):
        # the mistake: the softmax over the picks this chip HOLDS alone
        mine = (top >= first) & (top < first + held)
        w_top = jax.nn.softmax(jnp.where(mine, chosen, -jnp.inf), axis=-1)
        w_top = jnp.where(mine, w_top, 0.0)
    else:
        w_top = jax.nn.softmax(chosen, axis=-1)

    def block(wg, wu, wd):
        g, u = _mm(h, wg, acc), _mm(h, wu, acc)
        if hp.get("up_half_gated"):
            g, u = u, g  # the mistake: the input matrix's SECOND half gated
        return _mm(jax.nn.silu(g) * u, wd, acc)

    def add_expert(i, out):
        w_e = jnp.sum(jnp.where(top == first + i, w_top, 0.0), axis=-1)  # [S]
        return out + w_e[:, None] * block(
            _at(lp["wg"], i), _at(lp["wu"], i), _at(lp["wd"], i))

    out = jax.lax.fori_loop(0, held, add_expert, jnp.zeros_like(h))
    if not hp.get("no_shared_expert"):
        out = out + block(lp["ws_g"], lp["ws_u"], lp["ws_d"])
    return out, gap, top


def _freeze(hp: Dict[str, Any]):
    return tuple(sorted((k, v) for k, v in hp.items()
                        if not isinstance(v, (list, dict))))


@partial(jax.jit, static_argnames=("hp", "kind"))
def _layer(x, layers, stack, l, nth, forced, forced_from, *, hp, kind: str):
    """Layer `l` (its norms and routed block `layers[..][l]`), its mixer the
    `nth` of its kind's `stack`.  Returns the stream, the router's gap a row
    and the experts a row took."""
    hp = dict(hp)
    lp = {name: _at(w, l) for name, w in layers.items()}
    mp = {name: _at(w, nth) for name, w in stack.items()}
    eps, r = hp["rms_norm_eps"], hp["residual_multiplier"]
    u = _rms_norm(x, lp["ln_attn"], eps)
    m = _ssm(u, mp, hp) if kind == MAMBA2 else _attention(u, mp, hp)
    x = x + (1.0 if hp.get("no_residual_multiplier_mixer") else r) * m
    v = _rms_norm(x, lp["ln_mlp"], eps)
    f, gap, top = _moe(v, lp, hp, forced, forced_from)
    x = x + (1.0 if hp.get("no_residual_multiplier_ffn") else r) * f
    return x, gap, top


@partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, positions_out, mult, *, eps: float):
    return (_rms_norm(x, final_norm, eps)[positions_out] @ _f32(embed).T
            ) * mult


@jax.jit
def _embed(table, ids, mult):
    return _f32(table[ids]) * mult


# what `hyper` gives: a variant's further keys name its mistake
PLAIN_KEYS = ("layer_types", "rms_norm_eps", "num_experts_per_tok",
              "expert_offset", "ssd_heads", "ssd_head_dim", "ssd_d_state",
              "ssd_groups", "embedding_multiplier", "lm_head_multiplier",
              "residual_multiplier", "attention_multiplier")


def hyper(model_cfg) -> Dict[str, Any]:
    """The numbers the reference needs, read by attribute name off the
    served model's config (any object with these attributes)."""
    kinds = list(model_cfg.layer_types)
    if MAMBA2 not in kinds or set(kinds) - {MAMBA2, GLOBAL}:
        raise ValueError("granitemoehybrid: mamba2 and full_attention "
                         "layers, a routed feed-forward behind each")
    if not model_cfg.tie_word_embeddings or not model_cfg.num_experts:
        raise ValueError("granitemoehybrid: a tied head, routed experts")
    if GLOBAL not in model_cfg.unrotated_kinds:
        raise ValueError("granitemoehybrid: attention that does not rotate")
    if model_cfg.moe_scoring != "softmax" or not model_cfg.attention_multiplier:
        raise ValueError("granitemoehybrid: the softmax rule and a published "
                         "softmax scale (attention_multiplier)")
    return {
        "layer_types": kinds,
        "rms_norm_eps": float(model_cfg.rms_norm_eps),
        "num_experts_per_tok": int(model_cfg.num_experts_per_tok),
        "expert_offset": int(model_cfg.expert_offset),
        "ssd_heads": int(model_cfg.ssd_heads),
        "ssd_head_dim": int(model_cfg.ssd_head_dim),
        "ssd_d_state": int(model_cfg.ssd_d_state),
        "ssd_groups": int(model_cfg.ssd_groups),
        "embedding_multiplier": float(model_cfg.embedding_multiplier),
        # (1 / `logits_scaling`, as the program's config holds it)
        "lm_head_multiplier": float(model_cfg.lm_head_multiplier),
        "residual_multiplier": float(model_cfg.residual_multiplier),
        "attention_multiplier": float(model_cfg.attention_multiplier),
    }


def _pass(params, hp, ids, positions_out, picks, forced_from: int):
    """One causal forward over `ids` [S]: the logits at `positions_out`, each
    row's smallest router gap over the layers, and the experts every layer's
    rows took [layers, S, k].  Rows from `forced_from` on take `picks`'."""
    frozen = _freeze({k: v for k, v in hp.items() if k != "forced"})
    kinds = list(hp["layer_types"])
    seen: Dict[str, int] = {}
    took = []
    x = _embed(params["embed"], ids,
               1.0 if hp.get("no_embedding_multiplier")
               else hp["embedding_multiplier"])
    min_gap = jnp.full((ids.shape[0],), jnp.inf)
    for l, kind in enumerate(kinds):
        nth = seen.get(kind, 0)
        seen[kind] = nth + 1
        x, gap, top = _layer(
            x, params["layers"], params["attn"][kind], jnp.int32(l),
            jnp.int32(nth), picks[l], jnp.int32(forced_from), hp=frozen,
            kind=kind)
        min_gap = jnp.minimum(min_gap, gap)
        took.append(top)
    logits = _head(x, params["final_norm"], params["embed"],
                   jnp.asarray(positions_out, jnp.int32),
                   1.0 if hp.get("no_logits_scaling")
                   else hp["lm_head_multiplier"], eps=hp["rms_norm_eps"])
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
    Each is `forced` as the served program is.  `conv_tail_zeroed_at_chunk`
    zeroes every convolution's tail, and `state_lost_at_chunk` every head's S,
    ahead of the run-in's first row, which resumes from the first launch's
    snapshot (a snapshot that was not restored)."""
    def forced(**mistake):
        return dict(hp, forced=True, **mistake)

    return {
        "bf16_accumulate": forced(bf16_accumulate=128),
        "bf16_accumulate_256": forced(bf16_accumulate=256),
        "bf16_state": forced(bf16_state=True),
        "bf16_router_logits": forced(bf16_router_logits=True),
        "no_residual_multiplier_mixer": forced(
            no_residual_multiplier_mixer=True),
        "no_residual_multiplier_ffn": forced(no_residual_multiplier_ffn=True),
        "scale_rsqrt_head_dim": forced(scale_rsqrt_head_dim=True),
        "renormalised_over_held": forced(renormalised_over_held=True),
        "softmax_over_all": forced(softmax_over_all=True),
        "rotation_on": forced(rotation_on=10000.0),
        "no_logits_scaling": forced(no_logits_scaling=True),
        "no_embedding_multiplier": forced(no_embedding_multiplier=True),
        "no_shared_expert": forced(no_shared_expert=True),
        "up_half_gated": forced(up_half_gated=True),
        "no_d_skip": forced(no_d_skip=True),
        "no_dt_bias": forced(no_dt_bias=True),
        "no_conv_bias": forced(no_conv_bias=True),
        "norm_per_head": forced(norm_per_head=True),
        "norm_before_gate": forced(norm_before_gate=True),
        "conv_tail_zeroed_at_chunk": forced(tail_lost_behind=1 - RUN_IN),
        "state_lost_at_chunk": forced(state_lost_behind=1 - RUN_IN),
    }
