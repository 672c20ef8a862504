"""Model configurations served by the TPU engine: Llama-style decoders
(dense or Mixtral-routed MLP), decoders whose layers follow a static
pattern of windowed and global attention with rotary parameters per kind
(Mellum2: three sliding-window layers, then one full-attention layer), and
`deepseek_v3`-style decoders: latent (MLA) attention whose cache row is one
compressed vector a token, leading dense layers ahead of routed ones, and
sigmoid-scored routing with a selection bias beside always-on shared experts.
A latent decoder may also mix KINDS of layer whose attention blocks have
sizes of their own (`dots3_note`: full layers that attend a learned top-k
selection of keys, chosen by an indexer with a cache row of its own, beside
sliding-window latent layers of another geometry), and hold a share of the
routed experts (one chip's part of an expert-parallel layer).  The same
feed-forward tree stands on grouped-query attention too (`exaone_moe`:
K-EXAONE), whose attention adds per-head QK-norm and full-attention layers
that do not rotate, and under a second hybrid layout (`lfm2_moe`:
LFM2-8B-A1B) whose layers are gated short convolutions, each with a two-row
tail in a state slot, beside full-attention layers that alone hold rows, and
under a third (`solar_open2`: Solar-Open2-250B) whose layers are gated
delta-rule linear attention, each with a matrix a head in a state slot, beside
gated full-attention layers that do not rotate.  A fourth (`falcon_h1`:
Falcon-H1-34B) is a dense decoder every layer of which holds rows AND a
state: grouped-query attention and a Mamba-2 (SSD) mixer side by side on one
normed input, under the family's muP multipliers.  A fifth (`nemotron_h`:
Nemotron-3-Nano-30B-A3B) gives every layer ONE sublayer: a Mamba-2 mixer that
stands alone, grouped-query attention that does not rotate, or a routed
feed-forward of ungated squared-ReLU experts, by a pattern of three kinds;
which half a layer has is its kind's (`mixer_of`, `has_ffn`).  A sixth
(`granitemoehybrid`: Granite-4.0-H) gives every layer TWO sublayers: a lone
Mamba-2 mixer OR grouped-query attention that does not rotate, then a
softmax-routed feed-forward beside a shared expert, each added into the stream
at `residual_multiplier`; attention's softmax scale is the published
`attention_multiplier`.  A seventh (`olmo_hybrid`: Olmo-Hybrid-7B) is the
third's layout with Gated DeltaNet layers (ONE decay a head, key and value
heads of sizes of their own: `delta_gate`, `delta_value_dim`), every layer
dense, the norms on the sublayers' OUTPUTS (`norm_position`) and multi-head
attention that does not rotate under a QK-norm over the whole projection
(`qk_norm_whole`).

The reference service routed model names to remote providers by string
heuristics (src/llm/utils.py:11-29); here a model name resolves to a local
architecture config + checkpoint path instead.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional, Tuple

import jax.numpy as jnp

from .vision import VisionConfig

# The kinds of layer a pattern is made of.  WINDOWED and GLOBAL are HF
# `layer_types` and own cache rows; three are `phi4flash`'s hybrid decoder's:
# a state-space mixer whose per-thread state is a fixed-size slot and no rows
# (MAMBA), a gated memory unit with no state at all (GMU), and attention that
# READS the last GLOBAL layer's rows and writes none (CROSS); CONV is
# `lfm2_moe`'s gated short convolution, whose state is the last
# conv_L_cache - 1 rows of its gated input and no rows either; DELTA is
# `solar_open2`'s gated delta-rule linear attention, whose state is a
# [head_dim, head_dim] matrix a head beside the tails of its three short
# convolutions, and no rows; PARALLEL is `falcon_h1`'s layer, grouped-query
# attention and a Mamba-2 (SSD) mixer on the same normed input, their outputs
# summed into the residual: the one kind that holds rows AND a state.  Two are
# `nemotron_h`'s, whose layers hold ONE sublayer each: MAMBA2, a Mamba-2 (SSD)
# mixer that stands alone (a state, no rows, no feed-forward), and MOE, a
# routed feed-forward that stands alone (no mixer, no state, no rows); beside
# them a GLOBAL layer is attention alone.  `granitemoehybrid` names MAMBA2
# and GLOBAL layers WITHOUT naming MOE ones: every layer then has its
# feed-forward behind the mixer (`ModelConfig.mixer_then_ffn`).
WINDOWED = "sliding_attention"
GLOBAL = "full_attention"
MAMBA = "mamba"
GMU = "gmu"
CROSS = "cross_attention"
CONV = "conv"
DELTA = "linear_attention"
PARALLEL = "parallel_ssd_attention"
MAMBA2 = "mamba2"
MOE = "moe"


def holds_rows(kind: str) -> bool:
    """A layer of `kind` holds rows of its own in the paged pool."""
    return kind in (WINDOWED, GLOBAL, PARALLEL)


def holds_state(kind: str) -> bool:
    """A layer of `kind` holds a recurrent state (a state slot a thread)."""
    return kind in (MAMBA, CONV, DELTA, PARALLEL, MAMBA2)


class UnsupportedConfigError(ValueError):
    """A published config.json asks for something the program cannot honour
    (a dense MLP among routed layers, un-normalised top-k weights, an unknown
    kind of layer or rope, a hybrid decoder whose layout is neither of the
    two served).  Raised, naming the key, instead of reading it silently."""


@dataclasses.dataclass(frozen=True)
class RopeParams:
    """Rotary parameters of ONE kind of layer (HF `rope_parameters[kind]`).
    rope_type "default": theta alone.  "yarn": HF `_compute_yarn_parameters`
    (ops/rope.yarn_frequencies); `attention_factor` None means HF's default
    0.1 * ln(factor) + 1."""

    rope_type: str = "default"
    rope_theta: float = 10000.0
    factor: float = 1.0
    original_max_position: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None
    # `deepseek_v3`'s `rope_scaling.mscale_all_dim` (0 = absent): the softmax
    # scale of a latent layer is then multiplied by (0.1 * mscale_all_dim *
    # ln(factor) + 1) ** 2 (`ModelConfig.latent_softmax_scale`)
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class LatentGeometry:
    """The sizes of ONE kind of latent-attention layer (the published
    `swa_*` keys give a sliding-window layer its own).  `q_lora_rank` 0 = no
    query low-rank: q = x W_q."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


def _tail_layout(rows: int, width: int) -> Tuple[int, int]:
    """A convolution's tail of `rows` x `width` values as a state slot holds
    it: over 8 rows where they divide so (a leaf whose second-minor axis is 3
    is tiled to 8 on the device: 2.7x the bytes)."""
    return (8, rows * width // 8) if rows * width % 8 == 0 else (rows, width)


def _lane_tiles(width: int) -> int:
    """`width` values padded to whole 128-lane tiles (Pallas page DMAs)."""
    return -(-width // 128) * 128


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters (decoder-only transformer: pre-norm,
    rotary GQA attention, SwiGLU MLP dense or routed; optionally a static
    per-layer pattern of windowed and global attention)."""

    name: str = "tiny"
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 16
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_context: int = 8192
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    # Llama-3.x rope scaling (NTK-by-parts). None disables.
    rope_scaling_factor: Optional[float] = None
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # Paged-decode attention backend: "xla" (gather + reference attention) or
    # "pallas" (ops/pallas paged kernel; interpret mode off-TPU).  Engines
    # resolve EngineConfig.attention_backend="auto" to one of these — plain
    # forward() callers keep the portable XLA path by default.
    attention_backend: str = "xla"
    # Chunked-prefill attention over an "sp" mesh axis (ring attention, the
    # chunk sequence-sharded; parallel/ring_attention.py).  Set by the
    # engine when its mesh has sp > 1; forward(..., mesh=...) must receive
    # the mesh.
    prefill_ring: bool = False
    # context-parallel strategy when prefill_ring is on: "ring" rotates KV
    # shards over ICI neighbors; "ulysses" all_to_alls to head-sharded
    # layout (parallel/ring_attention.py — needs heads/tp % sp == 0)
    cp_strategy: str = "ring"
    # Mixture-of-experts MLP (Mixtral-style): 0 = dense.  When >0 each
    # layer's MLP is num_experts stacked SwiGLU experts with top-k routing
    # (softmax over the top-k router logits); expert weights shard over
    # the "ep" mesh axis (parallel/sharding.py).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Vision input (Llava-style soft prompt, models/vision.py): a ViT +
    # projector encodes images into num_patches embeddings that replace
    # `image_token_id` placeholder positions at prefill.  None = text-only
    # (image parts answer a typed 400 at the provider).
    vision: Optional[VisionConfig] = None
    image_token_id: Optional[int] = None
    # Static layer pattern (HF `layer_types`): one kind per layer, WINDOWED
    # or GLOBAL.  Empty = every layer global, the model-wide rope fields
    # above: exactly what a config was before patterns existed.  A WINDOWED
    # layer's query at position p attends keys p - sliding_window < k <= p
    # (HF's sliding mask: `sliding_window` keys, the query's own included),
    # on every attention path.  `rope_by_kind` holds (kind, RopeParams)
    # pairs; a kind without an entry uses the model-wide fields.
    layer_types: Tuple[str, ...] = ()
    sliding_window: Optional[int] = None
    rope_by_kind: Tuple[Tuple[str, RopeParams], ...] = ()
    # Latent attention (MLA, HF `deepseek_v3` without a query low-rank):
    # kv_lora_rank > 0 turns it on.  A token caches ONE row shared by all
    # heads: the normed latent c~ (kv_lora_rank) and the roped key part k_r
    # (qk_rope_head_dim); a head's keys and values are c~ through its slice
    # of W_kvb ([k_nope (qk_nope_head_dim) | v (v_head_dim)]).  `head_dim`
    # is the published one, the rotary width.  `num_kv_heads` is published
    # too and means nothing for the cache.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rotary pairs are published interleaved (x0 x1 | x2 x3 ...): the values
    # are de-interleaved, then rotated half-split (HF
    # `apply_rotary_pos_emb_interleave`)
    rope_interleave: bool = False
    # The first `first_k_dense` layers have a dense SwiGLU MLP of
    # `dense_intermediate_size`; the rest are routed.  0 = homogeneous.
    first_k_dense: int = 0
    dense_intermediate_size: int = 0
    # Always-on shared experts beside the routed ones: one SwiGLU of this
    # width (n_shared_experts * moe_intermediate_size).  0 = none.
    shared_intermediate_size: int = 0
    # Routing rule.  "softmax": softmax over exactly the top-k logits
    # (Mixtral).  "sigmoid": the top-k of sigmoid(logits) + a per-expert
    # selection bias are chosen, weighed by sigmoid(logits) alone,
    # renormalised over the chosen and scaled by `routed_scaling_factor`
    # (HF deepseek_v3 `noaux_tc` with one group).
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # -- latent attention past Kanana-2's block; any of these makes the
    # attention leaves and the paged pool per KIND of layer (`by_kind`) --
    # query low-rank: c_q = rms(x W_qa), q = c_q W_qb
    q_lora_rank: int = 0
    # the normed latents are multiplied by sqrt(hidden_size / rank)
    # (`apply_mla_qkv_lora_rescale`)
    latent_rescale: bool = False
    # "headwise": head h's attention output times sigmoid(x W_g)[h] ahead
    # of W_o ("" = no gate)
    attention_gate: str = ""
    # Learned key selection on GLOBAL layers (DeepSeek-V3.2's indexer):
    # index_topk > 0 turns it on.  A token caches one more row, the
    # indexer's key k^I (index_head_dim); a query scores every causal key
    # with index_n_heads small heads and attends only the index_topk best.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # the sizes of a WINDOWED layer's latent block where they are its own
    # (None = the model-wide ones above)
    windowed_latent: Optional[LatentGeometry] = None
    # Experts HELD: `num_experts` expert leaves are stored and computed,
    # experts expert_offset .. expert_offset + num_experts of the
    # `num_experts_routed` the router knows (0 = all of them, held whole).
    # A token's weights are chosen and renormalised over all the router's
    # experts; what the absent ones would add is left out.
    num_experts_routed: int = 0
    expert_offset: int = 0
    # Per-head RMSNorm of q and k over head_dim, with a learned weight a
    # layer, ahead of the rotation (GQA attention; EXAONE-4's QK-norm).
    qk_norm: bool = False
    # Kinds of layer whose q and k are NOT rotated (EXAONE-4's hybrid: the
    # full-attention layers carry no positional signal of their own).
    unrotated_kinds: Tuple[str, ...] = ()
    # Published `num_nextn_predict_layers`: the multi-token-prediction
    # module is recorded and NOT built (a loader that drops `mtp.*` serves
    # the model); the engine refuses speculative decoding for such a model.
    nextn_predict_layers: int = 0
    # -- a widened residual stream (`xing4_0`; `_hc_in` / `_hc_out` in
    # models/residual.py; "mHC: Manifold-Constrained Hyper-Connections"):
    # `hc_mult` = n > 1 turns it on.  A token's state is n rows of
    # hidden_size; every sublayer reads one row mixed from them by a
    # per-token H_pre (sigmoid) and writes back H_res X + H_post^T y, H_post
    # = 2 sigmoid and H_res an n x n matrix made doubly stochastic by
    # `hc_sinkhorn_iters` Sinkhorn rounds over exp of its clamped logits
    # (`hc_eps` in every divisor).  1 = absent: one row, `h + y`, and not an
    # op traced.  The stream lives inside `forward` alone. --
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # -- a hybrid decoder (`phi4flash`; models/hybrid.py): `mamba_d_state`
    # > 0 turns it on and `layer_types` then names MAMBA / GMU / CROSS
    # layers beside the attention kinds.  A MAMBA layer is a Mamba-1 mixer
    # of inner width mamba_expand * hidden_size whose per-thread state (the
    # last mamba_d_conv - 1 conv inputs and h [d_state, inner], float32)
    # lives in a STATE SLOT beside the pages (runtime/kv_cache.py); only
    # WINDOWED / GLOBAL layers hold rows in the paged pool. --
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # -- the second hybrid layout (`lfm2_moe`; the conv mixer in
    # mixers/state.py): `conv_L_cache` > 0 turns it on and `layer_types` then
    # names CONV layers beside GLOBAL ones, in any order.  A CONV layer is a
    # gated short convolution: [B | C | u] = x W_in, a causal depthwise conv
    # of conv_L_cache taps over B * u, times C, through W_out.  Its
    # per-thread state is the last conv_L_cache - 1 rows of B * u, in a state
    # slot of its own shape; the rest of the decoder (RMSNorm, rotation,
    # QK-norm, the dense lead and the routed experts) is the lead-and-routed
    # tree's. --
    conv_L_cache: int = 0
    # -- the third hybrid layout (`solar_open2`; `_delta_attention_block` in
    # mixers/state.py): `delta_heads` > 0 turns it on and `layer_types` then
    # names DELTA layers beside GLOBAL ones, in any order.  A DELTA layer is
    # the gated delta rule with a decay per key channel (Kimi Delta
    # Attention): delta_heads heads of delta_head_dim keys and values, q / k /
    # v each through a depthwise causal convolution of delta_conv_kernel taps
    # and SiLU, the decay and the output gate through low-rank pairs of
    # delta_head_dim, beta in (0, 2) where `delta_neg_eigval`.  Its per-thread
    # state is the convolutions' delta_conv_kernel - 1 rows and S^T,
    # [delta_heads * delta_head_dim, delta_head_dim] float32, in a state slot
    # of its own shape. --
    delta_heads: int = 0
    delta_head_dim: int = 0
    delta_conv_kernel: int = 4
    delta_neg_eigval: bool = False
    # Gated DeltaNet's form of the same layer (`olmo_hybrid`; arXiv:2412.06464):
    # `delta_gate` "head" is ONE log-decay a head from a full-rank projection
    # [H, heads] (no low-rank pair) and a full-rank SiLU output gate, where
    # "channel" is the decay a key channel above; `delta_value_dim` > 0 is a
    # value head of another size than the key head's (0 = delta_head_dim:
    # square heads).  The state a head is then [delta_head_dim,
    # delta_value_dim], NOT transposed, the heads side by side along the
    # lanes (ops/pallas/gdn.py).
    delta_gate: str = "channel"
    delta_value_dim: int = 0
    # Where a sublayer's RMSNorm sits: "pre", h + F(norm(h)), or "post", h +
    # norm(F(h)), the reordered norm of Olmo 2 / Olmo 3 (on each sublayer's
    # OUTPUT, none on its input; the leaves keep the names ln_attn / ln_mlp).
    norm_position: str = "pre"
    # QK-norm over the WHOLE projection (all heads' values under one weight
    # of heads x head_dim) ahead of the split into heads, where `qk_norm`
    # alone is a norm a head.
    qk_norm_whole: bool = False
    # -- the parallel layout (`falcon_h1`; `_ssd_block` in mixers/state.py):
    # `ssd_heads` > 0 turns it on and every layer is then PARALLEL:
    # grouped-query attention and a Mamba-2 (SSD) mixer read ONE normed input
    # and both add into the residual.  The mixer has ssd_heads heads of
    # ssd_head_dim channels, a scalar decay a head, B and C of ssd_d_state
    # values shared by the heads of each of ssd_groups groups, a depthwise
    # causal convolution of ssd_conv_kernel taps with a bias over [x | B | C]
    # and a gated RMSNorm over each group's channels.  A layer holds rows in
    # the paged pool AND, in a state slot, the convolution's ssd_conv_kernel -
    # 1 rows and the heads' states [ssd_heads * ssd_head_dim, ssd_d_state]
    # float32 (ops/pallas/ssd.py). --
    ssd_heads: int = 0
    ssd_head_dim: int = 0
    ssd_d_state: int = 0
    ssd_groups: int = 1
    ssd_conv_kernel: int = 4
    # -- the family's muP scalars, applied to ACTIVATIONS where the equations
    # put them and folded into no weight; 1.0 (and () for the two vectors) =
    # absent, so a model without them traces as it always did.
    # `ssm_multipliers` is (z, x, B, C, dt) over the column ranges of the
    # mixer's input projection, `mlp_multipliers` (gate, down). --
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = ()
    mlp_multipliers: Tuple[float, ...] = ()
    # `granitemoehybrid`'s two: every sublayer's output enters the stream
    # times `residual_multiplier`, h <- h + r F(norm(h)) (1.0 = absent), and
    # grouped-query attention's softmax scale is `attention_multiplier` in
    # place of head_dim ** -0.5 (0.0 = absent: `softmax_scale` None)
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    # The feed-forward's activation, a row of models/ffn.ACTIVATIONS: "silu"
    # is the gated SwiGLU every other model has (gate, up and down matrices);
    # "relu2" is `nemotron_h`'s UNGATED squared ReLU, down(relu(up(x)) ** 2):
    # two matrices a block, in the experts and the shared branch alike.
    mlp_act: str = "silu"

    def __post_init__(self):
        if self.mlp_act not in ("silu", "relu2"):
            raise UnsupportedConfigError(
                f"mlp_act {self.mlp_act!r}: known 'silu' (gated), 'relu2' "
                "(ungated squared ReLU)")
        if self.mlp_act != "silu" and not self.lone_layers:
            raise UnsupportedConfigError(
                f"mlp_act {self.mlp_act!r} is built in the one-sublayer "
                "layout's routed feed-forward only (layer_types naming "
                f"{MOE!r} layers)")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise UnsupportedConfigError(
                f"moe_scoring {self.moe_scoring!r}: known 'softmax', "
                "'sigmoid'")
        if self.attention_gate not in ("", "headwise", "elementwise"):
            raise UnsupportedConfigError(
                f"attention_gate_type {self.attention_gate!r} is not "
                "served: 'headwise' (latent attention) and 'elementwise' "
                "(grouped-query attention) are")
        if (self.index_topk or self.windowed_latent or self.q_lora_rank
                or self.attention_gate == "headwise" or self.latent_rescale) \
                and not self.is_latent:
            raise UnsupportedConfigError(
                "a query low-rank, a latent rescale, a headwise attention "
                "gate, an indexer and per-kind attention sizes are built "
                "with latent attention (kv_lora_rank) only")
        if self.attention_gate == "elementwise" and (
                self.is_latent or self.mamba_d_state):
            raise UnsupportedConfigError(
                "attention_gate_type 'elementwise' is built on grouped-query "
                "attention only (no latent attention, no Mamba decoder)")
        if self.index_topk and not (self.index_n_heads > 0
                                    and self.index_head_dim > 0):
            raise UnsupportedConfigError(
                "index_topk needs index_n_heads and index_head_dim")
        if (self.qk_norm or self.unrotated_kinds) and (
                self.is_latent or self.mamba_d_state):
            raise UnsupportedConfigError(
                "qk_norm and unrotated_kinds are built with grouped-query "
                "attention only (no latent attention, no Mamba decoder)")
        if self.qk_norm_whole and not self.qk_norm:
            raise UnsupportedConfigError(
                "qk_norm_whole says how wide qk_norm is: it needs qk_norm")
        if self.norm_position not in ("pre", "post"):
            raise UnsupportedConfigError(
                f"norm_position {self.norm_position!r}: known 'pre', 'post'")
        if self.norm_position == "post" and (
                self.mamba_d_state or self.lone_layers or self.hc_mult > 1
                or self.residual_multiplier != 1.0):
            raise UnsupportedConfigError(
                "norm_position 'post' (a norm on each sublayer's output) is "
                "built on the two-sublayer layer of models/llama.forward "
                "with a one-row stream and no residual_multiplier")
        if set(self.unrotated_kinds) - {WINDOWED, GLOBAL}:
            raise UnsupportedConfigError(
                f"unrotated_kinds {list(self.unrotated_kinds)}: known "
                f"{[WINDOWED, GLOBAL]}")
        if self.num_experts_routed and not (
                0 <= self.expert_offset and self.expert_offset
                + self.num_experts <= self.num_experts_routed):
            raise UnsupportedConfigError(
                "a share of the routed experts (num_experts_routed) needs "
                "expert_offset + num_experts within the router's width")
        if self.num_experts_routed and self.moe_scoring == "softmax" \
                and not self.mixer_then_ffn:
            # (the homogeneous stack shards whole experts over "ep"; a held
            # share is the lead-and-routed tree's)
            raise UnsupportedConfigError(
                "a share of the routed experts (num_experts_routed) under "
                "the softmax rule is built in the mixer-then-feed-forward "
                f"layout only (layer_types naming {MAMBA2!r} and no {MOE!r} "
                "layers); elsewhere it needs sigmoid routing")
        if self.attention_multiplier < 0 or self.residual_multiplier <= 0:
            raise UnsupportedConfigError(
                f"attention_multiplier = {self.attention_multiplier!r} and "
                f"residual_multiplier = {self.residual_multiplier!r}: a "
                "softmax scale and a residual scale are positive")
        if self.attention_multiplier and (self.is_latent
                                          or self.mamba_d_state):
            raise UnsupportedConfigError(
                "attention_multiplier (a published softmax scale) is built "
                "on grouped-query attention only (no latent attention, no "
                "Mamba-1 decoder)")
        if self.residual_multiplier != 1.0 and (
                self.hc_mult > 1 or self.mamba_d_state):
            raise UnsupportedConfigError(
                "residual_multiplier is built on the one-row residual "
                "stream of models/llama.forward (no hc_mult > 1, no Mamba-1 "
                "decoder)")
        if self.hc_mult < 1 or self.hc_mult > 1 and (
                self.hc_sinkhorn_iters < 1 or not self.is_latent
                or self.has_state):
            raise UnsupportedConfigError(
                f"hc_mult = {self.hc_mult} (a residual stream of that many "
                "rows a token) is built with latent attention "
                "(kv_lora_rank), hc_sinkhorn_iters >= 1 and no "
                "state-holding kind of layer")
        if self.first_k_dense and not (
                self.is_moe and 0 < self.first_k_dense < self.num_layers
                and self.dense_intermediate_size > 0):
            raise UnsupportedConfigError(
                "first_k_dense needs routed layers after the dense ones and "
                "a dense_intermediate_size")
        if not self.layer_types:
            if self.mamba_d_state:
                raise UnsupportedConfigError(
                    "mamba_d_state needs layer_types naming the mamba layers")
            return
        known = {WINDOWED, GLOBAL} | (
            {MAMBA, GMU, CROSS} if self.mamba_d_state else set()) | (
            {CONV} if self.conv_L_cache else set()) | (
            {DELTA} if self.delta_heads else set()) | (
            {PARALLEL, MAMBA2, MOE} if self.ssd_heads else set())
        bad = set(self.layer_types) - known
        if bad:
            raise UnsupportedConfigError(
                f"layer_types holds unknown kinds {sorted(bad)}; known: "
                f"{sorted(known)}")
        if self.mamba_d_state:
            self._check_hybrid()
        if self.conv_L_cache:
            self._check_conv_layout()
        if self.delta_heads:
            self._check_delta_layout()
        if self.lone_layers:
            self._check_lone_layout()
        elif MAMBA2 in self.layer_types:
            self._check_mixer_then_ffn_layout()
        elif self.ssd_heads:
            self._check_parallel_layout()
        if len(self.layer_types) != self.num_layers:
            raise UnsupportedConfigError(
                f"layer_types has {len(self.layer_types)} entries for "
                f"{self.num_layers} layers")
        if WINDOWED in self.layer_types and not (
                self.sliding_window and self.sliding_window > 0):
            raise UnsupportedConfigError(
                "a sliding_attention layer needs a positive sliding_window")

    def _check_hybrid(self) -> None:
        """`phi4flash`'s layout (models/hybrid.py): n x [mamba, sliding],
        then [mamba, full], then m x [gmu, cross]; the cross layers read the
        one full layer's rows, the gmu layers the last mamba layer's
        memory."""
        kinds = self.layer_types
        n_self = 2 * kinds.count(MAMBA)
        want = ((MAMBA, WINDOWED) * (n_self // 2 - 1) + (MAMBA, GLOBAL)
                + (GMU, CROSS) * ((len(kinds) - n_self) // 2))
        if kinds != want or MAMBA not in kinds:
            raise UnsupportedConfigError(
                "a hybrid decoder is served as n x [mamba, "
                "sliding_attention], [mamba, full_attention], then m x "
                f"[gmu, cross_attention]; layer_types is {list(kinds)}")
        if self.is_latent or self.is_moe or self.vision is not None:
            raise UnsupportedConfigError(
                "the Mamba hybrid decoder is dense GQA: no latent attention, "
                "experts or vision tower")
        if (self.num_heads % 2 or self.num_kv_heads % 2
                or self.num_heads % self.num_kv_heads):
            raise UnsupportedConfigError(
                "differential attention pairs heads: even head counts, "
                "query heads a multiple of the key-value heads")
        if self.mamba_dt_rank <= 0 or self.mamba_d_conv < 2:
            raise UnsupportedConfigError(
                "mamba_dt_rank and mamba_d_conv >= 2 are needed")

    def _check_conv_layout(self) -> None:
        """The second hybrid layout (`lfm2_moe`): short convolutions and full
        attention in whatever order `layer_types` gives, judged by what the
        program needs of it and not against a sequence.  The mixers are
        chosen by kind inside the lead-and-routed tree's period body
        (models/llama.forward), so the order is free; what is needed is a
        tail to carry (two taps or more), rows for some layer to hold (the
        page table and the prefix cache key on pages), grouped-query
        attention on one model-wide rope, and no second kind of state."""
        kinds = set(self.layer_types)
        if CONV not in kinds or kinds - {CONV, GLOBAL}:
            raise UnsupportedConfigError(
                "conv_L_cache is served with layer_types of conv and "
                f"full_attention layers; layer_types is "
                f"{list(self.layer_types)}")
        if GLOBAL not in kinds:
            raise UnsupportedConfigError(
                "layer_types names no full_attention layer: the paged pool "
                "and the prefix cache need one layer that holds rows")
        if self.conv_L_cache < 2:
            raise UnsupportedConfigError(
                f"conv_L_cache = {self.conv_L_cache}: a short convolution "
                "of two taps or more is served (its state is the "
                "conv_L_cache - 1 rows before the pass)")
        if self.is_latent or self.mamba_d_state or self.vision is not None:
            raise UnsupportedConfigError(
                "conv layers stand beside grouped-query attention: no latent "
                "attention, Mamba layers or vision tower")

    def _check_delta_layout(self) -> None:
        """The third hybrid layout (`solar_open2`): linear attention and full
        attention in whatever order `layer_types` gives, judged by what the
        program needs of it, as the conv layout is: a matrix to carry, a
        tail to carry (two taps or more), rows for some layer to hold,
        grouped-query attention, and no second kind of state."""
        kinds = set(self.layer_types)
        if DELTA not in kinds or kinds - {DELTA, GLOBAL}:
            raise UnsupportedConfigError(
                "linear attention is served with layer_types of "
                "linear_attention and full_attention layers; layer_types is "
                f"{list(self.layer_types)}")
        if GLOBAL not in kinds:
            raise UnsupportedConfigError(
                "layer_types names no full_attention layer: the paged pool "
                "and the prefix cache need one layer that holds rows")
        if self.delta_head_dim <= 0 or self.delta_conv_kernel < 2:
            raise UnsupportedConfigError(
                f"linear attention needs a head size (delta_head_dim = "
                f"{self.delta_head_dim}) and a short convolution of two taps "
                f"or more (delta_conv_kernel = {self.delta_conv_kernel})")
        if self.delta_gate not in ("channel", "head"):
            raise UnsupportedConfigError(
                f"delta_gate {self.delta_gate!r}: known 'channel' (a decay a "
                "key channel), 'head' (one decay a head)")
        if self.delta_value_dim < 0 or (
                self.delta_value_dim and self.delta_gate != "head"):
            raise UnsupportedConfigError(
                f"delta_value_dim = {self.delta_value_dim} (a value head of "
                "its own size) is built with delta_gate 'head' only: a decay "
                "a key channel keeps square heads")
        if (self.is_latent or self.mamba_d_state or self.conv_L_cache
                or self.vision is not None):
            raise UnsupportedConfigError(
                "linear-attention layers stand beside grouped-query "
                "attention: no latent attention, Mamba or conv layers, or "
                "vision tower")

    def _check_parallel_layout(self) -> None:
        """The parallel layout (`falcon_h1`): every layer PARALLEL, judged by
        what the program needs of it: a state to carry (heads, a head size, a
        state size), groups that divide the heads, a tail to carry (two taps
        or more), the homogeneous dense tree on grouped-query attention, and
        no second kind of state."""
        if set(self.layer_types) != {PARALLEL}:
            raise UnsupportedConfigError(
                "an SSD mixer beside attention is served with every layer "
                f"of kind {PARALLEL}; layer_types is "
                f"{list(self.layer_types)}")
        self._check_ssd_mixer()
        if self.ssm_multipliers and len(self.ssm_multipliers) != 5:
            raise UnsupportedConfigError(
                f"ssm_multipliers has {len(self.ssm_multipliers)} entries: "
                "five are served (z, x, B, C, dt)")
        if self.mlp_multipliers and len(self.mlp_multipliers) != 2:
            raise UnsupportedConfigError(
                f"mlp_multipliers has {len(self.mlp_multipliers)} entries: "
                "two are served (gate, down)")
        if (self.is_latent or self.is_moe or self.mamba_d_state
                or self.conv_L_cache or self.delta_heads
                or self.vision is not None or self.lead_tree):
            raise UnsupportedConfigError(
                "the parallel layout is a dense grouped-query decoder: no "
                "latent attention, experts, Mamba-1, conv or linear-attention "
                "layers, or vision tower")

    def _check_ssd_mixer(self) -> None:
        """What an SSD mixer needs wherever it stands: a state to carry
        (heads, a head size, a state size), groups that divide the heads, a
        tail to carry (two taps or more)."""
        if (self.ssd_head_dim <= 0 or self.ssd_d_state <= 0
                or self.ssd_groups <= 0 or self.ssd_heads % self.ssd_groups
                or self.ssd_conv_kernel < 2):
            raise UnsupportedConfigError(
                f"the SSD mixer needs a head size (ssd_head_dim = "
                f"{self.ssd_head_dim}), a state size (ssd_d_state = "
                f"{self.ssd_d_state}), groups that divide its heads "
                f"({self.ssd_heads} heads, {self.ssd_groups} groups) and a "
                f"convolution of two taps or more (ssd_conv_kernel = "
                f"{self.ssd_conv_kernel})")

    def _check_lone_layout(self) -> None:
        """The one-sublayer layout (`nemotron_h`): every layer ONE sublayer,
        a lone SSD mixer (MAMBA2), attention (GLOBAL) or a routed feed-forward
        (MOE), in whatever order `layer_types` gives, judged by what the
        program needs of it: an SSD mixer's geometry, rows for some layer to
        hold (the page table and the prefix cache key on pages), experts
        routed by the sigmoid rule for the MOE layers, grouped-query
        attention, one row a token and no second kind of state."""
        kinds = set(self.layer_types)
        if kinds - {MAMBA2, GLOBAL, MOE}:
            raise UnsupportedConfigError(
                "a one-sublayer pattern is served with layer_types of "
                f"{MAMBA2}, {GLOBAL} and {MOE} layers; layer_types is "
                f"{list(self.layer_types)}")
        if GLOBAL not in kinds:
            raise UnsupportedConfigError(
                "layer_types names no full_attention layer: the paged pool "
                "and the prefix cache need one layer that holds rows")
        if MOE not in kinds:
            raise UnsupportedConfigError(
                f"a lone SSD mixer ({MAMBA2}) is served in a pattern that "
                f"names {MOE} layers (the one-sublayer layout); layer_types "
                f"is {list(self.layer_types)}")
        if MAMBA2 in kinds:
            self._check_ssd_mixer()
        if not (self.is_moe and self.moe_scoring == "sigmoid"):
            raise UnsupportedConfigError(
                f"a {MOE} layer is a routed feed-forward: it needs experts "
                "(num_experts) routed by the sigmoid rule")
        if (self.is_latent or self.mamba_d_state or self.conv_L_cache
                or self.delta_heads or self.vision is not None
                or self.first_k_dense or self.hc_mult > 1 or self.qk_norm
                or self.ssm_multipliers or self.mlp_multipliers):
            raise UnsupportedConfigError(
                "the one-sublayer layout stands on grouped-query attention "
                "and one row a token: no latent attention, Mamba-1, conv or "
                "linear-attention layers, vision tower, dense lead, widened "
                "residual stream (hc_mult > 1), QK-norm or muP multipliers")

    def _check_mixer_then_ffn_layout(self) -> None:
        """The mixer-then-feed-forward layout (`granitemoehybrid`): every
        layer a lone SSD mixer (MAMBA2) OR attention (GLOBAL), and BEHIND it,
        under a norm of its own, the routed feed-forward, in whatever order
        `layer_types` gives, judged by what the program needs of it: an SSD
        mixer's geometry, rows for some layer to hold (the page table and the
        prefix cache key on pages), experts for the feed-forward (the tree is
        the lead-and-routed one with the mixers' leaves per kind), grouped-
        query attention, one row a token and no second kind of state."""
        kinds = set(self.layer_types)
        if kinds - {MAMBA2, GLOBAL}:
            raise UnsupportedConfigError(
                f"a lone SSD mixer ({MAMBA2}) with a feed-forward behind it "
                f"is served with layer_types of {MAMBA2} and {GLOBAL} layers "
                f"(and with {MOE} layers in the one-sublayer layout); "
                f"layer_types is {list(self.layer_types)}")
        if GLOBAL not in kinds:
            raise UnsupportedConfigError(
                "layer_types names no full_attention layer: the paged pool "
                "and the prefix cache need one layer that holds rows")
        self._check_ssd_mixer()
        if not self.is_moe:
            raise UnsupportedConfigError(
                f"a {MAMBA2} layer with a feed-forward behind it is served "
                "with a ROUTED feed-forward (num_experts): the dense tree "
                "has no leaves per kind")
        if (self.is_latent or self.mamba_d_state or self.conv_L_cache
                or self.delta_heads or self.vision is not None
                or self.first_k_dense or self.hc_mult > 1 or self.qk_norm
                or self.ssm_multipliers or self.mlp_multipliers
                or self.mlp_act != "silu"):
            raise UnsupportedConfigError(
                "the mixer-then-feed-forward layout stands on grouped-query "
                "attention, gated SiLU experts and one row a token: no "
                "latent attention, Mamba-1, conv or linear-attention layers, "
                "vision tower, dense lead, widened residual stream (hc_mult "
                "> 1), QK-norm or muP vectors")

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def has_state(self) -> bool:
        """Some KIND of layer this model has carries a recurrent per-thread
        state (a state slot)."""
        return any(holds_state(kind) for kind in self.layer_types)

    @property
    def hybrid_decoder(self) -> bool:
        """`phi4flash`'s decoder-hybrid-decoder, a forward pass and a
        parameter tree of its own (models/hybrid.py)."""
        return MAMBA in self.layer_types

    @property
    def lone_layers(self) -> bool:
        """Every layer holds ONE sublayer (`nemotron_h`: a mixer OR a
        feed-forward, one norm, one residual add), which a layout shows by
        naming layers that are a routed feed-forward alone."""
        return MOE in self.layer_types

    @property
    def mixer_then_ffn(self) -> bool:
        """Lone SSD mixers (and attention) each with the feed-forward BEHIND
        them in the same layer (`granitemoehybrid`): a layout that names
        MAMBA2 layers and no layer that is a feed-forward alone."""
        return MAMBA2 in self.layer_types and not self.lone_layers

    @property
    def softmax_scale(self) -> Optional[float]:
        """Grouped-query attention's softmax scale where the config
        publishes one (`attention_multiplier`); None: head_dim ** -0.5, which
        every attention path defaults to itself (and not an argument
        traced)."""
        return self.attention_multiplier or None

    def has_ffn(self, kind: str) -> bool:
        """A layer of `kind` has a feed-forward half: every layer, but in the
        one-sublayer layout the MOE layers alone."""
        return not self.lone_layers or kind == MOE

    @property
    def routed_layers(self) -> int:
        """Layers whose feed-forward is the routed block."""
        if not self.is_moe:
            return 0
        if self.lone_layers:
            return self.layers_of(MOE)
        return self.num_layers - self.first_k_dense

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def ssd_conv_dim(self) -> int:
        """Channels of the SSD mixer's convolution: [x | B | C]."""
        return (self.ssd_heads * self.ssd_head_dim
                + 2 * self.ssd_groups * self.ssd_d_state)

    @property
    def delta_v_dim(self) -> int:
        """A linear-attention head's VALUE size (the key size where the
        config names no other)."""
        return self.delta_value_dim or self.delta_head_dim

    @property
    def delta_conv_dim(self) -> int:
        """Channels of a linear-attention layer's three convolutions side by
        side: [q | k | v]."""
        return self.delta_heads * (2 * self.delta_head_dim
                                   + self.delta_v_dim)

    @property
    def state_layers(self) -> int:
        """Layers that hold a recurrent state."""
        return sum(holds_state(kind) for kind in self.layer_types)

    @property
    def kv_layers(self) -> int:
        """Layers that hold rows in the paged pool: all of them, but for a
        hybrid decoder, whose attention layers with K/V of their own do."""
        if not self.has_state:
            return self.num_layers
        return sum(holds_rows(kind) for kind in self.layer_types)

    def state_shapes(self) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
        """(leaf, shape of ONE slot of ONE layer) of the recurrent state,
        float32: THE definition of a state slot, which the allocation
        (runtime/kv_cache.make_state_arrays), the memory plan and /metrics
        ask, of the KIND of state layer the model has.  A Mamba layer: the
        conv tail and `ssm`, h transposed, [d_state, inner] (the wide axis in
        the lanes).  A short convolution: its tail alone.  A linear-attention
        layer: the tails of its three convolutions side by side (q | k | v),
        their taps - 1 rows laid out over 8 rows where they divide so (a leaf
        whose second-minor axis is 3 is tiled to 8 on the device: 2.7x the
        bytes, and the layer scan copies such a leaf whole every pass), and
        `delta`, S transposed a head, the heads stacked along the rows
        (ops/pallas/gated_delta.py).  A parallel layer: the tail of its one
        convolution over [x | B | C], laid out by the same rule, and `ssd`,
        the heads' states [head size, state size] stacked along the rows
        (ops/pallas/ssd.py); a lone SSD mixer's slot is the same."""
        if PARALLEL in self.layer_types or MAMBA2 in self.layer_types:
            return (("conv", _tail_layout(self.ssd_conv_kernel - 1,
                                          self.ssd_conv_dim)),
                    ("ssd", (self.ssd_heads * self.ssd_head_dim,
                             self.ssd_d_state)))
        if MAMBA in self.layer_types:
            di = self.mamba_d_inner
            return (("conv", (self.mamba_d_conv - 1, di)),
                    ("ssm", (self.mamba_d_state, di)))
        if CONV in self.layer_types:
            # the rows of B * u before the pass: nothing accumulates
            return (("conv", (self.conv_L_cache - 1, self.hidden_size)),)
        if DELTA in self.layer_types:
            tail = _tail_layout(self.delta_conv_kernel - 1,
                                self.delta_conv_dim)
            if self.delta_gate == "head":
                # S itself, [d_k, d_v] a head, the heads side by side along
                # the lanes (ops/pallas/gdn.py): 96 x 5,760 is whole tiles
                # where S transposed would pad 96 lanes to 128
                return (("conv", tail),
                        ("delta", (self.delta_head_dim,
                                   self.delta_heads * self.delta_v_dim)))
            wide = self.delta_heads * self.delta_head_dim
            return (("conv", tail), ("delta", (wide, self.delta_head_dim)))
        return ()

    @property
    def state_bytes_per_slot(self) -> int:
        """Bytes one state slot holds over all state layers (float32)."""
        return 4 * self.state_layers * sum(
            a * b for _, (a, b) in self.state_shapes())

    @property
    def pattern(self) -> Tuple[int, Tuple[str, ...]]:
        """(lead, period) of the layers after the leading dense ones: `lead`
        layers that stand alone ahead of whole periods of `period` kinds,
        the split with the fewest unrolled bodies (lead + period; ties to
        the shorter lead).  (0, (GLOBAL,)) for a config without a pattern.
        The layer scan runs over whole periods and the lead layers unrolled
        ahead of it (models/llama.forward).  dots3's 45 routed layers, one
        full and then 11 x (3 sliding, 1 full), give (1, (s, s, s, f))."""
        kinds = self.layer_types[self.first_k_dense:]
        n = len(kinds)
        best = None
        for p in range(1, n + 1):
            lead = n % p
            rest = kinds[lead:]
            if rest == rest[:p] * (len(rest) // p) and (
                    best is None or (lead + p, lead) < (
                        best[0] + len(best[1]), best[0])):
                best = (lead, rest[:p])
        return best or (0, (GLOBAL,))

    @property
    def layer_period(self) -> Tuple[str, ...]:
        """The kinds of one period of the pattern (see `pattern`)."""
        return self.pattern[1]

    def kind_of(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else GLOBAL

    def layers_of(self, kind: str) -> int:
        """How many layers are of `kind`."""
        if not self.layer_types:
            return self.num_layers if kind == GLOBAL else 0
        return self.layer_types.count(kind)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The kinds of layer this model has, in order of first use."""
        return tuple(dict.fromkeys(self.layer_types)) or (GLOBAL,)

    @property
    def is_windowed(self) -> bool:
        return WINDOWED in self.layer_types

    def mixer_of(self, kind: str) -> Optional[str]:
        """Which mixer a layer of `kind` takes: the key of its row in
        `models/mixers.MIXERS`; None for a kind that has none (a layer that
        is a feed-forward alone)."""
        return {CONV: "conv", DELTA: "delta", PARALLEL: "ssd",
                MAMBA2: "mamba2", MOE: None}.get(
            kind, "latent" if self.is_latent else "gqa")

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == WINDOWED else None

    def rope_of(self, kind: str) -> Optional[RopeParams]:
        return dict(self.rope_by_kind).get(kind)

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def lead_tree(self) -> bool:
        """The parameter tree is the lead-and-routed one ("dense_layers"
        beside "layers", a selection bias, a shared branch: init_params.py's
        `_init_lead_tree_params`, per kind `_init_kind_params`), not the
        homogeneous stack of `init_params`."""
        return bool(self.is_latent or self.first_k_dense
                    or self.shared_intermediate_size
                    or self.moe_scoring != "softmax"
                    or CONV in self.layer_types
                    or DELTA in self.layer_types
                    or self.mixer_then_ffn)

    @property
    def kind_leaves(self) -> bool:
        """The mixer's leaves are stacked per KIND of layer, under
        `params["attn"][kind]` in layer order, and "layers" /
        "dense_layers" hold the norms and the feed-forward leaves: a latent
        model whose kinds differ, and the conv and linear-attention layouts
        (a CONV or DELTA layer's leaves have nothing in common with an
        attention layer's), the mixer-then-feed-forward layout (a MAMBA2
        layer's neither), and the one-sublayer layout, whose routed
        feed-forward's leaves are stacked per kind too, under
        `params["ffn"][kind]`, and whose "layers" holds each layer's one
        norm."""
        return (self.by_kind or CONV in self.layer_types
                or DELTA in self.layer_types or self.lone_layers
                or self.mixer_then_ffn)

    @property
    def by_kind(self) -> bool:
        """The attention leaves and the paged pool are PER KIND of layer: a
        latent model with a layer pattern or anything past Kanana-2's block.
        One page table serves every kind (a page id means the same tokens in
        every layer); rows differ by kind (`kv_row_widths`)."""
        return self.is_latent and bool(
            self.layer_types or self.index_topk or self.q_lora_rank
            or self.attention_gate or self.latent_rescale
            or self.windowed_latent or self.hc_mult > 1)

    def geometry_of(self, kind: str = GLOBAL) -> LatentGeometry:
        """The latent block's sizes in a layer of `kind`."""
        if kind == WINDOWED and self.windowed_latent is not None:
            return self.windowed_latent
        return LatentGeometry(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)

    def has_indexer(self, kind: str = GLOBAL) -> bool:
        return self.index_topk > 0 and kind == GLOBAL

    def latent_softmax_scale(self, kind: str = GLOBAL) -> float:
        """The softmax scale of a latent layer of `kind`: (nope + rope
        widths) ** -0.5, times m^2 where the kind's rotation is YaRN's with
        `mscale_all_dim` (HF `DeepseekV3Attention`: m = 0.1 *
        mscale_all_dim * ln(factor) + 1; Xing4.0's 1.4159^2 = 2.0048).  A
        kind without such a rotation: the plain scale, the float it always
        was."""
        g = self.geometry_of(kind)
        scale = (g.qk_nope_head_dim + g.qk_rope_head_dim) ** -0.5
        rp = self.rope_of(kind)
        if rp is not None and rp.mscale_all_dim and rp.factor > 1:
            m = 0.1 * rp.mscale_all_dim * math.log(rp.factor) + 1.0
            scale = scale * m * m
        return scale

    @property
    def num_router_experts(self) -> int:
        """Columns of the router: the published expert count."""
        return self.num_experts_routed or self.num_experts

    def kv_row_widths(self, kind: str = GLOBAL) -> Tuple[int, ...]:
        """Values a token stores in one layer of `kind`, one entry a pool
        array: THE definition of the paged pool's rows, which the pool's
        allocation (runtime/kv_cache.py), the memory plan
        (runtime/planner.py), the engine's backend rules and /metrics all
        ask.  GQA: Hkv*D keys and as many values.  Latent: the k pool holds
        c~ (kv_lora_rank) and the v pool the roped k_r padded to whole
        128-lane tiles, which the Pallas page DMAs need (64 -> 128: 640
        values for 576 stored); a layer with an indexer stores its key k^I
        in a third row, padded likewise."""
        if self.is_latent:
            g = self.geometry_of(kind)
            index = ((_lane_tiles(self.index_head_dim),)
                     if self.has_indexer(kind) else ())
            return (g.kv_lora_rank, _lane_tiles(g.qk_rope_head_dim)) + index
        if not holds_rows(kind):
            return ()  # mamba / gmu / cross / conv / delta layers: no rows
        return (self.num_kv_heads * self.head_dim,) * 2

    @property
    def kv_values_per_token(self) -> int:
        """Values one cached token holds over all layers, as allocated."""
        return sum(self.layers_of(kind) * sum(self.kv_row_widths(kind))
                   for kind in self.kinds)

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# Registry of named configs. Sizes follow the published Llama architectures;
# "tiny"/"debug" variants keep tests fast and fit the CPU mesh.
CONFIGS = {
    "tiny": ModelConfig(),
    "tiny-gqa": ModelConfig(name="tiny-gqa", num_heads=8, num_kv_heads=2, hidden_size=128, head_dim=16),
    "debug-290m": ModelConfig(
        name="debug-290m",
        vocab_size=32000,
        hidden_size=1024,
        intermediate_size=2816,
        num_layers=12,
        num_heads=16,
        num_kv_heads=4,
        head_dim=64,
    ),
    "llama-3.2-1b": ModelConfig(
        name="llama-3.2-1b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        max_context=131072,
        tie_word_embeddings=True,
        rope_scaling_factor=32.0,
    ),
    "llama-3.2-3b": ModelConfig(
        name="llama-3.2-3b",
        vocab_size=128256,
        hidden_size=3072,
        intermediate_size=8192,
        num_layers=28,
        num_heads=24,
        num_kv_heads=8,
        head_dim=128,
        max_context=131072,
        tie_word_embeddings=True,
        rope_scaling_factor=32.0,
    ),
    "llama-3-8b": ModelConfig(
        name="llama-3-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_context=8192,
        tie_word_embeddings=False,
    ),
    "llama-3.1-8b": ModelConfig(
        name="llama-3.1-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        max_context=131072,
        tie_word_embeddings=False,
        rope_scaling_factor=8.0,
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe", num_heads=8, num_kv_heads=2, hidden_size=128,
        head_dim=16, num_experts=4, num_experts_per_tok=2,
    ),
    # Mixtral 8x7B architecture (HF mistralai/Mixtral-8x7B-v0.1
    # config.json): the servable MoE flagship shape.  Experts shard over
    # "ep"; attention + per-expert FFN still shard over "tp".
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=1e6,
        max_context=32768,
        tie_word_embeddings=False,
        num_experts=8,
        num_experts_per_tok=2,
    ),
    # Llava-class tiny vision model for tests/dev: byte tokenizer vocab
    # (262) + 1 reserved image-placeholder id.  A real deployment loads a
    # Llava checkpoint's ViT the same way (vision tower + projector).
    "tiny-vision": ModelConfig(
        name="tiny-vision", vocab_size=263, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, vision=VisionConfig(), image_token_id=262,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        max_context=8192,
        tie_word_embeddings=False,
    ),
}


def get_config(name: str) -> ModelConfig:
    """Resolve a model name (case/sep-insensitive) to a config."""
    key = name.lower().replace("_", "-").replace("meta-llama/", "")
    aliases = {
        "llama-3.2-1b-instruct": "llama-3.2-1b",
        "llama-3.2-3b-instruct": "llama-3.2-3b",
        "llama-3-8b-instruct": "llama-3-8b",
        "llama-3.1-8b-instruct": "llama-3.1-8b",
        "llama-3-70b-instruct": "llama-3-70b",
        "meta-llama-3-8b": "llama-3-8b",
    }
    key = aliases.get(key, key)
    if key not in CONFIGS:
        raise KeyError(f"unknown model {name!r}; known: {sorted(CONFIGS)}")
    return CONFIGS[key]


def _rope_params(kind: str, rp: dict) -> RopeParams:
    rope_type = rp.get("rope_type", rp.get("type", "default"))
    if rope_type not in ("default", "yarn"):
        raise UnsupportedConfigError(
            f"rope_parameters[{kind!r}]: rope_type {rope_type!r} is not "
            "served per layer kind (known: 'default', 'yarn')")
    return RopeParams(
        rope_type=rope_type,
        rope_theta=float(rp.get("rope_theta", 10000.0)),
        factor=float(rp.get("factor", 1.0)),
        original_max_position=int(
            rp.get("original_max_position_embeddings", 8192)),
        beta_fast=float(rp.get("beta_fast", 32.0)),
        beta_slow=float(rp.get("beta_slow", 1.0)),
        attention_factor=rp.get("attention_factor"),
    )


def _layer_pattern(hf: dict) -> dict:
    """The pattern keys of a published config.json (`layer_types`,
    `sliding_window`, `rope_parameters` by kind) as ModelConfig fields; {}
    where the config declares no `layer_types`."""
    kinds = hf.get("layer_types")
    if not kinds:
        return {}
    # a depth-cut copy of a published config keeps the published list: the
    # first num_hidden_layers entries are the layers that exist (a list
    # that is too SHORT fails ModelConfig's own check)
    kinds = tuple(kinds)[:hf["num_hidden_layers"]]
    if WINDOWED in kinds and hf.get("use_sliding_window") is False:
        raise UnsupportedConfigError(
            "layer_types names sliding_attention layers but "
            "use_sliding_window is false")
    by_kind = hf.get("rope_parameters") or {}
    if not by_kind and "swa_rope_theta" in hf:
        # `dots3_note` spells the two kinds' thetas as flat keys
        by_kind = {GLOBAL: {"rope_theta": hf["rope_theta"]},
                   WINDOWED: {"rope_theta": hf["swa_rope_theta"]}}
    nested = {k: v for k, v in by_kind.items() if isinstance(v, dict)}
    # (an unknown kind is ModelConfig's own error, not a missing rope)
    missing = (set(kinds) & {WINDOWED, GLOBAL}) - set(nested) if nested \
        else set()
    if missing:
        raise UnsupportedConfigError(
            f"rope_parameters has no entry for {sorted(missing)}")
    return {
        "layer_types": kinds,
        "sliding_window": hf.get("sliding_window",
                                 hf.get("sliding_window_size")),
        "rope_by_kind": tuple(
            (k, _rope_params(k, nested[k]))
            for k in sorted(set(kinds) & set(nested))),
    }


def _refuse_unless(hf: dict, served) -> None:
    """Raise, by key, where a published key holds another value than the
    one served: `served` is (key, the value served and the default where
    the key is absent, what another value would mean)."""
    for key, want, what in served:
        got = hf.get(key, want)
        if got != want:
            raise UnsupportedConfigError(
                f"{key} = {got!r} ({what}) is not served: only {want!r} is")


def _refuse_unnormalised_sigmoid(hf: dict) -> None:
    if hf.get("norm_topk_prob") is not True:
        raise UnsupportedConfigError(
            "norm_topk_prob must be true: sigmoid routing weights are "
            "renormalised over the chosen experts")


def _latent_keys(hf: dict) -> dict:
    """The `deepseek_v3` keys of a published config.json (latent attention,
    leading dense layers, sigmoid routing, shared experts) as ModelConfig
    fields; {} for any other model.  What is not served is an
    UnsupportedConfigError, by key."""
    if not hf.get("kv_lora_rank"):
        return {}
    served = (
        ("topk_method", "noaux_tc", "another expert selection method"),
        ("scoring_func", "sigmoid", "another router scoring function"),
        ("moe_layer_freq", 1, "dense layers among the routed ones"),
        ("n_group", 1, "group-limited expert selection"),
        ("topk_group", 1, "group-limited expert selection"),
        ("attention_bias", False, "attention biases"),
    )
    _refuse_unless(hf, served)
    _refuse_unnormalised_sigmoid(hf)
    dense = int(hf.get("first_k_dense_replace", 0))
    return {
        **_kind_keys(hf),
        **_latent_rope_scaling(hf),
        "nextn_predict_layers": int(hf.get("num_nextn_predict_layers") or 0),
        "kv_lora_rank": int(hf["kv_lora_rank"]),
        "qk_nope_head_dim": int(hf["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(hf["qk_rope_head_dim"]),
        "v_head_dim": int(hf["v_head_dim"]),
        # HF DeepseekV3Config's own default: a file that leaves the key out
        # publishes interleaved pairs
        "rope_interleave": bool(hf.get("rope_interleave", True)),
        "first_k_dense": dense,
        "dense_intermediate_size": int(hf["intermediate_size"]) if dense else 0,
        "shared_intermediate_size": (int(hf.get("n_shared_experts") or 0)
                                     * int(hf["moe_intermediate_size"])),
        "moe_scoring": "sigmoid",
        "routed_scaling_factor": float(hf.get("routed_scaling_factor", 1.0)),
    }


# `rope_scaling` of a `deepseek_v3`-style config.json, as served: YaRN with
# exactly these keys (the type under either spelling)
_YARN_KEYS = {"type", "rope_type", "factor",
              "original_max_position_embeddings", "beta_fast", "beta_slow",
              "mscale", "mscale_all_dim"}


def _latent_rope_scaling(hf: dict) -> dict:
    """`rope_scaling` on latent attention: {} where absent; YaRN as HF
    `DeepseekV3` applies it (the blended frequencies of
    ops/rope.yarn_frequencies, cos and sin times get_mscale(mscale) /
    get_mscale(mscale_all_dim), the softmax scale times
    get_mscale(mscale_all_dim) ** 2) as the GLOBAL kind's RopeParams.
    Anything else is refused by name."""
    rs = hf.get("rope_scaling")
    if not rs:
        return {}
    kind = rs.get("rope_type", rs.get("type"))
    if kind != "yarn":
        raise UnsupportedConfigError(
            f"rope_scaling type {kind!r} on latent attention is not served: "
            "only 'yarn' is")
    unknown = set(rs) - _YARN_KEYS
    if unknown:
        raise UnsupportedConfigError(
            f"rope_scaling keys {sorted(unknown)} on latent attention are "
            f"not served: known {sorted(_YARN_KEYS)}")
    if hf.get("layer_types") or any(k.startswith("swa_") for k in hf):
        raise UnsupportedConfigError(
            "rope_scaling on a latent model with kinds of layer "
            "(layer_types / swa_*: scaled rotary positions on a windowed "
            "kind) is not served")
    missing = {"factor", "original_max_position_embeddings"} - set(rs)
    if missing:
        raise UnsupportedConfigError(
            f"rope_scaling of type yarn on latent attention needs "
            f"{sorted(missing)}")
    mscale = float(rs.get("mscale", 1.0))
    all_dim = float(rs.get("mscale_all_dim", 0.0))
    if mscale != all_dim:
        raise UnsupportedConfigError(
            f"rope_scaling mscale = {mscale!r} differs from mscale_all_dim "
            f"= {all_dim!r}: the factor on cos and sin would be "
            "get_mscale(mscale) / get_mscale(mscale_all_dim) != 1, which "
            "latent attention is not held to; only equal values are served")
    return {"rope_by_kind": ((GLOBAL, RopeParams(
        rope_type="yarn",
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        factor=float(rs["factor"]),
        original_max_position=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs.get("beta_fast", 32.0)),
        beta_slow=float(rs.get("beta_slow", 1.0)),
        attention_factor=1.0,  # get_mscale(mscale) / get_mscale(all_dim)
        mscale_all_dim=all_dim)),)}


def _stream_keys(hf: dict) -> dict:
    """The widened residual stream's keys (`hc_mult`, `hc_sinkhorn_iters`,
    `hc_eps`, `mhc_h_res_clamp_min` / `_max`) as ModelConfig fields; {} for
    a config without `hc_mult` or with one row."""
    n = int(hf.get("hc_mult", 1))
    if n == 1:
        return {}
    return {
        "hc_mult": n,
        "hc_sinkhorn_iters": int(hf.get("hc_sinkhorn_iters", 20)),
        "hc_eps": float(hf.get("hc_eps", 1e-6)),
        "hc_res_clamp_min": float(hf.get("mhc_h_res_clamp_min", -30.0)),
        "hc_res_clamp_max": float(hf.get("mhc_h_res_clamp_max", 30.0)),
    }


def _kind_keys(hf: dict) -> dict:
    """The keys of a latent decoder past Kanana-2's block (`dots3_note`: a
    query low-rank, the rescale, the headwise gate, the indexer, the `swa_*`
    sizes of sliding layers, a share of the experts) as ModelConfig fields."""
    out = {
        "q_lora_rank": int(hf.get("q_lora_rank") or 0),
        "latent_rescale": bool(hf.get("apply_mla_qkv_lora_rescale", False)),
        "attention_gate": hf.get("attention_gate_type") or "",
        "index_n_heads": int(hf.get("index_n_heads") or 0),
        "index_head_dim": int(hf.get("index_head_dim") or 0),
        "index_topk": int(hf.get("index_topk") or 0),
    }
    swa = {k[4:]: v for k, v in hf.items() if k.startswith("swa_")}
    if swa:
        for key, why in (
                ("index_topk", "an indexer on a sliding layer"),
                ("index_n_heads", "an indexer on a sliding layer"),
                ("rope_scaling", "scaled rotary positions on sliding layers")):
            if swa.get(key):
                raise UnsupportedConfigError(
                    f"swa_{key} = {swa[key]!r} ({why}) is not served")
        gate = swa.get("attention_gate_type") or ""
        if gate != out["attention_gate"]:
            raise UnsupportedConfigError(
                f"swa_attention_gate_type = {gate!r} differs from "
                f"attention_gate_type = {out['attention_gate']!r}: one gate "
                "type a model is served")
        out["windowed_latent"] = LatentGeometry(
            num_heads=int(swa.get("num_attention_heads",
                                  hf["num_attention_heads"])),
            q_lora_rank=int(swa.get("q_lora_rank") or 0),
            kv_lora_rank=int(swa.get("kv_lora_rank", hf["kv_lora_rank"])),
            qk_nope_head_dim=int(swa.get("qk_nope_head_dim",
                                         hf["qk_nope_head_dim"])),
            qk_rope_head_dim=int(swa.get("qk_rope_head_dim",
                                         hf["qk_rope_head_dim"])),
            v_head_dim=int(swa.get("v_head_dim", hf["v_head_dim"])))
    published = int(hf.get("n_routed_experts_published") or 0)
    if published:
        out["num_experts_routed"] = published
        out["expert_offset"] = int(hf.get("expert_share_offset", 0))
    return out


def _routed_lead_keys(hf: dict) -> dict:
    """The `deepseek_v3`-style FEED-FORWARD keys of a config.json whose
    attention is grouped-query (`exaone_moe`: K-EXAONE): a dense lead
    (`first_k_dense_replace`, `mlp_layer_types`), sigmoid routing with a
    selection bias and a scale, shared experts, a held share of the experts;
    plus what that family's attention adds (QK-norm, full-attention layers
    that do not rotate) and its multi-token-prediction count.  {} for a
    latent model (`_latent_keys` reads those) and for one without the keys.
    What is not served is an UnsupportedConfigError, by key."""
    if hf.get("kv_lora_rank") or not (
            "first_k_dense_replace" in hf or "scoring_func" in hf
            or hf.get("model_type") == "solar_open2"):
        return {}
    served = (
        ("scoring_func", "sigmoid", "another router scoring function"),
        ("n_group", 1, "group-limited expert selection"),
        ("topk_group", 1, "group-limited expert selection"),
        ("hidden_act", "silu", "another MLP activation"),
        ("attention_bias", False, "attention biases"),
    )
    _refuse_unless(hf, served)
    _refuse_unnormalised_sigmoid(hf)
    dense = int(hf.get("first_k_dense_replace", 0))
    mlp_kinds = list(hf.get("mlp_layer_types") or ())
    if mlp_kinds:
        lead = next((i for i, k in enumerate(mlp_kinds) if k != "dense"),
                    len(mlp_kinds))
        if "dense" in mlp_kinds[lead:]:
            raise UnsupportedConfigError(
                "mlp_layer_types puts a sparse layer ahead of a dense one: "
                "dense layers are served as a lead only")
        if lead != dense:
            raise UnsupportedConfigError(
                f"mlp_layer_types leads with {lead} dense layers but "
                f"first_k_dense_replace is {dense}")
    shared = int(hf.get("num_shared_experts", hf.get("n_shared_experts"))
                 or 0)
    out = {
        "first_k_dense": dense,
        "dense_intermediate_size": int(hf["intermediate_size"]) if dense else 0,
        "shared_intermediate_size": shared * int(hf["moe_intermediate_size"]),
        "moe_scoring": "sigmoid",
        "routed_scaling_factor": float(hf.get("routed_scaling_factor", 1.0)),
        "nextn_predict_layers": int(hf.get("num_nextn_predict_layers") or 0),
    }
    published = int(hf.get("num_experts_published") or 0)
    if published:
        out["num_experts_routed"] = published
        out["expert_offset"] = int(hf.get("expert_share_offset", 0))
    if hf.get("model_type") in ("exaone_moe", "exaone4"):
        # the EXAONE-4 block: per-head RMSNorm of q and k, and in a model
        # that mixes kinds the full-attention layers do not rotate
        out["qk_norm"] = True
        if WINDOWED in (hf.get("layer_types") or ()):
            out["unrotated_kinds"] = (GLOBAL,)
    return out


def _hybrid_keys(hf: dict) -> dict:
    """The keys of a `phi4flash` config.json (Phi-4-mini-flash-reasoning: a
    decoder-hybrid-decoder of Mamba-1 mixers, sliding and full differential
    attention, gated memory units and cross attention over ONE full cache)
    as ModelConfig fields; {} for any other model.  The layout is the
    modeling file's rule over `num_hidden_layers` and `mb_per_layer`; what
    the config has no key for (the Mamba sizes, differential attention, the
    biases) is the configuration class's and the modeling file's default,
    listed as `assumed` beside the benchmark's copy of the file."""
    if hf.get("model_type") != "phi4flash":
        return {}
    served = (
        ("hidden_act", "silu", "another MLP activation"),
        ("mb_per_layer", 2, "another spacing of the Mamba layers"),
        ("mlp_bias", False, "MLP biases"),
        ("lm_head_bias", False, "a bias on the head"),
        ("tie_word_embeddings", True, "an untied head"),
        ("rope_scaling", None, "rotary positions (the model has none)"),
    )
    _refuse_unless(hf, served)
    n = int(hf["num_hidden_layers"])
    if n % 4 or n < 4:
        raise UnsupportedConfigError(
            f"num_hidden_layers = {n}: the hybrid layout needs a multiple "
            "of 4")
    window = hf.get("sliding_window")
    if not isinstance(window, int) or window <= 0:
        raise UnsupportedConfigError(
            f"sliding_window = {window!r}: one positive window is served")
    own = n // 2 + 2  # layers with a mixer state or K/V of their own
    kinds = tuple(
        (MAMBA if i % 2 == 0 else GLOBAL if i == own - 1 else WINDOWED)
        if i < own else (GMU if i % 2 == 0 else CROSS)
        for i in range(n))
    hidden = int(hf["hidden_size"])
    return {
        "layer_types": kinds,
        "sliding_window": window,
        "mamba_d_state": int(hf.get("mamba_d_state", 16)),
        "mamba_d_conv": int(hf.get("mamba_d_conv", 4)),
        "mamba_expand": int(hf.get("mamba_expand", 2)),
        "mamba_dt_rank": int(hf.get("mamba_dt_rank", -(-hidden // 16))),
        "rms_norm_eps": float(hf.get("layer_norm_eps", 1e-5)),
    }


def _conv_keys(hf: dict) -> dict:
    """The keys of an `lfm2_moe` config.json (LFM2-8B-A1B: gated short
    convolutions beside full attention, `num_dense_layers` dense layers, then
    sigmoid-routed experts chosen with a bias) as ModelConfig fields; {} for
    a config whose `layer_types` names no conv layer.  What the config has no
    key for (the order of the conv's three chunks, QK-norm, the tied head) is
    the family's modeling code's, listed as `assumed` beside the benchmark's
    copy of the file; the renormalisation adds the sigmoid rule's one 1e-20
    to the sum (the family's 1e-6 is a relative 5e-7 of a sum near 2: under
    what bfloat16 or any test here reads).  What is not served is an
    UnsupportedConfigError, by key."""
    if CONV not in (hf.get("layer_types") or ()):
        return {}
    served = (
        ("conv_bias", False, "biases on the short convolution"),
        ("use_expert_bias", True, "experts chosen without a selection bias"),
        ("hidden_act", "silu", "another MLP activation"),
        ("rope_scaling", None, "scaled rotary positions"),
        ("attention_bias", False, "attention biases"),
    )
    _refuse_unless(hf, served)
    experts = int(hf.get("num_experts") or 0)
    dense = int(hf.get("num_dense_layers", 0))
    out = {
        "conv_L_cache": int(hf.get("conv_L_cache", 3)),
        "qk_norm": True,
        "tie_word_embeddings": bool(hf.get("tie_word_embeddings", True)),
        "rms_norm_eps": float(hf.get("norm_eps", 1e-5)),
    }
    if experts:
        _refuse_unnormalised_sigmoid(hf)
        out.update({
            "first_k_dense": dense,
            "dense_intermediate_size": (int(hf["intermediate_size"])
                                        if dense else 0),
            "moe_scoring": "sigmoid",
            "routed_scaling_factor": float(
                hf.get("routed_scaling_factor", 1.0)),
        })
    return out


def _delta_keys(hf: dict) -> dict:
    """The keys of a `solar_open2` config.json (Solar-Open2-250B: gated
    delta-rule linear attention beside gated full attention that does not
    rotate, every layer routed) as ModelConfig fields; {} for any other
    model.  The feed-forward keys are `_routed_lead_keys`' (the family's
    modeling code derives from `glm4_moe`: sigmoid scores, a selection bias,
    one shared expert).  What the config has no key for (the low-rank width
    of the decay and the output gate, the position of the attention gate, the
    router's scoring) is listed as `assumed` beside the benchmark's copy of
    the file.  What is not served is an UnsupportedConfigError, by key."""
    if hf.get("model_type") != "solar_open2":
        return {}
    served = (
        ("kda_use_full_proj", False, "a full-rank decay projection"),
        ("partial_rotary_factor", 1, "a partial rotation"),
        ("rope_scaling", None, "scaled rotary positions"),
        ("hidden_act", "silu", "another MLP activation"),
        ("attention_bias", False, "attention biases"),
    )
    _refuse_unless(hf, served)
    n = int(hf["num_hidden_layers"])
    gqa = sorted(int(i) for i in hf.get("gqa_layers") or ())
    if not gqa:
        raise UnsupportedConfigError(
            "gqa_layers names no layer: the paged pool and the prefix cache "
            "need one full-attention layer that holds rows")
    every = int(hf.get("gqa_interval", 0)) + 1
    if every > 1 and any(i % every for i in gqa):
        raise UnsupportedConfigError(
            f"gqa_layers {gqa} is not every {every}th layer, which "
            f"gqa_interval = {every - 1} says")
    lin = hf.get("linear_attn_config") or {}
    heads = int(lin.get("num_heads") or 0)
    if lin.get("num_kv_heads") not in (None, heads):
        raise UnsupportedConfigError(
            f"linear_attn_config.num_kv_heads = {lin['num_kv_heads']!r} "
            "(grouped keys and values in linear attention) is not served: "
            "only null is")
    # a depth-cut copy keeps the published list: the layers that exist
    kinds = tuple(GLOBAL if i in gqa else DELTA for i in range(n))
    spelt = tuple(hf.get("layer_types") or kinds)[:n]
    if spelt != kinds:
        raise UnsupportedConfigError(
            f"layer_types {list(spelt)} is not what gqa_layers {gqa} says")
    out = {
        "layer_types": kinds,
        "delta_heads": heads,
        "delta_head_dim": int(lin.get("head_dim") or 0),
        "delta_conv_kernel": int(lin.get("short_conv_kernel_size", 4)),
        "delta_neg_eigval": bool(hf.get("kda_allow_neg_eigval", False)),
        "attention_gate": "elementwise" if hf.get("use_gqa_gate") else "",
        "unrotated_kinds": () if hf.get("use_rope", False) else (GLOBAL,),
    }
    published = int(hf.get("n_routed_experts_published") or 0)
    if published:
        out["num_experts_routed"] = published
        out["expert_offset"] = int(hf.get("expert_share_offset", 0))
    return out


def _gdn_keys(hf: dict) -> dict:
    """The keys of an `olmo_hybrid` config.json (Olmo-Hybrid-7B: Gated
    DeltaNet layers beside post-normed multi-head attention, every layer
    dense) as ModelConfig fields; {} for any other model.  `linear_*` are the
    arguments of flash-linear-attention's GatedDeltaNet one for one (heads, a
    key and a value head size, the convolution's taps, `allow_neg_eigval`);
    `rope_parameters.rope_theta` null is NO rotation (a theta of null cannot
    be rotated by).  What the config has no key for (where the norms sit,
    the width of the QK-norm) is the family's convention, listed as `assumed`
    beside the benchmark's copy of the file.  What is not served is an
    UnsupportedConfigError, by key."""
    if hf.get("model_type") != "olmo_hybrid":
        return {}
    _refuse_unless(hf, (
        ("hidden_act", "silu", "another MLP activation"),
        ("attention_bias", False, "attention biases"),
        ("rope_scaling", None, "scaled rotary positions"),
        ("sliding_window", None, "a sliding window"),
    ))
    heads = int(hf.get("linear_num_key_heads") or 0)
    if hf.get("linear_num_value_heads", heads) != heads:
        raise UnsupportedConfigError(
            f"linear_num_value_heads = {hf['linear_num_value_heads']!r} "
            f"beside linear_num_key_heads = {heads} (grouped keys in linear "
            "attention) is not served: only equal counts are")
    kinds = tuple(hf.get("layer_types") or ())[:int(hf["num_hidden_layers"])]
    rope = hf.get("rope_parameters") or {}
    theta = rope.get("rope_theta", hf.get("rope_theta"))
    d_k = int(hf.get("linear_key_head_dim") or 0)
    d_v = int(hf.get("linear_value_head_dim") or d_k)
    return {
        "layer_types": kinds,
        "delta_heads": heads,
        "delta_head_dim": d_k,
        "delta_value_dim": 0 if d_v == d_k else d_v,
        "delta_conv_kernel": int(hf.get("linear_conv_kernel_dim", 4)),
        "delta_neg_eigval": bool(hf.get("linear_allow_neg_eigval", False)),
        "delta_gate": "head",
        "norm_position": "post",
        "qk_norm": True,
        "qk_norm_whole": True,
        "unrotated_kinds": (GLOBAL,) if theta is None else (),
    }


def _parallel_keys(hf: dict) -> dict:
    """The keys of a `falcon_h1` config.json (Falcon-H1: every layer a
    Mamba-2 mixer in parallel with grouped-query attention, a SwiGLU MLP, the
    family's muP multipliers) as ModelConfig fields; {} for any other model.
    What the config has no key for (the order of the input projection's
    columns, the ranges of the muP vector, the grouped gated norm) is listed
    as `assumed` beside the benchmark's copy of the file.  What is not served
    is an UnsupportedConfigError, by key."""
    if hf.get("model_type") != "falcon_h1":
        return {}
    served = (
        ("attn_layer_indices", None, "attention in some layers only"),
        ("rope_scaling", None, "scaled rotary positions"),
        ("mamba_use_mlp", True, "a block without its MLP"),
        ("mamba_rms_norm", True, "a mixer without its gated norm"),
        ("mamba_norm_before_gate", False, "the norm ahead of the gate"),
        ("hidden_act", "silu", "another MLP activation"),
        ("attention_bias", False, "attention biases"),
        ("mamba_proj_bias", False, "biases on the mixer's projections"),
        ("mlp_bias", False, "MLP biases"),
        ("projectors_bias", False, "biases on the projections"),
        ("mamba_conv_bias", True, "a convolution without its bias"),
    )
    _refuse_unless(hf, served)
    heads, size = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    if hf.get("mamba_d_ssm", heads * size) != heads * size:
        raise UnsupportedConfigError(
            f"mamba_d_ssm = {hf['mamba_d_ssm']!r} is not mamba_n_heads x "
            f"mamba_d_head = {heads} x {size}")
    out = {
        "layer_types": (PARALLEL,) * int(hf["num_hidden_layers"]),
        "ssd_heads": heads,
        "ssd_head_dim": size,
        "ssd_d_state": int(hf["mamba_d_state"]),
        "ssd_groups": int(hf.get("mamba_n_groups", 1)),
        "ssd_conv_kernel": int(hf.get("mamba_d_conv", 4)),
        "ssm_multipliers": tuple(
            float(m) for m in hf.get("ssm_multipliers") or ()),
        "mlp_multipliers": tuple(
            float(m) for m in hf.get("mlp_multipliers") or ()),
    }
    for key in ("embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"):
        out[key] = float(hf.get(key, 1.0))
    return out


# `hybrid_override_pattern`'s letters (`nemotron_h`): one sublayer a layer
_LONE_KINDS = {"M": MAMBA2, "*": GLOBAL, "E": MOE}


def _lone_keys(hf: dict) -> dict:
    """The keys of a `nemotron_h` config.json (Nemotron-H / Nemotron-3: every
    layer ONE sublayer by `hybrid_override_pattern`'s letter: M a Mamba-2
    mixer, * grouped-query attention that does not rotate, E a sigmoid-routed
    feed-forward of ungated squared-ReLU experts beside one shared expert) as
    ModelConfig fields; {} for any other model.  What the config has no key
    for (the order of the input projection's columns, the unclamped step, the
    grouped gated norm, the absent rotation) is listed as `assumed` beside
    the benchmark's copy of the file.  What is not served is an
    UnsupportedConfigError, by key."""
    if hf.get("model_type") != "nemotron_h":
        return {}
    served = (
        ("mlp_hidden_act", "relu2", "another feed-forward activation"),
        ("mamba_hidden_act", "silu", "another activation in the mixer"),
        ("attention_bias", False, "attention biases"),
        ("mamba_proj_bias", False, "biases on the mixer's projections"),
        ("mlp_bias", False, "feed-forward biases"),
        ("use_bias", False, "biases on the projections"),
        ("use_conv_bias", True, "a convolution without its bias"),
        ("n_group", 1, "group-limited expert selection"),
        ("topk_group", 1, "group-limited expert selection"),
        ("sliding_window", None, "a sliding window"),
        ("residual_in_fp32", False, "a float32 residual stream"),
        ("rope_scaling", None, "scaled rotary positions (the attention "
                               "layers do not rotate)"),
    )
    _refuse_unless(hf, served)
    _refuse_unnormalised_sigmoid(hf)
    n = int(hf["num_hidden_layers"])
    # a depth-cut copy keeps the published pattern: the layers that exist
    letters = str(hf.get("hybrid_override_pattern") or "")[:n]
    bad = sorted(set(letters) - set(_LONE_KINDS))
    if bad or len(letters) != n:
        raise UnsupportedConfigError(
            f"hybrid_override_pattern = {hf.get('hybrid_override_pattern')!r}"
            f" for {n} layers: one letter a layer of M (Mamba-2), * "
            "(attention) and E (routed feed-forward) is served"
            + (f"; {bad} (a dense MLP layer is '-') is not" if bad else ""))
    experts = int(hf.get("n_routed_experts") or 0)
    if not experts:
        raise UnsupportedConfigError(
            "n_routed_experts = 0: an E layer is a ROUTED feed-forward")
    kinds = tuple(_LONE_KINDS[c] for c in letters)
    # (a copy of the file may spell the pattern out, so that a program that
    # does not know the kinds refuses it by that name)
    spelt = tuple(hf.get("layer_types") or kinds)[:n]
    if spelt != kinds:
        raise UnsupportedConfigError(
            f"layer_types {list(spelt)} is not what hybrid_override_pattern "
            f"{letters!r} says")
    out = {
        "layer_types": kinds,
        "unrotated_kinds": (GLOBAL,),
        "ssd_heads": int(hf["mamba_num_heads"]),
        "ssd_head_dim": int(hf["mamba_head_dim"]),
        "ssd_d_state": int(hf["ssm_state_size"]),
        "ssd_groups": int(hf.get("n_groups", 1)),
        "ssd_conv_kernel": int(hf.get("conv_kernel", 4)),
        "mlp_act": "relu2",
        "moe_scoring": "sigmoid",
        "routed_scaling_factor": float(hf.get("routed_scaling_factor", 1.0)),
        "shared_intermediate_size": int(
            hf.get("moe_shared_expert_intermediate_size")
            or int(hf.get("n_shared_experts") or 0)
            * int(hf["moe_intermediate_size"])),
        "rms_norm_eps": float(hf.get("layer_norm_epsilon",
                                     hf.get("norm_eps", 1e-5))),
    }
    published = int(hf.get("n_routed_experts_published") or 0)
    if published:
        out["num_experts_routed"] = published
        out["expert_offset"] = int(hf.get("expert_share_offset", 0))
    return out

# `granitemoehybrid`'s published `layer_types` words.  "mamba" there is a
# Mamba-2 mixer; letter for letter it is this module's MAMBA, `phi4flash`'s
# Mamba-1 layer with another state, another tree and another forward pass:
# the words are translated by `model_type`, never read as kinds.
_GRANITE_KINDS = {"mamba": MAMBA2, "attention": GLOBAL}


def _granite_keys(hf: dict) -> dict:
    """The keys of a `granitemoehybrid` config.json (Granite-4.0-H: every
    layer a Mamba-2 mixer OR grouped-query attention that does not rotate,
    then a softmax-routed feed-forward of gated SiLU experts beside one
    shared expert; four muP scalars) as ModelConfig fields; {} for any other
    model.  `logits_scaling` DIVIDES the logits (read into
    `lm_head_multiplier` as its reciprocal), `embedding_multiplier`
    multiplies the embedding, `residual_multiplier` every sublayer's output
    on its way into the stream and `attention_multiplier` IS the softmax
    scale.  What the config has no key for (the order of the input
    projection's columns, the unclamped step, the gated norm over the one
    group, which half of an expert's input matrix is gated) is listed as
    `assumed` beside the benchmark's copy of the file.  What is not served is
    an UnsupportedConfigError, by key."""
    if hf.get("model_type") != "granitemoehybrid":
        return {}
    served = (
        ("hidden_act", "silu", "another feed-forward activation"),
        ("attention_bias", False, "attention biases"),
        ("mamba_proj_bias", False, "biases on the mixer's projections"),
        ("mamba_conv_bias", True, "a convolution without its bias"),
        ("normalization_function", "rmsnorm", "another normalisation"),
        ("position_embedding_type", "nope", "rotary positions: the "
         "attention layers of the hybrid carry no position signal"),
        ("rope_scaling", None, "scaled rotary positions (the attention "
                               "layers do not rotate)"),
        ("tie_word_embeddings", True, "an untied head"),
    )
    _refuse_unless(hf, served)
    n = int(hf["num_hidden_layers"])
    # a depth-cut copy keeps the published list: the layers that exist
    words = tuple(hf.get("layer_types") or ())[:n]
    bad = sorted(set(words) - set(_GRANITE_KINDS))
    if bad or len(words) != n:
        raise UnsupportedConfigError(
            f"layer_types {list(hf.get('layer_types') or ())} for {n} "
            f"layers: one word a layer of {sorted(_GRANITE_KINDS)} is "
            "served under granitemoehybrid"
            + (f"; {bad} is not" if bad else ""))
    heads, size = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
    if int(hf.get("mamba_expand", 2)) * int(hf["hidden_size"]) != heads * size:
        raise UnsupportedConfigError(
            f"mamba_expand x hidden_size = {hf.get('mamba_expand', 2)} x "
            f"{hf['hidden_size']} is not mamba_n_heads x mamba_d_head = "
            f"{heads} x {size}")
    if not int(hf.get("num_local_experts") or 0):
        raise UnsupportedConfigError(
            "num_local_experts = 0 (a dense feed-forward behind the mixers) "
            "is not served: the layout's feed-forward is the routed block")
    scaling = float(hf.get("logits_scaling", 1.0))
    if scaling <= 0:
        raise UnsupportedConfigError(
            f"logits_scaling = {scaling!r}: the logits are divided by it")
    out = {
        "layer_types": tuple(_GRANITE_KINDS[w] for w in words),
        "unrotated_kinds": (GLOBAL,),
        "ssd_heads": heads,
        "ssd_head_dim": size,
        "ssd_d_state": int(hf["mamba_d_state"]),
        "ssd_groups": int(hf.get("mamba_n_groups", 1)),
        "ssd_conv_kernel": int(hf.get("mamba_d_conv", 4)),
        "shared_intermediate_size": int(
            hf.get("shared_intermediate_size") or 0),
        "embedding_multiplier": float(hf.get("embedding_multiplier", 1.0)),
        "lm_head_multiplier": 1.0 / scaling,
        "residual_multiplier": float(hf.get("residual_multiplier", 1.0)),
        "attention_multiplier": float(hf.get("attention_multiplier", 0.0)),
        "tie_word_embeddings": True,
    }
    published = int(hf.get("num_local_experts_published") or 0)
    if published:
        out["num_experts_routed"] = published
        out["expert_offset"] = int(hf.get("expert_share_offset", 0))
    return out


def config_from_hf_json(path: str) -> ModelConfig:
    """Build a ModelConfig from a HuggingFace config.json: Llama / Mixtral
    keys, the published keys of a patterned routed decoder (Mellum2:
    `layer_types`, `sliding_window`, `rope_parameters`, `num_experts`,
    `moe_intermediate_size`, `norm_topk_prob`, `mlp_layer_types`), those
    of a `deepseek_v3` decoder (`_latent_keys`), the same feed-forward keys
    on grouped-query attention (`_routed_lead_keys`: `exaone_moe`), those of
    a `phi4flash` hybrid decoder (`_hybrid_keys`), those of an `lfm2_moe`
    one (`_conv_keys`), those of a `solar_open2` one (`_delta_keys`), those
    of an `olmo_hybrid` one (`_gdn_keys`), those of a `falcon_h1` one (`_parallel_keys`), those of a `nemotron_h` one
    (`_lone_keys`) and those of a `granitemoehybrid` one (`_granite_keys`).
    A key the program cannot honour
    is an UnsupportedConfigError."""
    with open(path) as f:
        hf = json.load(f)
    latent = _latent_keys(hf)
    routed_lead = _routed_lead_keys(hf)
    delta = _delta_keys(hf)
    lone = _lone_keys(hf)
    # (a latent model's `rope_scaling` is `_latent_rope_scaling`'s, not the
    # Llama-3 form the model-wide fields hold)
    rs = {} if latent else hf.get("rope_scaling") or {}
    # honor the checkpoint's own precision ("dtype" since transformers
    # 4.56+, "torch_dtype" before); fp16 checkpoints run as bf16 (same
    # width, TPU-native — fp16 has no MXU path)
    dtype = {"float32": "float32", "bfloat16": "bfloat16",
             "float16": "bfloat16"}.get(
        hf.get("dtype", hf.get("torch_dtype")), "bfloat16"
    )
    # MoE: `num_local_experts` (HF Mixtral) or `num_experts` with the
    # experts' own width in `moe_intermediate_size`; absent -> 0 = dense
    num_experts = (hf.get("num_local_experts", hf.get("num_experts", 0))
                   or (hf.get("n_routed_experts", 0)
                       if latent or delta or lone else 0) or 0)
    # (a dense LEAD is `_routed_lead_keys`', checked there)
    mlp_kinds = hf.get("mlp_layer_types")
    if routed_lead.get("first_k_dense"):
        mlp_kinds = mlp_kinds and mlp_kinds[routed_lead["first_k_dense"]:]
    if mlp_kinds and (set(mlp_kinds) != {"sparse"} or not num_experts):
        raise UnsupportedConfigError(
            "mlp_layer_types must be all 'sparse' (with experts) or absent: "
            f"found {sorted(set(mlp_kinds))} with {num_experts} experts; a "
            "mix of dense and routed layers is not served")
    if num_experts and not (latent or routed_lead) \
            and hf.get("norm_topk_prob") is False:
        raise UnsupportedConfigError(
            "norm_topk_prob false (top-k weights of a softmax over ALL "
            "experts, not renormalised) is not served: routing here is a "
            "softmax over exactly the top-k logits")
    hybrid = (_hybrid_keys(hf) or _conv_keys(hf) or delta or _gdn_keys(hf)
              or _parallel_keys(hf) or lone or _granite_keys(hf))
    pattern = {} if "layer_types" in hybrid else _layer_pattern(hf)
    rope_theta = hf.get("rope_theta")
    if rope_theta is None:
        ropes = dict(pattern.get("rope_by_kind", ()))
        flat = hf.get("rope_parameters") or {}
        if GLOBAL in ropes:
            rope_theta = ropes[GLOBAL].rope_theta
        elif "rope_theta" in flat and flat["rope_theta"] is None:
            # a theta of null: the layers do not rotate, which only a reader
            # that says so may serve (`_gdn_keys`); never read as a number
            if GLOBAL not in hybrid.get("unrotated_kinds", ()):
                raise UnsupportedConfigError(
                    "rope_parameters.rope_theta = null (no rotation) is "
                    f"not served for model_type {hf.get('model_type')!r}")
            rope_theta = 10000.0  # recorded, read by no layer
        elif "rope_theta" in flat:
            # one table for every kind, spelt as a flat `rope_parameters`
            rope_theta = _rope_params("rope_parameters", flat).rope_theta
            if flat.get("rope_type", "default") != "default":
                raise UnsupportedConfigError(
                    f"rope_parameters: rope_type {flat['rope_type']!r} is "
                    "served per layer kind only")
        else:
            rope_theta = 10000.0
    return ModelConfig(
        dtype=dtype,
        num_experts=num_experts,
        num_experts_per_tok=hf.get("num_experts_per_tok", 2),
        name=os.path.basename(os.path.dirname(os.path.abspath(path))),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=(hf["moe_intermediate_size"]
                           if num_experts and "moe_intermediate_size" in hf
                           else hf["intermediate_size"]),
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        # (latent attention: the rotary width, what a published `head_dim` is)
        head_dim=(latent["qk_rope_head_dim"] if latent else hf.get(
            "head_dim") or hf["hidden_size"] // hf["num_attention_heads"]),
        # (a theta past int32, Falcon-H1's 1e11 spelt as an integer, would
        # be parsed as one where the frequencies are computed)
        rope_theta=(float(rope_theta) if abs(rope_theta) >= 2**31
                    else rope_theta),
        rms_norm_eps=hybrid.pop("rms_norm_eps", None) or hf.get(
            "rms_norm_eps", 1e-5),
        max_context=hf.get("max_position_embeddings", 8192),
        tie_word_embeddings=hybrid.pop(
            "tie_word_embeddings", hf.get("tie_word_embeddings", False)),
        rope_scaling_factor=rs.get("factor"),
        rope_low_freq_factor=rs.get("low_freq_factor", 1.0),
        rope_high_freq_factor=rs.get("high_freq_factor", 4.0),
        rope_original_max_position=rs.get("original_max_position_embeddings", 8192),
        **pattern,
        **latent,
        **routed_lead,
        **hybrid,
        # (ModelConfig refuses the stream where it is not built)
        **_stream_keys(hf),
    )
