"""Decoder-only transformer in pure functional JAX: the Llama family, Mixtral's
routed MLP, layer patterns of windowed and global attention (Mellum2), and
latent attention behind leading dense layers with sigmoid-routed and shared
experts (HF `deepseek_v3`; "Latent attention" below), and that same
lead-and-routed tree on grouped-query attention with QK-norm and kinds of
layer that do not rotate (`exaone_moe`: K-EXAONE), and with gated short
convolutions for mixers in most layers, each carrying a two-row tail in a
state slot beside the pages (`lfm2_moe`: LFM2-8B-A1B; "The conv layout"
below), or with gated delta-rule linear attention for mixers, each carrying a
matrix a head and its convolutions' tails in a state slot, beside gated
full-attention layers that do not rotate (`solar_open2`: Solar-Open2-250B;
`_delta_attention_block`, the conv layout's mechanism with a second state
leaf).  `falcon_h1` (Falcon-H1-34B) is the homogeneous dense stack with TWO
mixers a layer: grouped-query attention and a Mamba-2 (SSD) mixer read one
normed input and both add into the residual (`_ssd_block`; every layer holds
rows in the paged pool AND a state slot), under the family's muP multipliers.

Design (TPU-first, not a port — the reference has no model code at all; its
LLM compute lived behind a remote gateway, src/llm/portkey.py):

* **Stacked layer parameters + `lax.scan`** — all L layers' weights are
  stored as one pytree of [L, ...] arrays and the layer body is scanned.
  One compiled layer body instead of L inlined copies: fast compiles, and
  the leading layer axis is exactly what pipeline-parallel stage splitting
  shards later.
* **Pure functions** — `init_params`, `forward`. No module framework; the
  engine jits/shard_maps these directly with explicit sharding rules
  (parallel/sharding.py maps each param path to mesh axes).
* **BSHD activations** ([batch, seq, heads, head_dim]) so the "tp" mesh axis
  lands on heads/hidden and "sp"/"cp" on seq.
* **bf16 params/activations, f32 norms & attention softmax** — the standard
  TPU numerics recipe.
* Attention runs through ops.attention (XLA reference) or the Pallas
  kernels on TPU; the choice is a config knob threaded by the engine.

Two cache forms go through the same layer math: the *contiguous*
[L, B, C, Hkv, D] KVCache addressed by absolute position == slot index
(tests, `generate`), and the *paged* pool [L, SLOTS, Hkv*D] that serving uses
(runtime/kv_cache.py) with a PagedView index plan.

* **Layer patterns** — a config whose layers alternate kinds
  (`ModelConfig.layer_types`: sliding-window and full attention, each with
  its own rotary table) is scanned over whole PERIODS of the pattern: the
  weights stay stacked [L, ...], the scan runs over the index of each
  period's first layer, and its body is the p layers of one period
  unrolled, each indexing its own weights, each kind its own code under its
  own scope with its own static window.  A period of one is the plain scan.

* **Latent attention (MLA)** — `cfg.is_latent`.  A token caches ONE row a
  layer, shared by all heads: the normed latent c~ and the roped key part
  k_r.  In the paged pool c~ is the k pool's row and k_r (padded to whole
  lane tiles) the v pool's (`ModelConfig.kv_row_widths`); expanded keys and
  values never enter a pool.  Two forms of the same attention: *expanded*,
  as published (each cached row goes through W_kvb to a head's keys and
  values), for the uncached and contiguous caches and for paged prefill
  (a walk of the live keys chunk by chunk, `_latent_prefill_walk`);
  *absorbed* for paged decode (W_kvb's key half multiplied into the query,
  scores and the weighted sum taken over the latent rows themselves, W_kvb's
  value half applied to the result), on the Pallas latent kernel or on XLA.
  Leading dense layers (`cfg.first_k_dense`) are a stacked tree of their
  own, `params["dense_layers"]`, run (unrolled) ahead of the scan over the
  routed `params["layers"]`; both index the one stacked pool by absolute
  layer.

* **The conv layout** — `cfg.conv_L_cache`.  The period body picks each
  layer's MIXER by its kind: attention, or the gated short convolution
  (`_short_conv_block`).  Mixer leaves are stacked per kind under
  `params["attn"][kind]` (`cfg.kind_leaves`); norms and feed-forward leaves
  stay in "dense_layers" / "layers".  Only the attention layers hold rows:
  the paged pool is [attention layers, SLOTS, Hkv*D], and the v pool is a
  dict {"v": rows, "conv": [conv layers, n_slots, L - 1, H] float32}, the
  state slots riding in its pytree as `phi4flash`'s do, addressed by the
  same `PagedView.state` plan through the same slot read and write
  (models/hybrid.py).  A paged prefill returns its lanes' last real rows
  only, logits [B, 1, V], as every model with a state does.

* **The widened residual stream** — `cfg.hc_mult` = n > 1 (`xing4_0`, on
  latent attention).  The hidden state in the scan's carry is n rows a token,
  [B, T, n * C], widened once under `embed` and collapsed once under `head`;
  every sublayer reads ONE row mixed from them and writes all n back, by
  per-token mappings (`_hc_in` / `_hc_out`, the only code that knows).  The
  mappings' leaves (`hc_<site>_*`, HC_SITES) are stacked beside the norms in
  "dense_layers" / "layers".  With n = 1 the two helpers are `h` and `h + y`
  and not an op is traced (tests/test_lowered_pins.py).

**The stacked cache is scan CARRY, never a scanned input.**  The layer scan
runs over (layer params, layer index); the caches of all layers travel
through it whole and a layer addresses its part by index.  The paged pool is
viewed flat as [L*SLOTS, Hkv*D] (merging the two major axes is a bitcast)
and the layer's offset goes into the INDICES: slot indices move by
layer*SLOTS, page ids by layer*num_pages (_layer_view), so the scatter of
the new rows, the page gather and the Pallas kernels' page-table DMAs all
address the donated buffer where it lies.  Scanning over the pool instead
(xs in, ys out) made XLA slice every layer's whole pool out and write it
back on every forward pass: a third of a decode step's device time moving
pages that the step reads once (PERF.md, PR 25).
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import (
    CONV,
    DELTA,
    GLOBAL,
    PARALLEL,
    ModelConfig,
    UnsupportedConfigError,
)
from ..ops.attention import (
    NEG_INF,
    causal_attention,
    common_pages,
    paged_decode_walk,
)
from ..ops.norms import rms_norm
from ..ops.pallas.gated_delta import gated_delta
from ..ops.pallas.ssd import ssd
from ..ops.rope import (
    apply_rope,
    kind_frequencies,
    rope_cos_sin,
    rope_frequencies,
)
from .hybrid import HybridPathError, StatePlan, _read_state, _write_state
from .quant import QTensor, dequantize, quantize_array

Params = Dict[str, Any]


def _w(lp: Params, name: str, dtype) -> jnp.ndarray:
    """Fetch a weight, dequantizing int8 QTensors in-graph (models/quant.py:
    XLA fuses the convert into the matmul's operand read, keeping HBM
    traffic int8-sized)."""
    return dequantize(lp[name], dtype)


def _flat_pool(pool):
    """Stacked pool [L, SLOTS, HD] (each leaf of an int8 QTensor pool)
    viewed as [L*SLOTS, HD]."""
    return jax.tree.map(lambda a: a.reshape(-1, a.shape[-1]), pool)


def _stacked_pool(pool, num_layers: int):
    """Inverse of _flat_pool."""
    return jax.tree.map(
        lambda a: a.reshape(num_layers, -1, a.shape[-1]), pool)


@jax.named_scope("kv_write")
def _kv_write(cache, idx, rows: jnp.ndarray):
    """Scatter new KV rows into a pool at flat slot indices.

    Dense pool: cast to the pool dtype.  Int8 pool (QTensor, per-slot
    symmetric scales — runtime/kv_cache.py): quantize each row against its
    own abs-max so one outlier token cannot flatten the whole window's
    resolution, store int8 + f32 scale.  The numerics policy (scale floor,
    rounding, cast order) is models/quant.py's — one recipe for weights
    and KV.  rows [..., Hkv*D]."""
    if isinstance(cache, QTensor):
        qt = quantize_array(rows, (rows.ndim - 1,))
        return QTensor(q=cache.q.at[idx].set(qt.q),
                       s=cache.s.at[idx].set(qt.s))
    return cache.at[idx].set(rows.astype(cache.dtype))


@jax.named_scope("attn_gather")
def _kv_read(cache, idx, dtype) -> jnp.ndarray:
    """Gather pool rows at flat indices, dequantizing int8 pools in-graph
    (the gather reads int8 — HALF the window traffic — and XLA fuses the
    convert+scale into the consumer, models/quant.py dequantize rounding)."""
    if isinstance(cache, QTensor):
        return dequantize(QTensor(q=cache.q[idx], s=cache.s[idx]), dtype)
    return cache[idx]


@jax.named_scope("attn_gather")
def _kv_read_pages(cache, page_table: jnp.ndarray, page_size: int,
                   dtype) -> jnp.ndarray:
    """`_read_pages` under the `attn_gather` scope: the gather that
    materialises (part of) an attention window on the XLA paths."""
    return _read_pages(cache, page_table, page_size, dtype)


def _read_pages(cache, page_table: jnp.ndarray, page_size: int,
                dtype) -> jnp.ndarray:
    """Gather the rows of `page_table`'s pages, [B, P * page_size, Hkv*D],
    by PAGE rather than by slot.

    The slot-granular gather moves B*C separate ~1 KB rows — descriptor-
    bound on TPU (measured: the b32 XLA decode path ran at half the
    Pallas kernel's rate with the KV bytes nowhere near the roofline).
    Page-granular gathering moves B*P contiguous page_size-row blocks,
    16x fewer descriptors at page_size 16.  page_table: [B, P]: a lane's
    whole table (the static window: prefill chunks and verify, s > 1), or
    the columns of one chunk of the decode walk (`_decode_walk`), which
    never gathers the window."""
    ps = page_size
    lead = page_table.shape[:-1]
    if isinstance(cache, QTensor):
        slots, hd = cache.q.shape
        # [pages, ps, hd] view keeps the lane axis separate so a
        # tp-sharded pool's spec propagates through the gather unchanged
        q = cache.q.reshape(slots // ps, ps, hd)[page_table]
        s = cache.s.reshape(slots // ps, ps, 1)[page_table]
        return dequantize(
            QTensor(q=q.reshape(*lead, -1, hd), s=s.reshape(*lead, -1, 1)),
            dtype,
        )
    slots, hd = cache.shape
    win = cache.reshape(slots // ps, ps, hd)[page_table]
    return win.reshape(*lead, -1, hd)


class KVCache(NamedTuple):
    """Contiguous per-layer KV cache: k/v are [L, B, C, Hkv, D]."""

    k: jnp.ndarray
    v: jnp.ndarray

    @property
    def capacity(self) -> int:
        return self.k.shape[2]


class PagedView(NamedTuple):
    """Index plan for one step against a paged KV pool.

    The pool stores k/v as [L, num_pages * page_size, Hkv*D] — a flat slot
    axis shared by all sequences, heads merged into the minor axis (see
    runtime/kv_cache.py). The runtime's page tables translate each
    sequence's logical positions to physical slots; the model only ever sees
    these precomputed flat indices, so the same layer math serves contiguous
    and paged caches.  Indices are WITHIN a layer, the same for every layer:
    the layer scan adds each layer's offset in the stacked pool
    (_layer_view), callers never do.

    write_idx:    [B, S]  flat slot for each new token's k/v
    read_idx:     [B, C]  flat slots forming each sequence's attention window
    kv_positions: [B, C]  absolute position of each window slot
    kv_valid:     [B, C]  False for unallocated/beyond-length slots
    page_table:   [B, P]  physical page ids
    seq_lens:     [B]     cached token counts (decode and verify plans)
    page_size:    static int
    The last three reach both backends: the Pallas kernels and the XLA
    decode walk (`_decode_walk`) address the pool by page and bound their
    reads by seq_lens; the XLA read at s > 1 gathers by page and masks
    with kv_positions / kv_valid.  A view without a page table (pp) falls
    back to the slot gather over read_idx.
    """

    write_idx: jnp.ndarray
    read_idx: jnp.ndarray
    kv_positions: jnp.ndarray
    kv_valid: jnp.ndarray
    page_table: Optional[jnp.ndarray] = None
    seq_lens: Optional[jnp.ndarray] = None
    page_size: Optional[int] = None
    # prefill-chunk bounds (pallas flash prefill backend only)
    start: Optional[jnp.ndarray] = None
    chunk_len: Optional[jnp.ndarray] = None
    # a hybrid decoder's recurrent state: which state slot each lane reads
    # and writes (models/hybrid.StatePlan); None for every other model
    state: Optional[Any] = None


@jax.named_scope("step_ctl")
def _layer_view(paged: PagedView, layer, slots: int) -> PagedView:
    """`paged` re-addressed to `layer` of the flat [L*SLOTS, HD] pool: slot
    indices move by layer*SLOTS and page ids by layer*num_pages, so page 0
    of the layer (its trash page) is page layer*num_pages of the flat pool.
    """
    base = layer * slots
    view = paged._replace(write_idx=paged.write_idx + base,
                          read_idx=paged.read_idx + base)
    if paged.page_table is not None and paged.page_size is not None:
        view = view._replace(
            page_table=paged.page_table + base // paged.page_size)
    return view


def init_kv_cache(cfg: ModelConfig, batch: int, capacity: int, dtype=None) -> KVCache:
    dtype = dtype or cfg.activation_dtype
    if cfg.has_state:
        raise HybridPathError(
            "a decoder with a recurrent state has no contiguous cache: rows "
            "go through the paged pool, the state through its slots")
    if cfg.by_kind:
        # per kind of layer, as the paged pool (runtime/kv_cache.py)
        def rows(n, width):
            return jnp.zeros((n, batch, capacity, 1, width), dtype)

        k, v = {}, {}
        for kind in cfg.kinds:
            g, n = cfg.geometry_of(kind), cfg.layers_of(kind)
            k[kind] = rows(n, g.kv_lora_rank)
            v[kind] = rows(n, g.qk_rope_head_dim)
            if cfg.has_indexer(kind):
                v[INDEX] = rows(n, cfg.index_head_dim)
        return KVCache(k=k, v=v)
    if cfg.is_latent:
        # one "head": k holds the latent c~, v the roped k_r (no padding:
        # nothing DMAs this cache by lane tile)
        lead = (cfg.num_layers, batch, capacity, 1)
        return KVCache(k=jnp.zeros(lead + (cfg.kv_lora_rank,), dtype),
                       v=jnp.zeros(lead + (cfg.qk_rope_head_dim,), dtype))
    shape = (cfg.num_layers, batch, capacity, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init parameters (layer-stacked). Serving loads checkpoints
    instead; random init exists for tests and micro-benchmarks."""
    dtype = dtype or cfg.activation_dtype
    if cfg.hybrid_decoder:
        from .hybrid import init_params as init_hybrid_params

        return init_hybrid_params(cfg, key, dtype)
    if cfg.by_kind:
        return _init_kind_params(cfg, key, dtype)
    if cfg.lead_tree:
        # a tree and a random stream of its own: the stream below is what
        # every other configuration's seeded weights come from
        return _init_lead_tree_params(cfg, key, dtype)
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    keys = jax.random.split(key, 10)
    if cfg.ssd_heads:
        return _init_parallel_params(cfg, keys, dtype)

    def norm01(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(dtype)

    layers: Params = {
        "ln_attn": jnp.ones((L, h), dtype),
        "ln_mlp": jnp.ones((L, h), dtype),
        "wq": norm01(keys[1], (L, h, hq, d), h),
        "wk": norm01(keys[2], (L, h, hkv, d), h),
        "wv": norm01(keys[3], (L, h, hkv, d), h),
        "wo": norm01(keys[4], (L, hq, d, h), hq * d),
    }
    if cfg.is_moe:
        # Mixtral-style MoE MLP: router [L, H, E] + E stacked SwiGLU
        # experts per layer (expert axis shards over "ep")
        E = cfg.num_experts
        layers["router"] = norm01(keys[9], (L, h, E), h)
        layers["wg"] = norm01(keys[5], (L, E, h, f), h)
        layers["wu"] = norm01(keys[6], (L, E, h, f), h)
        layers["wd"] = norm01(keys[7], (L, E, f, h), f)
    else:
        layers["wg"] = norm01(keys[5], (L, h, f), h)
        layers["wu"] = norm01(keys[6], (L, h, f), h)
        layers["wd"] = norm01(keys[7], (L, f, h), f)
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[8], (h, cfg.vocab_size), h)
    return params


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


def _init_parallel_params(cfg: ModelConfig, keys, dtype,
                          scaled: bool = True) -> Params:
    """Random weights of the parallel layout (`falcon_h1`): `init_params`'
    homogeneous stack with the SSD mixer's leaves beside the attention's in
    "layers" (`_ssd_block` names them): w_in [H, 2 d_ssm + 2 groups N +
    heads] (columns z | x | B | C | dt), the taps [L, conv] and their bias,
    A_log a head drawn log U(1, 16), dt_bias the inverse softplus of a step
    drawn log-uniform in [0.001, 0.1] (Mamba-2's own initialiser: a head's
    decay a row spreads over 0.9999 .. 0.2), D and the gated norm's weight
    spread around 1, w_out [d_ssm, H].

    THE MULTIPLIERS.  Every leaf that a muP multiplier scales is drawn at its
    fan-in standard deviation DIVIDED by that multiplier (`scaled`; the
    input projection's columns by their range's entry of `ssm_multipliers`
    too; the embedding at 1 / its multiplier, so that a row times it is of
    unit variance), so that scores, both mixers' outputs, the MLP's and the
    logits are of order 1 as every other preset's are.  At 1 / sqrt(fan_in) the
    published `key_multiplier` 0.011 would flatten every softmax to a mean
    over the keys and both mixers would enter the residual at 0.04 and 0.09:
    a check on the logits would be blind to a wrong mask, rotation or scan
    (a trained model's weights have grown against their multipliers)."""
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    H, P = cfg.ssd_heads, cfg.ssd_head_dim
    d_ssm, conv, taps = H * P, cfg.ssd_conv_dim, cfg.ssd_conv_kernel
    proj = d_ssm + conv + H
    gate_m, down_m = cfg.mlp_multipliers or (1.0, 1.0)

    @partial(jax.jit, static_argnums=(1, 2, 3))
    def norm01(k, shape, fan_in, mult=1.0):
        # one program a leaf: no float32 copy of a 0.8G-element leaf is held
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5 / (mult if scaled else 1.0))).astype(dtype)

    def spread(k, shape, out_dtype=dtype):
        return (1.0 + 0.2 * jax.random.normal(k, shape, jnp.float32)
                ).astype(out_dtype)

    ks = jax.random.split(keys[9], 9)
    mup = ssd_mup_vector(cfg)
    w_in = norm01(ks[0], (L, h, proj), h, cfg.ssm_in_multiplier)
    if mup is not None and scaled:
        # (one program: no float32 copy of the leaf is held)
        w_in = jax.jit(lambda w: (w / mup).astype(dtype),
                       donate_argnums=0)(w_in)
    step = jnp.exp(jax.random.uniform(
        ks[5], (L, H), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
    a_in = cfg.attention_in_multiplier
    layers: Params = {
        "ln_attn": jnp.ones((L, h), dtype),
        "ln_mlp": jnp.ones((L, h), dtype),
        "wq": norm01(keys[1], (L, h, hq, d), h, a_in),
        "wk": norm01(keys[2], (L, h, hkv, d), h, a_in * cfg.key_multiplier),
        "wv": norm01(keys[3], (L, h, hkv, d), h, a_in),
        "wo": norm01(keys[4], (L, hq, d, h), hq * d,
                     cfg.attention_out_multiplier),
        "wg": norm01(keys[5], (L, h, f), h, gate_m),
        "wu": norm01(keys[6], (L, h, f), h),
        "wd": norm01(keys[7], (L, f, h), f, down_m),
        "w_in": w_in,
        "conv_w": norm01(ks[1], (L, taps, conv), taps),
        "conv_b": (0.1 * jax.random.normal(ks[2], (L, conv), jnp.float32)
                   ).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            ks[3], (L, H), jnp.float32, 1.0, 16.0)),
        "D": spread(ks[4], (L, H), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(step)),
        "ln_ssd": spread(ks[6], (L, d_ssm)),
        "w_out": norm01(ks[7], (L, d_ssm, h), d_ssm, cfg.ssm_out_multiplier),
    }
    params: Params = {
        # (a row times its multiplier of unit variance, as the blocks'
        # outputs are: the embedding then weighs in the residual stream)
        "embed": norm01(keys[0], (cfg.vocab_size, h), 1 if scaled else h,
                        cfg.embedding_multiplier),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[8], (h, cfg.vocab_size), h,
                                   cfg.lm_head_multiplier)
    return params


def _init_lead_tree_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights of a `deepseek_v3`-style tree: `first_k_dense` dense
    layers stacked under "dense_layers", the routed ones (router + selection
    bias, experts, shared branch) under "layers", every layer with the SAME
    attention block: latent (`cfg.is_latent`: Kanana-2) or grouped-query
    (K-EXAONE: wq / wk / wv / wo, and with `cfg.qk_norm` a norm weight of
    head_dim a layer for q and for k).  Expert leaves are the `num_experts`
    HELD; the router and its bias keep the router's full width.  The
    selection bias is N(0, 0.1^2), not zero: with b = 0 a program that
    weighs by sigma + b, or chooses by sigma, passes every check; the latent
    norm's and the q / k norms' weights are 1 + N(0, 0.2^2) for the same
    reason.  The latent model's random stream is what it was before the
    grouped-query block came to this tree.

    The conv layout (`cfg.conv_L_cache`: LFM2) keeps "dense_layers" and
    "layers" for the norms and the feed-forward leaves and stacks each
    KIND's mixer under `params["attn"][kind]` in layer order
    (`cfg.kind_leaves`): the grouped-query block above for the attention
    layers, and for a conv layer W_in [H, 3H] (chunks B | C | u), the taps
    [L, H] (tap L - 1 multiplies the row's own product; N(0, 1 / L), so the
    taps that read the tail weigh as much as the one that does not and a
    check on the logits sees a lost tail) and W_out [H, H].

    The linear-attention layout (`cfg.delta_heads`: Solar-Open2) is the conv
    layout's tree with a DELTA mixer (`_delta_attention_block` names the
    leaves; with W = heads x head size: wq / wk / wv [H, W], the three
    convolutions' taps side by side [L, 3W], the decay's and the output
    gate's low-rank pairs [H, head size] and [head size, W], A_log a head
    drawn log U(1, 16) and dt_bias a channel the inverse softplus of a step
    drawn log-uniform in [0.001, 0.1], so a channel's decay a row spreads
    over 0.9999 .. 0.2 and a decay taken per head, or a state rounded to
    bfloat16, moves the logits) and, where the config gates its attention
    elementwise, "wgate" [H, heads x head_dim] among the attention leaves."""
    h, hq = cfg.hidden_size, cfg.num_heads

    @partial(jax.jit, static_argnums=(1, 2))
    def norm01(k, shape, fan_in):
        # one program a leaf: the draw, the scale and the cast fuse, so no
        # float32 copy of a 1G-element leaf is ever held (init_params' eager
        # form holds two, which is what caps the other configurations' depth)
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5)).astype(dtype)

    def spread(k, shape):
        return (1.0 + 0.2 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def norms(n):
        return {"ln_attn": jnp.ones((n, h), dtype),
                "ln_mlp": jnp.ones((n, h), dtype)}

    def latent_attention(k, n):
        r = cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        ks = jax.random.split(k, 5)
        return {
            **norms(n),
            # not ones: over 512 lanes of unit-variance c the RMS is already
            # 1 +- 3%, so with a unit weight a program that skips this norm
            # would pass every check
            "ln_kv": spread(ks[4], (n, r)),
            "wq": norm01(ks[0], (n, h, hq, dn + dr), h),
            "wkva": norm01(ks[1], (n, h, r + dr), h),
            # per head [k_nope | v]; the latent axis next to last, where
            # every stacked matrix has its contracted axis
            "wkvb": norm01(ks[2], (n, hq, r, dn + dv), r),
            "wo": norm01(ks[3], (n, hq, dv, h), hq * dv),
        }

    def gqa_attention(k, n, with_norms=True):
        hkv, d = cfg.num_kv_heads, cfg.head_dim
        ks = jax.random.split(k, 6)
        out = {
            **(norms(n) if with_norms else {}),
            "wq": norm01(ks[0], (n, h, hq, d), h),
            "wk": norm01(ks[1], (n, h, hkv, d), h),
            "wv": norm01(ks[2], (n, h, hkv, d), h),
            "wo": norm01(ks[3], (n, hq, d, h), hq * d),
        }
        if cfg.qk_norm:
            out["ln_q"] = spread(ks[4], (n, d))
            out["ln_k"] = spread(ks[5], (n, d))
        if cfg.attention_gate == "elementwise":
            out["wgate"] = norm01(jax.random.fold_in(k, 6), (n, h, hq * d), h)
        return out

    def delta_mixer(k, n):
        H, D, taps = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_conv_kernel
        W = H * D
        ks = jax.random.split(k, 13)
        step = jnp.exp(jax.random.uniform(
            ks[11], (n, W), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "wq": norm01(ks[0], (n, h, W), h),
            "wk": norm01(ks[1], (n, h, W), h),
            "wv": norm01(ks[2], (n, h, W), h),
            "conv_w": norm01(ks[3], (n, taps, 3 * W), taps),
            "wf1": norm01(ks[4], (n, h, D), h),
            "wf2": norm01(ks[5], (n, D, W), D),
            "wg1": norm01(ks[6], (n, h, D), h),
            "wg2": norm01(ks[7], (n, D, W), D),
            "wbeta": norm01(ks[8], (n, h, H), h),
            "ln_o": spread(ks[9], (n, D)),
            "A_log": jnp.log(jax.random.uniform(
                ks[10], (n, H), jnp.float32, 1.0, 16.0)),
            "dt_bias": jnp.log(jnp.expm1(step)),
            "w_out": norm01(ks[12], (n, W, h), W),
        }

    def conv_mixer(k, n):
        taps = cfg.conv_L_cache
        ks = jax.random.split(k, 3)
        return {"w_in": norm01(ks[0], (n, h, 3 * h), h),
                "conv_w": norm01(ks[1], (n, taps, h), taps),
                "w_out": norm01(ks[2], (n, h, h), h)}

    attention = latent_attention if cfg.is_latent else gqa_attention
    # the conv layout: a mixer a KIND, and the two stacks keep the norms
    mixers = {CONV: conv_mixer, DELTA: delta_mixer,
              GLOBAL: partial(gqa_attention, with_norms=False)}
    kinded = CONV in cfg.layer_types or DELTA in cfg.layer_types
    if kinded:
        def attention(k, n):
            return norms(n)

    def mlp(k, n, f, names=("wg", "wu", "wd")):
        ks = jax.random.split(k, 3)
        return {names[0]: norm01(ks[0], (n, h, f), h),
                names[1]: norm01(ks[1], (n, h, f), h),
                names[2]: norm01(ks[2], (n, f, h), f)}

    keys = jax.random.split(key, 10)
    n_dense = cfg.first_k_dense
    n = cfg.num_layers - n_dense
    layers = attention(keys[1], n)
    if cfg.is_moe:
        E, f = cfg.num_experts, cfg.intermediate_size
        routed = cfg.num_router_experts
        layers["router"] = norm01(keys[2], (n, h, routed), h)
        if cfg.moe_scoring == "sigmoid":
            layers["router_bias"] = 0.1 * jax.random.normal(
                keys[3], (n, routed), jnp.float32)
        layers["wg"] = norm01(keys[4], (n, E, h, f), h)
        layers["wu"] = norm01(keys[5], (n, E, h, f), h)
        layers["wd"] = norm01(keys[6], (n, E, f, h), f)
        if cfg.shared_intermediate_size:
            layers.update(mlp(keys[7], n, cfg.shared_intermediate_size,
                              ("ws_g", "ws_u", "ws_d")))
    else:
        layers.update(mlp(keys[4], n, cfg.intermediate_size))
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
    }
    if kinded:
        params["attn"] = {
            kind: mixers[kind](jax.random.fold_in(keys[1], i),
                               cfg.layers_of(kind))
            for i, kind in enumerate(cfg.kinds)}
    if n_dense:
        kd = jax.random.split(keys[8], 2)
        params["dense_layers"] = {
            **attention(kd[0], n_dense),
            **mlp(kd[1], n_dense, cfg.dense_intermediate_size)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[9], (h, cfg.vocab_size), h)
    return params


# the pool entry (beside the kinds') that holds the indexer's key rows
INDEX = "index"


def _init_kind_params(cfg: ModelConfig, key: jax.Array, dtype) -> Params:
    """Random weights of a latent decoder whose attention is PER KIND of
    layer (`cfg.by_kind`): "attn" holds, per kind, the attention leaves of
    all that kind's layers stacked in layer order (dense and routed alike);
    "dense_layers" and "layers" hold the norms and the FFN leaves.  Expert
    leaves are the `num_experts` HELD; the router and its selection bias keep
    the router's full width.  A matrix that reads a rescaled latent counts
    the rescale in its fan-in, so queries, keys and values come out at unit
    scale as everywhere else; norm weights and biases are spread (not 1 / 0)
    so that a program that skips one fails the check, as
    `_init_lead_tree_params` says."""
    h = cfg.hidden_size

    @partial(jax.jit, static_argnums=(1, 2))
    def norm01(k, shape, fan_in):
        # one program a leaf: no float32 copy of a 0.75G-element leaf is held
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in**-0.5)).astype(dtype)

    def spread(k, shape, mean=1.0, sd=0.2):
        return (mean + sd * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def gain(rank):
        return h / rank if cfg.latent_rescale else 1.0

    def attention(k, kind, n):
        g = cfg.geometry_of(kind)
        hq, r, rq = g.num_heads, g.kv_lora_rank, g.q_lora_rank
        dn, dr, dv = g.qk_nope_head_dim, g.qk_rope_head_dim, g.v_head_dim
        ks = jax.random.split(k, 12)
        out = {
            "ln_kv": spread(ks[0], (n, r)),
            "wkva": norm01(ks[1], (n, h, r + dr), h),
            "wkvb": norm01(ks[2], (n, hq, r, dn + dv), r * gain(r)),
            "wo": norm01(ks[3], (n, hq, dv, h), hq * dv),
        }
        if rq:
            out["wqa"] = norm01(ks[4], (n, h, rq), h)
            out["ln_q"] = spread(ks[5], (n, rq))
            out["wqb"] = norm01(ks[6], (n, rq, hq, dn + dr), rq * gain(rq))
        else:
            out["wq"] = norm01(ks[4], (n, h, hq, dn + dr), h)
        if cfg.attention_gate:
            out["wgate"] = norm01(ks[7], (n, h, hq), h)
        if cfg.has_indexer(kind):
            hi, di = cfg.index_n_heads, cfg.index_head_dim
            src, fan = (rq, rq * gain(rq)) if rq else (h, h)
            out["wiq"] = norm01(ks[8], (n, src, hi, di), fan)
            out["wik"] = norm01(ks[9], (n, h, di), h)
            out["ln_ik"] = spread(ks[10], (n, di))
            out["ln_ik_b"] = spread(jax.random.fold_in(ks[10], 1), (n, di),
                                    0.0, 0.1)
            out["wiw"] = norm01(ks[11], (n, h, hi), h)
        return out

    def mlp(k, n, f, names=("wg", "wu", "wd")):
        ks = jax.random.split(k, 3)
        return {names[0]: norm01(ks[0], (n, h, f), h),
                names[1]: norm01(ks[1], (n, h, f), h),
                names[2]: norm01(ks[2], (n, f, h), f)}

    def norms(n):
        return {"ln_attn": jnp.ones((n, h), dtype),
                "ln_mlp": jnp.ones((n, h), dtype)}

    def stream_maps(k, n):
        """The residual stream's mappings, both sites of `n` layers (the
        leaves `_hc_in` names; {} where the stream is one row).  NOT the
        paper's initial values (alpha 0.01, H_res near the identity): there
        the dynamic term sits under any bfloat16 tolerance and Sinkhorn's
        input is nearly a permutation, so a program without either would
        pass every check.  alpha = 1; Phi N(0, 1 / nC), so its product with
        the normed stream is of unit scale; the biases N(0, 1), H_res's plus
        2 I; the stream norm's weight spread like every norm a check must
        see."""
        m = cfg.hc_mult
        if m == 1:
            return {}
        out = {}
        for s, site in enumerate(HC_SITES):
            ks = jax.random.split(jax.random.fold_in(k, s), 3)
            bias = jax.random.normal(ks[1], (n, 2 * m + m * m), jnp.float32)
            out.update({
                f"hc_{site}_phi": norm01(ks[0], (n, m * h, 2 * m + m * m),
                                         m * h),
                f"hc_{site}_bias": bias.at[:, 2 * m:].add(
                    2.0 * jnp.eye(m).reshape(-1)),
                f"hc_{site}_alpha": jnp.ones((n, 3), jnp.float32),
                f"hc_{site}_norm": spread(ks[2], (n, m * h)),
            })
        return out

    keys = jax.random.split(key, 12)
    n_dense = cfg.first_k_dense
    n = cfg.num_layers - n_dense
    layers = {**norms(n), **stream_maps(keys[10], n)}
    if cfg.is_moe:
        E, f = cfg.num_experts, cfg.intermediate_size
        layers["router"] = norm01(keys[2], (n, h, cfg.num_router_experts), h)
        if cfg.moe_scoring == "sigmoid":
            layers["router_bias"] = 0.1 * jax.random.normal(
                keys[3], (n, cfg.num_router_experts), jnp.float32)
        layers["wg"] = norm01(keys[4], (n, E, h, f), h)
        layers["wu"] = norm01(keys[5], (n, E, h, f), h)
        layers["wd"] = norm01(keys[6], (n, E, f, h), f)
        if cfg.shared_intermediate_size:
            layers.update(mlp(keys[7], n, cfg.shared_intermediate_size,
                              ("ws_g", "ws_u", "ws_d")))
    else:
        layers.update(mlp(keys[4], n, cfg.intermediate_size))
    params: Params = {
        "embed": norm01(keys[0], (cfg.vocab_size, h), h),
        "final_norm": jnp.ones((h,), dtype),
        "layers": layers,
        "attn": {kind: attention(jax.random.fold_in(keys[1], i), kind,
                                 cfg.layers_of(kind))
                 for i, kind in enumerate(cfg.kinds)},
    }
    if n_dense:
        params["dense_layers"] = {
            **norms(n_dense), **stream_maps(keys[11], n_dense),
            **mlp(keys[8], n_dense, cfg.dense_intermediate_size)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = norm01(keys[9], (h, cfg.vocab_size), h)
    return params


# The two sublayers of a layer, each with mappings of its own where the
# residual stream is widened (`cfg.hc_mult` > 1): leaves `hc_<site>_phi`
# [nC, n + n + n^2] (ONE matrix: H~_pre | H~_post | H~_res, split after the
# one product), `hc_<site>_bias` [n + n + n^2], `hc_<site>_alpha` [3] (both
# float32) and `hc_<site>_norm` [nC].
HC_SITES = ("attn", "mlp")


@partial(jax.jit, static_argnames="cfg")
def _sinkhorn(logits: jnp.ndarray, cfg: ModelConfig):
    """[..., n * n] float32 logits (a row's matrix row-major) -> the doubly
    stochastic matrix as n x n arrays [...], res[i][j]: exp of the clamped
    logits, then `cfg.hc_sinkhorn_iters` rounds of (each row by its sum +
    eps; each column by its sum + eps).  All the rounds, unrolled: no early
    exit.  Entry by entry on purpose: a sum over an axis of 4 is a reduction
    XLA fuses nothing across (eighty fusions a site on the v5e's compiler);
    sums of four arrays are elementwise, and the rounds compile to ONE.
    Jitted so that its 1,300 equations are traced once a shape and lowered
    as one function the sites call (XLA inlines it): unjitted, six sites a
    program cost a boot 25 s of tracing."""
    n = cfg.hc_mult
    m = jnp.exp(jnp.clip(logits, cfg.hc_res_clamp_min, cfg.hc_res_clamp_max))
    m = [[m[..., i * n + j] for j in range(n)] for i in range(n)]
    for _ in range(cfg.hc_sinkhorn_iters):
        for i in range(n):
            inv = 1.0 / (sum(m[i]) + cfg.hc_eps)
            m[i] = [v * inv for v in m[i]]
        for j in range(n):
            inv = 1.0 / (sum(m[i][j] for i in range(n)) + cfg.hc_eps)
            for i in range(n):
                m[i][j] = m[i][j] * inv
    return tuple(tuple(row) for row in m)


def _hc_rows(h: jnp.ndarray, n: int):
    """The stream's n rows of a token, float32: h is [B, T, n * C], row j at
    lanes j * C .. (j + 1) * C (a [.., n, C] array would be tiled with its
    second-minor axis padded from 4 to 8 or 16 sublanes on the device)."""
    c = h.shape[-1] // n
    return [h[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def _hc_in(h: jnp.ndarray, lp: Params, site: str, cfg: ModelConfig):
    """Ahead of a sublayer: (u, maps).  One row a token (`cfg.hc_mult` 1): h
    itself and None, and not an op traced.  n rows: the site's per-token
    mappings from the normed stream (`hc_map`: float32, the one product with
    `hc_<site>_phi` at full precision), u = H_pre X (`hc_mix`) and maps =
    (H_post [B, T, n], H_res as `_sinkhorn` gives it) for `_hc_out`."""
    n = cfg.hc_mult
    if n == 1:
        return h, None
    with jax.named_scope("hc_map"):
        x = h.astype(jnp.float32)
        x = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        x = x * lp[f"hc_{site}_norm"].astype(jnp.float32)
        t = jnp.einsum("btk,km->btm", x,
                       lp[f"hc_{site}_phi"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        alpha, bias = lp[f"hc_{site}_alpha"], lp[f"hc_{site}_bias"]
        pre = jax.nn.sigmoid(alpha[0] * t[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(
            alpha[1] * t[..., n:2 * n] + bias[n:2 * n])
        res = _sinkhorn(alpha[2] * t[..., 2 * n:] + bias[2 * n:], cfg)
    with jax.named_scope("hc_mix"):
        rows = _hc_rows(h, n)
        u = sum(pre[..., j, None] * rows[j] for j in range(n)).astype(h.dtype)
    return u, (post, res)


def _hc_out(h: jnp.ndarray, y: jnp.ndarray, maps, scope: str) -> jnp.ndarray:
    """After a sublayer: one row a token, `h + y` under `scope` (where the
    add always sat); n rows, X <- H_res X + H_post^T y under `hc_mix`."""
    if maps is None:
        with jax.named_scope(scope):
            return h + y
    post, res = maps
    n = post.shape[-1]
    with jax.named_scope("hc_mix"):
        rows, y32 = _hc_rows(h, n), y.astype(jnp.float32)
        return jnp.concatenate(
            [sum(res[i][j][..., None] * rows[j] for j in range(n))
             + post[..., i, None] * y32 for i in range(n)],
            axis=-1).astype(h.dtype)


def _attention_block(
    x: jnp.ndarray,
    lp: Params,
    cfg: ModelConfig,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
    k_cache: Optional[jnp.ndarray],
    v_cache: Optional[jnp.ndarray],
    kv_valid: Optional[jnp.ndarray],
    cache_positions: Optional[jnp.ndarray],
    paged: Optional["PagedView"] = None,
    mesh=None,
    layer=None,
    window: Optional[int] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """One attention sublayer. x: [B, S, H]. Returns (out, k_cache', v_cache').

    k_cache/v_cache are the STACKED caches of all layers the caller scans
    (None = uncached) and `layer` is this layer's index in them; they are
    returned stacked, with only this layer's new rows written.  `window`
    (static) makes this a sliding-window layer; its attention proper runs
    under the `attn_window` scope inside `attn_core`, so a device trace
    splits attention time by kind of layer.  `cos` None: this kind of layer
    does not rotate q and k (`cfg.unrotated_kinds`); "ln_q" / "ln_k" among
    the leaves: QK-norm, under its own scope `qk_norm`."""
    dt = x.dtype
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wq", dt))
        k = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wk", dt))
        v = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wv", dt))
        if cfg.key_multiplier != 1.0:
            k = k * jnp.asarray(cfg.key_multiplier, dt)
    if "ln_q" in lp:
        # QK-norm: each head's q and k RMS-normed over head_dim with the
        # layer's learned weights, ahead of the rotation
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, lp["ln_q"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["ln_k"], cfg.rms_norm_eps)
    if cos is not None:  # (None: a kind of layer that does not rotate)
        with jax.named_scope("attn_qkv"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    if paged is not None:
        # Paged pool [L, TOTAL_SLOTS, Hkv*D] (dense arrays, or QTensor
        # int8+scales when kv_quantize is on), addressed flat: from here to
        # the end of attention k_cache/v_cache are [L*TOTAL_SLOTS, Hkv*D]
        # and `paged` carries this layer's offset in every index.
        b, s, hkv, d = k.shape
        num_layers, slots = k_cache.shape[:2]
        paged = _layer_view(paged, layer, slots)
        k_cache = _kv_write(
            _flat_pool(k_cache), paged.write_idx, k.reshape(b, s, hkv * d))
        v_cache = _kv_write(
            _flat_pool(v_cache), paged.write_idx, v.reshape(b, s, hkv * d))
    with jax.named_scope("attn_core"), (
            nullcontext() if window is None
            else jax.named_scope("attn_window")):
        out, k_cache, v_cache = _attention_core(
            q, k, v, cfg, positions, k_cache, v_cache, kv_valid,
            cache_positions, paged, mesh, layer, window,
        )
    if paged is not None:
        k_cache = _stacked_pool(k_cache, num_layers)
        v_cache = _stacked_pool(v_cache, num_layers)
    if "wgate" in lp:
        # the elementwise output gate ("Gated Attention for LLMs", G1): every
        # value of every head's output times sigmoid(x W_gate), ahead of W_o
        with jax.named_scope("attn_gate"):
            gate = jnp.einsum("bsh,hw->bsw", x, _w(lp, "wgate", dt))
            out = out * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(out.dtype).reshape(out.shape)
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bsnd,ndh->bsh", out, _w(lp, "wo", out.dtype))
    return out, k_cache, v_cache


def _tail_conv_silu(rows: jnp.ndarray, w: jnp.ndarray, bias, leaf, layer,
                    plan: StatePlan):
    """SiLU of a depthwise causal convolution whose tail is a layer's STATE
    (the delta layout's three convolutions side by side, the parallel
    layout's one over [x | B | C]).  rows [B, S, C]; w [taps, C] float32 (tap
    taps - 1 multiplies the row's own value); bias [C] float32 or None.  The
    taps - 1 rows before the pass come from `leaf`, the stacked state array
    (laid out in the slot as `cfg.state_shapes` says; None: uncached, zeros)
    at `layer`, and the last taps - 1 REAL rows (`plan.lens`; as
    `_short_conv_block`'s tail) go back to it (models/hybrid._read_state /
    _write_state).  Returns (float32 [B, S, C], leaf')."""
    f32 = jnp.float32
    b, s, c = rows.shape
    taps = w.shape[0]
    tail = (jnp.zeros((b, taps - 1, c), f32) if leaf is None
            else _read_state(leaf, layer, plan, b).reshape(b, taps - 1, c))
    seq = jnp.concatenate([tail, rows.astype(f32)], axis=1)
    out = sum(w[j] * seq[:, j:j + s] for j in range(taps))
    out = jax.nn.silu(out if bias is None else out + bias)
    if leaf is not None:
        new = jax.vmap(
            lambda rows, n: jax.lax.dynamic_slice_in_dim(
                rows, n, taps - 1, axis=0))(seq, plan.lens)
        slot = (b,) + leaf.shape[2:]
        leaf = _write_state(leaf, layer, plan, new.reshape(slot),
                            tail.reshape(slot))
    return out, leaf


def _delta_attention_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
                           leaves, layer, plan: StatePlan):
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
    `cfg.state_shapes` says), read and written as a
    short convolution's tail is (models/hybrid._read_state / _write_state),
    and "delta", S transposed a head, float32, which ops/pallas/gated_delta
    updates IN PLACE on the Pallas backend (the chunk kernel at S > 1, the
    step kernel in decode) and through the same slot read and write under a
    row-by-row scan elsewhere.  Everything between the projections and W_o is
    float32.  Returns (out [B, S, H] ahead of the residual add, leaves')."""
    dt, f32 = x.dtype, jnp.float32
    b, s, _ = x.shape
    H, D = cfg.delta_heads, cfg.delta_head_dim
    with jax.named_scope("kda_proj"):
        qkv = jnp.concatenate(
            [jnp.einsum("bsh,hw->bsw", x, _w(lp, n, dt))
             for n in ("wq", "wk", "wv")], axis=-1)
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
            qkv, lp["conv_w"].astype(f32), None, conv_leaf, layer, plan)
    with jax.named_scope("kda_gate"):
        q, k, v = (a.reshape(b, s, H, D) for a in jnp.split(qkv, 3, axis=-1))

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

        q, k = unit(q) * D**-0.5, unit(k)
        g = -jnp.exp(lp["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            decay.astype(f32) + lp["dt_bias"].astype(f32)
        ).reshape(b, s, H, D)
        beta = jax.nn.sigmoid(beta.astype(f32)) * (
            2.0 if cfg.delta_neg_eigval else 1.0)
    with jax.named_scope("kda_delta"):
        o, delta_leaf = gated_delta(
            delta_leaf, layer, plan, q, k, v, g, beta,
            kernel=cfg.attention_backend == "pallas",
            read_state=_read_state, write_state=_write_state)
    with jax.named_scope("kda_gate"):
        o = rms_norm(o, lp["ln_o"].astype(f32), cfg.rms_norm_eps) \
            * jax.nn.sigmoid(gate.astype(f32)).reshape(b, s, H, D)
    with jax.named_scope("kda_proj"):
        out = jnp.einsum("bsw,wh->bsh", o.astype(dt).reshape(b, s, H * D),
                         _w(lp, "w_out", dt))
    if leaves is not None:
        leaves = {**leaves, "conv": conv_leaf, "delta": delta_leaf}
    return out, leaves


def _ssd_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig, leaves, layer,
               plan: StatePlan):
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
    (laid out in the slot as `cfg.state_shapes` says; models/hybrid._read_state
    / _write_state), and "ssd", S a head, float32, which ops/pallas/ssd
    updates IN PLACE on the Pallas backend (the chunk kernel at S > 1, the
    step kernel in decode) and through the same slot read and write under a
    row-by-row scan elsewhere.  Everything between the projections is
    float32.  Returns (out [B, S, H] ahead of the residual add, leaves')."""
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
            conv_leaf, layer, plan)
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
            read_state=_read_state, write_state=_write_state)
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


def _short_conv_block(x: jnp.ndarray, lp: Params, leaf, layer,
                      plan: StatePlan):
    """One gated short convolution (`lfm2_moe`'s conv mixer).  x: [B, S, H].
    [B | C | u] = x W_in; z = B * u; c_t = sum_j w_j * z_(t - L + 1 + j)
    over the L taps (depthwise, causal: tap L - 1 is the row's own); the
    block is (C * c) W_out.  The L - 1 products z before the pass are the
    layer's STATE: `leaf` is the stacked state array [conv layers, n_slots,
    L - 1, H] float32 (None: uncached, a zero tail) and `layer` this layer's
    place in it; a lane's tail comes from its `plan.src` slot and the tail
    after its last real row (`plan.lens`) goes to `dst` and `snap`, an
    inactive lane's passing through (models/hybrid._read_state /
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
                else _read_state(leaf, layer, plan, b))
        seq = jnp.concatenate([tail, gate_b.astype(f32) * u.astype(f32)],
                              axis=1)
        c = sum(w[j] * seq[:, j:j + s] for j in range(taps))
        if leaf is not None:
            # the last L - 1 REAL products: rows lens - L + 1 .. lens - 1 of
            # the pass are rows lens .. lens + L - 2 of `seq`
            new = jax.vmap(
                lambda rows, n: jax.lax.dynamic_slice_in_dim(
                    rows, n, taps - 1, axis=0))(seq, plan.lens)
            leaf = _write_state(leaf, layer, plan, new, tail)
        y = gate_c * c.astype(dt)
    with jax.named_scope("conv_proj"):
        out = jnp.einsum("bsh,hk->bsk", y, _w(lp, "w_out", dt))
    return out, leaf


class WindowedPathError(NotImplementedError):
    """An attention path that has no sliding-window form was reached by a
    windowed layer.  The engine refuses such configurations when it is
    built (runtime/engine.py); this is the backstop for direct callers of
    `forward`, so that no path ever ignores a window."""


def _attention_core(q, k, v, cfg, positions, k_cache, v_cache, kv_valid,
                    cache_positions, paged, mesh, layer, window=None):
    """Scores, softmax and weighted sum for one layer, by cache form and
    backend.  `window` (static, None = global): the layer attends
    q_pos - window < kv_pos <= q_pos; every path below honours it or raises
    WindowedPathError.  Paged: k_cache/v_cache are the flat [L*SLOTS, Hkv*D] pools,
    the new rows already in them, and `paged` addresses this layer
    (_attention_block did both).  Contiguous: the stacked [L, B, C, Hkv, D]
    cache is written here at `layer`.  Returns (out [B, S, Hq, D],
    k_cache', v_cache')."""
    dt = q.dtype
    if paged is not None:
        b, s, hkv, d = k.shape
        if (
            cfg.attention_backend == "pallas"
            and s == 1
            and paged.page_table is not None
        ):
            interp = jax.default_backend() != "tpu"
            on_mesh = mesh is not None and mesh.size > 1
            if isinstance(k_cache, QTensor):
                # int8 pool: the int8 kernel DMAs half the bytes and
                # fuses the per-slot dequant into scores/probabilities
                if window is not None:
                    raise WindowedPathError(
                        "kv_quantize int8 paged-decode kernel has no "
                        "sliding-window form")
                from ..ops.pallas import (
                    paged_decode_attention_int8,
                    paged_decode_attention_int8_sharded,
                )

                if on_mesh:
                    out = paged_decode_attention_int8_sharded(
                        mesh, q[:, 0],
                        k_cache.q, k_cache.s, v_cache.q, v_cache.s,
                        paged.page_table, paged.seq_lens,
                        page_size=paged.page_size, interpret=interp,
                    )[:, None]
                else:
                    out = paged_decode_attention_int8(
                        q[:, 0],
                        k_cache.q, k_cache.s, v_cache.q, v_cache.s,
                        paged.page_table, paged.seq_lens,
                        page_size=paged.page_size, interpret=interp,
                    )[:, None]
            elif on_mesh:
                # per-shard kernel over the tp(/tq) head split: shard_map
                # runs the custom call GSPMD cannot partition (engine
                # validates pallas_mesh_ok at construction)
                from ..ops.pallas import paged_decode_attention_sharded

                out = paged_decode_attention_sharded(
                    mesh,
                    q[:, 0],  # [B, Hq, D]
                    k_cache,
                    v_cache,
                    paged.page_table,
                    paged.seq_lens,
                    page_size=paged.page_size,
                    interpret=interp,
                    window=window,
                )[:, None]
            elif window is not None:
                from ..ops.pallas import paged_decode_attention_window

                out = paged_decode_attention_window(
                    q[:, 0],
                    k_cache,
                    v_cache,
                    paged.page_table,
                    paged.seq_lens,
                    window=window,
                    page_size=paged.page_size,
                    interpret=interp,
                )[:, None]
            else:
                from ..ops.pallas import paged_decode_attention

                out = paged_decode_attention(
                    q[:, 0],  # [B, Hq, D]
                    k_cache,
                    v_cache,
                    paged.page_table,
                    paged.seq_lens,
                    page_size=paged.page_size,
                    interpret=interp,
                )[:, None]  # [B, 1, Hq, D]
        elif (
            cfg.attention_backend == "pallas"
            and s > 1
            and paged.seq_lens is not None
            and paged.page_table is not None
            and not isinstance(k_cache, QTensor)
        ):
            # Speculative verify step (StepPrograms.verify): S = K+1
            # query tokens per lane against the paged pool, each causally
            # masked to its own position.  seq_lens present + s>1
            # distinguishes it from prefill chunks (which carry `start`)
            # and plain decode (s == 1).  Int8 pools fall through to the
            # dequantizing XLA gather below.
            if window is not None:
                raise WindowedPathError(
                    "speculative verify (paged_verify_attention) has no "
                    "sliding-window form")
            from ..ops.pallas import (
                paged_verify_attention,
                paged_verify_attention_sharded,
            )

            interp = jax.default_backend() != "tpu"
            if mesh is not None and mesh.size > 1:
                out = paged_verify_attention_sharded(
                    mesh, q, k_cache, v_cache,
                    paged.page_table, paged.seq_lens, paged.chunk_len,
                    page_size=paged.page_size, interpret=interp,
                )
            else:
                out = paged_verify_attention(
                    q, k_cache, v_cache,
                    paged.page_table, paged.seq_lens, paged.chunk_len,
                    page_size=paged.page_size, interpret=interp,
                )
        elif (
            cfg.attention_backend == "pallas"
            and s > 1
            and b == 1
            and (mesh is None or mesh.size == 1)
            and not isinstance(k_cache, QTensor)
            and paged.page_table is not None
            and paged.start is not None
        ):
            from ..ops.pallas import paged_prefill_attention

            out = paged_prefill_attention(
                q[0],  # [S, Hq, D]
                k_cache,
                v_cache,
                paged.page_table[0],
                paged.start,
                paged.chunk_len,
                page_size=paged.page_size,
                interpret=jax.default_backend() != "tpu",
                window=window,
            )[None]
        elif cfg.prefill_ring and s > 1:
            # Chunked prefill over the sp axis: the chunk's own q/k/v ride
            # the ring sequence-sharded; the paged window of earlier chunks
            # (ctx_valid excludes the chunk's freshly written positions —
            # those would otherwise be counted twice) is read locally from
            # the pool by every sp rank (heads stay tp-sharded).
            from ..parallel.ring_attention import (
                ring_prefill_sharded,
                ulysses_prefill_sharded,
            )

            if window is not None:
                raise WindowedPathError(
                    "prefill_ring (ring / ulysses prefill over sp) has no "
                    "sliding-window form")
            if mesh is None:
                raise RuntimeError(
                    "prefill_ring requires the mesh (forward(..., mesh=...))"
                )
            k_win = _kv_read(k_cache, paged.read_idx, dt).reshape(b, -1, hkv, d)
            v_win = _kv_read(v_cache, paged.read_idx, dt).reshape(b, -1, hkv, d)
            ctx_valid = paged.kv_valid & (paged.kv_positions < positions[:, :1])
            cp = (ulysses_prefill_sharded if cfg.cp_strategy == "ulysses"
                  else ring_prefill_sharded)
            out = cp(
                mesh, q, k, v, positions,
                k_win, v_win, paged.kv_positions, ctx_valid,
            )
        elif (
            s == 1
            and paged.seq_lens is not None
            and paged.page_table is not None
            and paged.page_size is not None
        ):
            out = _decode_walk(q, k_cache, v_cache, paged, hkv, window, mesh)
        elif paged.page_table is not None and paged.page_size is not None:
            # s > 1 (prefill chunks, verify): page-granular gather of the
            # static window (see _kv_read_pages: the slot-granular form is
            # descriptor-bound), attended in one shot
            k_win = _kv_read_pages(
                k_cache, paged.page_table, paged.page_size, dt
            ).reshape(b, -1, hkv, d)
            v_win = _kv_read_pages(
                v_cache, paged.page_table, paged.page_size, dt
            ).reshape(b, -1, hkv, d)
            out = causal_attention(
                q,
                k_win,
                v_win,
                q_positions=positions,
                kv_positions=paged.kv_positions,
                kv_valid=paged.kv_valid,
                window=window,
            )
        else:
            k_win = _kv_read(k_cache, paged.read_idx, dt).reshape(b, -1, hkv, d)
            v_win = _kv_read(v_cache, paged.read_idx, dt).reshape(b, -1, hkv, d)
            out = causal_attention(
                q,
                k_win,
                v_win,
                q_positions=positions,
                kv_positions=paged.kv_positions,
                kv_valid=paged.kv_valid,
                window=window,
            )
    elif k_cache is None:
        out = causal_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            window=window,
        )
    else:
        # Scatter new k/v rows into cache slots (slot == absolute position
        # for the contiguous cache; the engine passes explicit slots for
        # chunked prefill/decode).
        slots = positions if cache_positions is None else cache_positions
        b_idx = jnp.arange(q.shape[0])[:, None]
        with jax.named_scope("kv_write"):
            k_cache = k_cache.at[layer, b_idx, slots].set(
                k.astype(k_cache.dtype))
            v_cache = v_cache.at[layer, b_idx, slots].set(
                v.astype(v_cache.dtype))
        cap = k_cache.shape[2]
        kv_pos = jnp.broadcast_to(jnp.arange(cap)[None, :], (q.shape[0], cap))
        out = causal_attention(
            q,
            k_cache[layer],
            v_cache[layer],
            q_positions=positions,
            kv_positions=kv_pos,
            kv_valid=kv_valid,
            window=window,
        )
    return out, k_cache, v_cache


def _decode_walk(q, k_cache, v_cache, paged: PagedView, hkv: int,
                 window: Optional[int], mesh) -> jnp.ndarray:
    """The XLA decode read (s == 1, page table present): walk each lane's
    live context chunk by chunk in the pool's own [.., Hkv*D] rows
    (ops/attention.py paged_decode_walk) rather than gather its static
    window and re-lay it out by head.  A lane is active iff its position 0
    is valid (decode_plan folds activity into kv_valid).  On a mesh of
    more than one device heads stay a batch dimension of the contraction.
    q [B, 1, Hq, D] -> [B, 1, Hq, D]."""
    ps, dt = paged.page_size, q.dtype

    def read_pages(pages):
        return (_kv_read_pages(k_cache, pages, ps, dt),
                _kv_read_pages(v_cache, pages, ps, dt))

    return paged_decode_walk(
        q[:, 0], read_pages, paged.page_table, paged.seq_lens,
        paged.kv_valid[:, 0], page_size=ps, num_kv_heads=hkv, window=window,
        heads_batched=mesh is not None and mesh.size > 1,
    )[:, None]


class LatentPathError(NotImplementedError):
    """An attention path that has no latent (MLA) form was reached by a
    latent-attention model.  The engine refuses such options when it is
    built (runtime/engine.py LatentAttentionUnsupported); this is the
    backstop for direct callers of `forward`."""


def _deinterleave(x: jnp.ndarray) -> jnp.ndarray:
    """x0 x1 x2 x3 ... -> x0 x2 ... | x1 x3 ...: published interleaved rotary
    pairs into the half-split pairing `apply_rope` rotates."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _latent_attend(q_a, q_rope, keys_a, k_rope, values, mask, scale,
                   shared: bool):
    """softmax((q_a . keys_a + q_rope . k_rope) * scale) . values in f32
    scores, the one latent attention proper on XLA.  `shared` False, the
    expanded form: keys_a / values are per head, [B, T, N, d].  True, the
    absorbed form: they are the latent rows themselves, [B, T, r], shared by
    all heads (and `values is keys_a`).  q_a [B, S, N, d|r], q_rope
    [B, S, N, dr], k_rope [B, T, dr] (one vector a token), mask [B, S, T]."""
    kv = "bkr" if shared else "bknr"
    logits = (
        jnp.einsum(f"bqnr,{kv}->bnqk", q_a, keys_a,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bqnd,bkd->bnqk", q_rope, k_rope,
                     preferred_element_type=jnp.float32)
    ) * scale
    logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(f"bnqk,{kv}->bqnr", probs.astype(values.dtype), values,
                     preferred_element_type=jnp.float32)
    return out.astype(q_a.dtype)


def _latent_attention_block(
    x: jnp.ndarray,
    lp: Params,
    cfg: ModelConfig,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: jnp.ndarray,
    k_cache,
    v_cache,
    kv_valid: Optional[jnp.ndarray],
    cache_positions: Optional[jnp.ndarray],
    paged: Optional["PagedView"] = None,
    mesh=None,
    layer=None,
    kind: str = GLOBAL,
    i_cache=None,
):
    """One latent-attention (MLA) sublayer of a layer of `kind`; the cache
    contract of _attention_block, with one more cache: returns (out,
    k_cache', v_cache', i_cache').  What is cached per token is (c~, roped
    k_r): k_cache holds c~, v_cache k_r (module docstring); where the kind
    has an indexer (`cfg.has_indexer`), i_cache holds its key k^I.  Paged
    decode runs the absorbed form, everything else the expanded one; what
    only the latent form adds around attention proper (the absorb and
    un-absorb einsums, the expansion of cached rows through W_kvb) sits
    under `attn_latent_proj` inside `attn_core`.  Paged prefill (s > 1) of
    every latent model walks the live keys in chunks with a running softmax
    (`_latent_prefill_walk`) and never holds [Hq, S, window] scores; a paged
    plan addresses the pool by page (every plan builder hands a page table).

    A `cfg.by_kind` model's block also has, by what its leaves and its
    config say: a query low-rank ("wqa"), the rescale of the normed latents,
    a sliding window (`cfg.window_of(kind)`: every path masks to it, paged
    decode reads the window's pages only), the learned key selection
    (`attn_index`: indexer projections, scores over the live context, exact
    top-k; `attn_select`: the read of the chosen rows), and the headwise
    gate (`attn_gate`).  Its prefill walk is masked to the chosen keys or
    to the window."""
    dt = x.dtype
    g = cfg.geometry_of(kind)
    r, dn = g.kv_lora_rank, g.qk_nope_head_dim
    scale = cfg.latent_softmax_scale(kind)
    window = cfg.window_of(kind)
    indexed = cfg.has_indexer(kind)
    if mesh is not None and mesh.size > 1:
        raise LatentPathError(
            "latent attention on a mesh of more than one device (tp / ep / "
            "sp over the latent pool)")
    if cfg.prefill_ring:
        raise LatentPathError("prefill_ring has no latent form")
    if any(isinstance(c, QTensor) for c in (k_cache, v_cache, i_cache)):
        raise LatentPathError("the int8 KV pool has no latent form")
    with jax.named_scope("attn_qkv"):
        if "wqa" in lp:
            c_q = rms_norm(jnp.einsum("bsh,hr->bsr", x, _w(lp, "wqa", dt)),
                           lp["ln_q"], cfg.rms_norm_eps)
            c_q = _rescaled(c_q, cfg)
            q = jnp.einsum("bsr,rnd->bsnd", c_q, _w(lp, "wqb", dt))
        else:
            c_q = x
            q = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wq", dt))
        kva = jnp.einsum("bsh,hr->bsr", x, _w(lp, "wkva", dt))
        c = _rescaled(rms_norm(kva[..., :r], lp["ln_kv"], cfg.rms_norm_eps),
                      cfg)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        k_rope = kva[..., None, r:]  # ONE vector a token: a head axis of 1
        if cfg.rope_interleave:
            q_rope, k_rope = _deinterleave(q_rope), _deinterleave(k_rope)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope, cos, sin)[..., 0, :]
    if indexed:
        q_idx, k_idx, w_idx = _index_projections(x, c_q, lp, cfg, cos, sin)
    wkvb = _w(lp, "wkvb", dt)  # [N, r, dn + dv]
    b, s = x.shape[:2]
    absorbed = False
    if paged is not None:
        if paged.page_table is None or paged.page_size is None:
            raise LatentPathError("a paged plan without a page table (pp)")
        # Paged pools [L, SLOTS, r] and [L, SLOTS, lanes >= dr], addressed
        # flat with this layer's offset in every index (_attention_block)
        num_layers, slots = k_cache.shape[:2]
        paged = _layer_view(paged, layer, slots)
        lanes = v_cache.shape[-1]
        k_cache = _kv_write(_flat_pool(k_cache), paged.write_idx, c)
        v_cache = _kv_write(
            _flat_pool(v_cache), paged.write_idx,
            jnp.pad(k_rope, ((0, 0), (0, 0), (0, lanes - k_rope.shape[-1]))))
        if indexed:
            i_lanes = i_cache.shape[-1]
            i_cache = _kv_write(
                _flat_pool(i_cache), paged.write_idx,
                jnp.pad(k_idx, ((0, 0), (0, 0), (0, i_lanes - k_idx.shape[-1]))))
        if s > 1 and paged.seq_lens is not None:
            raise LatentPathError(
                "speculative verify (K+1 queries a lane) has no latent form")
        absorbed = s == 1
    kernel = absorbed and cfg.attention_backend == "pallas" and not indexed
    # the paged forms that read less than the static window: the chosen
    # rows, the window's pages, prefill's walk of the live keys
    chosen_rows = absorbed and indexed
    window_pages = absorbed and window is not None and not kernel
    walk = paged is not None and not absorbed
    with jax.named_scope("attn_core"), (
            nullcontext() if window is None
            else jax.named_scope("attn_window")):
        mask = None
        if not (kernel or chosen_rows or window_pages or walk):
            # the XLA forms: the window of cached rows and who may attend it
            if paged is not None:
                # absorbed decode in XLA: the static window, page by page,
                # less the rotary rows' lane padding
                table, ps = paged.page_table, paged.page_size
                c_win = _kv_read_pages(k_cache, table, ps, dt)
                r_win = _kv_read_pages(v_cache, table, ps, dt)
                r_win = r_win[..., :k_rope.shape[-1]]
                kv_pos, valid = paged.kv_positions, paged.kv_valid
            elif k_cache is None:
                c_win, r_win, kv_pos, valid = c, k_rope, positions, None
                if indexed:
                    i_win = k_idx
            else:
                idx = positions if cache_positions is None else cache_positions
                b_idx = jnp.arange(b)[:, None]
                with jax.named_scope("kv_write"):
                    k_cache = k_cache.at[layer, b_idx, idx, 0].set(
                        c.astype(k_cache.dtype))
                    v_cache = v_cache.at[layer, b_idx, idx, 0].set(
                        k_rope.astype(v_cache.dtype))
                    if indexed:
                        i_cache = i_cache.at[layer, b_idx, idx, 0].set(
                            k_idx.astype(i_cache.dtype))
                c_win, r_win = k_cache[layer][:, :, 0], v_cache[layer][:, :, 0]
                cap = c_win.shape[1]
                kv_pos = jnp.broadcast_to(jnp.arange(cap)[None, :], (b, cap))
                valid = kv_valid
                if indexed:
                    i_win = i_cache[layer][:, :, 0].astype(dt)
            c_win, r_win = c_win.astype(dt), r_win.astype(dt)
            mask = positions[:, :, None] >= kv_pos[:, None, :]
            if window is not None:
                mask = mask & (kv_pos[:, None, :]
                               > positions[:, :, None] - window)
            if valid is not None:
                mask = mask & valid[:, None, :]
            if indexed:
                with jax.named_scope("attn_index"):
                    scores = _index_scores(q_idx, w_idx, i_win)
                    mask = _chosen_mask(scores, mask, cfg.index_topk)
            if cfg.by_kind:
                # a masked row may hold anything (a page never written)
                c_win = _zero_unattended(c_win, mask)
                r_win = _zero_unattended(r_win, mask)
        elif chosen_rows:
            with jax.named_scope("attn_index"):
                chosen, mask = _paged_index_choice(
                    q_idx, w_idx, i_cache, paged, positions, cfg, dt)
            with jax.named_scope("attn_select"):
                c_win, r_win = _read_chosen_rows(
                    k_cache, v_cache, chosen, dt)
                r_win = r_win[..., :k_rope.shape[-1]]
        elif window_pages:
            c_win, r_win, mask = _latent_window_pages(
                k_cache, v_cache, paged, window, dt)
            r_win = r_win[..., :k_rope.shape[-1]]
        if absorbed:
            with jax.named_scope("attn_latent_proj"):
                q_lat = jnp.einsum("bsnd,nrd->bsnr", q_nope, wkvb[..., :dn])
            if kernel:
                from ..ops.pallas import paged_decode_attention_latent

                o_lat = paged_decode_attention_latent(
                    q_lat[:, 0], q_rope[:, 0], k_cache, v_cache,
                    paged.page_table, paged.seq_lens, scale=scale,
                    page_size=paged.page_size,
                    interpret=jax.default_backend() != "tpu",
                    **({} if window is None else {"window": window}),
                )[:, None]
            else:
                o_lat = _latent_attend(q_lat, q_rope, c_win, r_win, c_win,
                                       mask, scale, shared=True)
            with jax.named_scope("attn_latent_proj"):
                out = jnp.einsum("bsnr,nrd->bsnd", o_lat, wkvb[..., dn:])
        elif walk:
            chosen_of = None
            if indexed:
                with jax.named_scope("attn_index"):
                    _, chosen_of = _paged_index_choice(
                        q_idx, w_idx, i_cache, paged, positions, cfg, dt,
                        as_mask=True)
            out = _latent_prefill_walk(
                q_nope, q_rope, wkvb, k_cache, v_cache, paged, positions,
                scale, dn, k_rope.shape[-1], window, chosen_of,
                kernel=cfg.attention_backend == "pallas")
        else:
            with jax.named_scope("attn_latent_proj"):
                kv = jnp.einsum("btr,nrd->btnd", c_win, wkvb)
            out = _latent_attend(q_nope, q_rope, kv[..., :dn], r_win,
                                 kv[..., dn:], mask, scale, shared=False)
    if paged is not None:
        k_cache = _stacked_pool(k_cache, num_layers)
        v_cache = _stacked_pool(v_cache, num_layers)
        if indexed:
            i_cache = _stacked_pool(i_cache, num_layers)
    if "wgate" in lp:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(jnp.einsum(
                "bsh,hn->bsn", x, _w(lp, "wgate", dt),
                preferred_element_type=jnp.float32))
            out = (out * gate[..., None]).astype(out.dtype)
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bsnd,ndh->bsh", out, _w(lp, "wo", out.dtype))
    return out, k_cache, v_cache, i_cache


def _rescaled(latent: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """A normed latent times sqrt(hidden_size / its rank) where the config
    asks (`apply_mla_qkv_lora_rescale`); as it is where not."""
    if not cfg.latent_rescale:
        return latent
    return latent * jnp.asarray(
        (cfg.hidden_size / latent.shape[-1]) ** 0.5, latent.dtype)


def _zero_unattended(rows: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """rows [B, T, w] with every row no query attends (mask [B, S, T]) set
    to zero: a probability of 0 times a NaN a never-written page may hold
    is NaN."""
    return jnp.where(jnp.any(mask, axis=1)[..., None], rows, 0)


def _index_projections(x, c_q, lp: Params, cfg: ModelConfig, cos, sin):
    """The indexer's three projections (DeepSeek-V3.2's sparse attention),
    under `attn_index`: q^I [B, S, Hi, Di] from the query latent, the key k^I
    [B, S, Di] = layernorm(x W^I_k), ONE row a token, and the head weights
    w [B, S, Hi] in f32, the two score scales folded in.  Rotary on the
    first `qk_rope_head_dim` values of q^I and k^I, half-split pairs (never
    de-interleaved), the layer's own table."""
    dt = x.dtype
    hi, di, dr = cfg.index_n_heads, cfg.index_head_dim, cos.shape[-1] * 2
    with jax.named_scope("attn_index"):
        q_idx = jnp.einsum("bsr,rnd->bsnd", c_q, _w(lp, "wiq", dt))
        k32 = jnp.einsum("bsh,hd->bsd", x, _w(lp, "wik", dt),
                         preferred_element_type=jnp.float32)
        mu = jnp.mean(k32, axis=-1, keepdims=True)
        var = jnp.mean((k32 - mu) ** 2, axis=-1, keepdims=True)
        k_idx = ((k32 - mu) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
                 * lp["ln_ik"].astype(jnp.float32)
                 + lp["ln_ik_b"].astype(jnp.float32)).astype(dt)
        q_idx = jnp.concatenate(
            [apply_rope(q_idx[..., :dr], cos, sin), q_idx[..., dr:]], axis=-1)
        k_idx = jnp.concatenate(
            [apply_rope(k_idx[..., None, :dr], cos, sin)[..., 0, :],
             k_idx[..., dr:]], axis=-1)
        w_idx = jnp.einsum("bsh,hn->bsn", x, _w(lp, "wiw", dt),
                           preferred_element_type=jnp.float32
                           ) * (hi ** -0.5 * di ** -0.5)
    return q_idx, k_idx, w_idx


def _index_scores(q_idx, w_idx, k_idx) -> jnp.ndarray:
    """I[t, s] = sum_j w[t, j] * relu(q^I[t, j] . k^I[s]) in f32.  q_idx
    [B, S, Hi, Di], w_idx [B, S, Hi] f32, k_idx [B, T, Di] -> [B, S, T];
    k_idx [T, Di] where every lane scores the SAME keys: one
    [B * S * Hi, Di] x [Di, T] product, the keys read once."""
    keys = "btd" if k_idx.ndim == 3 else "td"
    dots = jnp.einsum(f"bsnd,{keys}->bsnt", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bsn,bsnt->bst", w_idx, jax.nn.relu(dots))


def _chosen_mask(scores: jnp.ndarray, mask: jnp.ndarray,
                 top_k: int) -> jnp.ndarray:
    """`mask` [B, S, T] narrowed to each query's chosen keys: of the keys it
    allows, the `top_k` of largest score (all of them where it allows no
    more), EXACTLY the set `lax.top_k` picks, ties to the lower position.

    No sort: XLA's top-k of 2,048 among 32,768 sorts the whole row (4.1 ms
    a layer a decode pass, 17 ms a 512-row prefill launch: my chip run 2,
    PR 33).  The scores become unsigned keys of the same order; the k-th
    largest key is built bit by bit from the top (32 counts of `key >=
    candidate`), then the lowest positions among the keys EQUAL to it fill
    what is left of k, by the same construction over the position's bits.
    47 passes of compare-and-count over the row, each a few microseconds at
    decode."""
    t = scores.shape[-1]
    if t <= top_k:
        return mask  # every allowed key is chosen
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores).astype(jnp.float32), jnp.int32)
    keys = jax.lax.bitcast_convert_type(
        bits ^ ((bits >> 31) & 0x7FFFFFFF), jnp.uint32) ^ jnp.uint32(1 << 31)
    keys = jnp.where(mask, keys, jnp.uint32(0))  # under every real score
    k = jnp.minimum(jnp.sum(mask, axis=-1, dtype=jnp.int32), top_k)

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    def key_bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(keys >= cand[..., None]) >= k, cand, kth)

    kth = jax.lax.fori_loop(0, 32, key_bit, jnp.zeros(k.shape, jnp.uint32))
    above = keys > kth[..., None]
    equal = keys == kth[..., None]
    left = k - count(above)  # how many of the equal keys are chosen
    pos = jnp.arange(t, dtype=jnp.int32)
    n_bits = max(t - 1, 1).bit_length()

    def pos_bit(i, last):
        cand = last | (1 << (n_bits - 1 - i))
        return jnp.where(count(equal & (pos < cand[..., None])) < left,
                         cand, last)

    # the position of the `left`-th equal key: the largest p with fewer than
    # `left` equal keys under it
    last = jax.lax.fori_loop(0, n_bits, pos_bit, jnp.zeros(k.shape, jnp.int32))
    return above | (equal & (pos <= last[..., None]) & (left > 0)[..., None])


COMPACT_BLOCK = 128


def _compact_chosen(chosen: jnp.ndarray, values: jnp.ndarray, top_k: int):
    """(values [B, K], ok [B, K]): `values` [B, T] (int32, under 2**23) at
    the positions `chosen` [B, T] marks, in ascending position, K =
    min(top_k, T); `ok` is False past the last one where fewer than K are
    marked, and such an entry repeats the first value.

    No sort, no scatter and no gather of single elements (65k of them cost
    0.65 ms on the v5e, a binary search over a running count 10 ms a layer:
    my chip run 3, PR 33).  The row is cut in blocks of 128; an output
    slot's block is found by counting the blocks that end at or before it;
    ONE gather of whole 128-value rows brings each slot its block, in which
    every value is packed with its rank among the block's marked ones, and
    the slot takes the value whose rank is its own."""
    b, t = values.shape
    k, blk = min(top_k, t), COMPACT_BLOCK
    pad = -t % blk
    marked = jnp.pad(chosen, ((0, 0), (0, pad))).reshape(b, -1, blk)
    vals = jnp.pad(values, ((0, 0), (0, pad))).reshape(b, -1, blk)
    ones = marked.astype(jnp.int32)
    upto = jnp.cumsum(ones, axis=-1)                 # within the block
    counts = upto[..., -1]                           # [B, blocks]
    ends = jnp.cumsum(counts, axis=-1)
    slots = jnp.arange(k, dtype=jnp.int32)
    before = ends[:, None, :] <= slots[None, :, None]    # [B, K, blocks]
    block_of = jnp.minimum(jnp.sum(before, axis=-1, dtype=jnp.int32),
                           marked.shape[1] - 1)
    rank = slots[None, :] - jnp.sum(
        jnp.where(before, counts[:, None, :], 0), axis=-1)
    # (value, rank among the block's marked ones | 255 where unmarked)
    packed = (vals << 8) | jnp.where(marked, upto - ones, 255)
    rows = jnp.take_along_axis(packed, block_of[..., None], axis=1)
    out = jnp.sum(jnp.where((rows & 255) == rank[..., None], rows >> 8, 0),
                  axis=-1)
    ok = slots[None, :] < ends[:, -1:]
    return jnp.where(ok, out, out[:, :1]), ok


# Keys one trip of the paged index scoring and of the latent prefill walk
# reads (fewer at many queries: `_walk_chunks`): [Hi | Hq, S, keys] f32
# scores are held a trip, not a window.
INDEX_WALK_KEYS = 2048
PREFILL_WALK_KEYS = 1024


def walk_pages(P: int, ps: int, keys: int, queries: int = 1) -> int:
    """Pages a trip of a walk over a page table of width P reads: about
    `keys` keys, fewer where `queries` rows would make a trip's f32 scores
    large.  Plain ints: the engine counts trips with it on the host."""
    keys = max(ps, min(keys, (1 << 19) // max(queries, 1)))
    return max(1, min(keys // ps, P))


def prefill_walk_pages(P: int, ps: int, queries: int, kernel: bool) -> int:
    """Pages a trip of `_latent_prefill_walk`: PREFILL_WALK_KEYS keys where
    the Pallas kernel folds (no score tensor to bound), fewer at many
    `queries` where XLA does."""
    return walk_pages(P, ps, PREFILL_WALK_KEYS, 1 if kernel else queries)


def _walk_chunks(paged: "PagedView", cp: int):
    """(padded page table, trips): a walk over the page table's LIVE part
    in chunks of `cp` pages (`walk_pages`), up to the longest lane's last
    valid key: the bound is computed on the device."""
    ps = paged.page_size
    P = paged.page_table.shape[1]
    table = jnp.pad(paged.page_table, ((0, 0), (0, -P % cp)))
    n_keys = jnp.max(jnp.sum(paged.kv_valid, axis=-1))
    trips = jnp.minimum((n_keys + cp * ps - 1) // (cp * ps),
                        table.shape[1] // cp)
    return table, trips


def _common_pages(paged: "PagedView"):
    """`common_pages` over the lanes that hold keys: (the first of them, the
    page table's leading columns that name its page in every one)."""
    return common_pages(paged.page_table, jnp.sum(paged.kv_valid, axis=-1) > 0)


def _paged_index_scores(q_idx, w_idx, i_cache, paged: "PagedView",
                        dt) -> jnp.ndarray:
    """Index scores of every query against the lanes' live keys, f32
    [B, S, C], walked chunk by chunk off the indexer's own pool rows (keys
    past the longest live context stay unscored, 0).

    At decode (S = 1) the walk splits where the lanes' page tables part
    (`_common_pages`): a trip whose pages every lane shares reads them ONCE
    and scores all lanes against them in one product (an indexer key is
    rotated by position, not by lane), whole trips only; from there on, and
    from trip 0 where the lanes share nothing, a trip gathers each lane's
    own pages.  The same scores either way, in the same places.  A prefill
    chunk (S > 1) is one lane's rows against its own keys and never splits."""
    ps = paged.page_size
    b, s = q_idx.shape[:2]
    di = q_idx.shape[-1]
    C = paged.kv_positions.shape[1]
    cp = walk_pages(paged.page_table.shape[1], ps, INDEX_WALK_KEYS,
                    b * s if s > 1 else 1)
    table, trips = _walk_chunks(paged, cp)

    def score(rows):
        """A trip over `rows`: every lane's page-table row [B, P], or the
        one row [P] all of them share."""
        def trip(c, scores):
            pages = jax.lax.dynamic_slice_in_dim(rows, c * cp, cp, axis=-1)
            keys = _read_pages(i_cache, pages, ps, dt)[..., :di]
            return jax.lax.dynamic_update_index_in_dim(
                scores, _index_scores(q_idx, w_idx, keys), c, 0)
        return trip

    # held trip-major while the walk runs: a trip's scores land in one
    # block (as a slice of [B, S, C]'s key axis they are B x S strided rows,
    # 15 us a trip at decode on the v5e: twice the product that makes them)
    scores = jnp.zeros((table.shape[1] // cp, b, s, cp * ps), jnp.float32)
    own = 0  # the first trip that gathers lane by lane
    if s == 1:
        lane, common = _common_pages(paged)
        own = jnp.minimum(common // cp, trips)
        scores = jax.lax.fori_loop(0, own, score(table[lane]), scores)
    scores = jax.lax.fori_loop(own, trips, score(table), scores)
    return jnp.moveaxis(scores, 0, 2).reshape(b, s, -1)[..., :C]


def _paged_index_choice(q_idx, w_idx, i_cache, paged: "PagedView", positions,
                        cfg: ModelConfig, dt, as_mask: bool = False):
    """The selection step over a paged pool: `_paged_index_scores`, then
    the exact top-k of each query's causal keys.  Returns for decode (S = 1)
    the chosen keys' pool slots and which of them are real, (slots [B, K],
    ok [B, 1, K]); with `as_mask` (None, chosen [B, S, C]) for a walk that
    masks."""
    scores = _paged_index_scores(q_idx, w_idx, i_cache, paged, dt)
    mask = (paged.kv_valid[:, None, :]
            & (paged.kv_positions[:, None, :] <= positions[:, :, None]))
    chosen = _chosen_mask(scores, mask, cfg.index_topk)
    if as_mask:
        return None, chosen
    # decode: the chosen keys' pool slots (read_idx names every position's)
    if i_cache.shape[0] >= 1 << 23:
        raise LatentPathError(
            "a pool of 2**23 slots or more a kind (slots are packed with "
            "their ranks in 32 bits when the chosen keys are compacted)")
    slots, ok = _compact_chosen(chosen[:, 0], paged.read_idx, cfg.index_topk)
    return slots, ok[:, None]


def _read_chosen_rows(k_cache, v_cache, slots, dt):
    """The flat pools' rows at the chosen keys' slots [B, K] (decode):
    (c~ [B, K, r], k_r [B, K, lanes])."""
    return k_cache[slots].astype(dt), v_cache[slots].astype(dt)


def _latent_window_pages(k_cache, v_cache, paged: "PagedView", window: int,
                         dt):
    """Decode read of a sliding-window latent layer on XLA: the pages that
    hold positions seq_len - window + 1 .. seq_len of each lane, and no
    others ((c~, k_r) [B, n * page_size, .], mask [B, 1, n * page_size])."""
    ps = paged.page_size
    P = paged.page_table.shape[1]
    n = min(P, -(-(window - 1) // ps) + 1)
    lens = paged.seq_lens
    first = jnp.clip(jnp.maximum(lens - window + 1, 0) // ps, 0, P - n)
    cols = first[:, None] + jnp.arange(n)[None, :]
    pages = jnp.take_along_axis(paged.page_table, cols, axis=1)
    pos = (cols[:, :, None] * ps + jnp.arange(ps)[None, None, :]).reshape(
        lens.shape[0], n * ps)
    mask = ((pos <= lens[:, None]) & (pos > lens[:, None] - window)
            & paged.kv_valid[:, :1])[:, None, :]
    c_win = _zero_unattended(_kv_read_pages(k_cache, pages, ps, dt), mask)
    r_win = _zero_unattended(_kv_read_pages(v_cache, pages, ps, dt), mask)
    return c_win, r_win, mask


def _latent_prefill_walk(q_nope, q_rope, wkvb, k_cache, v_cache,
                         paged: "PagedView", positions, scale: float, dn: int,
                         dr: int, window: Optional[int], chosen_of,
                         kernel: bool = False):
    """Latent attention of a prefill chunk over the paged pool, expanded
    form, walking the keys chunk by chunk with a running max / sum in f32
    (PR 32's decode walk at s > 1): a trip gathers one chunk's pages,
    expands its rows through W_kvb (`attn_latent_proj`) and folds it in, so
    [Hq, S, window] scores never exist.  A query attends causal valid keys,
    narrowed to its window (walked from the chunk that holds the window's
    first key) or to `chosen_of` [B, S, C].  q_nope / q_rope [B, S, N, .];
    returns [B, S, N, dv] in the query's dtype.

    One algorithm, two executors of a trip's fold.  In XLA the [Hq, S, keys]
    f32 scores and probabilities of a trip pass through HBM.  With `kernel`
    (the Pallas backend) the fold is `latent_prefill_fold`: the score tile
    stays in VMEM, rows in the lanes, so the queries, the accumulator and a
    trip's values are held transposed ([.., d, rows] / [.., dv, keys]) and
    the bucket is padded to whole lane tiles; a trip is PREFILL_WALK_KEYS
    keys whatever the rows, there being no score tensor to bound."""
    ps, dt = paged.page_size, q_nope.dtype
    b, s, n = q_nope.shape[:3]
    dv = wkvb.shape[-1] - dn
    cp = prefill_walk_pages(paged.page_table.shape[1], ps, b * s, kernel)
    table, trips = _walk_chunks(paged, cp)
    ck = cp * ps
    pad = table.shape[1] * ps - paged.kv_valid.shape[1]
    kv_valid = jnp.pad(paged.kv_valid, ((0, 0), (0, pad)))
    if chosen_of is not None:
        chosen_of = jnp.pad(chosen_of, ((0, 0), (0, 0), (0, pad)))

    def chunk(c):
        """Trip c's latent and rotary rows and who attends them
        ([B, ck, r], [B, ck, dr], mask [B, S, ck])."""
        pages = jax.lax.dynamic_slice_in_dim(table, c * cp, cp, axis=1)
        pos = c * ck + jnp.arange(ck)[None, None, :]
        mask = (jax.lax.dynamic_slice_in_dim(kv_valid, c * ck, ck, 1)[:, None]
                & (pos <= positions[:, :, None]))
        if window is not None:
            mask = mask & (pos > positions[:, :, None] - window)
        if chosen_of is not None:
            mask = mask & jax.lax.dynamic_slice_in_dim(
                chosen_of, c * ck, ck, 2)
        c_win = _zero_unattended(_kv_read_pages(k_cache, pages, ps, dt), mask)
        r_win = _zero_unattended(
            _kv_read_pages(v_cache, pages, ps, dt)[..., :dr], mask)
        return c_win, r_win, mask

    if kernel:
        from ..ops.pallas import latent_prefill_fold

        rows = s + -s % 128  # whole lane tiles
        lanes = ((0, 0), (0, 0), (0, 0), (0, rows - s))
        qn_t = jnp.pad(jnp.transpose(q_nope, (0, 2, 3, 1)), lanes)
        qr_t = jnp.pad(jnp.transpose(q_rope, (0, 2, 3, 1)), lanes)
        w_k, w_v = wkvb[..., :dn], wkvb[..., dn:]

        def fold(c, carry):
            c_win, r_win, mask = chunk(c)
            with jax.named_scope("attn_latent_proj"):
                k_nope = jnp.einsum("btr,nrd->bntd", c_win, w_k)
                v_t = jnp.einsum("btr,nrd->bndt", c_win, w_v)
            bias = jnp.where(
                jnp.pad(jnp.swapaxes(mask, 1, 2), lanes[1:]), 0.0, NEG_INF)
            return latent_prefill_fold(
                qn_t, qr_t, k_nope, r_win, v_t, bias, *carry, scale=scale,
                interpret=jax.default_backend() != "tpu")

        acc_shape, l_axis, out_axes = (b, n, dv, rows), 2, (0, 3, 1, 2)
    else:
        q = jnp.concatenate([q_nope, q_rope], axis=-1)

        def fold(c, carry):
            m, l, acc = carry
            c_win, r_win, mask = chunk(c)
            with jax.named_scope("attn_latent_proj"):
                kv = jnp.einsum("btr,nrd->btnd", c_win, wkvb)
                # a head's whole key, [k_nope | k_r]: ONE score matmul a
                # trip.  The two partial products apart were two [Hq, S,
                # keys] f32 tensors through HBM and an add (23 + 13 ms a
                # layer a 512-row launch against 13 for one: my chip run 3,
                # PR 33)
                keys = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(
                        r_win[:, :, None, :], kv.shape[:3] + (dr,))], axis=-1)
            sc = jnp.einsum("bqnd,bknd->bnqk", q, keys,
                            preferred_element_type=jnp.float32) * scale
            sc = jnp.where(mask[:, None], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(mask[:, None], jnp.exp(sc - m_new[..., None]), 0.0)
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bnqk,bknd->bnqd", p.astype(dt), kv[..., dn:],
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        rows, acc_shape, l_axis, out_axes = s, (b, n, s, dv), 3, (0, 2, 1, 3)

    first = 0
    if window is not None:
        live = jnp.any(paged.kv_valid, axis=-1)
        lo = jnp.min(jnp.where(live, positions[:, 0] - window + 1,
                               jnp.iinfo(jnp.int32).max))
        first = jnp.minimum(jnp.maximum(lo, 0) // ck, trips)
    _, l, acc = jax.lax.fori_loop(
        first, trips, fold,
        (jnp.full((b, n, rows), NEG_INF, jnp.float32),
         jnp.zeros((b, n, rows), jnp.float32),
         jnp.zeros(acc_shape, jnp.float32)))
    out = acc / jnp.expand_dims(jnp.maximum(l, 1e-30), l_axis)
    return jnp.transpose(out, out_axes)[:, :s].astype(dt)


def _mlp_block(x: jnp.ndarray, lp: Params,
               names=("wg", "wu", "wd"),
               multipliers: Tuple[float, ...] = ()) -> jnp.ndarray:
    """SwiGLU MLP: down( silu(gate(x)) * up(x) ).  `multipliers` (gate,
    down), a muP model's: the gate's pre-activation and the block's output
    are scaled, in the activations' dtype."""
    g = jnp.einsum("bsh,hf->bsf", x, _w(lp, names[0], x.dtype))
    u = jnp.einsum("bsh,hf->bsf", x, _w(lp, names[1], x.dtype))
    if not multipliers:
        return jnp.einsum(
            "bsf,fh->bsh", jax.nn.silu(g) * u, _w(lp, names[2], x.dtype))
    gate_m, down_m = (jnp.asarray(m, x.dtype) for m in multipliers)
    return jnp.einsum(
        "bsf,fh->bsh", jax.nn.silu(g * gate_m) * u,
        _w(lp, names[2], x.dtype)) * down_m


def _routing_weights(t: jnp.ndarray, router: jnp.ndarray,
                     top_k: int, picks: bool = False):
    """Per-token expert weights [T, E]: softmax over EXACTLY the top-k
    router logits, scattered back (HF MixtralSparseMoeBlock semantics —
    a >=threshold mask would activate extra experts on k-th-place ties).
    The canonical routing implementation; parallel/expert.py reuses it.
    `picks`: the same choice unscattered, (experts [T, k] i32, weights
    [T, k] f32), for token dispatch (`_experts_token`).
    """
    logits = jnp.einsum(
        "th,he->te", t, router, preferred_element_type=jnp.float32
    )
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    w_top = jax.nn.softmax(top_vals, axis=-1)
    if picks:
        return top_idx, w_top
    return jnp.zeros_like(logits).at[
        jnp.arange(t.shape[0])[:, None], top_idx
    ].set(w_top)


def _routing_weights_sigmoid(t: jnp.ndarray, router: jnp.ndarray,
                             bias: jnp.ndarray, top_k: int,
                             scale: float, picks: bool = False):
    """Per-token expert weights [T, E] of HF deepseek_v3's `noaux_tc` rule
    with one group: sigma = sigmoid(logits) in f32; the top_k experts by
    sigma + bias are CHOSEN (the bias chooses, it does not weigh; ties go to
    the lower index, as lax.top_k); a chosen expert weighs
    scale * sigma_e / (sum of the chosen sigma + 1e-20).  `picks` as in
    `_routing_weights`."""
    logits = jnp.einsum(
        "th,he->te", t, router, preferred_element_type=jnp.float32
    )
    sigma = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(sigma + bias.astype(jnp.float32), top_k)
    rows = jnp.arange(t.shape[0])[:, None]
    chosen = sigma[rows, top_idx]
    w_top = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    if picks:
        return top_idx, w_top
    return jnp.zeros_like(sigma).at[rows, top_idx].set(w_top)


# Rows of one pass (lanes x bucket) from which the routed block dispatches by
# token.  Dense dispatch does 2 T FLOPs a weight element (2 bytes), so on a
# v5e (197 TFLOP/s, 819 GB/s) it is weight-read-bound below T ~ 240 and
# compute-bound above.  Measured, the block alone on the chip, us a layer,
# dense | token (scripts/moe_dispatch_bench.py; PERF.md section 6, PR 45):
#   rows  Mixtral      Mellum2      Kanana-2     K-EXAONE     dots3
#   256   4348 | 4986  1267 | 1367  1969 | 1870  1921 | 1968  2362 | 2361
#   320   5001 | 5570  1586 | 1465  2360 | 1930  2295 | 2084  2808 | 2361
#   384   6137 | 5587  1810 | 1548  2817 | 1978  2755 | 2294  3416 | 2448
#   512   8370 | 6188  2435 | 1723  3689 | 2118  3820 | 2424  4613 | 2626
# 384 is the first row count at which the token form is the faster one at
# every routed configuration (a sort, two row gathers and a visit a (row
# tile, expert) are what it pays below).
TOKEN_DISPATCH_MIN_ROWS = 384


# Below TOKEN_DISPATCH_MIN_ROWS the weights' read bounds the block, and token
# dispatch visits only the experts that have rows: the form reads fewer bytes
# where the pass leaves a share of the held experts unpicked.  That share is
# expected to be (1 - top_k / routed) ** rows under even routing (random
# weights route evenly; a trained router is more skewed and reads fewer).
# Measured, the block alone on the chip, every row active, us a layer,
# dense | token, the experts read of those held and the expected unread share
# (scripts/moe_dispatch_bench.py --rows 16 32 64; PERF.md section 6, PR 48):
#   rows  Mixtral              Mellum2                LFM2
#   16    3743 | 3772  8/8  .010   1095 |  928 55/64 .118    943 | 787 26/32 .118
#   32    3746 | 3776  8/8  .000   1102 | 1056 62/64 .014    948 | 965 32/32 .014
#   64    3796 | 3793  8/8  .000   1095 | 1151 64/64 .000    957 | 979 32/32 .000
#   rows  Kanana-2             K-EXAONE               dots3
#   16    1613 |  871  67/128 .464  1665 | 1463 14/16 .356   2018 |  931 14/32 .602
#   32    1620 | 1273  97/128 .215  1686 | 1288 12/16 .127   2030 | 1210 18/32 .362
#   64    1637 | 1569 119/128 .046  1745 | 1711 16/16 .016   2053 | 1873 28/32 .131
# From an expected share of 0.046 up the token form is the faster one at every
# configuration and row count measured (by 4 % at the least); at 0.016 and
# under it is within 5 % of dense on either side (its sort and two row
# gathers, with little or nothing left unread to pay for them).  (A kernel
# that walked the picked experts with dense dispatch's arithmetic, no sort
# and no gathers, read 0-4 % faster than the token form in the same table
# and was not kept.)
TOKEN_DISPATCH_MIN_UNREAD = 0.04
# ... and the fewest rows that table timed, one sublane tile of bf16: a pass
# of fewer rows (a single stream's decode, the benchmark's one-lane logit
# check) keeps the dense einsums.
TOKEN_DISPATCH_UNREAD_ROWS = 16


def moe_dispatch_form(rows: int, held: int, top_k: int, sharded: bool,
                      routed: Optional[int] = None,
                      int8: bool = False) -> str:
    """"token" or "dense": the form of the routed block for a pass of `rows`
    rows (static) over `held` experts, of the `routed` the router knows
    (None: all held), of which a row picks `top_k`; `int8`: the experts'
    leaves are quantized.  Token dispatch where dense dispatch is
    compute-bound and computes products it then zeroes
    (TOKEN_DISPATCH_MIN_ROWS), and below that, where the weights' read
    bounds both, where few rows over many experts are expected to leave a
    share of them unpicked (TOKEN_DISPATCH_MIN_UNREAD: decode at 16-32
    lanes over 64 experts or more, a 64-row launch over 128 or more): token
    dispatch fetches no expert without rows.  Dense between the two (nearly
    every expert is somebody's pick: no sort, no gather), where every held
    expert takes every row anyway, at decode over int8 experts (dequantized
    whole, a layer) and on an ep / tp mesh (GSPMD partitions the dense
    einsums; a sharded grouped matmul is ROADMAP R4's).  The one rule:
    `_moe_block` traces by it and the engine counts launches by it."""
    if sharded or held <= top_k:
        return "dense"
    if rows >= TOKEN_DISPATCH_MIN_ROWS:
        return "token"
    if (not int8 and rows >= TOKEN_DISPATCH_UNREAD_ROWS
            and (1.0 - top_k / (routed or held)) ** rows
            >= TOKEN_DISPATCH_MIN_UNREAD):
        return "token"
    return "dense"


# XLA's row gather on the v5e (jaxlib 0.9.0) keeps an operand of up to ~7.3 MB
# in VMEM and then asks for twice the operand + ~3 MiB of scoped VMEM, of which
# a fusion has 16 MiB: with an operand between ~6.9 and ~7.3 MB the program
# does not compile ("Ran out of memory in memory space vmem ... please file a
# bug against XLA": Mellum2's 1,536 rows x 2,304 bf16, the logit check's
# launch; 1,504 and 1,600 rows compile).  `_experts_token` pads an operand of
# (6, 7.5] MiB past the window, where the gather reads it from HBM as it does
# every larger one; tests/test_exaone_moe.py compiles the case for a
# described v5e.
GATHER_VMEM_WINDOW = (6 << 20, 15 << 19)

# the routed experts' leaves: what token dispatch reads from the layer stack
EXPERT_LEAVES = ("wg", "wu", "wd")


def experts_int8(layers: Params) -> bool:
    """Whether the routed experts' leaves of a layer tree (stacked, or one
    layer's) are int8 `QTensor`s."""
    return any(isinstance(layers.get(name), QTensor)
               for name in EXPERT_LEAVES)


def _experts_token(t: jnp.ndarray, top_idx: jnp.ndarray, w_top: jnp.ndarray,
                   stack: Params, layer, routed: int, offset: int,
                   real: Optional[jnp.ndarray] = None):
    """The routed experts by token: the T x k (row, expert, weight) picks
    sorted by expert, the rows gathered into that order, each projection ONE
    grouped matmul whose groups are the held experts (operands in t's dtype,
    f32 accumulation, as the dense einsums), and each row's k results
    weighted and summed in f32.  t [T, H]; `stack` the expert leaves stacked
    over layers [L, E, ...], of which this is `layer`; top_idx [T, k] counts
    over ALL the router's `routed` experts, of which this chip holds
    offset.. ; `real` [T] bool marks the rows that hold a token.  A pick of
    an expert held elsewhere, or of a pad row, sorts past every group: no
    matmul rows, zero weight.  No capacity, nothing dropped.  -> (out [T, H],
    the held experts that have rows, i32: the ones whose weights the grouped
    matmuls read)."""
    from ..ops.pallas.grouped_matmul import grouped_matmul, tile_rows

    n, k = top_idx.shape
    held = stack["wg"].shape[1]
    e = top_idx - offset
    mine = (e >= 0) & (e < held)
    if real is not None:
        mine = mine & real[:, None]
    e = jnp.where(mine, e, held).reshape(-1)
    order = jnp.argsort(e, stable=True)
    sizes = jnp.sum(e[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    # whole row tiles: the rows added sort past every group too
    tile = tile_rows(n * k, routed)
    src, row_bytes = t, t.shape[1] * t.dtype.itemsize
    low, high = GATHER_VMEM_WINDOW
    if low < n * row_bytes <= high:
        src = jnp.pad(t, ((0, high // row_bytes + 1 - n), (0, 0)))
    xs = src[jnp.pad(order // k, (0, -(n * k) % tile))]
    g = grouped_matmul(xs, stack["wg"], sizes, layer, tile)
    u = grouped_matmul(xs, stack["wu"], sizes, layer, tile)
    y = grouped_matmul(jax.nn.silu(g) * u, stack["wd"], sizes, layer, tile)
    # each pick's result from where the sort put it (a pick that is not
    # `mine` finds a row no group wrote: whatever the buffer held)
    y = y[jnp.argsort(order)].reshape(n, k, -1)
    y = jnp.where(mine[:, :, None], y.astype(jnp.float32), 0.0)
    out = jnp.sum(y * w_top[:, :, None], axis=1).astype(t.dtype)
    return out, jnp.sum(sizes > 0, dtype=jnp.int32)


def _moe_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig,
               chunk_len: Optional[jnp.ndarray] = None,
               sharded: bool = False,
               stacked: Optional[Tuple[Params, Any]] = None):
    """Top-k routed MoE MLP. x: [B, S, H] -> (output [B, S, H], the held
    experts whose weights the block read: an i32 the token form counts, all
    of them, a Python int, in the dense form).

    Routing: softmax over the top-k router logits only (HF
    MixtralSparseMoeBlock semantics), computed in f32; `cfg.moe_scoring`
    "sigmoid" picks deepseek_v3's rule instead.  The experts then run in one
    of two forms of the same arithmetic, chosen by `moe_dispatch_form` from
    the pass's static row count B x S:

    dense (verify, the prefill buckets under TOKEN_DISPATCH_MIN_ROWS, decode
    where nearly every expert is some lane's pick or the experts are int8,
    every mesh): every expert computes every row (parallel/expert.py's
    capacity-unlimited formulation, validated there against a per-token
    loop), the [T, E]
    routing weights zero the non-selected contributions, and the combine
    einsum contracts the expert axis.  Below ~240 rows the experts' weight
    read bounds the block and the products thrown away are free.  With
    wg/wu/wd sharded P(layer, "ep", ..., "tp") GSPMD partitions the expert
    einsums over ep and inserts the combine psum automatically, so the same
    program serves single-device, ep, and ep x tp meshes: meshes keep this
    form until a sharded grouped matmul exists (ROADMAP R4).

    token (on one device: prefill launches of TOKEN_DISPATCH_MIN_ROWS rows or
    more, and the passes of few rows over many experts, decode at 16-32
    lanes over 64 or more): `_experts_token`, each row through its own k
    experts only: where dense dispatch would be compute-bound at E / k times
    the FLOPs needed, and where it would read experts no row picked.
    `chunk_len` [B] or scalar (the view's: a prefill's real rows, a decode
    step's active lanes): rows at or past it are padding, fall in no group,
    pick nothing and get a zero routed output in the token form (nothing
    reads their feed-forward output; None: every row is real).
    `stacked`: (the EXPERT_LEAVES as the layer stack holds them, this
    layer's index), which `forward` hands over in place of `lp`'s slices of
    them so that the grouped matmul reads the weights where they lie (None:
    `lp` holds the layer's own).

    A shared branch (`cfg.shared_intermediate_size`: one always-on SwiGLU
    beside the routed experts) runs under its own scope, `moe_shared`.
    A config that HOLDS a share of the experts (`cfg.num_experts_routed`: one
    chip of an expert-parallel layer) routes over all the router knows and
    computes the part of the result its own experts give; what the absent
    ones would add is left out (the other chips' part of the combine).
    """
    b, s, h = x.shape
    t = x.reshape(b * s, h)
    token = moe_dispatch_form(
        b * s, cfg.num_experts, cfg.num_experts_per_tok, sharded,
        cfg.num_router_experts, experts_int8(lp)) == "token"
    with jax.named_scope("moe_router"):
        if cfg.moe_scoring == "sigmoid":
            w = _routing_weights_sigmoid(
                t, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, token)
        else:
            w = _routing_weights(
                t, lp["router"], cfg.num_experts_per_tok, token)
        if cfg.num_experts_routed and not token:
            # the weights of the experts HELD: chosen and renormalised over
            # all the router's experts, then this share's columns
            w = w[:, cfg.expert_offset:cfg.expert_offset + cfg.num_experts]
    read = cfg.num_experts
    with jax.named_scope("moe_experts"):
        if token:
            real = None
            if chunk_len is not None:
                real = (jnp.arange(s)[None, :]
                        < jnp.reshape(chunk_len, (-1, 1))).reshape(b * s)
            stack, at = stacked or (
                {name: _w(lp, name, t.dtype)[None] for name in EXPERT_LEAVES},
                0)
            out, read = _experts_token(
                t, *w, stack, at, cfg.num_router_experts,
                cfg.expert_offset if cfg.num_experts_routed else 0, real)
        else:
            g = jnp.einsum("th,ehf->tef", t, _w(lp, "wg", t.dtype))
            u = jnp.einsum("th,ehf->tef", t, _w(lp, "wu", t.dtype))
            y = jnp.einsum(
                "tef,efh->teh", jax.nn.silu(g) * u, _w(lp, "wd", t.dtype))
            out = jnp.einsum("te,teh->th", w.astype(y.dtype), y)
    out = out.reshape(b, s, h)
    if cfg.shared_intermediate_size:
        with jax.named_scope("moe_shared"):
            out = out + _mlp_block(x, lp, ("ws_g", "ws_u", "ws_d"))
    return out, read


def forward(
    params: Params,
    cfg: ModelConfig,
    token_ids: jnp.ndarray,
    positions: jnp.ndarray,
    kv_cache: Optional[KVCache] = None,
    kv_valid: Optional[jnp.ndarray] = None,
    cache_positions: Optional[jnp.ndarray] = None,
    paged: Optional[PagedView] = None,
    mesh=None,
    embed_override: Optional[jnp.ndarray] = None,
    override_on: Optional[jnp.ndarray] = None,
    expert_reads: bool = False,
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Run the decoder.

    token_ids, positions: [B, S] int32.
    kv_cache: optional KVCache. Contiguous form: k/v [L, B, C, Hkv, D],
        new k/v written at `cache_positions` (default `positions`), attention
        over the whole cache gated by `kv_valid` [B, C]. Paged form (when
        `paged` is given): k/v [L, TOTAL_SLOTS, Hkv*D] (heads merged into
        the minor axis, runtime/kv_cache.py), reads/writes follow the
        PagedView index plan.
    embed_override [B, S, H] + override_on [B, S] bool: positions whose
        input embedding is REPLACED (image patches entering as soft-prompt
        tokens, models/vision.py; the reference forwarded images to remote
        vision models, src/llm/portkey.py:276).
    Returns (logits [B, S, vocab] float32, updated cache or None), and with
    `expert_reads` (a routed model's own layer tree) a third: the held
    experts whose weights this pass's routed layers read, summed over them
    (i32; `_moe_block`'s count, what token dispatch leaves unread).

    A model with a recurrent state (`cfg.has_state`): its paged pool carries
    the state (`kv_cache.v` a dict), `paged.state` says which slots, and a
    paged prefill returns its lanes' last real rows only, logits [B, 1,
    vocab].  `phi4flash`'s decoder (`cfg.hybrid_decoder`) is
    models/hybrid.forward behind this same entry; the conv layout runs here,
    its mixers chosen by kind in the layer body.
    """
    plan = None
    if cfg.has_state:
        if mesh is not None and mesh.size > 1 or embed_override is not None:
            raise HybridPathError(
                "a decoder with a recurrent state runs on one device a "
                "replica, text only")
        if cfg.hybrid_decoder:
            from .hybrid import forward as hybrid_forward

            return hybrid_forward(params, cfg, token_ids, positions,
                                  kv_cache, paged)
        if kv_cache is not None and (paged is None or paged.state is None
                                     or paged.page_table is None):
            raise HybridPathError(
                "a decoder with a recurrent state has no contiguous cache "
                "and no paged plan without a page table and a StatePlan: "
                "rows go through the paged pool, the state through its "
                "slots")
        plan = paged.state if paged is not None else StatePlan(
            lens=jnp.full(token_ids.shape[:1], token_ids.shape[1],
                          jnp.int32))
    with jax.named_scope("embed"):
        embed = params["embed"]
        if isinstance(embed, QTensor):
            # per-row dequant of only the looked-up rows (scale is [V, 1])
            x = (
                embed.q[token_ids].astype(cfg.activation_dtype)
                * embed.s[token_ids].astype(cfg.activation_dtype)
            )
        else:
            x = embed[token_ids].astype(cfg.activation_dtype)
        if embed_override is not None:
            x = jnp.where(
                override_on[..., None],
                embed_override.astype(cfg.activation_dtype), x,
            )
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        # one rotary table per kind of layer, built once per forward pass;
        # each layer takes its kind's (a config without a pattern has one)
        lead, period = cfg.pattern
        if cfg.layer_types or cfg.rope_by_kind:
            rope = {kind: rope_cos_sin(positions, *kind_frequencies(cfg, kind))
                    for kind in cfg.kinds if kind not in (CONV, DELTA)}
        else:
            inv_freq = rope_frequencies(cfg)
            rope = {GLOBAL: rope_cos_sin(positions, inv_freq)}
        for kind in cfg.unrotated_kinds:
            rope[kind] = (None, None)
        if cfg.hc_mult > 1:
            # the widened residual stream: the embedding row in every one of
            # a token's n rows ([B, S, n * C]: `_hc_rows`)
            x = jnp.tile(x, (1, 1, cfg.hc_mult))

    # Where the routed blocks dispatch by token (moe_dispatch_form: this
    # pass's rows), the expert leaves stay out of what is sliced a layer:
    # `_moe_block` is handed the stack and the layer's index in it (a third
    # entry of `scanned`), and a program that keeps the dense form is traced
    # as it always was.  (int8 experts are dequantized a layer, from
    # their slices.)
    sharded = mesh is not None and mesh.size > 1
    layers, experts = params["layers"], None
    if (cfg.is_moe and moe_dispatch_form(
            token_ids.shape[0] * token_ids.shape[1], cfg.num_experts,
            cfg.num_experts_per_tok, sharded,
            cfg.num_router_experts) == "token"
            and not experts_int8(layers)):
        experts = {n: layers[n] for n in EXPERT_LEAVES}
        layers = {n: a for n, a in layers.items() if n not in experts}

    def indexed(index):
        """`scanned`'s third entry, the layer's index() in the expert stack
        (nothing, and not an op traced, where the block keeps `lp`'s)."""
        return () if experts is None else (index(),)

    # The stacked caches are CARRY (module docstring): the scan slices only
    # the layer's weights.  Every op of the layer body sits under a leaf
    # scope (residual adds included), so what a device trace shows under
    # `layers` alone is the scan's own slicing of its stacked inputs.
    def layer_body(carry, scanned, kind=GLOBAL, routed=cfg.is_moe):
        h, kc, vc, tally = carry
        lp, layer, *slot = scanned
        cos, sin = (None, None) if kind in (CONV, DELTA) else rope[kind]
        u, maps = _hc_in(h, lp, "attn", cfg)
        with jax.named_scope("attn_norm"):
            attn_in = rms_norm(u, lp["ln_attn"], cfg.rms_norm_eps)
        if kind == DELTA:
            # `layer` counts the linear layers: its place in both state leaves
            attn_out, vc = _delta_attention_block(
                attn_in, lp, cfg, vc, layer, plan)
        elif kind == CONV:
            # `layer` counts the conv layers: its place in the state array
            attn_out, tail = _short_conv_block(
                attn_in, lp, None if vc is None else vc["conv"], layer, plan)
            if vc is not None:
                vc = {**vc, "conv": tail}
        elif cfg.by_kind:
            # this kind's own caches, `layer` its index among the kind's
            has_index = cfg.has_indexer(kind) and vc is not None
            attn_out, k_new, v_new, i_new = _latent_attention_block(
                attn_in, lp, cfg, cos, sin, positions,
                None if kc is None else kc[kind],
                None if vc is None else vc[kind], kv_valid,
                cache_positions, paged, mesh, layer, kind,
                vc[INDEX] if has_index else None,
            )
            if kc is not None:
                kc = {**kc, kind: k_new}
                vc = {**vc, kind: v_new,
                      **({INDEX: i_new} if has_index else {})}
        elif cfg.is_latent:
            attn_out, kc, vc, _ = _latent_attention_block(
                attn_in, lp, cfg, cos, sin, positions, kc, vc, kv_valid,
                cache_positions, paged, mesh, layer,
            )
        else:
            # (beside conv layers `layer` counts the layers that hold rows,
            # and their v pool rides beside the state in the v pool's dict)
            in_dict = plan is not None and vc is not None
            a_in = attn_in
            if cfg.attention_in_multiplier != 1.0:
                with jax.named_scope("attn_qkv"):
                    a_in = attn_in * jnp.asarray(
                        cfg.attention_in_multiplier, attn_in.dtype)
            attn_out, kc, v_rows = _attention_block(
                a_in, lp, cfg, cos, sin, positions, kc,
                vc["v"] if in_dict else vc, kv_valid,
                cache_positions, paged, mesh, layer, cfg.window_of(kind),
            )
            vc = {**vc, "v": v_rows} if in_dict else v_rows
            if cfg.attention_out_multiplier != 1.0:
                with jax.named_scope("attn_out"):
                    attn_out = attn_out * jnp.asarray(
                        cfg.attention_out_multiplier, attn_out.dtype)
        if kind == PARALLEL:
            # the layer's SECOND mixer, on the same normed input: ONE `layer`
            # indexes the page pool above and both state leaves here
            ssd_out, vc = _ssd_block(attn_in, lp, cfg, vc, layer, plan)
            with jax.named_scope("ssd_proj"):
                attn_out = ssd_out + attn_out
        h = _hc_out(h, attn_out, maps,
                    {CONV: "conv_proj", DELTA: "kda_proj"}.get(
                        kind, "attn_out"))
        u, maps = _hc_in(h, lp, "mlp", cfg)
        with jax.named_scope("mlp_norm"):
            mlp_in = rms_norm(u, lp["ln_mlp"], cfg.rms_norm_eps)
        if routed:
            ffn_out, read = _moe_block(
                mlp_in, lp, cfg, None if paged is None else paged.chunk_len,
                sharded, (experts, slot[0]) if slot else None)
            if tally is not None:
                tally = tally + read
            h = _hc_out(h, ffn_out, maps, "moe_experts")
        else:
            with jax.named_scope("mlp"):
                ffn_out = _mlp_block(mlp_in, lp,
                                     multipliers=cfg.mlp_multipliers)
            h = _hc_out(h, ffn_out, maps, "mlp")
        return (h, kc, vc, tally), None

    def at(stacked, i, static: bool):
        """Layer i's leaves of a stacked tree: `a[i]` where i is static,
        one dynamic slice a leaf where it is the scan's."""
        if static:
            return jax.tree.map(lambda a: a[i], stacked)
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(
                a, i, axis=0, keepdims=False), stacked)

    def layer_of(stack: str, i, layer, kind, static: bool, nth=None):
        """(leaves, cache index) of layer `layer` (absolute), the i-th of
        `params[stack]`.  A by_kind model takes its attention leaves from
        its kind's own stack and indexes its kind's caches, both at `nth`,
        the layer's place among its kind."""
        routed = stack == "layers"
        lp = at(layers if routed else params[stack], i, static)
        if not cfg.kind_leaves:
            return (lp, layer) + (indexed(lambda: i) if routed else ())
        return ({**lp, **at(params["attn"][kind], nth, static)}, nth) + (
            indexed(lambda: i) if routed else ())

    def period_body(carry, first):
        """One whole period of the pattern, from absolute layer `first`: its
        layers unrolled, each kind its own code with its own static window
        and rotary table.
        Each layer's weights are indexed out of the stacked [L, ...] arrays
        at `first + j`, one dynamic slice a leaf exactly as the plain scan
        takes them: scanning over a [L/p, p, ...] view instead made XLA
        materialise the whole period's weights every iteration (3.2 GB of
        copies a period at Mellum2's widths, rehearsed for the v5e)."""
        ahead = n_dense + lead
        # (no arithmetic on `first` where there is nothing ahead: the
        # program of a model without dense or lead layers stays as it was)
        stacked = first - n_dense if n_dense else first
        t = (first - ahead) // len(period) if cfg.kind_leaves else None
        for j, kind in enumerate(period):
            if cfg.kind_leaves:
                nth = (before(ahead, kind) + t * period.count(kind)
                       + period[:j].count(kind))
                scanned = layer_of("layers", stacked + j, first + j, kind,
                                   False, nth)
            else:
                # (the index summed anew for every leaf, as it always was:
                # the lowered text of a patterned GQA model does not move)
                lp = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, stacked + j, axis=0, keepdims=False),
                    layers)
                scanned = (lp, first + j) + indexed(lambda: stacked + j)
            carry, _ = layer_body(carry, scanned, kind)
        return carry, None

    def before(layer: int, kind: str) -> int:
        # (a model without `layer_types` has one kind: every layer is of it)
        return sum(cfg.kind_of(i) == kind for i in range(layer))

    with jax.named_scope("layers"):
        kc, vc = (None, None) if kv_cache is None else kv_cache
        num_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        n_dense = 0
        # (the tally of experts read rides the carry: None, no leaf, unless
        # `expert_reads`)
        carry = (x, kc, vc, jnp.int32(0) if expert_reads else None)
        if "dense_layers" in params:
            # leading dense layers: a stacked tree of another shape, run
            # ahead of the scan over the routed layers, which count on from
            # them.  Unrolled, not a scan of their own: one innermost loop a
            # forward pass is what a device trace counts passes by.
            n_dense = jax.tree.leaves(params["dense_layers"])[0].shape[0]
            for i in range(n_dense):
                kind = cfg.kind_of(i)
                carry, _ = layer_body(
                    carry, layer_of("dense_layers", i, i, kind, True,
                                    before(i, kind)),
                    kind, routed=False)
        if len(period) > 1 and num_layers - lead == len(period):
            # ONE whole period: XLA removes a one-trip loop anyway, and
            # nested in the fused program's scan over steps it then copied
            # every slice the body had taken at the scan's index out of the
            # stacked leaves (15 x 480 MB of expert weights: my chip run 1,
            # PR 33).  Unrolled here, each layer's leaves are static slices
            # of the leading axis: views.
            lead = num_layers
        for i in range(lead):
            # the layers that stand alone ahead of whole periods, unrolled
            # beside the dense ones
            kind = cfg.kind_of(n_dense + i)
            carry, _ = layer_body(
                carry, layer_of("layers", i, n_dense + i, kind, True,
                                before(n_dense + i, kind)), kind)
        if len(period) == 1 and not (cfg.kind_leaves or lead):
            layer_ids = (jnp.arange(n_dense, n_dense + num_layers) if n_dense
                         else jnp.arange(num_layers))
            carry, _ = jax.lax.scan(
                partial(layer_body, kind=period[0]),
                carry,
                (layers, layer_ids) + indexed(
                    lambda: jnp.arange(num_layers)),
            )
        elif lead < num_layers:
            p = len(period)
            if (num_layers - lead) % p:
                raise ValueError(
                    f"{num_layers} stacked layers are not {lead} and whole "
                    f"periods of the {p}-layer pattern")
            carry, _ = jax.lax.scan(
                period_body, carry,
                jnp.arange(n_dense + lead, n_dense + num_layers, p))
        x, kc, vc, tally = carry
        new_cache = None if kv_cache is None else KVCache(k=kc, v=vc)

    with jax.named_scope("head"):
        if plan is not None and paged is not None and x.shape[1] > 1:
            # a prefill launch of a model with a state: each lane's last
            # real row is all anybody reads (as models/hybrid.forward)
            last = jnp.clip(plan.lens - 1, 0, x.shape[1] - 1)
            x = jnp.take_along_axis(x, last[:, None, None], axis=1)
        if cfg.hc_mult > 1:
            # the stream collapses to the sum of a token's rows
            x = sum(_hc_rows(x, cfg.hc_mult)).astype(x.dtype)
        logits = _logits_head(x, params, cfg)
    if expert_reads:
        return logits, new_cache, tally
    return logits, new_cache


def _logits_head(x: jnp.ndarray, params: Params,
                 cfg: ModelConfig) -> jnp.ndarray:
    """Final RMSNorm + vocabulary projection: [B, S, H] -> f32 logits."""
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    # bf16 matmul with f32 accumulation: the MXU-native mode. Casting the
    # [V, H] table to f32 would stream an extra ~1 GB per step through HBM
    # on a 128k vocab for no accuracy the f32 accumulator doesn't already
    # provide.
    # Int8 heads: the matmul streams the int8 table upcast to bf16 and the
    # per-vocab-row scale applies to the f32 OUTPUT — exact (scales are
    # per output channel) and cheaper than dequantizing the [V, H] table.
    if cfg.tie_word_embeddings:
        head = params["embed"]  # [V, H]
        if isinstance(head, QTensor):
            logits = jnp.einsum(
                "bsh,vh->bsv", x, head.q.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) * head.s.reshape(1, 1, -1)
        else:
            logits = jnp.einsum(
                "bsh,vh->bsv", x, head, preferred_element_type=jnp.float32
            )
    else:
        head = params["lm_head"]  # [H, V]
        if isinstance(head, QTensor):
            logits = jnp.einsum(
                "bsh,hv->bsv", x, head.q.astype(x.dtype),
                preferred_element_type=jnp.float32,
            ) * head.s.reshape(1, 1, -1)
        else:
            logits = jnp.einsum(
                "bsh,hv->bsv", x, head, preferred_element_type=jnp.float32
            )
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return logits
