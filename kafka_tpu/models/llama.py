"""Decoder-only transformer in pure functional JAX: the Llama family, Mixtral's
routed MLP, and layer patterns of windowed and global attention (Mellum2).

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

from ..models.config import GLOBAL, ModelConfig
from ..ops.attention import causal_attention
from ..ops.norms import rms_norm
from ..ops.rope import (
    apply_rope,
    kind_frequencies,
    rope_cos_sin,
    rope_frequencies,
)
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
    """Gather a [B, C, Hkv*D] window by PAGE rather than by slot.

    The slot-granular gather moves B*C separate ~1 KB rows — descriptor-
    bound on TPU (measured: the b32 XLA decode path ran at half the
    Pallas kernel's rate with the KV bytes nowhere near the roofline).
    Page-granular gathering moves B*P contiguous page_size-row blocks,
    16x fewer descriptors at page_size 16.  page_table: [B, P]."""
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
    page_table:   [B, P]  physical page ids (pallas decode backend only)
    seq_lens:     [B]     cached token counts (pallas decode backend only)
    page_size:    static int (pallas decode backend only)
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
    shape = (cfg.num_layers, batch, capacity, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Params:
    """Random-init parameters (layer-stacked). Serving loads checkpoints
    instead; random init exists for tests and micro-benchmarks."""
    dtype = dtype or cfg.activation_dtype
    h, f, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv, L = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers
    keys = jax.random.split(key, 10)

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
    splits attention time by kind of layer."""
    dt = x.dtype
    with jax.named_scope("attn_qkv"):
        q = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wq", dt))
        k = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wk", dt))
        v = jnp.einsum("bsh,hnd->bsnd", x, _w(lp, "wv", dt))
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
    with jax.named_scope("attn_out"):
        out = jnp.einsum("bsnd,ndh->bsh", out, _w(lp, "wo", out.dtype))
    return out, k_cache, v_cache


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
        elif paged.page_table is not None and paged.page_size is not None:
            # page-granular window gather (see _kv_read_pages: the
            # slot-granular form is descriptor-bound)
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


def _mlp_block(x: jnp.ndarray, lp: Params) -> jnp.ndarray:
    """SwiGLU MLP: down( silu(gate(x)) * up(x) )."""
    g = jnp.einsum("bsh,hf->bsf", x, _w(lp, "wg", x.dtype))
    u = jnp.einsum("bsh,hf->bsf", x, _w(lp, "wu", x.dtype))
    return jnp.einsum("bsf,fh->bsh", jax.nn.silu(g) * u, _w(lp, "wd", x.dtype))


def _routing_weights(t: jnp.ndarray, router: jnp.ndarray,
                     top_k: int) -> jnp.ndarray:
    """Per-token expert weights [T, E]: softmax over EXACTLY the top-k
    router logits, scattered back (HF MixtralSparseMoeBlock semantics —
    a >=threshold mask would activate extra experts on k-th-place ties).
    The canonical routing implementation; parallel/expert.py reuses it.
    """
    logits = jnp.einsum(
        "th,he->te", t, router, preferred_element_type=jnp.float32
    )
    top_vals, top_idx = jax.lax.top_k(logits, top_k)
    w_top = jax.nn.softmax(top_vals, axis=-1)
    return jnp.zeros_like(logits).at[
        jnp.arange(t.shape[0])[:, None], top_idx
    ].set(w_top)


def _moe_block(x: jnp.ndarray, lp: Params, cfg: ModelConfig) -> jnp.ndarray:
    """Mixtral-style top-k routed MoE MLP. x: [B, S, H].

    Dense dispatch (parallel/expert.py's capacity-unlimited formulation,
    validated there against a per-token loop): every expert computes every
    token, the [T, E] routing weights zero the non-selected contributions,
    and the combine einsum contracts the expert axis.  With wg/wu/wd
    sharded P(layer, "ep", ..., "tp") GSPMD partitions the expert einsums
    over ep and inserts the combine psum automatically — the same program
    serves single-device, ep, and ep x tp meshes.  Routing: softmax over
    the top-k router logits only (HF MixtralSparseMoeBlock semantics),
    computed in f32.
    """
    b, s, h = x.shape
    t = x.reshape(b * s, h)
    with jax.named_scope("moe_router"):
        w = _routing_weights(t, lp["router"], cfg.num_experts_per_tok)
    with jax.named_scope("moe_experts"):
        g = jnp.einsum("th,ehf->tef", t, _w(lp, "wg", t.dtype))
        u = jnp.einsum("th,ehf->tef", t, _w(lp, "wu", t.dtype))
        y = jnp.einsum(
            "tef,efh->teh", jax.nn.silu(g) * u, _w(lp, "wd", t.dtype))
        out = jnp.einsum("te,teh->th", w.astype(y.dtype), y)
    return out.reshape(b, s, h)


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
    Returns (logits [B, S, vocab] float32, updated cache or None).
    """
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
        # one rotary table per kind of layer, built once per forward pass;
        # each layer takes its kind's (a config without a pattern has one)
        period = cfg.layer_period
        if cfg.layer_types:
            rope = {kind: rope_cos_sin(positions, *kind_frequencies(cfg, kind))
                    for kind in dict.fromkeys(period)}
        else:
            inv_freq = rope_frequencies(cfg)
            rope = {GLOBAL: rope_cos_sin(positions, inv_freq)}

    # The stacked caches are CARRY (module docstring): the scan slices only
    # the layer's weights.  Every op of the layer body sits under a leaf
    # scope (residual adds included), so what a device trace shows under
    # `layers` alone is the scan's own slicing of its stacked inputs.
    def layer_body(carry, scanned, kind=GLOBAL):
        h, kc, vc = carry
        lp, layer = scanned
        cos, sin = rope[kind]
        with jax.named_scope("attn_norm"):
            attn_in = rms_norm(h, lp["ln_attn"], cfg.rms_norm_eps)
        attn_out, kc, vc = _attention_block(
            attn_in, lp, cfg, cos, sin, positions, kc, vc, kv_valid,
            cache_positions, paged, mesh, layer, cfg.window_of(kind),
        )
        with jax.named_scope("attn_out"):
            h = h + attn_out
        with jax.named_scope("mlp_norm"):
            mlp_in = rms_norm(h, lp["ln_mlp"], cfg.rms_norm_eps)
        if cfg.is_moe:
            ffn_out = _moe_block(mlp_in, lp, cfg)
            with jax.named_scope("moe_experts"):
                h = h + ffn_out
        else:
            with jax.named_scope("mlp"):
                h = h + _mlp_block(mlp_in, lp)
        return (h, kc, vc), None

    def period_body(carry, first):
        """One whole period of the pattern: its layers unrolled, each kind
        its own code with its own static window and rotary table.  Each
        layer's weights are indexed out of the stacked [L, ...] arrays at
        `first + j`, one dynamic slice a leaf exactly as the plain scan
        takes them: scanning over a [L/p, p, ...] view instead made XLA
        materialise the whole period's weights every iteration (3.2 GB of
        copies a period at Mellum2's widths, rehearsed for the v5e)."""
        for j, kind in enumerate(period):
            lp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, first + j, axis=0, keepdims=False),
                params["layers"])
            carry, _ = layer_body(carry, (lp, first + j), kind)
        return carry, None

    with jax.named_scope("layers"):
        kc, vc = (None, None) if kv_cache is None else kv_cache
        num_layers = jax.tree.leaves(params["layers"])[0].shape[0]
        if len(period) == 1:
            (x, kc, vc), _ = jax.lax.scan(
                partial(layer_body, kind=period[0]),
                (x, kc, vc),
                (params["layers"], jnp.arange(num_layers)),
            )
        else:
            p = len(period)
            if num_layers % p:
                raise ValueError(
                    f"{num_layers} stacked layers are not whole periods of "
                    f"the {p}-layer pattern")
            (x, kc, vc), _ = jax.lax.scan(
                period_body, (x, kc, vc), jnp.arange(0, num_layers, p))
        new_cache = None if kv_cache is None else KVCache(k=kc, v=vc)

    with jax.named_scope("head"):
        logits = _logits_head(x, params, cfg)
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
    return logits
