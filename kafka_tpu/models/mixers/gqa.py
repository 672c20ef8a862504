"""Grouped-query attention: the Llama family's mixer, with what its cousins
add by leaf or by config (QK-norm, kinds of layer that do not rotate, a
sliding window a kind, an elementwise output gate, muP multipliers).
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ...ops import pallas as kernels
from ...ops.attention import causal_attention, paged_decode_walk
from ...ops.norms import rms_norm
from ...ops.rope import apply_rope
from ..cache import (
    PagedView, _flat_pool, _kv_read, _kv_read_pages, _kv_write, _layer_view,
    _stacked_pool,
)
from ..config import ModelConfig
from ..quant import Params, QTensor, _w


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
            if cfg.qk_norm_whole:
                # over the WHOLE projection, all heads' values under one
                # weight, ahead of the split into heads (Olmo 2 / Olmo 3)
                q, k = (rms_norm(a.reshape(a.shape[:2] + (-1,)), lp[n],
                                 cfg.rms_norm_eps).reshape(a.shape)
                        for a, n in ((q, "ln_q"), (k, "ln_k")))
            else:
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
    WindowedPathError.  A published softmax scale (`cfg.softmax_scale`, else
    None: each path's own default and not an argument more) goes to every
    path that takes one; the mesh paths take none and raise.
    Paged: k_cache/v_cache are the flat [L*SLOTS, Hkv*D] pools,
    the new rows already in them, and `paged` addresses this layer
    (_attention_block did both).  Contiguous: the stacked [L, B, C, Hkv, D]
    cache is written here at `layer`.  Returns (out [B, S, Hq, D],
    k_cache', v_cache')."""
    dt = q.dtype
    # (nothing where the model publishes no scale: the calls below are then
    # what they always were)
    scaled = {} if cfg.softmax_scale is None else {
        "scale": cfg.softmax_scale}
    if scaled and (cfg.prefill_ring or mesh is not None and mesh.size > 1):
        raise NotImplementedError(
            "a published softmax scale (attention_multiplier) has no "
            "sharded attention path")
    if paged is not None:
        b, s, hkv, d = k.shape
        if (
            cfg.attention_backend == "pallas"
            and s == 1
            and paged.page_table is not None
        ):
            on_mesh = mesh is not None and mesh.size > 1
            pools, kw = (k_cache, v_cache), dict(scaled)
            if isinstance(k_cache, QTensor):
                # int8 pool: the int8 kernel DMAs half the bytes and
                # fuses the per-slot dequant into scores/probabilities
                if window is not None:
                    raise WindowedPathError(
                        "kv_quantize int8 paged-decode kernel has no "
                        "sliding-window form")
                pools = (k_cache.q, k_cache.s, v_cache.q, v_cache.s)
                kernel = (kernels.paged_decode_attention_int8_sharded
                          if on_mesh else kernels.paged_decode_attention_int8)
            elif on_mesh:
                # per-shard kernel over the tp(/tq) head split: shard_map
                # runs the custom call GSPMD cannot partition (engine
                # validates pallas_mesh_ok at construction)
                kernel = kernels.paged_decode_attention_sharded
                kw = {"window": window}
            elif window is not None:
                kernel = kernels.paged_decode_attention_window
                kw = {"window": window, **scaled}
            else:
                kernel = kernels.paged_decode_attention
            out = kernel(
                *((mesh,) if on_mesh else ()), q[:, 0],  # [B, Hq, D]
                *pools, paged.page_table, paged.seq_lens,
                page_size=paged.page_size,
                interpret=jax.default_backend() != "tpu", **kw,
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
            on_mesh = mesh is not None and mesh.size > 1
            out = (kernels.paged_verify_attention_sharded if on_mesh
                   else kernels.paged_verify_attention)(
                *((mesh,) if on_mesh else ()), q, k_cache, v_cache,
                paged.page_table, paged.seq_lens, paged.chunk_len,
                page_size=paged.page_size,
                interpret=jax.default_backend() != "tpu", **scaled,
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
            out = kernels.paged_prefill_attention(
                q[0],  # [S, Hq, D]
                k_cache,
                v_cache,
                paged.page_table[0],
                paged.start,
                paged.chunk_len,
                page_size=paged.page_size,
                interpret=jax.default_backend() != "tpu",
                window=window, **scaled,
            )[None]
        elif cfg.prefill_ring and s > 1:
            # Chunked prefill over the sp axis: the chunk's own q/k/v ride
            # the ring sequence-sharded; the paged window of earlier chunks
            # (ctx_valid excludes the chunk's freshly written positions —
            # those would otherwise be counted twice) is read locally from
            # the pool by every sp rank (heads stay tp-sharded).
            from ...parallel.ring_attention import (
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
            out = _decode_walk(q, k_cache, v_cache, paged, hkv, window, mesh,
                               cfg.softmax_scale)
        else:
            # s > 1 (prefill chunks, verify): page-granular gather of the
            # static window (see _kv_read_pages: the slot-granular form is
            # descriptor-bound; a view without a page table, pp, keeps it),
            # attended in one shot
            if paged.page_table is not None and paged.page_size is not None:
                k_win, v_win = (
                    _kv_read_pages(c, paged.page_table, paged.page_size, dt
                                   ).reshape(b, -1, hkv, d)
                    for c in (k_cache, v_cache))
            else:
                k_win, v_win = (
                    _kv_read(c, paged.read_idx, dt).reshape(b, -1, hkv, d)
                    for c in (k_cache, v_cache))
            out = causal_attention(
                q,
                k_win,
                v_win,
                q_positions=positions,
                kv_positions=paged.kv_positions,
                kv_valid=paged.kv_valid,
                window=window, **scaled,
            )
    elif k_cache is None:
        out = causal_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            window=window, **scaled,
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
            window=window, **scaled,
        )
    return out, k_cache, v_cache


def _decode_walk(q, k_cache, v_cache, paged: PagedView, hkv: int,
                 window: Optional[int], mesh,
                 scale: Optional[float] = None) -> jnp.ndarray:
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
        heads_batched=mesh is not None and mesh.size > 1, scale=scale,
    )[:, None]



def mix(x, lp: Params, ctx, kc, vc, layer, kind):
    """`MIXERS["gqa"]`.  Beside a recurrent state (`ctx.plan`) `layer` counts
    the layers that hold rows and theirs ride under "v" of the v pool's dict;
    the muP multipliers around the block are applied here."""
    cfg = ctx.cfg
    cos, sin = ctx.rope[kind]
    in_dict = ctx.plan is not None and vc is not None
    a_in = x
    if cfg.attention_in_multiplier != 1.0:
        with jax.named_scope("attn_qkv"):
            a_in = x * jnp.asarray(cfg.attention_in_multiplier, x.dtype)
    out, kc, v_rows = _attention_block(
        a_in, lp, cfg, cos, sin, ctx.positions, kc,
        vc["v"] if in_dict else vc, ctx.kv_valid,
        ctx.cache_positions, ctx.paged, ctx.mesh, layer, cfg.window_of(kind),
    )
    vc = {**vc, "v": v_rows} if in_dict else v_rows
    if cfg.attention_out_multiplier != 1.0:
        with jax.named_scope("attn_out"):
            out = out * jnp.asarray(cfg.attention_out_multiplier, out.dtype)
    return out, kc, vc
