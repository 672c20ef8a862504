"""Latent attention (MLA), `cfg.is_latent` (Kanana-2; per kind of layer with
a learned key selection, dots3; under a widened residual stream, Xing4.0).  A
token caches ONE row a layer, shared by all heads: the normed latent c~ (the
k pool's row) and the roped key part k_r (the v pool's, padded to whole lane
tiles); expanded keys and values never enter a pool.  Two forms of the same
attention: *expanded*, as published (each cached row through W_kvb), for the
uncached and contiguous caches and for paged prefill (`_latent_prefill_walk`);
*absorbed* for paged decode (W_kvb's key half multiplied into the query, its
value half applied to the result), on the Pallas latent kernel or on XLA.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Optional

import jax
import jax.numpy as jnp

from ...ops.attention import NEG_INF
from ...ops.norms import rms_norm
from ...ops.rope import apply_rope
from ..cache import (
    INDEX, PagedView, _flat_pool, _kv_read_pages, _kv_write, _layer_view,
    _stacked_pool,
)
from ..config import GLOBAL, ModelConfig
from ..quant import Params, QTensor, _w
from .index import (
    LatentPathError, _chosen_mask, _index_projections, _index_scores,
    _paged_index_choice, _read_chosen_rows, _walk_chunks, walk_pages,
)


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
                from ...ops.pallas import paged_decode_attention_latent

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


# Keys one trip of the latent prefill walk reads (fewer at many queries on
# XLA: `walk_pages`): [Hq, S, keys] f32 scores are held a trip, not a window.
PREFILL_WALK_KEYS = 1024


def prefill_walk_pages(P: int, ps: int, queries: int, kernel: bool) -> int:
    """Pages a trip of `_latent_prefill_walk`: PREFILL_WALK_KEYS keys where
    the Pallas kernel folds (no score tensor to bound), fewer at many
    `queries` where XLA does."""
    return walk_pages(P, ps, PREFILL_WALK_KEYS, 1 if kernel else queries)


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
        from ...ops.pallas import latent_prefill_fold

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



def mix(x, lp: Params, ctx, kc, vc, layer, kind):
    """`MIXERS["latent"]`.  A `cfg.by_kind` model's pools are {kind: rows}
    with the indexer's key rows at INDEX of the v pool, `layer` the layer's
    index among its kind; every other latent model's are the bare arrays."""
    cfg = ctx.cfg
    cos, sin = ctx.rope[kind]
    if not cfg.by_kind:
        out, kc, vc, _ = _latent_attention_block(
            x, lp, cfg, cos, sin, ctx.positions, kc, vc, ctx.kv_valid,
            ctx.cache_positions, ctx.paged, ctx.mesh, layer,
        )
        return out, kc, vc
    has_index = cfg.has_indexer(kind) and vc is not None
    out, k_new, v_new, i_new = _latent_attention_block(
        x, lp, cfg, cos, sin, ctx.positions,
        None if kc is None else kc[kind],
        None if vc is None else vc[kind], ctx.kv_valid,
        ctx.cache_positions, ctx.paged, ctx.mesh, layer, kind,
        vc[INDEX] if has_index else None,
    )
    if kc is not None:
        kc = {**kc, kind: k_new}
        vc = {**vc, kind: v_new, **({INDEX: i_new} if has_index else {})}
    return out, kc, vc
