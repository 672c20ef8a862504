"""What a layer holds between passes and how a mixer addresses it: the two
cache forms (`KVCache`, `PagedView`), the paged pool's views and row
accessors, and the STATE SLOTS of a decoder with a recurrent state.  Every
mixer, both forward passes and runtime/kv_cache.py need this layer, and it
needs none of them.

The *contiguous* [L, B, C, Hkv, D] KVCache is addressed by absolute position
== slot index (tests, `generate`); the *paged* pool [L, SLOTS, Hkv*D] that
serving uses (runtime/kv_cache.py) by a PagedView index plan, viewed flat as
[L*SLOTS, Hkv*D] (a bitcast) with the layer's offset in the INDICES
(`_layer_view`).  A pool is an array, or a dict where a model holds more than
one kind of row, each mixer owning its part: {kind: rows} with the indexer's
key rows at `INDEX` of the v pool (`cfg.by_kind`); beside a recurrent state
the v pool is {"v": attention rows, "conv" / "delta" / "ssd" / "ssm": state
leaves [layers, n_slots, ...] float32}, so every step program donates and
returns the state without a signature of its own (`PagedView.state`).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .quant import QTensor, dequantize, quantize_array


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


# the pool entry (beside the kinds') that holds the indexer's key rows
INDEX = "index"


class HybridPathError(NotImplementedError):
    """A path that cannot carry a recurrent state (or has no differential
    form) was reached by a decoder with a state.  The engine refuses such options by
    name when it is built (runtime/engine.py RecurrentStateUnsupported); this
    is the backstop for direct callers of `forward`."""


class StatePlan(NamedTuple):
    """Which state slot each lane of a pass reads and writes.

    src / dst / snap: [B] int32 slot ids, or all None for decode, where lane
    i's slot is slot i.  A lane's incoming state is `src` (zeros where
    `fresh`), its outgoing state goes to `dst` AND to `snap` (a snapshot the
    prefix cache may keep; the engine's trash slot when none is wanted).
    lens: [B] int32, the pass's real rows a lane (0 = the lane is inactive:
    its state passes through untouched)."""

    lens: jnp.ndarray
    src: Optional[jnp.ndarray] = None
    dst: Optional[jnp.ndarray] = None
    snap: Optional[jnp.ndarray] = None
    fresh: Optional[jnp.ndarray] = None


def _read_state(leaf, layer, plan: StatePlan, batch: int):
    """Lanes' incoming state of state layer `layer`, [B, ...] float32, read
    where it lies in the stacked leaf [n, n_slots, ...] (no layer's slots are
    sliced out: the leaf is the layer scan's carry).  A prefill launch's rows
    (`plan.src`) are a value of their own before anything is written: left to
    fuse into `_write_state`'s write of ANOTHER slot of the same buffer, the
    read makes XLA copy the whole leaf ahead of every write (PERF.md section
    6, PR 59)."""
    if plan.src is None:
        return jax.lax.dynamic_slice(
            leaf, (layer, 0, 0, 0), (1, batch) + leaf.shape[2:])[0]
    rows = jax.lax.optimization_barrier(leaf[layer, plan.src])
    if plan.fresh is not None:
        # (an inactive lane writes back what it read: not zeros)
        fresh = plan.fresh & (plan.lens > 0)
        rows = jnp.where(fresh[:, None, None], 0.0, rows)
    return rows


def _write_state(leaf, layer, plan: StatePlan, new, old):
    """`leaf` with the lanes' outgoing state of state layer `layer` written
    (an inactive lane writes back what it read).  A prefill launch writes one
    slot a lane, `dst` of every lane and then `snap` of every lane, each a
    slice update that the layer scan makes in place (a scatter over the slot
    axis kept a copy of the leaf a write; the lanes of a prefill are few and
    static; slot ids are the engine's, in range: a slice update clamps one
    that is not, where the scatter dropped it)."""
    new = jnp.where((plan.lens > 0)[:, None, None], new, old).astype(leaf.dtype)
    if plan.dst is None:
        return jax.lax.dynamic_update_slice(leaf, new[None], (layer, 0, 0, 0))
    for slots in (plan.dst, plan.snap):
        if slots is None:
            continue
        for lane in range(new.shape[0]):
            leaf = jax.lax.dynamic_update_slice(
                leaf, new[lane][None, None], (layer, slots[lane], 0, 0))
    return leaf
