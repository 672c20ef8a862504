"""Tiered KV cache: a host-RAM page tier (with optional disk spill) under
the PagePool, and the page-shipping substrate between tiers.

At millions-of-threads scale almost every thread is idle, and an idle
thread's conversation KV must not occupy HBM — yet a thread resuming after
hours should not re-prefill its whole 32k-token history either (ROADMAP
"KV tiering", BASELINE config 5).  Pages are the natural unit of demotion
(vLLM's PagedAttention), and a serialize/ship-a-page-run substrate between
memory tiers is the standard production architecture for KV-centric
serving (Mooncake; cf. DistServe's disaggregated prefill/decode):

* **Demotion** — when the radix prefix cache's leaf-LRU eviction or
  page-pressure ``reclaim()`` would free a node's pages, the engine instead
  copies them device->host (async D2H: the gather is enqueued on the device
  stream *before* the pages are released, so in-order execution reads them
  pre-overwrite; the host-side transfer completes in the background) and
  the radix node is retained as a *host-resident* run.
* **Promotion** — a ``lookup()`` hit against a host-resident run allocates
  fresh pool pages and enqueues the H2D scatter *before* the suffix
  prefill, so the copy overlaps the dispatch pipeline and the returning
  thread re-materializes its KV instead of recomputing it
  (``cache_source="host_tier"``).
* **Second-chance LRU + disk** — the host pool lives under a byte budget
  (``KAFKA_TPU_KV_HOST_TIER_MB``, charged by the MemoryPlan planner as
  host RAM, not HBM).  Overflow gives each run one second chance (the
  radix walk touching a host node sets its reference bit), then spills it
  to ``KAFKA_TPU_KV_DISK_TIER_DIR`` (background writer thread) or drops it
  when no disk tier is configured.
* **Failure semantics** — a failed or torn promote frees the destination
  pages and removes the radix node: the request degrades to re-prefill,
  never to corrupt KV.  A failed demote falls back to plain eviction.
  Both copies are chaos-testable via the ``kv.demote`` / ``kv.promote``
  failpoints (fired once per shipped chunk, so an ``nth=2`` error rule
  produces a genuinely torn multi-chunk copy).

:class:`PageShipper` is deliberately transport-agnostic: today's only
implementation copies between local tiers of one engine, but the same
export/import seam is what a prefill-specialized replica will use to ship
computed pages to a decode replica (disaggregated serving — the next step
named in ROADMAP).
"""

from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
import uuid
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .failpoints import failpoint
from ..tracing import record_span

logger = logging.getLogger("kafka_tpu.kv_tier")

ENV_HOST_MB = "KAFKA_TPU_KV_HOST_TIER_MB"
ENV_DISK_DIR = "KAFKA_TPU_KV_DISK_TIER_DIR"
# Cross-replica ship transport (ISSUE 19): "host" (the PR-12 host-staged
# path, the default — unset keeps today's behavior bit-identical),
# "device" (force the zero-host-copy DeviceShipper), or "auto" (device
# when both replicas' pools are in-process jax arrays, host otherwise —
# i.e. whenever a same-process handoff can skip the host hop, it does).
ENV_SHIP_TRANSPORT = "KAFKA_TPU_SHIP_TRANSPORT"
# Byte bound on host-staged ship copies (MiB, 0 = unbounded).  The
# host-staged path holds one numpy copy per in-flight chunk until its
# scatter lands; under a burst of concurrent handoffs those copies can
# balloon host RSS silently — over budget, staging waits for the
# outstanding scatters before materializing another chunk (RSS bounded
# to budget + one chunk).
ENV_SHIP_STAGING_MB = "KAFKA_TPU_SHIP_STAGING_MB"

MiB = 1024 * 1024

# Pages per gather/scatter dispatch.  Shipping in fixed buckets (padded
# with trash-page slots) keeps the number of compiled transfer programs
# O(len(buckets)) instead of one per distinct run length; runs longer than
# the largest bucket ship as a chunk sequence.  Padding round-trips
# harmlessly: padded gathers read trash rows that resolution trims, padded
# scatters write their rows INTO the trash page, which is garbage by
# contract (kv_cache.TRASH_PAGE).
SHIP_BUCKETS = (1, 2, 4, 8, 16, 32, 64)

_TRASH_PAGE = 0  # mirrors kv_cache.TRASH_PAGE (import cycle avoidance)


class ShipError(RuntimeError):
    """A page-run transfer failed (torn copy, missing payload).  The tier
    manager converts this into degrade-to-re-prefill, never corruption."""


def host_tier_mb_from_env() -> int:
    """The host-tier byte budget knob, clamped (negatives = disabled)."""
    try:
        return max(0, int(os.environ.get(ENV_HOST_MB, "0") or 0))
    except ValueError:
        return 0


def disk_tier_dir_from_env() -> Optional[str]:
    return os.environ.get(ENV_DISK_DIR) or None


def ship_transport_from_env() -> str:
    """The cross-replica ship transport knob (unknown values -> host:
    the conservative path can move any payload)."""
    t = (os.environ.get(ENV_SHIP_TRANSPORT) or "host").strip().lower()
    return t if t in ("auto", "host", "device") else "host"


def ship_staging_budget_bytes() -> int:
    try:
        mb = max(0, int(os.environ.get(ENV_SHIP_STAGING_MB, "0") or 0))
    except ValueError:
        mb = 0
    return mb * MiB


def _pools_on_device(owner: Any) -> bool:
    """True when the owner's pools are in-process jax arrays a
    device-to-device transfer can address (always for live engines; a
    cross-process transport stub holding opaque handles returns False
    and keeps the host-staged wire path)."""
    try:
        for pool in (owner.k_pool, owner.v_pool):
            for a in jax.tree.leaves(pool):
                if not isinstance(a, jax.Array):
                    return False
    except Exception:
        return False
    return True


def resolve_ship_transport(src_owner: Any, dst_owner: Any,
                           mode: Optional[str] = None) -> str:
    """Resolve auto-selection: device only when BOTH pools are reachable
    in-process (see _pools_on_device).  Explicit host/device are taken
    at their word."""
    mode = mode or ship_transport_from_env()
    if mode == "auto":
        return (
            "device"
            if _pools_on_device(src_owner) and _pools_on_device(dst_owner)
            else "host"
        )
    return mode


# -- host-staged ship accounting (ISSUE 19 satellite) -----------------------
# Module-level because staging RSS is a PROCESS property: every
# CrossReplicaPageShipper (they are constructed per handoff) adds to the
# same pool of pinned host copies.
_ship_stage_lock = threading.Lock()
_ship_stage_bytes = 0
_ship_stage_peak = 0


def _ship_stage_add(n: int) -> None:
    global _ship_stage_bytes, _ship_stage_peak
    with _ship_stage_lock:
        _ship_stage_bytes += n
        if _ship_stage_bytes > _ship_stage_peak:
            _ship_stage_peak = _ship_stage_bytes


def _ship_stage_sub(n: int) -> None:
    global _ship_stage_bytes
    with _ship_stage_lock:
        _ship_stage_bytes = max(0, _ship_stage_bytes - n)


def ship_staging_bytes() -> int:
    """Host bytes currently pinned by in-flight host-staged ship chunks."""
    with _ship_stage_lock:
        return _ship_stage_bytes


def ship_staging_peak(reset: bool = False) -> int:
    """Peak staged bytes; with reset=True, re-armed at the current level
    (peak-since-last-snapshot, the queue_depth_peak idiom) so every
    scrape interval reports its own high-water mark."""
    global _ship_stage_peak
    with _ship_stage_lock:
        peak = _ship_stage_peak
        if reset:
            _ship_stage_peak = _ship_stage_bytes
        return peak


def _bucketize(n_pages: int) -> List[int]:
    """Split a run of n pages into SHIP_BUCKET-sized chunk lengths."""
    out: List[int] = []
    biggest = SHIP_BUCKETS[-1]
    while n_pages > biggest:
        out.append(biggest)
        n_pages -= biggest
    if n_pages > 0:
        out.append(next(b for b in SHIP_BUCKETS if b >= n_pages))
    return out  # each entry is the PADDED chunk length


def _flat_slots(pages: Sequence[int], page_size: int, pad_to: int) -> np.ndarray:
    """Flat pool-slot indices for `pages`, padded to `pad_to` pages with
    trash-page slots."""
    padded = list(pages) + [_TRASH_PAGE] * (pad_to - len(pages))
    idx = np.empty(pad_to * page_size, np.int32)
    for i, p in enumerate(padded):
        idx[i * page_size:(i + 1) * page_size] = np.arange(
            p * page_size, (p + 1) * page_size, dtype=np.int32
        )
    return idx


@jax.jit
def _gather_rows(k_pool, v_pool, idx):
    """Read the page rows at flat slot indices `idx` out of both pools.

    NOT donating: the result is a fresh buffer whose D2H copy can complete
    while later (donating) dispatches keep updating the pool in place —
    in-order device execution guarantees the gather reads pre-overwrite
    values even though the host only resolves the bytes later.
    """
    take = lambda a: jnp.take(a, idx, axis=1)
    return jax.tree.map(take, k_pool), jax.tree.map(take, v_pool)


def _scatter_rows(k_pool, v_pool, idx, k_rows, v_rows):
    """Write page rows back into both pools at flat slot indices.  The
    pools are DONATED (updated in place), same as every decode/prefill
    dispatch — callers must reassign their pool references."""

    def put(a, rows):
        return a.at[:, idx].set(rows.astype(a.dtype))

    return jax.tree.map(put, k_pool, k_rows), jax.tree.map(
        put, v_pool, v_rows
    )


_scatter_jit = jax.jit(_scatter_rows, donate_argnums=(0, 1))


def _np_dtype(name: str) -> np.dtype:
    """Resolve a dtype name, including ml_dtypes extras (bfloat16 &c.)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _storable(arr: np.ndarray) -> Tuple[np.ndarray, str]:
    """An npz-serializable view + the original dtype name (ml_dtypes
    types are not npz-portable; view them as same-width unsigned ints)."""
    name = arr.dtype.name
    try:
        np.dtype(name)  # numpy-native? store as-is
        return arr, name
    except TypeError:
        width = {1: np.uint8, 2: np.uint16, 4: np.uint32}[arr.dtype.itemsize]
        return arr.view(width), name


def encode_run_npz(k_leaves: Sequence[np.ndarray],
                   v_leaves: Sequence[np.ndarray], n_pages: int) -> bytes:
    """ONE wire format for persisted page runs — the disk tier's spill
    files and the object tier's payloads both use exactly this (meta
    json + k{i}/v{i} arrays, ml_dtypes stored as same-width uints), so
    a dtype/layout fix cannot drift between them."""
    import io

    arrays: Dict[str, np.ndarray] = {}
    meta: Dict[str, Any] = {"n_pages": n_pages, "k": [], "v": []}
    for side, leaves in (("k", k_leaves), ("v", v_leaves)):
        for i, a in enumerate(leaves):
            stored, dtype_name = _storable(np.ascontiguousarray(a))
            arrays[f"{side}{i}"] = stored
            meta[side].append(dtype_name)
    buf = io.BytesIO()
    np.savez(buf, meta=json.dumps(meta), **arrays)
    return buf.getvalue()


def decode_run_npz(
    data: bytes,
) -> Tuple[List[np.ndarray], List[np.ndarray], int]:
    import io

    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        meta = json.loads(str(z["meta"]))
        k_leaves = [
            z[f"k{i}"].view(_np_dtype(name))
            for i, name in enumerate(meta["k"])
        ]
        v_leaves = [
            z[f"v{i}"].view(_np_dtype(name))
            for i, name in enumerate(meta["v"])
        ]
    return k_leaves, v_leaves, int(meta["n_pages"])


class PageShipper:
    """Transport seam for page runs: export to a portable payload, import
    a payload into destination pages.  Local tier copies implement it with
    device gathers/scatters; a cross-replica transport implements the same
    two calls over the wire (the payload is plain numpy leaves)."""

    def export_run(self, pages: Sequence[int]) -> "_PendingExport":
        raise NotImplementedError

    def resolve(self, pending: "_PendingExport") -> Tuple[List[np.ndarray], List[np.ndarray]]:
        raise NotImplementedError

    def import_run(
        self,
        k_leaves: List[np.ndarray],
        v_leaves: List[np.ndarray],
        n_pages: int,
        dest_pages: Sequence[int],
    ) -> None:
        raise NotImplementedError

    def bytes_per_page(self) -> int:
        raise NotImplementedError


class _PendingExport:
    """An in-flight D2H export: per-chunk device arrays whose host copy
    was started asynchronously.  `ready()` is advisory; `resolve` blocks."""

    __slots__ = ("n_pages", "chunk_pages", "chunks")

    def __init__(self, n_pages: int, chunk_pages: List[int],
                 chunks: List[Tuple[List[Any], List[Any]]]):
        self.n_pages = n_pages
        self.chunk_pages = chunk_pages  # REAL pages per chunk (unpadded)
        self.chunks = chunks  # [(k_leaf_arrays, v_leaf_arrays), ...]

    def ready(self) -> bool:
        for k_leaves, v_leaves in self.chunks:
            for a in (*k_leaves, *v_leaves):
                is_ready = getattr(a, "is_ready", None)
                if is_ready is not None and not is_ready():
                    return False
        return True

    @property
    def nbytes(self) -> int:
        return sum(
            a.nbytes for k, v in self.chunks for a in (*k, *v)
        )


class LocalPageShipper(PageShipper):
    """Ship page runs between one engine's HBM pool and host memory.

    `owner` exposes mutable ``k_pool`` / ``v_pool`` attributes (the engine;
    tests use a stub).  Scatters donate and REASSIGN the owner's pools, so
    imports must run on the thread that owns dispatch (the engine thread —
    the same single-writer contract every jitted step obeys).
    """

    def __init__(self, owner: Any, page_size: int):
        self.owner = owner
        self.page_size = page_size

    # -- export (demotion: D2H) -----------------------------------------

    def export_run(self, pages: Sequence[int]) -> _PendingExport:
        ps = self.page_size
        chunks: List[Tuple[List[Any], List[Any]]] = []
        chunk_pages: List[int] = []
        off = 0
        for padded in _bucketize(len(pages)):
            failpoint("kv.demote")
            real = min(padded, len(pages) - off)
            idx = _flat_slots(pages[off:off + real], ps, padded)
            k_rows, v_rows = _gather_rows(
                self.owner.k_pool, self.owner.v_pool, jnp.asarray(idx)
            )
            k_leaves = jax.tree.leaves(k_rows)
            v_leaves = jax.tree.leaves(v_rows)
            for a in (*k_leaves, *v_leaves):
                start = getattr(a, "copy_to_host_async", None)
                if start is not None:
                    start()
            chunks.append((k_leaves, v_leaves))
            chunk_pages.append(real)
            off += real
        return _PendingExport(len(pages), chunk_pages, chunks)

    def resolve(
        self, pending: _PendingExport
    ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """Materialize an export on host: trim chunk padding, concatenate
        chunks — one numpy array per pool leaf, [L, n_pages*ps, ...]."""
        ps = self.page_size
        k_parts: List[List[np.ndarray]] = []
        v_parts: List[List[np.ndarray]] = []
        for (k_leaves, v_leaves), real in zip(
            pending.chunks, pending.chunk_pages
        ):
            k_parts.append([np.asarray(a)[:, : real * ps] for a in k_leaves])
            v_parts.append([np.asarray(a)[:, : real * ps] for a in v_leaves])
        n_leaves = len(k_parts[0])
        k_out = [
            np.concatenate([part[i] for part in k_parts], axis=1)
            if len(k_parts) > 1 else np.ascontiguousarray(k_parts[0][i])
            for i in range(n_leaves)
        ]
        v_out = [
            np.concatenate([part[i] for part in v_parts], axis=1)
            if len(v_parts) > 1 else np.ascontiguousarray(v_parts[0][i])
            for i in range(n_leaves)
        ]
        return k_out, v_out

    # -- import (promotion: H2D) ----------------------------------------

    def import_run(
        self,
        k_leaves: List[np.ndarray],
        v_leaves: List[np.ndarray],
        n_pages: int,
        dest_pages: Sequence[int],
    ) -> None:
        if len(dest_pages) != n_pages:
            raise ShipError(
                f"import of {n_pages}-page run into {len(dest_pages)} pages"
            )
        ps = self.page_size
        treedef_k = jax.tree.structure(self.owner.k_pool)
        treedef_v = jax.tree.structure(self.owner.v_pool)
        off = 0
        for padded in _bucketize(n_pages):
            failpoint("kv.promote")
            real = min(padded, n_pages - off)
            idx = _flat_slots(dest_pages[off:off + real], ps, padded)
            lo, hi = off * ps, (off + real) * ps
            pad_rows = (padded - real) * ps

            def chunk_of(a: np.ndarray) -> np.ndarray:
                rows = a[:, lo:hi]
                if pad_rows:
                    pad = np.zeros(
                        (rows.shape[0], pad_rows) + rows.shape[2:],
                        rows.dtype,
                    )
                    rows = np.concatenate([rows, pad], axis=1)
                return rows

            k_rows = jax.tree.unflatten(
                treedef_k, [chunk_of(a) for a in k_leaves]
            )
            v_rows = jax.tree.unflatten(
                treedef_v, [chunk_of(a) for a in v_leaves]
            )
            self.owner.k_pool, self.owner.v_pool = _scatter_jit(
                self.owner.k_pool, self.owner.v_pool, jnp.asarray(idx),
                k_rows, v_rows,
            )
            off += real

    def bytes_per_page(self) -> int:
        ps = self.page_size
        total = 0
        for pool in (self.owner.k_pool, self.owner.v_pool):
            for a in jax.tree.leaves(pool):
                per_slot = int(np.prod(a.shape[2:])) if a.ndim > 2 else 1
                total += a.shape[0] * ps * per_slot * a.dtype.itemsize
        return total


class DeviceShipper(PageShipper):
    """Device-to-device page-run transport: zero host copies (ISSUE 19).

    The same export/resolve/import seam as :class:`LocalPageShipper`,
    but no leaf is ever materialized as numpy: export's bucketed gathers
    stay on the source mesh, resolve re-places the buffers onto the
    destination pool's sharding with ``jax.device_put`` (a no-op
    placement when both replicas share devices, an ICI/DMA transfer when
    they don't — the KV pool's slot axis is unsharded, so the gathered
    rows take the pool's NamedSharding directly), and import runs the
    donating scatter on the destination.

    :meth:`ship` is the chunk-aligned fast path
    :class:`CrossReplicaPageShipper` routes to: gather -> device_put ->
    scatter per SHIP_BUCKETS chunk, skipping resolve's trim/concat (the
    padded rows ride along and land in the destination trash page, same
    as the host transport).  The ``kv.ship`` failpoint fires once per
    chunk here too, so torn-copy chaos rules (``error:nth=2``) behave
    identically across transports, and so does the cleanup contract:
    ship() raising means the destination pages are PARTIAL and the
    caller frees them all.
    """

    def __init__(self, src_owner: Any, dst_owner: Any, page_size: int):
        self.src = src_owner
        self.dst = dst_owner
        self.page_size = page_size

    def _place(self, leaves: List[Any], refs: List[Any]) -> List[Any]:
        """Move gathered leaves onto the matching destination pool
        leaves' shardings, staying on device."""
        out = []
        for a, ref in zip(leaves, refs):
            sh = getattr(ref, "sharding", None)
            out.append(a if sh is None else jax.device_put(a, sh))
        return out

    # -- the PageShipper seam ------------------------------------------

    def export_run(self, pages: Sequence[int]) -> _PendingExport:
        ps = self.page_size
        chunks: List[Tuple[List[Any], List[Any]]] = []
        chunk_pages: List[int] = []
        off = 0
        for padded in _bucketize(len(pages)):
            real = min(padded, len(pages) - off)
            idx = _flat_slots(pages[off:off + real], ps, padded)
            k_rows, v_rows = _gather_rows(
                self.src.k_pool, self.src.v_pool, jnp.asarray(idx)
            )
            # NO copy_to_host_async: the buffers stay device-resident
            chunks.append((jax.tree.leaves(k_rows), jax.tree.leaves(v_rows)))
            chunk_pages.append(real)
            off += real
        return _PendingExport(len(pages), chunk_pages, chunks)

    def resolve(self, pending: _PendingExport) -> Tuple[List[Any], List[Any]]:
        """Trim chunk padding and concatenate ON DEVICE, then place the
        run onto the destination pool's sharding — one jax array per
        pool leaf, never numpy."""
        ps = self.page_size
        k_parts: List[List[Any]] = []
        v_parts: List[List[Any]] = []
        for (k_leaves, v_leaves), real in zip(
            pending.chunks, pending.chunk_pages
        ):
            k_parts.append([a[:, : real * ps] for a in k_leaves])
            v_parts.append([a[:, : real * ps] for a in v_leaves])
        n_leaves = len(k_parts[0])
        k_out = [
            jnp.concatenate([part[i] for part in k_parts], axis=1)
            if len(k_parts) > 1 else k_parts[0][i]
            for i in range(n_leaves)
        ]
        v_out = [
            jnp.concatenate([part[i] for part in v_parts], axis=1)
            if len(v_parts) > 1 else v_parts[0][i]
            for i in range(n_leaves)
        ]
        return (
            self._place(k_out, jax.tree.leaves(self.dst.k_pool)),
            self._place(v_out, jax.tree.leaves(self.dst.v_pool)),
        )

    def import_run(
        self,
        k_leaves: List[Any],
        v_leaves: List[Any],
        n_pages: int,
        dest_pages: Sequence[int],
    ) -> None:
        if len(dest_pages) != n_pages:
            raise ShipError(
                f"import of {n_pages}-page run into {len(dest_pages)} pages"
            )
        ps = self.page_size
        treedef_k = jax.tree.structure(self.dst.k_pool)
        treedef_v = jax.tree.structure(self.dst.v_pool)
        off = 0
        for padded in _bucketize(n_pages):
            real = min(padded, n_pages - off)
            idx = _flat_slots(dest_pages[off:off + real], ps, padded)
            lo, hi = off * ps, (off + real) * ps
            pad_rows = (padded - real) * ps

            def chunk_of(a):
                rows = a[:, lo:hi]
                if pad_rows:
                    pad = jnp.zeros(
                        (rows.shape[0], pad_rows) + tuple(rows.shape[2:]),
                        rows.dtype,
                    )
                    rows = jnp.concatenate([rows, pad], axis=1)
                return rows

            self.dst.k_pool, self.dst.v_pool = _scatter_jit(
                self.dst.k_pool, self.dst.v_pool, jnp.asarray(idx),
                jax.tree.unflatten(treedef_k, [chunk_of(a) for a in k_leaves]),
                jax.tree.unflatten(treedef_v, [chunk_of(a) for a in v_leaves]),
            )
            off += real

    def bytes_per_page(self) -> int:
        ps = self.page_size
        total = 0
        for pool in (self.src.k_pool, self.src.v_pool):
            for a in jax.tree.leaves(pool):
                per_slot = int(np.prod(a.shape[2:])) if a.ndim > 2 else 1
                total += a.shape[0] * ps * per_slot * a.dtype.itemsize
        return total

    # -- the chunk-aligned ship fast path ------------------------------

    def ship(self, src_pages: Sequence[int],
             dest_pages: Sequence[int]) -> int:
        ps = self.page_size
        treedef_k = jax.tree.structure(self.dst.k_pool)
        treedef_v = jax.tree.structure(self.dst.v_pool)
        dst_k_refs = jax.tree.leaves(self.dst.k_pool)
        dst_v_refs = jax.tree.leaves(self.dst.v_pool)
        off = 0
        nbytes = 0
        for padded in _bucketize(len(src_pages)):
            failpoint("kv.ship")
            real = min(padded, len(src_pages) - off)
            sidx = _flat_slots(src_pages[off:off + real], ps, padded)
            k_rows, v_rows = _gather_rows(
                self.src.k_pool, self.src.v_pool, jnp.asarray(sidx)
            )
            k_leaves = self._place(jax.tree.leaves(k_rows), dst_k_refs)
            v_leaves = self._place(jax.tree.leaves(v_rows), dst_v_refs)
            frac = real / padded
            nbytes += int(sum(
                a.nbytes * frac for a in (*k_leaves, *v_leaves)
            ))
            didx = _flat_slots(dest_pages[off:off + real], ps, padded)
            self.dst.k_pool, self.dst.v_pool = _scatter_jit(
                self.dst.k_pool, self.dst.v_pool, jnp.asarray(didx),
                jax.tree.unflatten(treedef_k, k_leaves),
                jax.tree.unflatten(treedef_v, v_leaves),
            )
            off += real
        return nbytes


class CrossReplicaPageShipper:
    """Ship a page run from one replica's PagePool into another's
    (disaggregated prefill/decode, ISSUE 12).

    Same bucketed gather/scatter programs as the local tier copies.  Two
    transports (ISSUE 19, ``KAFKA_TPU_SHIP_TRANSPORT``): the default
    HOST-STAGED path gathers each chunk out of the source pool,
    materializes it on host (the D2H resolve blocks), and scatters it
    into the destination pool (H2D); the DEVICE path
    (:class:`DeviceShipper`) replaces the host hop with a
    ``jax.device_put`` onto the destination sharding — the seam stays
    transport-agnostic, so callers never change.  Both pools' scatters
    donate, so ship() must run on the thread that owns dispatch for BOTH
    replicas (the DP router's worker thread drives every replica, so
    this holds by construction).

    Chunks are padded to SHIP_BUCKETS with trash-page slots on both
    sides: padded gather rows are garbage read out of the source trash
    page, and their scatter writes land INSIDE the destination trash
    page, which is garbage by contract.

    Failure semantics: the ``kv.ship`` failpoint fires once per chunk, so
    an ``error:nth=2`` rule on a multi-chunk run produces a genuinely
    torn copy — earlier chunks already scattered into the destination.
    ship() raising means the destination pages are PARTIAL; the caller
    (dp_router._ship_run) frees every destination page (they were
    freshly allocated and shared with nobody, so the cleanup is
    complete) and the thread degrades to re-prefill.
    """

    def __init__(self, src_owner: Any, dst_owner: Any, page_size: int,
                 transport: Optional[str] = None):
        self.src = src_owner
        self.dst = dst_owner
        self.page_size = page_size
        self.transport = resolve_ship_transport(
            src_owner, dst_owner, transport
        )
        self._device = (
            DeviceShipper(src_owner, dst_owner, page_size)
            if self.transport == "device" else None
        )

    def bytes_per_page(self) -> int:
        ps = self.page_size
        total = 0
        for pool in (self.src.k_pool, self.src.v_pool):
            for a in jax.tree.leaves(pool):
                per_slot = int(np.prod(a.shape[2:])) if a.ndim > 2 else 1
                total += a.shape[0] * ps * per_slot * a.dtype.itemsize
        return total

    def ship(self, src_pages: Sequence[int],
             dest_pages: Sequence[int]) -> int:
        """Copy `src_pages` (source pool) into `dest_pages` (destination
        pool), chunk by chunk.  Returns the real (unpadded) bytes moved.
        Raises on a torn chunk — see class docstring for the cleanup
        contract."""
        if len(src_pages) != len(dest_pages):
            raise ShipError(
                f"ship of {len(src_pages)} pages into "
                f"{len(dest_pages)} destination pages"
            )
        if self._device is not None:
            return self._device.ship(src_pages, dest_pages)
        return self._ship_host(src_pages, dest_pages)

    def _ship_host(self, src_pages: Sequence[int],
                   dest_pages: Sequence[int]) -> int:
        ps = self.page_size
        treedef_k = jax.tree.structure(self.dst.k_pool)
        treedef_v = jax.tree.structure(self.dst.v_pool)
        off = 0
        nbytes = 0
        budget = ship_staging_budget_bytes()
        for padded in _bucketize(len(src_pages)):
            failpoint("kv.ship")
            real = min(padded, len(src_pages) - off)
            sidx = _flat_slots(src_pages[off:off + real], ps, padded)
            if budget and ship_staging_bytes() >= budget:
                # staging over budget: let the outstanding scatters land
                # (releasing their pinned host copies) before pinning
                # another chunk — RSS bounded to budget + one chunk
                jax.block_until_ready((self.dst.k_pool, self.dst.v_pool))
            k_rows, v_rows = _gather_rows(
                self.src.k_pool, self.src.v_pool, jnp.asarray(sidx)
            )
            # host staging: materialize the PADDED rows (pad rows are
            # source-trash garbage that lands in the destination trash
            # page below), then scatter device-side on the destination
            k_leaves = [np.asarray(a) for a in jax.tree.leaves(k_rows)]
            v_leaves = [np.asarray(a) for a in jax.tree.leaves(v_rows)]
            staged = int(sum(
                a.nbytes for a in (*k_leaves, *v_leaves)
            ))
            _ship_stage_add(staged)
            try:
                frac = real / padded
                nbytes += int(sum(
                    a.nbytes * frac for a in (*k_leaves, *v_leaves)
                ))
                didx = _flat_slots(dest_pages[off:off + real], ps, padded)
                self.dst.k_pool, self.dst.v_pool = _scatter_jit(
                    self.dst.k_pool, self.dst.v_pool, jnp.asarray(didx),
                    jax.tree.unflatten(treedef_k, k_leaves),
                    jax.tree.unflatten(treedef_v, v_leaves),
                )
            finally:
                # the scatter dispatch has consumed the staged copies
                # (jax holds its own references until the H2D lands)
                _ship_stage_sub(staged)
            off += real
        return nbytes


# ---------------------------------------------------------------------------
# host + disk tiers
# ---------------------------------------------------------------------------


class HostRun:
    """One demoted page run resident in the host tier (or below)."""

    __slots__ = (
        "run_id", "n_pages", "nbytes", "location", "pending",
        "k_leaves", "v_leaves", "ref_bit", "discarded",
        "path_runs", "threads", "object_key",
    )

    def __init__(self, run_id: str, n_pages: int, nbytes: int,
                 pending: Optional[_PendingExport]):
        self.run_id = run_id
        self.n_pages = n_pages
        self.nbytes = nbytes
        # "pending" (D2H still materializing) -> "host" -> "spilling"
        # -> "disk"; "object" = archived into the shared object store
        # (runtime/object_tier.py) — payload-less locally, fetched back
        # on promote
        self.location = "pending"
        self.pending = pending
        self.k_leaves: Optional[List[np.ndarray]] = None
        self.v_leaves: Optional[List[np.ndarray]] = None
        self.ref_bit = False  # second-chance LRU
        self.discarded = False
        # Content-address context (object tier): the per-node token runs
        # of the radix path from the root THROUGH this run, and the
        # prefix keys claiming the node at demotion time.  A run's KV
        # depends on its whole prefix, so only the full path names its
        # content; None = demoted before the object tier existed / by a
        # caller that cannot supply it (such runs never archive).
        self.path_runs: Optional[List[List[int]]] = None
        self.threads: Tuple[str, ...] = ()
        self.object_key: Optional[str] = None


class KVTierManager:
    """The host-RAM (+ optional disk) KV tier and its shipping policy.

    Single-writer like the engine: demote/promote/split/discard run on the
    engine thread (they mutate pool arrays through the shipper); only the
    background spill writer touches disk state, under ``_lock``.
    ``snapshot()`` is read from serving threads and is torn-tolerant.
    """

    def __init__(
        self,
        shipper: PageShipper,
        host_budget_bytes: int,
        disk_dir: Optional[str] = None,
        page_size: int = 16,
    ):
        self.shipper = shipper
        self.host_budget_bytes = int(host_budget_bytes)
        self.disk_dir = disk_dir
        self.page_size = page_size
        if disk_dir:
            os.makedirs(disk_dir, exist_ok=True)
        self._runs: "OrderedDict[str, HostRun]" = OrderedDict()
        self._lock = threading.Lock()
        # run ids are namespaced per manager so DP replicas (or restarts)
        # sharing one disk dir never collide on file names
        self._uid = uuid.uuid4().hex[:8]
        self._next_id = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        self.disk_runs = 0
        # engine plumbing: the trace context of the request whose pressure
        # (or prefix hit) drives the current demote/promote — spans attach
        # to it; None = untraced (record_span is then a no-op)
        self.trace_ctx = None
        # counters (KV_TIER_METRIC_KEYS; exported via /metrics + Prometheus)
        self.demotions = 0
        self.pages_demoted = 0
        self.bytes_demoted = 0
        self.demote_failures = 0
        self.promotions = 0
        self.pages_promoted = 0
        self.bytes_promoted = 0
        self.promote_failures = 0
        self.host_evictions = 0  # runs dropped (no disk tier / lost)
        self.disk_spills = 0
        self.disk_loads = 0
        self._spill_q: "queue.Queue[Optional[HostRun]]" = queue.Queue()
        self._spill_thread: Optional[threading.Thread] = None
        # Object-store tier below host+disk (runtime/object_tier.py,
        # ISSUE 14): when attached, a run the local ladder would DROP is
        # archived into the shared store instead (content-addressed, so
        # identical prefixes dedupe across hosts) and stays promotable.
        # None = the pre-object ladder, byte-identical.
        self.object = None

    def attach_object(self, obj: Any) -> None:
        """Mount the object tier (engine construction).  The tier reads
        this manager's trace context so kv.object_* spans attach to the
        request whose pressure or wake drives them."""
        self.object = obj
        obj.manager = self

    # -- sizing ----------------------------------------------------------

    def bytes_for_pages(self, n_pages: int) -> int:
        return n_pages * self.shipper.bytes_per_page()

    # -- demote ----------------------------------------------------------

    def demote(self, pages: Sequence[int],
               path_runs: Optional[List[List[int]]] = None,
               threads: Sequence[str] = ()) -> Optional[str]:
        """Copy `pages` D2H and admit them as a host run.  Returns the run
        id, or None when the copy failed or the run cannot fit — the
        caller then falls back to plain eviction (pages are simply freed).
        The gather is enqueued before the caller releases the pages, so
        in-order device execution reads them pre-overwrite; only the host
        materialization is deferred (see drain()).

        `path_runs` / `threads` carry the radix-path content context the
        OBJECT tier needs (root->run token runs + claiming prefix keys):
        with them, a run this tier would later drop archives into the
        shared store under its content address instead (see _archive)."""
        from .autoscaler import background_deferred

        if background_deferred():
            # overload degradation (autoscaler ladder rung 3): demotion
            # is background D2H work — refuse, the caller falls back to
            # plain eviction (a dropped cold run re-prefills later; a
            # D2H copy competes with serving NOW)
            return None
        est = self.bytes_for_pages(len(pages))
        if est > self.host_budget_bytes:
            return None  # a run larger than the whole tier never fits
        t0 = time.monotonic()
        try:
            pending = self.shipper.export_run(pages)
        except Exception as e:  # injected fault / transfer error
            self.demote_failures += 1
            logger.warning("kv demote of %d pages failed: %s", len(pages), e)
            return None
        nbytes = pending.nbytes
        self._evict_for(nbytes)
        with self._lock:
            self._next_id += 1
            run = HostRun(f"{self._uid}.r{self._next_id}", len(pages),
                          nbytes, pending)
            run.path_runs = path_runs
            run.threads = tuple(threads)
            self._runs[run.run_id] = run
            self.host_bytes += nbytes
        dur = time.monotonic() - t0
        self.demotions += 1
        self.pages_demoted += len(pages)
        self.bytes_demoted += nbytes
        record_span(
            self.trace_ctx, "kv.demote", dur,
            attrs={"pages": len(pages), "bytes": nbytes, "overlap": "async"},
        )
        return run.run_id

    # -- promote ---------------------------------------------------------

    def promote(self, run_id: str, dest_pages: Sequence[int]) -> bool:
        """Ship a host run back into freshly-allocated pool pages.

        The scatter is enqueued ahead of the caller's suffix prefill, so
        the H2D copy overlaps the dispatch pipeline.  Returns False on any
        failure (missing run, torn copy): the destination pages are the
        caller's to free and the run is gone — degrade to re-prefill,
        never serve partial KV.  A torn scatter only ever wrote pages the
        caller just allocated (shared with nobody), so freeing them is
        complete cleanup."""
        t0 = time.monotonic()
        run = self._take(run_id)
        if run is None:
            self.promote_failures += 1
            return False
        src = "disk" if run.location == "disk" else "host"
        try:
            k_leaves, v_leaves = self._materialize(run)
            self.shipper.import_run(
                k_leaves, v_leaves, run.n_pages, dest_pages
            )
        except Exception as e:
            self.promote_failures += 1
            logger.warning(
                "kv promote of run %s (%d pages) failed: %s — degrading "
                "to re-prefill", run_id, run.n_pages, e,
            )
            return False
        dur = time.monotonic() - t0
        self.promotions += 1
        self.pages_promoted += run.n_pages
        self.bytes_promoted += run.nbytes
        record_span(
            self.trace_ctx, "kv.promote", dur,
            attrs={
                "pages": run.n_pages, "bytes": run.nbytes, "source": src,
                "overlap": "prefill",
            },
        )
        return True

    # -- structure ops (radix-tree splits / invalidation) ----------------

    def split(self, run_id: str, front_pages: int) -> Optional[Tuple[str, str]]:
        """Split a run at a page boundary into (front, back) runs — the
        host-side mirror of a radix-node split.  None when the run is gone
        (the caller removes the node instead)."""
        run = self._take(run_id)
        if run is None or not (0 < front_pages < run.n_pages):
            if run is not None:
                self._readmit(run)
            return None
        try:
            k_leaves, v_leaves = self._materialize(run)
        except Exception as e:
            logger.warning("kv split of run %s failed: %s", run_id, e)
            return None
        cut = front_pages * self.page_size
        # content-address context splits at the same boundary: the front
        # piece's path ends at the cut, the back piece's path carries
        # both halves — losing it here would make every split run
        # permanently ineligible for the object archive
        front_path = back_path = None
        if run.path_runs:
            head, last = run.path_runs[:-1], run.path_runs[-1]
            front_path = head + [last[:cut]]
            back_path = head + [last[:cut], last[cut:]]
        ids: List[str] = []
        for lo, hi, n, path in (
            (0, cut, front_pages, front_path),
            (cut, None, run.n_pages - front_pages, back_path),
        ):
            k_part = [np.ascontiguousarray(a[:, lo:hi]) for a in k_leaves]
            v_part = [np.ascontiguousarray(a[:, lo:hi]) for a in v_leaves]
            nbytes = sum(a.nbytes for a in (*k_part, *v_part))
            with self._lock:
                self._next_id += 1
                piece = HostRun(f"{self._uid}.r{self._next_id}", n,
                                nbytes, None)
                piece.location = "host"
                piece.k_leaves, piece.v_leaves = k_part, v_part
                piece.path_runs = path
                piece.threads = run.threads
                self._runs[piece.run_id] = piece
                self.host_bytes += nbytes
            ids.append(piece.run_id)
        self._evict_for(0)  # splitting resolved/copied: re-check budget
        return ids[0], ids[1]

    def touch(self, run_id: str) -> None:
        """Second-chance reference bit: the radix walk crossed this run."""
        with self._lock:
            run = self._runs.get(run_id)
            if run is not None:
                run.ref_bit = True

    def discard(self, run_id: str) -> None:
        """Drop a run (node invalidated, or its pages were re-adopted).
        An object-archived run also drops this owner's store reference —
        the object itself survives while any other host references it."""
        run = self._take(run_id, load=False)
        if run is not None:
            run.discarded = True
            if (run.location == "object" and run.object_key is not None
                    and self.object is not None):
                self.object.release(run.object_key)

    def peek(
        self, run_id: str
    ) -> Optional[Tuple[List[np.ndarray], List[np.ndarray]]]:
        """Read-only materialization for the sleep path: the run's host
        leaves wherever it lives, WITHOUT removing it from the tier.
        None for object-archived runs (already in the store) and on any
        load failure (the sleep entry is skipped)."""
        with self._lock:
            run = self._runs.get(run_id)
        if run is None or run.location == "object":
            return None
        try:
            if run.location == "disk":
                return self._disk_load(run)
            return self._materialize(run)
        except Exception as e:
            logger.warning("kv peek of run %s failed: %s", run_id, e)
            return None

    # -- background resolution & spill -----------------------------------

    def drain(self, force: bool = False) -> None:
        """Materialize pending D2H exports whose transfer completed.

        Called at scheduler cadence (engine.step) so pending runs release
        their device buffers promptly — an unresolved export pins its
        gather result in HBM, which is exactly what demotion exists to
        free.  `force` resolves everything (tests, spill pressure)."""
        if not self._runs:  # hot-path fast exit (torn-tolerant read)
            return
        with self._lock:
            todo = [
                r for r in self._runs.values() if r.location == "pending"
            ]
        for run in todo:
            if force or run.pending is None or run.pending.ready():
                try:
                    self._materialize(run)
                except Exception as e:
                    logger.warning(
                        "kv demote resolution of %s failed: %s",
                        run.run_id, e,
                    )
                    self.discard(run.run_id)
                    self.host_evictions += 1
        if todo:
            # a pressure moment with every run still in flight may have
            # overshot the budget (_evict_for tolerates it rather than
            # block the scheduler); now that transfers resolved, re-
            # enforce it
            self._evict_for(0)

    def flush(self, timeout: float = 5.0) -> None:
        """Test/shutdown helper: resolve all pending exports and wait for
        the spill queue to empty."""
        self.drain(force=True)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                busy = any(
                    r.location == "spilling" for r in self._runs.values()
                )
            if not busy and self._spill_q.empty():
                return
            time.sleep(0.005)

    def _materialize(self, run: HostRun) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        """Resolve a run to host numpy leaves wherever it currently lives."""
        if run.k_leaves is not None:
            return run.k_leaves, run.v_leaves
        if run.location == "object":
            if self.object is None or run.object_key is None:
                raise ShipError(f"run {run.run_id} archived but no "
                                "object tier is attached")
            got = self.object.get_run(run.object_key)
            if got is None or got[2] != run.n_pages:
                # a lost object OR a payload of the wrong span (content
                # keys include the start boundary, so this should be
                # unreachable — but importing mismatched KV would be
                # silent corruption, so it is a hard miss regardless)
                raise ShipError(
                    f"object tier lost run {run.run_id} "
                    f"(key {run.object_key})"
                )
            return got[0], got[1]
        if run.location == "disk":
            k_leaves, v_leaves = self._disk_load(run)
            self.disk_loads += 1
            return k_leaves, v_leaves
        if run.pending is None:
            raise ShipError(f"run {run.run_id} has no payload")
        k_leaves, v_leaves = self.shipper.resolve(run.pending)
        run.k_leaves, run.v_leaves = k_leaves, v_leaves
        run.pending = None
        if run.location == "pending":
            run.location = "host"
        return k_leaves, v_leaves

    def _take(self, run_id: str, load: bool = True) -> Optional[HostRun]:
        """Remove a run from the tier (promote/split/discard paths).  Its
        bytes are uncharged immediately.  Disk-resident runs are loaded
        into memory BEFORE their file is unlinked (`load=False` skips the
        read for discards); a failed load leaves the run payload-less and
        the caller's _materialize raises ShipError."""
        with self._lock:
            run = self._runs.pop(run_id, None)
            if run is None:
                return None
            if run.location == "disk":
                self.disk_bytes -= run.nbytes
                self.disk_runs -= 1
            elif run.location == "object":
                pass  # archived runs charge nothing locally
            else:
                self.host_bytes -= run.nbytes
        if run.location == "disk":
            if load:
                try:
                    run.k_leaves, run.v_leaves = self._disk_load(run)
                    self.disk_loads += 1
                except ShipError as e:
                    logger.warning("%s", e)
            try:
                os.unlink(self._disk_path(run.run_id))
            except OSError:
                pass
        return run

    def _readmit(self, run: HostRun) -> None:
        # a taken disk run's file is already unlinked and its payload (if
        # any) loaded — it re-enters as a host-resident run.  An archived
        # run re-enters as-is: its payload still lives in the store and
        # it charges nothing locally.
        if run.location == "object":
            with self._lock:
                self._runs[run.run_id] = run
            return
        if run.location == "disk":
            run.location = "host"
        with self._lock:
            self._runs[run.run_id] = run
            self.host_bytes += run.nbytes

    def _evict_for(self, incoming_bytes: int) -> None:
        """Second-chance LRU over host-resident runs: referenced runs get
        one more cycle; unreferenced ones spill to disk (when configured)
        or drop.  Dropped runs are discovered lazily — the radix node
        still references the run id, and the promote that misses removes
        the node (degrade to re-prefill).

        Runs whose D2H transfer is still in flight are never victims:
        this runs on the ENGINE THREAD inside the reclaim path, and
        resolving an unfinished export would block the scheduler on the
        copy — the opposite of the overlap model.  If every host-side run
        is still in flight the budget transiently overshoots instead;
        drain() (step cadence) resolves them and the next demote re-
        enforces the budget."""
        scanned = 0
        while True:
            with self._lock:
                if self.host_bytes + incoming_bytes <= self.host_budget_bytes:
                    return
                ready = [
                    r for r in self._runs.values()
                    if r.location == "host" or (
                        r.location == "pending"
                        and (r.pending is None or r.pending.ready())
                    )
                ]
                if not ready:
                    return  # in-flight/spilling only: tolerate overshoot
                victim = ready[0]
                if victim.ref_bit and scanned < len(ready):
                    victim.ref_bit = False
                    self._runs.move_to_end(victim.run_id)
                    scanned += 1
                    continue
            scanned = 0
            # materialize outside the lock — the transfer already
            # completed (ready()), so this is a copy-free numpy view fixup
            if victim.location == "pending":
                try:
                    self._materialize(victim)
                except Exception:
                    victim.location = "host"  # fall through to drop
                    victim.k_leaves = victim.v_leaves = None
            if self.disk_dir and victim.k_leaves is not None:
                with self._lock:
                    victim.location = "spilling"
                self._spill(victim)
            elif self._archive(victim):
                pass  # demoted past disk into the object store
            else:
                self._take(victim.run_id)
                self.host_evictions += 1

    def _archive(self, run: HostRun) -> bool:
        """Demotion past disk: archive a run the local ladder would drop
        into the shared object store (content-addressed — an identical
        prefix already archived by any host dedupes to a reference), and
        refresh its claimants' sleep manifests.  The run stays registered
        (payload-less, zero local bytes) so a later promote fetches it
        back transparently.  False = no object tier / no path context /
        store breaker open / torn put — the caller drops the run as
        before.  The availability gate is checked BEFORE encoding: with
        the breaker open the put cannot land, so the run degrades to
        plain eviction without paying the serialization either."""
        if (
            self.object is None
            or run.k_leaves is None
            or not run.path_runs
            or not self.object.available()
        ):
            return False
        flat = [t for seg in run.path_runs for t in seg]
        key = self.object.put_run(flat, run.k_leaves, run.v_leaves,
                                  run.n_pages)
        if key is None:
            return False
        with self._lock:
            run.location = "object"
            run.object_key = key
            run.k_leaves = run.v_leaves = None
            run.pending = None
            self.host_bytes -= run.nbytes
        if run.threads:
            self.object.note_archive(run.threads, run.path_runs)
        return True

    # -- disk tier -------------------------------------------------------

    def _disk_path(self, run_id: str) -> str:
        return os.path.join(self.disk_dir or "", f"{run_id}.kvrun.npz")

    def _spill(self, run: HostRun) -> None:
        if self._spill_thread is None:
            self._spill_thread = threading.Thread(
                target=self._spill_loop, name="kv-tier-spill", daemon=True
            )
            self._spill_thread.start()
        self._spill_q.put(run)

    def _spill_loop(self) -> None:
        while True:
            run = self._spill_q.get()
            if run is None:
                return
            try:
                self._spill_one(run)
            except Exception as e:
                logger.warning("kv disk spill of %s failed: %s",
                               run.run_id, e)
                self._take(run.run_id)
                self.host_evictions += 1

    def _spill_one(self, run: HostRun) -> None:
        if run.discarded:
            return
        data = encode_run_npz(run.k_leaves, run.v_leaves, run.n_pages)
        path = self._disk_path(run.run_id)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
        with self._lock:
            if run.discarded or run.run_id not in self._runs:
                try:
                    os.unlink(path)
                except OSError:
                    pass
                return
            run.location = "disk"
            run.k_leaves = run.v_leaves = None
            self.host_bytes -= run.nbytes
            self.disk_bytes += run.nbytes
            self.disk_runs += 1
            self.disk_spills += 1

    def _disk_load(self, run: HostRun) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        path = self._disk_path(run.run_id)
        try:
            with open(path, "rb") as f:
                k_leaves, v_leaves, _ = decode_run_npz(f.read())
        except (OSError, KeyError, ValueError) as e:
            raise ShipError(f"disk tier lost run {run.run_id}: {e}")
        return k_leaves, v_leaves

    # -- export ----------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """The /metrics "kv_tier" section (KV_TIER_METRIC_KEYS)."""
        with self._lock:
            host_runs = sum(
                1 for r in self._runs.values()
                if r.location not in ("disk", "object")
            )
        return {
            "host_budget_bytes": self.host_budget_bytes,
            "host_bytes": self.host_bytes,
            "host_runs": host_runs,
            "disk_bytes": self.disk_bytes,
            "disk_runs": self.disk_runs,
            "demotions": self.demotions,
            "pages_demoted": self.pages_demoted,
            "bytes_demoted": self.bytes_demoted,
            "demote_failures": self.demote_failures,
            "promotions": self.promotions,
            "pages_promoted": self.pages_promoted,
            "bytes_promoted": self.bytes_promoted,
            "promote_failures": self.promote_failures,
            "host_evictions": self.host_evictions,
            "disk_spills": self.disk_spills,
            "disk_loads": self.disk_loads,
        }
