"""Paged KV cache: device-side page pool + host-side allocator.

TPU-first replacement for what the reference outsourced entirely (its KV
state lived inside remote providers).  Here the KV pool is two device arrays
[L, num_pages * page_size, row] whose row widths `ModelConfig.kv_row_widths`
defines (GQA: Hkv*D each, heads merged into the minor axis — the lane-tile
alignment the Pallas paged kernel's DMAs require; latent attention: the
latent c~ in one, the roped k_r in the other; a model whose kinds of layer
store different rows has a pool pair per kind under the one page table; see
make_kv_pool_arrays);
sequences own ordered lists of physical pages.

A model with a recurrent state (`cfg.has_state`: state-space layers, or
gated short convolutions) also keeps, per thread, a state that no page can
hold: a fixed-size STATE SLOT of the device arrays `make_state_arrays`
allocates beside the pools (they ride in the v pool's pytree,
models/hybrid.py), handed out by `StatePool`.  The slot's shape is the
model's kind of state layer's (`cfg.state_shapes`: a Mamba layer's conv tail
and h, 3.5 MB a slot at Phi-4's sizes; a short convolution's two rows, 180 KB
at LFM2's); nothing here knows which.  Slot i
< lanes is decode lane i's for its life, one slot is the trash slot, the rest
are snapshots the prefix cache owns: a page can be shared from any page
boundary, a recurrence only from where a snapshot stands.  The
host-side allocator is refcounted so pages can be shared between sequences —
the mechanism behind thread-keyed cache reuse and prefix sharing (BASELINE
configs 2 and 5).

Page tables, not the pool, are what the jitted step functions consume: a
[B, max_pages] int32 array per step, from which read/write flat indices are
derived *on device* (models/cache.py PagedView).  Physical page 0 is
reserved as the trash page — inactive batch slots point their writes at it.

Page ids and slot indices are per layer and the same in every layer.  The
step programs never slice a layer out of the pool: the stacked arrays ride
the layer scan as carry, viewed flat as [L * num_pages * page_size, Hkv*D],
and layer l's page p is page l * num_pages + p of that view
(models/cache.py _layer_view) — so each layer has its own trash page, page
l * num_pages, which is what page 0 of its slice was.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..models.cache import INDEX
from ..models.config import ModelConfig
from ..ops.pallas.paged_attention import pages_one_run
from .failpoints import failpoint

TRASH_PAGE = 0


class OutOfPagesError(RuntimeError):
    """Raised when the pool cannot satisfy an allocation; the scheduler
    reacts by preempting or queueing (never a user-facing crash)."""


@dataclasses.dataclass
class SequencePages:
    """Host-side record of the pages backing one sequence."""

    seq_id: str
    pages: List[int] = dataclasses.field(default_factory=list)
    length: int = 0  # tokens currently materialized in the cache

    # `run_steps`' memory: _run_cum[i] of the first i whole groups of
    # _run_group pages are one ascending run each
    _run_group: int = 0
    _run_cum: List[int] = dataclasses.field(default_factory=lambda: [0])

    def capacity(self, page_size: int) -> int:
        return len(self.pages) * page_size

    def run_steps(self, group: int, upto: int) -> int:
        """Of the sequence's first `upto` groups of `group` pages (the
        Pallas decode walk's softmax steps), how many are ONE ascending run
        of physical pages (ops/pallas/paged_attention.py pages_one_run: the
        kernel fetches such a step as one copy).  Kept as the page list
        grows: a call looks only at the groups completed since the last one,
        so a decode dispatch pays O(1) a lane, not a scan of its pages.  The
        list grows by appending (`PagePool.ensure_capacity`) and shrinks
        only to nothing (`free_sequence`)."""
        cum = self._run_cum
        if group != self._run_group or (len(cum) - 1) * group > len(self.pages):
            self._run_group, cum = group, [0]
            self._run_cum = cum
        while len(cum) <= upto and len(cum) * group <= len(self.pages):
            cum.append(cum[-1] + pages_one_run(
                self.pages.__getitem__, (len(cum) - 1) * group, group))
        return cum[min(upto, len(cum) - 1)]


class PagePool:
    """Refcounted allocator over the physical page axis.

    Device arrays are owned by the engine (they thread through jit); this
    class only tracks ownership/refcounts on host.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is the trash page)")
        self.num_pages = num_pages
        self.page_size = page_size
        self.refcount = np.zeros(num_pages, dtype=np.int32)
        self.refcount[TRASH_PAGE] = 1  # never allocated
        # popped from the end, kept highest-first: the lowest free page next
        self._free: List[int] = list(range(num_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> List[int]:
        failpoint("kv.alloc")
        if n > len(self._free):
            raise OutOfPagesError(f"need {n} pages, have {len(self._free)} free")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self.refcount[p] = 1
        return out

    def retain(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p == TRASH_PAGE:
                continue
            assert self.refcount[p] > 0, f"retain of unowned page {p}"
            self.refcount[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        held = len(self._free)
        for p in pages:
            if p == TRASH_PAGE:
                continue
            assert self.refcount[p] > 0, f"double free of page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._free.append(p)
        # The lowest free page on top.  The list is all but sorted, so the
        # sort is linear: 22-46 us a release at 5,120-8,192 pages, 66-520 of
        # them given back (timeit on the sandbox's CPU, PR 53), once a
        # finished thread or an evicted prefix, never a dispatch.
        # What boot's warm-up requests gave back is then handed out again as
        # 1, 2, 3, ...: a prompt reserved in one go (the shared system
        # prompt) is ONE ascending run of pages, which the Pallas decode walk
        # copies a softmax step at a time (ops/pallas/paged_attention.py).
        if len(self._free) > held:
            self._free.sort(reverse=True)

    # -- sequence-level helpers ------------------------------------------

    def ensure_capacity(self, seq: SequencePages, new_length: int) -> List[int]:
        """Grow seq's page list to cover new_length tokens; returns pages added."""
        needed = -(-new_length // self.page_size)  # ceil
        added: List[int] = []
        if needed > len(seq.pages):
            added = self.alloc(needed - len(seq.pages))
            seq.pages.extend(added)
        return added

    def free_sequence(self, seq: SequencePages) -> None:
        self.release(seq.pages)
        seq.pages.clear()
        seq.length = 0
        seq._run_cum = [0]  # the runs `run_steps` had counted

    # -- leak detection (engine self-check) ------------------------------

    def check_consistency(self) -> List[str]:
        """Internal allocator invariants; returns human-readable problems.

        Every non-trash page must be exactly one of {free-listed with
        refcount 0, owned with refcount > 0}.  Anything else is a leak or
        a double free in the making.
        """
        problems: List[str] = []
        seen: set = set()
        for p in self._free:
            if p in seen:
                problems.append(f"page {p} duplicated in free list")
            seen.add(p)
            if p == TRASH_PAGE:
                problems.append("trash page in free list")
            elif self.refcount[p] != 0:
                problems.append(
                    f"page {p} free-listed with refcount {self.refcount[p]}"
                )
        for p in range(self.num_pages):
            if p == TRASH_PAGE:
                continue
            rc = int(self.refcount[p])
            if rc < 0:
                problems.append(f"page {p} has negative refcount {rc}")
            elif rc == 0 and p not in seen:
                problems.append(
                    f"page {p} leaked: refcount 0 but not in free list"
                )
        return problems

    def reconcile(
        self, expected: Dict[int, int], repair: bool = False
    ) -> List[str]:
        """Compare refcounts against the owners the caller enumerated.

        `expected` maps page -> number of live references (sequences +
        prefix-cache retains).  Pages whose refcount exceeds that are
        leaked (held by nobody); with `repair` the excess references are
        force-released back to the free list.  Refcounts BELOW the owner
        count mean a double free: repair re-pins them so a future release
        cannot corrupt a stranger's page.
        """
        reports: List[str] = []
        for p in range(self.num_pages):
            if p == TRASH_PAGE:
                continue
            rc = int(self.refcount[p])
            want = expected.get(p, 0)
            if rc == want:
                continue
            kind = "leaked" if rc > want else "double-freed"
            reports.append(
                f"page {p} {kind}: refcount {rc}, {want} live owners"
                + (" (repaired)" if repair else "")
            )
            if not repair:
                continue
            if rc > want:
                self.refcount[p] = want
                if want == 0 and p not in self._free:
                    self._free.append(p)
            else:
                if rc == 0 and p in self._free:
                    self._free.remove(p)
                self.refcount[p] = want
        return reports


class StatePool:
    """Host allocator over the state-slot axis of the state arrays of a
    model with a recurrent state.  Slots 0 .. lanes - 1 belong to the decode lanes (lane i reads
    and writes slot i: the decode programs address them by position), slot
    `lanes` is the trash slot (inactive prefill lanes, snapshots nobody
    wants), the rest are SNAPSHOT slots, refcounted as pages are: the radix
    node that owns a snapshot holds one reference, a lookup that means to
    restore it holds one until the copy is enqueued."""

    def __init__(self, n_slots: int, lanes: int):
        if n_slots < lanes + 1:
            raise ValueError(
                f"{n_slots} state slots cannot hold {lanes} lanes and the "
                "trash slot")
        self.n_slots, self.lanes = n_slots, lanes
        self.trash = lanes
        self.refcount = np.zeros(n_slots, dtype=np.int32)
        self._free: List[int] = list(range(n_slots - 1, lanes, -1))
        # monotonic
        self.allocs = 0
        self.alloc_failures = 0

    @property
    def snapshot_slots(self) -> int:
        return self.n_slots - self.lanes - 1

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def snapshots_live(self) -> int:
        return self.snapshot_slots - len(self._free)

    def alloc(self) -> Optional[int]:
        """A free snapshot slot with one reference, or None (the caller
        evicts a snapshot or goes without: never an error)."""
        if not self._free:
            self.alloc_failures += 1
            return None
        slot = self._free.pop()
        self.refcount[slot] = 1
        self.allocs += 1
        return slot

    def retain(self, slot: int) -> None:
        assert self.refcount[slot] > 0, f"retain of unowned state slot {slot}"
        self.refcount[slot] += 1

    def release(self, slot: int) -> None:
        assert self.refcount[slot] > 0, f"double free of state slot {slot}"
        self.refcount[slot] -= 1
        if self.refcount[slot] == 0:
            self._free.append(slot)

    def check_consistency(self) -> List[str]:
        problems = []
        free = set(self._free)
        for slot in range(self.lanes + 1, self.n_slots):
            rc = int(self.refcount[slot])
            if (rc == 0) != (slot in free):
                problems.append(
                    f"state slot {slot}: refcount {rc}, "
                    f"{'' if slot in free else 'not '}free-listed")
        return problems


def default_state_slots(lanes: int) -> int:
    """State slots of an engine with `lanes` decode lanes: a lane's each,
    the trash slot, and three snapshots a lane."""
    return 4 * lanes + 1


def make_state_arrays(cfg: ModelConfig, n_slots: int) -> Dict[str, Any]:
    """The device-side state slots of a model with a recurrent state: one
    float32 array a state leaf, [state layers, n_slots, ...]
    (`cfg.state_shapes`, which asks the kind of state layer the model has)."""
    return {name: jnp.zeros((cfg.state_layers, n_slots) + shape, jnp.float32)
            for name, shape in cfg.state_shapes()}


def make_kv_pool_arrays(
    cfg: ModelConfig, num_pages: int, page_size: int, dtype=None,
    quantize: str = "", state_slots: int = 0,
) -> Tuple[Any, Any]:
    """Allocate the device-side K and V pools.

    Layout is [L, TOTAL_SLOTS, row], the two rows' widths from
    `cfg.kv_row_widths`.  GQA: Hkv*D each — heads and head_dim merged into
    the minor (lane) axis.  This keeps the per-slot row a multiple of 128
    lanes for real model shapes, which the Pallas paged-decode kernel
    requires for its page DMAs (Mosaic slices must be lane-tile aligned); the
    XLA gather path just reshapes gathered rows back to [.., Hkv, D].  Latent
    attention: the k pool's row is the latent c~, the v pool's the roped k_r
    padded to whole lane tiles; the pools differ in width and nothing below
    the model code may assume otherwise.

    quantize="int8" returns each pool as a models.quant.QTensor pytree
    node: int8 slot rows plus a per-(layer, slot) f32 scale ([L, SLOTS, 1]).
    Per-slot symmetric quantization halves the KV window's HBM traffic —
    the growing share of the step at large batch (COVERAGE roofline) — and
    doubles how many context windows a pool holds (runtime/planner.py).
    Writes quantize rows in-graph at the attention layer; reads dequantize
    inside the gather (models/llama.py).  The QTensor shape rides through
    every jitted program as an ordinary pytree, so the engine's fns don't
    change signature.

    A model with a recurrent state (`cfg.has_state`) holds rows for
    `cfg.kv_layers` layers and `state_slots` state slots; its v pool is
    {"v": rows, **state leaves} ("conv" and "ssm" for Mamba layers, "conv"
    alone for short convolutions).
    """
    dtype = dtype or cfg.activation_dtype
    # (a model with a state: only its attention layers with K/V of their own)
    lead = (cfg.kv_layers, num_pages * page_size)
    if quantize == "int8":
        from ..models.quant import QTensor

        def pool(width, lead=lead):
            return QTensor(
                q=jnp.zeros(lead + (width,), jnp.int8),
                s=jnp.zeros(lead + (1,), jnp.float32),
            )
    elif quantize:
        raise ValueError(f"unknown kv quantize mode {quantize!r}")
    else:
        def pool(width, lead=lead):
            return jnp.zeros(lead + (width,), dtype)

    if cfg.by_kind:
        # Two kinds of row under ONE page table: a pool pair per kind of
        # layer, [layers of the kind, SLOTS, row], and the indexer's key
        # rows beside them.  A page id means the same tokens in every
        # layer of every kind, so the allocator, the page tables and the
        # prefix cache never see the difference.
        k, v = {}, {}
        for kind in cfg.kinds:
            widths = cfg.kv_row_widths(kind)
            of_kind = (cfg.layers_of(kind), num_pages * page_size)
            k[kind] = pool(widths[0], of_kind)
            v[kind] = pool(widths[1], of_kind)
            if len(widths) > 2:
                v[INDEX] = pool(widths[2], of_kind)
        return k, v
    k_width, v_width = cfg.kv_row_widths()
    if cfg.has_state:
        # the recurrent state rides in the v pool's pytree: every step
        # program donates, carries and returns it with the rows
        # (models/hybrid.py)
        return pool(k_width), {
            "v": pool(v_width), **make_state_arrays(cfg, state_slots)}
    return pool(k_width), pool(v_width)


def page_table_array(
    seqs: Sequence[Optional[SequencePages]], max_pages: int
) -> np.ndarray:
    """Stack per-slot page lists into a dense [B, max_pages] int32 table.

    Empty slots (None) and unallocated tail entries point at TRASH_PAGE.
    """
    table = np.full((len(seqs), max_pages), TRASH_PAGE, dtype=np.int32)
    for i, s in enumerate(seqs):
        if s is None:
            continue
        if len(s.pages) > max_pages:
            raise ValueError(
                f"sequence {s.seq_id} has {len(s.pages)} pages > table width {max_pages}"
            )
        table[i, : len(s.pages)] = s.pages
    return table
