"""Paged-attention decode kernel.

One decode step attends each sequence's KV window, which lives scattered
across physical pages of the shared pool (runtime/kv_cache.py).  The XLA
reference path materializes the whole [B, C, Hkv, D] window per layer via a
gather — it reads the *configured* window regardless of how long each
sequence actually is, and round-trips the gathered copy through HBM.  This
kernel walks each sequence's page list directly:

* grid = (B,): one program per sequence.  The page table and sequence
  lengths ride in as **scalar-prefetch** arguments so the kernel can
  dereference physical page ids at runtime: its own lane's and, since the
  walk is one pipeline across the call's lanes, its neighbours'.
* the kernel iterates only over the sequence's *valid* pages — a dynamic
  `fori_loop` over softmax steps of STEP_ROWS keys, each step's pages landed
  in VMEM by manually issued async DMAs into a ring of RING buffers, so the
  copies of the next two steps overlap this step's compute: the lane's own
  next steps or, in its last trips, the NEXT lane's first ones (the ring,
  its semaphores and the place in the stream outlive a grid step), so only
  the call's first lane waits for a copy nothing overlaps.  A sequence 300
  tokens into an 8k window reads 300 tokens' worth of KV, not 8k.  A
  windowed call starts its walk at the chunk (`pages_per_chunk` pages) that
  holds the window's first key.
* a whole step whose pages are ONE ascending run of physical pages
  (`page_table[b, base + j] == page_table[b, base] + j`: a prompt reserved
  in one go on a pool that hands out its lowest free page first, such as the
  system prompt every lane attaches) lies side by side in the pool and is
  fetched by ONE copy a pool; every other step by one copy a page.  The page
  table decides, step by step (`pages_one_run`); the rows and the buffer they
  land in are the same either way, so the result is too, bit for bit.  What
  it buys is the scalar core's time: 64 descriptors a step do not overlap
  the softmax step, two all but vanish (the numbers are at STEP_ROWS).
* online softmax (m, l, acc) in VMEM scratch across steps.  GQA is one
  merged-lane matmul over all heads — no repeat_kv materialization.
* only a walk's boundary steps (the last; windowed, also the first) can hold
  a row that is not attended: they alone build masks, zero V and count the
  pages they copy; every other step runs straight through.

(`_verify_kernel` and `_decode_kernel_int8` below keep the older walk: one
chunk a step, two buffers, masks and guards on every chunk.)

Layout contract: the pool stores each slot's row as Hkv*D merged lanes
([TOTAL_SLOTS, Hkv*D]) — Mosaic requires DMA slices to be lane-tile (128)
aligned, so per-head layouts with D=64 cannot be page-DMA'd; the merged row
(512 lanes for 8x64) can.  Mosaic also cannot unfold merged lanes back to
heads in-kernel, so GQA is expressed *algebraically*: the caller expands q
block-diagonally to [Hq, Hkv*D] (zeros outside each query head's own
kv-head lane block), QK^T over merged rows then contracts exactly the right
D lanes per head in one full-width MXU matmul, and the PV product yields
[Hq, Hkv*D] from which the caller slices each row's own kv-head block.

Numerics ground truth: ops.attention.causal_attention (tests compare both
paths on random page layouts), and the kernel keeps its precision: the MXU
operands are the pool's dtype (K and V as they lie in VMEM, q and the
probabilities cast to match, as `einsum(qg, k)` and
`einsum(probs.astype(v.dtype), v)` there), while scores, the softmax state
(running max and denominator) and the accumulator are f32.  An f32 pool
therefore multiplies in f32, a bf16 pool in bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# A softmax step attends STEP_ROWS keys at once and RING steps' buffers are in
# flight.  Measured alone at 32/4 x 128, page 16, contexts ~8.3k (PERF.md
# section 6, PR 30): a step costs ~0.33 us whatever it holds (matmul, max,
# exp, sum, matmul, one dependent chain) plus 0.08 us per 128 keys, and the
# scalar core's DMA starts and waits (0.3 us per 8 pages) do not overlap it:
# one 128-key chunk a step with two buffers ran 0.78 us a chunk against the
# walk's own 0.36.  512 keys a step over three buffers runs 0.43 on a
# scattered step, a copy a page (K and V: 64 descriptors a step, which the
# scalar core issues and which do not overlap the softmax step).  A RUN step
# is one copy a pool.  The WHOLE kernel a chunk, a table whose prompt is one
# run (14 of a lane's ~16 whole steps) beside the parent's per-page walk
# (PERF.md section 6, PR 53, `scripts/paged_decode_bench.py --prefix-run
# --parent`): rows of 512 + 128 lanes (the latent form, 164 KB a chunk)
# 0.431 -> 0.288 us, of 512 + 512 (Yi, LFM2) 0.433 -> 0.374, of 1,024 +
# 1,024 (byte-bound at 92% of the chip's bandwidth) 0.701 -> 0.702.  The run
# test is what a scattered table pays for it: 0.431 -> 0.458 (latent),
# 0.433 -> 0.430 (Yi), 0.701 -> 0.702.
STEP_ROWS = 512
RING = 3
# The bytes the ring's K and V buffers may hold together, and the scoped VMEM
# a call whose ring passes RING_VMEM_DEFAULT is compiled with.  A step
# shrinks, never the row: at Olmo-Hybrid's 30 KV heads x 128 a merged row is
# 3,840 lanes, 3.75 x the widest before it (1,024: a ring of 6.3 MB at 512
# keys a step), and 512 keys a step would be 23.6 MB of ring; 256 are 11.8
# MB, which with the step's temporaries passes the 16 MB a call gets unasked.
RING_VMEM_BYTES = 12 << 20
RING_VMEM_DEFAULT = 8 << 20
RING_VMEM_LIMIT = 40 << 20


def step_rows(row_bytes: int) -> int:
    """Keys a softmax step attends for pool rows of `row_bytes` (0: not
    said, STEP_ROWS): STEP_ROWS halved until the ring fits RING_VMEM_BYTES,
    never under a 128-key chunk."""
    rows = STEP_ROWS
    while rows > 128 and 2 * RING * rows * row_bytes > RING_VMEM_BYTES:
        rows //= 2
    return rows


def _attend(q_ref, kbuf, vbuf, m_ref, l_ref, acc_ref, slot, scale,
            remaining=None, below=None, latent=False):
    """One online-softmax step of _decode_kernel over ring slot `slot`.

    MXU operands are the pool's own rows, with q and the probabilities cast
    to match: what ops.attention.causal_attention does when it multiplies
    `qg, k` and `probs.astype(v.dtype), v`.  Scores, softmax state (m, l)
    and the accumulator are f32.  `remaining`: rows from there on are past
    the context and were never DMA'd; `below` (windowed): rows under it are
    under the window.  Both None: the whole step is attended, and no iota,
    mask or select is built.

    `latent` (MLA, absorbed form): kbuf holds the latent rows c~ [rows, r],
    which are keys AND values, and vbuf the roped key part k_r [rows, lanes];
    q_ref is [1, Hq, r + lanes] = [q^ | q_rope].  Scores are q^ . c~ +
    q_rope . k_r, the weighted sum is over the SAME c~ chunk: no value
    copy exists."""
    rows = kbuf.shape[1]
    dt = jnp.promote_types(q_ref.dtype, kbuf.dtype)
    kc = kbuf[slot].astype(dt)  # [rows, HD]
    vc = kc if latent else vbuf[slot]
    slot_mask = None
    if remaining is not None:
        # local slot index within the step vs remaining valid slots
        local = jax.lax.broadcasted_iota(jnp.int32, (1, rows), dimension=1)
        slot_mask = local < remaining  # [1, rows]
        if below is not None:
            # rows of the first step below the window were DMA'd (real data,
            # so V needs no zeroing) and are masked out of the scores
            slot_mask = slot_mask & (local >= below)
        local_col = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), dimension=0)
        # Zero V's never-DMA'd rows before the PV matmul — a NaN there would
        # poison the accumulator even under zero probability weight
        # (0 * NaN = NaN).  K needs no masking: its scores are overwritten
        # by the NEG_INF mask.  (Selected in f32, as the kernel always has.)
        vc = jnp.where(local_col < remaining, vc.astype(jnp.float32), 0.0)
    vc = vc.astype(dt)
    # Merged-lane compute: q arrives pre-expanded block-diagonally
    # ([Hq, Hkv*D], zeros outside each query head's own kv-head lane block),
    # so QK^T over the full merged row contracts exactly each head's D lanes
    # — one MXU matmul for all heads, no in-kernel reshape (Mosaic cannot
    # unfold merged lanes).
    q = q_ref[0].astype(dt)
    r = kbuf.shape[2]
    s = jax.lax.dot_general(
        q[:, :r] if latent else q, kc,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Hq, rows]
    if latent:
        # (a never-copied row of k_r or c~ may hold anything: its score is
        # overwritten by the mask below, as K's always were)
        s = s + jax.lax.dot_general(
            q[:, r:], vbuf[slot].astype(dt),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    s = s * scale
    if slot_mask is not None:
        s = jnp.where(slot_mask, s, NEG_INF)

    m_prev = m_ref[...]  # [Hq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    if slot_mask is not None:
        pexp = jnp.where(slot_mask, pexp, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    # [Hq, HD]: each row holds every kv head's weighted V; the caller slices
    # out the row's own kv-head lane block.
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp.astype(dt), vc,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new


def _decode_kernel(
    # scalar prefetch
    page_table_ref,  # [B, P] i32
    seq_lens_ref,    # [B] i32
    # inputs
    q_ref,        # [1, Hq, Hkv*D] VMEM block — block-diagonal expanded q
    k_rows_hbm,   # [num_pages * ps, Hkv*D] in HBM/ANY: the pool as it lies,
    v_rows_hbm,   # a page's ps rows side by side, page after page
    out_ref,      # [1, Hq, Hkv*D] VMEM block — caller slices per-head lanes
    # scratch
    kbuf,     # [RING, SP*ps, Hkv*D] pool dtype
    vbuf,     # [RING, SP*ps, Hkv*D]
    ksem,     # DMA sems [RING, SP]: a buffer's page copies all signal its
    vsem,     # first; the rest only space them (see _paged_decode)
    m_ref,    # [Hq, 1] f32 running max
    l_ref,    # [Hq, 1] f32 running denominator
    acc_ref,  # [Hq, Hkv*D] f32 running numerator
    call_ref,  # SMEM [5] i32: where the CALL's pipeline stands (see below)
    *,
    page_size: int,
    pages_per_chunk: int,
    scale: float,
    window: int | None = None,
    latent: bool = False,
):
    # The call's lanes are ONE stream of softmax steps, lane after lane, and
    # the ring runs over the stream: the copies of the step RING - 1 ahead
    # are started before a step is attended, whichever lane that step is in.
    # A lane's last RING - 1 trips so start its neighbour's first steps (the
    # neighbour after that one's, where the neighbour is one step long), and
    # the neighbour's program begins attending at once; only the call's lane
    # 0 fills the ring and only its last lane drains it.  The page table and
    # the lengths of every lane are in SMEM, the ring and its semaphores are
    # scratch and outlive a grid step, and the grid runs in order on one core
    # ("arbitrary").  call_ref carries the stream from program to program:
    # [0] the ring slot of this lane's first step, [1:4] the next step to
    # start (its lane, its first page there, the pages the lane holds from
    # that page on) and [4] its ring slot.  A step's rows, buffer and order
    # of arithmetic are what they were when each lane filled and drained a
    # ring of its own: only WHEN a copy starts differs.
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    ps = page_size
    ring, sp = kbuf.shape[0], kbuf.shape[1] // ps  # buffers, pages a step
    pools = ((k_rows_hbm, kbuf, ksem), (v_rows_hbm, vbuf, vsem))

    def walk(lane):
        """(keys, window's first key, first page, pages, steps) of a lane."""
        # query position is seq_len; it attends positions <= seq_len
        n_valid = seq_lens_ref[lane] + 1
        n_pages = pl.cdiv(n_valid, ps)
        # A windowed layer (static `window`) attends positions >= lo only:
        # the walk starts at the chunk (pages_per_chunk pages) that holds lo,
        # so the call DMAs at most ceil((window + chunk) / chunk) chunks
        # whatever the context (decode_chunk_range is the same arithmetic on
        # plain ints).
        page0, lo = 0, 0
        if window is not None:
            lo = jnp.maximum(n_valid - window, 0)
            page0 = lo // (pages_per_chunk * ps) * pages_per_chunk
        return n_valid, lo, page0, n_pages, pl.cdiv(n_pages - page0, sp)

    def rows(page, n_pages=1):
        """The pool rows of `n_pages` pages that lie side by side from `page`."""
        return pl.ds(pl.multiple_of(page * ps, ps), n_pages * ps)

    def dma(lane, base, left, slot, op, guarded):
        """Start or wait the copies into ring slot `slot` of the step of
        `lane`'s walk that begins at page `base`, `left` pages before the
        lane's end.  `guarded`: the step may end short of sp pages."""
        if op == "wait" and not guarded:
            # A DMA semaphore counts bytes: one wait for the whole buffer
            # stands for its sp page copies, or for the one run copy.
            for _, buf, sem in pools:
                pltpu.make_async_copy(
                    buf.at[slot], buf.at[slot], sem.at[slot, 0]).wait()
            return

        def copy(j, carry=None):  # start one scattered page, K and V
            page = page_table_ref[lane, base + j]
            row = j * ps if isinstance(j, int) else pl.multiple_of(j * ps, ps)
            for hbm, buf, sem in pools:
                pltpu.make_async_copy(
                    hbm.at[rows(page)], buf.at[slot, pl.ds(row, ps)],
                    sem.at[slot, 0]).start()
            return carry

        if guarded:
            # A loop over the pages the step has, not a guard a page: five
            # unrolled call sites of sp pages made `jit.lower` of one kernel
            # take 0.6 s (0.13 before PR 30) and Mellum2's warm boot 31%
            # longer; a walk's last step is one a lane.
            held = jnp.minimum(sp, left)
            if op == "start":
                jax.lax.fori_loop(0, held, copy, 0)
                return
            # The semaphore counts bytes, so the waits need not be a page
            # each: one a set bit of the page count, at most six in place of
            # thirty-two a pool (with the windowed first step's one wait,
            # 0.24 of a 1,024-key lane's 4.74 us; PERF.md section 6, PR 62).
            for bit in reversed(range(sp.bit_length())):
                @pl.when((held & (1 << bit)) != 0)
                def _():
                    for _, buf, sem in pools:
                        part = buf.at[slot, pl.ds(0, ps << bit)]
                        pltpu.make_async_copy(
                            part, part, sem.at[slot, 0]).wait()
            return

        def by_page():
            for j in range(sp):  # unrolled: 0.43 us a chunk, rolled 0.48
                copy(j)

        def as_run():
            # The step's pages lie side by side in the pool: ONE descriptor
            # a pool moves the same rows into the same buffer.
            for hbm, buf, sem in pools:
                pltpu.make_async_copy(
                    hbm.at[rows(page_table_ref[lane, base], sp)],
                    buf.at[slot], sem.at[slot, 0]).start()

        # sp - 1 compares on the scalar core, as one traced compare unrolled
        # where it is lowered (traced sp - 1 times, `jit.lower` of a kernel
        # took 0.06 s longer).  Straight-line code ahead of the branch: every
        # form that decided with less measured a scattered step SLOWER (the
        # run's two ends first in a `cond` of their own; one flag word a step
        # computed from the page table by an XLA op ahead of the call, read
        # here: 0.473 us a chunk against these compares' 0.458 and no test's
        # 0.431 at the latent geometry; PERF.md section 6, PR 53).
        run = pages_one_run(
            lambda i: page_table_ref[lane, i], base, sp,
            functools.partial(jax.lax.fori_loop, unroll=True))
        jax.lax.cond(run, as_run, by_page)

    n_valid, lo, page0, n_pages, n_steps = walk(b)
    last = n_steps - 1

    @pl.when(b == 0)
    def _():
        # nobody ran before lane 0: its own first step is the next to start
        for i, x in enumerate((0, 0, page0, n_pages - page0, 0)):
            call_ref[i] = x

    # ... and its first RING - 1 trips only start copies
    fill = jnp.where(b == 0, ring - 1, 0)

    def after(slot):
        return jnp.where(slot == ring - 1, 0, slot + 1)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(i, carry):
        # A trip starts the stream's next unstarted step, RING - 1 beyond
        # the step it then attends, this lane's k: every start of the call is
        # this one site.  Only a walk's last step can end short.
        lane, base, left, slot, mine = carry
        live = lane < lanes  # the call's last steps have nothing to start
        ends = left <= sp    # the step is its lane's last
        start = functools.partial(dma, lane, base, left, slot, "start")

        @pl.when(live & ~ends)
        def _():
            start(False)

        @pl.when(live & ends)
        def _():
            start(True)

        # the step after it: the lane's next, or the next lane's first
        _, _, its_page0, its_pages, _ = walk(jnp.minimum(lane + 1, lanes - 1))
        ahead = (jnp.where(live & ends, lane + 1, lane),
                 jnp.where(ends, its_page0, base + sp),
                 jnp.where(ends, its_pages - its_page0, left - sp),
                 jnp.where(live, after(slot), slot))

        # A step before the last (and, windowed, at or above lo) holds sp
        # whole pages of attended rows: no guard, no iota, no mask, no select.
        k = i - fill
        at = page0 + k * sp
        row0 = at * ps
        whole = k < last
        if window is not None:
            whole = whole & (row0 >= lo)
        state = (q_ref, kbuf, vbuf, m_ref, l_ref, acc_ref, mine, scale)
        wait = functools.partial(dma, b, at, n_pages - at, mine, "wait")

        def whole_step():
            wait(False)
            _attend(*state, latent=latent)

        def boundary_step():
            if window is None:
                wait(True)
            else:
                # a windowed walk's first step is a boundary step of sp
                # whole pages: one wait, as a whole step's
                jax.lax.cond(k < last, lambda: wait(False), lambda: wait(True))
            _attend(*state, remaining=n_valid - row0,
                    below=None if window is None else lo - row0,
                    latent=latent)

        @pl.when(k >= 0)
        def _():
            jax.lax.cond(whole, whole_step, boundary_step)

        return (*ahead, jnp.where(k >= 0, after(mine), mine))

    carry = jax.lax.fori_loop(
        0, n_steps + fill, body,
        (call_ref[1], call_ref[2], call_ref[3], call_ref[4], call_ref[0]))
    for i, x in enumerate((carry[4], *carry[:4])):
        call_ref[i] = x
    denom = jnp.maximum(l_ref[...], 1e-30)
    out_ref[0, :, :] = (acc_ref[...] / denom).astype(out_ref.dtype)


# _decode_kernel's pipeline runs from one lane's program into the next: the
# grid is one core's, in order.
_LANES_IN_ORDER = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
_LANES_IN_ORDER_WIDE = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=RING_VMEM_LIMIT)


def _fori(lo: int, hi: int, body, carry):
    for j in range(lo, hi):
        carry = body(j, carry)
    return carry


def pages_one_run(page_at, base, n: int, fori=_fori):
    """Whether pages `base` .. `base + n - 1` of a page list are ONE ascending
    run of physical pages, `page_at(base + j) == page_at(base) + j`: their
    rows then lie side by side in the pool.  _decode_kernel's own test of a
    softmax step (page ids read from SMEM, `fori` the device's loop), and on
    plain ints the host's."""
    first = page_at(base)
    return fori(
        1, n, lambda j, run: run & (page_at(base + j) == first + j), True)


def decode_step_runs(pages, seq_len: int, window: int | None, page_size: int,
                     max_pages: int, pages_per_chunk: int = 8,
                     row_bytes: int = 0) -> tuple:
    """(whole, run) softmax steps of _decode_kernel's walk over a lane whose
    page list is `pages` with `seq_len` cached tokens, under a page table
    `max_pages` wide: the steps before the walk's last, each fetched as sp
    whole pages, and those of them fetched as one run copy a pool.  The
    kernel's own arithmetic on plain ints: the tests' and the bench script's
    oracle (the engine counts a global walk's steps a lane at a time,
    StepPrograms.decode_steps, from SequencePages.run_steps)."""
    cp, sp = step_pages(max_pages, pages_per_chunk, page_size, row_bytes)
    first, end = decode_chunk_range(seq_len, window, page_size, cp)
    page0 = first * cp
    n_pages = -(-(seq_len + 1) // page_size)
    whole = -(-(n_pages - page0) // sp) - 1
    return whole, sum(
        pages_one_run(pages.__getitem__, page0 + k * sp, sp)
        for k in range(whole))


def decode_chunk_range(seq_len: int, window: int | None, page_size: int,
                       pages_per_chunk: int = 8) -> tuple:
    """(first, end) of the KV chunks _decode_kernel DMAs for a lane with
    `seq_len` cached tokens: the kernel's own arithmetic on plain ints, for
    tests and for the benchmark's byte count.  A global call reads every
    chunk up to the query; a windowed call at most
    ceil((window + chunk) / chunk) of them."""
    chunk = page_size * pages_per_chunk
    n_valid = seq_len + 1
    n_chunks = -(-(-(-n_valid // page_size)) // pages_per_chunk)
    first = 0 if window is None else max(n_valid - window, 0) // chunk
    return first, n_chunks


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_chunk", "scale", "interpret",
                     "diff"),
)
def paged_decode_attention(
    q: jnp.ndarray,            # [B, Hq, D] — one query token per sequence
    k_pool: jnp.ndarray,       # [TOTAL_SLOTS, Hkv*D] merged-lane pool
    v_pool: jnp.ndarray,       # [TOTAL_SLOTS, Hkv*D]
    page_table: jnp.ndarray,   # [B, P] i32 physical page ids
    seq_lens: jnp.ndarray,     # [B] i32 tokens already cached (query pos)
    *,
    page_size: int,
    pages_per_chunk: int = 8,
    scale: float | None = None,
    interpret: bool = False,
    diff: bool = False,
) -> jnp.ndarray:
    """Decode-step attention straight off the paged KV pool.

    Returns [B, Hq, D] in q.dtype.  Inactive batch lanes (whose table rows
    point at the trash page) produce garbage rows that the engine discards —
    same contract as the XLA gather path.  `diff` (differential attention,
    `diff_heads`): query head n scores key head 2 * (n // (2 G)) + n % 2 and
    the result is [B, Hq, 2 D], the weighted sum over BOTH value heads of
    that pair: the same kernel, another placement of q on the merged row and
    another slice of its output.
    """
    return _paged_decode(q, k_pool, v_pool, page_table, seq_lens,
                         page_size, pages_per_chunk, scale, interpret, None,
                         diff)


@functools.partial(
    jax.jit,
    static_argnames=("window", "page_size", "pages_per_chunk", "scale",
                     "interpret", "diff"),
)
def paged_decode_attention_window(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    window: int,
    page_size: int,
    pages_per_chunk: int = 8,
    scale: float | None = None,
    interpret: bool = False,
    diff: bool = False,
) -> jnp.ndarray:
    """paged_decode_attention for a sliding-window layer: the query at
    position seq_len attends seq_len - window < kv_pos <= seq_len, and the
    kernel starts its chunk loop at the window's first chunk.  A jitted
    function and a kernel name of its own (`paged_decode_attention_window`):
    a device trace tells the windowed calls from the global ones, and both
    still match `paged_decode`."""
    return _paged_decode(q, k_pool, v_pool, page_table, seq_lens,
                         page_size, pages_per_chunk, scale, interpret, window,
                         diff)


def step_pages(P: int, pages_per_chunk: int, page_size: int,
               row_bytes: int = 0) -> tuple:
    """(pages a DMA chunk, pages a softmax step) of _decode_kernel's walk for
    a page table of width P over pool rows of `row_bytes`: whole chunks a
    step, `step_rows` keys if the table names that many."""
    cp = min(pages_per_chunk, P)
    return cp, cp * max(1, min(step_rows(row_bytes) // (cp * page_size),
                               -(-P // cp)))


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_chunk", "scale", "interpret",
                     "window"),
)
def paged_decode_attention_latent(
    q_lat: jnp.ndarray,        # [B, Hq, r]  absorbed query q^ = q_nope W_kvb^K
    q_rope: jnp.ndarray,       # [B, Hq, dr] roped query part
    c_pool: jnp.ndarray,       # [TOTAL_SLOTS, r] normed latent rows c~
    r_pool: jnp.ndarray,       # [TOTAL_SLOTS, lanes >= dr] roped k_r, padded
    page_table: jnp.ndarray,   # [B, P] i32 physical page ids
    seq_lens: jnp.ndarray,     # [B] i32 tokens already cached (query pos)
    *,
    scale: float,              # 1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)
    page_size: int,
    pages_per_chunk: int = 8,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """Decode-step latent (MLA) attention in the absorbed form, straight off
    the paged pools: per lane Hq query rows against ONE row a token whose r
    latent lanes are keys and values both, plus the k_r lanes for the rotary
    part of the score.  _decode_kernel's walk (STEP_ROWS keys a softmax
    step, RING buffers, masks on boundary steps only); the only value read
    is the latent chunk the scores already hold.  Returns o^ [B, Hq, r] in
    q_lat.dtype: the caller applies W_kvb^V.  A kernel name of its own
    (`paged_decode_attention_latent`), so a device trace tells it from the
    GQA calls.  `window` (static): a sliding-window latent layer, the query
    at seq_len attends seq_len - window < kv_pos <= seq_len and the walk
    starts at the chunk that holds the window's first key, as
    `paged_decode_attention_window`'s does; that call is named
    `paged_decode_attention_latent_window`."""
    B, Hq, r = q_lat.shape
    lanes = r_pool.shape[1]
    P = page_table.shape[1]
    cp, sp = step_pages(P, pages_per_chunk, page_size)
    q = jnp.concatenate(
        [q_lat, jnp.pad(q_rope, ((0, 0), (0, 0), (0, lanes - q_rope.shape[-1])))],
        axis=-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, r + lanes), lambda b, pt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, r), lambda b, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((RING, sp * page_size, r), c_pool.dtype),
            pltpu.VMEM((RING, sp * page_size, lanes), r_pool.dtype),
            pltpu.SemaphoreType.DMA((RING, sp)),
            pltpu.SemaphoreType.DMA((RING, sp)),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, r), jnp.float32),
            pltpu.SMEM((5,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, page_size=page_size, pages_per_chunk=cp,
        scale=scale, latent=True, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, r), q_lat.dtype),
        interpret=interpret,
        name=("paged_decode_attention_latent" if window is None
              else "paged_decode_attention_latent_window"),
        compiler_params=_LANES_IN_ORDER,
    )(page_table, seq_lens, q, c_pool, r_pool)


def diff_heads(Hq: int, Hkv: int):
    """Differential attention's pairing on the merged row.  Heads come in
    pairs (2j, 2j + 1) of queries and (2g, 2g + 1) of keys and values; query
    pair j reads key-value pair g = j // G, its first head the pair's first
    key head, its second the second, and BOTH heads' values.  Returns
    (key head of each query head [Hq], value PAIR of each query head [Hq])."""
    n = jnp.arange(Hq)
    pair = n // (2 * (Hq // Hkv))
    return 2 * pair + n % 2, pair


def _paged_decode(q, k_pool, v_pool, page_table, seq_lens, page_size,
                  pages_per_chunk, scale, interpret, window, diff=False):
    B, Hq, D = q.shape
    HD = k_pool.shape[1]
    Hkv = HD // D
    G = Hq // Hkv
    P = page_table.shape[1]
    if scale is None:
        scale = D**-0.5
    row_bytes = HD * k_pool.dtype.itemsize
    cp, sp = step_pages(P, pages_per_chunk, page_size, row_bytes)
    # (a ring past what a call gets unasked is compiled with room for it)
    wide = 2 * RING * sp * page_size * row_bytes > RING_VMEM_DEFAULT

    # Block-diagonal query expansion (see module docstring): qx[b, qh] has
    # q[b, qh] in its own kv head's D-lane block and zeros elsewhere.
    if diff:
        kv_of_q, pair_of_q = diff_heads(Hq, Hkv)
    else:
        kv_of_q = jnp.repeat(jnp.arange(Hkv), G)  # [Hq]
    qx = jnp.zeros((B, Hq, Hkv, D), q.dtype)
    qx = qx.at[:, jnp.arange(Hq), kv_of_q].set(q)
    qx = qx.reshape(B, Hq, HD)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, HD), lambda b, pt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, HD), lambda b, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((RING, sp * page_size, HD), k_pool.dtype),
            pltpu.VMEM((RING, sp * page_size, HD), v_pool.dtype),
            # One semaphore a buffer is all the kernel uses.  Allocated sp
            # apart (the per-page layout the two-buffer kernel had) the
            # global call measured 0.429 us a chunk, packed [RING] 0.455
            # (PERF.md section 6, PR 30, my chip run 8).
            pltpu.SemaphoreType.DMA((RING, sp)),
            pltpu.SemaphoreType.DMA((RING, sp)),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, HD), jnp.float32),
            pltpu.SMEM((5,), jnp.int32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        pages_per_chunk=cp,
        scale=scale,
        window=window,
    )
    out_wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, HD), q.dtype),
        interpret=interpret,
        name=None if window is None else "paged_decode_attention_window",
        compiler_params=_LANES_IN_ORDER_WIDE if wide else _LANES_IN_ORDER,
    )(page_table, seq_lens, qx, k_pool, v_pool)
    if diff:
        # each query row's result over BOTH value heads of its pair
        return out_wide.reshape(
            B, Hq, Hkv // 2, 2 * D)[:, jnp.arange(Hq), pair_of_q]
    # each query row's result lives in its own kv head's lane block
    return out_wide.reshape(B, Hq, Hkv, D)[:, jnp.arange(Hq), kv_of_q]


def _verify_kernel(
    # scalar prefetch
    page_table_ref,  # [B, P] i32
    seq_lens_ref,    # [B] i32 tokens cached BEFORE this step
    q_lens_ref,      # [B] i32 valid queries this step (cand_len + 1)
    # inputs
    q_ref,        # [1, S*Hq, Hkv*D] VMEM — block-diagonal expanded q
    k_pages_hbm,  # [num_pages, ps, Hkv*D]
    v_pages_hbm,  # [num_pages, ps, Hkv*D]
    out_ref,      # [1, S*Hq, Hkv*D] VMEM
    # scratch
    kbuf, vbuf, ksem, vsem, m_ref, l_ref, acc_ref,
    *,
    page_size: int,
    pages_per_chunk: int,
    n_queries: int,  # S = speculative_k + 1 (static)
    heads: int,      # Hq (static)
    scale: float,
):
    """Speculative-verify attention: S = K+1 query tokens per sequence in
    one kernel launch (the decode kernel generalized from one query row
    group to S of them).  Query j sits at position seq_len + j and
    attends positions <= seq_len + j — per-ROW causal masking over the
    merged-lane score matrix (rows are (query, head) pairs, S-major), on
    top of the same double-buffered per-page DMA walk the decode kernel
    does.  One weight... one KV-stream serves all S queries — exactly the
    amortization speculative decoding exists for."""
    b = pl.program_id(0)
    ps, cp = page_size, pages_per_chunk
    chunk = cp * ps
    rows = n_queries * heads
    # valid KV = previously cached tokens + this step's q_len fresh writes
    n_valid = seq_lens_ref[b] + q_lens_ref[b]
    n_pages = pl.cdiv(n_valid, ps)
    n_chunks = pl.cdiv(n_pages, cp)

    def issue(c, slot):
        for j in range(cp):
            @pl.when(c * cp + j < n_pages)
            def _():
                page = page_table_ref[b, c * cp + j]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page],
                    kbuf.at[slot, pl.ds(j * ps, ps)],
                    ksem.at[slot, j],
                ).start()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page],
                    vbuf.at[slot, pl.ds(j * ps, ps)],
                    vsem.at[slot, j],
                ).start()

    def wait(c, slot):
        for j in range(cp):
            @pl.when(c * cp + j < n_pages)
            def _():
                page = page_table_ref[b, c * cp + j]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page],
                    kbuf.at[slot, pl.ds(j * ps, ps)],
                    ksem.at[slot, j],
                ).wait()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page],
                    vbuf.at[slot, pl.ds(j * ps, ps)],
                    vsem.at[slot, j],
                ).wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    issue(0, 0)

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            issue(c + 1, jax.lax.rem(c + 1, 2))

        wait(c, slot)

        remaining = n_valid - c * chunk
        # per-(query, head)-row causal mask: row r is query r // heads at
        # position seq_len + r // heads; column g is global slot
        # c*chunk + local — allow g <= qpos AND g < n_valid (garbage
        # queries past q_len are clamped to the valid window so stale
        # never-DMA'd rows cannot leak in; their outputs are discarded)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 0)
        qpos = seq_lens_ref[b] + row // heads
        g = c * chunk + col
        allow = (g <= qpos) & (col < remaining)  # [rows, chunk]
        local_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        col_mask = local_col < remaining  # [chunk, 1] — zero garbage V

        kc = kbuf[slot].astype(jnp.float32)  # [chunk, HD]
        vc = jnp.where(col_mask, vbuf[slot].astype(jnp.float32), 0.0)
        qx = q_ref[0].astype(jnp.float32)  # [rows, HD] block-diagonal
        s = (
            jax.lax.dot_general(
                qx, kc,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [rows, chunk]
        s = jnp.where(allow, s, NEG_INF)

        m_prev = m_ref[...]  # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(allow, pexp, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, vc,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)
    denom = jnp.maximum(l_ref[...], 1e-30)
    out_ref[0, :, :] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_chunk", "scale", "interpret"),
)
def paged_verify_attention(
    q: jnp.ndarray,            # [B, S, Hq, D] — K+1 query tokens per seq
    k_pool: jnp.ndarray,       # [TOTAL_SLOTS, Hkv*D] merged-lane pool
    v_pool: jnp.ndarray,       # [TOTAL_SLOTS, Hkv*D]
    page_table: jnp.ndarray,   # [B, P] i32
    seq_lens: jnp.ndarray,     # [B] i32 tokens cached before the step
    q_lens: jnp.ndarray,       # [B] i32 valid queries (cand_len + 1)
    *,
    page_size: int,
    pages_per_chunk: int = 8,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Speculative-verify attention off the paged pool: [B, S, Hq, D] in
    q.dtype, each query row causally masked to its own position.  The
    engine's verify step has already written the S input tokens' KV, so
    the kernel walks seq_len + q_len valid slots per sequence.  Rows for
    queries past q_len produce garbage the caller discards — same
    contract as inactive lanes in the decode kernel."""
    B, S, Hq, D = q.shape
    HD = k_pool.shape[1]
    Hkv = HD // D
    G = Hq // Hkv
    P = page_table.shape[1]
    if scale is None:
        scale = D**-0.5
    cp = min(pages_per_chunk, P)
    k_pages = k_pool.reshape(-1, page_size, HD)
    v_pages = v_pool.reshape(-1, page_size, HD)

    # block-diagonal query expansion, per query token (see module
    # docstring): row (s, qh) holds q[b, s, qh] in its own kv head's
    # D-lane block
    kv_of_q = jnp.repeat(jnp.arange(Hkv), G)  # [Hq]
    qx = jnp.zeros((B, S, Hq, Hkv, D), q.dtype)
    qx = qx.at[:, :, jnp.arange(Hq), kv_of_q].set(q)
    qx = qx.reshape(B, S * Hq, HD)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S * Hq, HD), lambda b, pt, sl, ql: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, S * Hq, HD),
                               lambda b, pt, sl, ql: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, cp * page_size, HD), k_pool.dtype),
            pltpu.VMEM((2, cp * page_size, HD), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, cp)),
            pltpu.SemaphoreType.DMA((2, cp)),
            pltpu.VMEM((S * Hq, 1), jnp.float32),
            pltpu.VMEM((S * Hq, 1), jnp.float32),
            pltpu.VMEM((S * Hq, HD), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _verify_kernel,
        page_size=page_size,
        pages_per_chunk=cp,
        n_queries=S,
        heads=Hq,
        scale=scale,
    )
    out_wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S * Hq, HD), q.dtype),
        interpret=interpret,
    )(page_table, seq_lens, q_lens, qx, k_pages, v_pages)
    return out_wide.reshape(B, S, Hq, Hkv, D)[
        :, :, jnp.arange(Hq), kv_of_q
    ]


def paged_verify_attention_sharded(
    mesh,
    q: jnp.ndarray,            # [B, S, Hq, D]
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    q_lens: jnp.ndarray,
    *,
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """The verify kernel on a tp(/tq) mesh — same per-shard head-split
    contract as paged_decode_attention_sharded (caller must have passed
    pallas_mesh_ok)."""
    from jax.sharding import PartitionSpec as P

    q_ax = ("tp", "tq") if mesh.shape.get("tq", 1) > 1 else "tp"
    fn = shard_map(
        functools.partial(
            paged_verify_attention, page_size=page_size,
            interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(P(None, None, q_ax, None), P(None, "tp"), P(None, "tp"),
                  P(None, None), P(None), P(None)),
        out_specs=P(None, None, q_ax, None),
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, page_table, seq_lens, q_lens)


def pallas_mesh_ok(mesh, num_heads: int, num_kv_heads: int) -> bool:
    """Can the decode kernel run per-shard on this mesh via shard_map?

    GSPMD cannot partition a Pallas custom call, but shard_map runs it
    per device on local shards.  The head split must line up with
    parallel/sharding.py's layout:

    * only the tensor axes may be >1 (dp/sp/pp/ep shard things the
      kernel's per-shard view cannot express);
    * kv heads split over "tp" (tp | Hkv), q heads over ("tp","tq");
    * per-shard GQA must keep the kernel's contiguous q->kv map: any
      local kv-head count works when tq == 1 (plain Megatron split), but
      a grouped mesh (tq > 1) needs exactly ONE kv head per shard — the
      same invariant ring_attention's _prefill_sharded enforces.
    """
    if mesh is None or mesh.size == 1:
        return True
    tp = mesh.shape.get("tp", 1)
    tq = mesh.shape.get("tq", 1)
    if tp * tq != mesh.size or tp <= 1:
        return False
    if num_kv_heads % tp or num_heads % (tp * tq):
        return False
    if (num_heads // num_kv_heads) % tq:
        return False
    return tq == 1 or num_kv_heads // tp == 1


def paged_decode_attention_sharded(
    mesh,
    q: jnp.ndarray,            # [B, Hq, D]
    k_pool: jnp.ndarray,       # [TOTAL_SLOTS, Hkv*D]
    v_pool: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, P]
    seq_lens: jnp.ndarray,     # [B]
    *,
    page_size: int,
    interpret: bool = False,
    window: int | None = None,
) -> jnp.ndarray:
    """The decode kernel on a tp(/tq) mesh: one kernel per device over its
    local head shard, zero collectives (heads are embarrassingly parallel
    in attention; the surrounding wo einsum pays the existing psum).

    q heads ride ("tp","tq") and the pool's merged kv axis rides "tp",
    matching the engine's placement (parallel/sharding.py), so shard_map
    introduces no resharding.  check_vma is off: pallas_call's out_shape
    carries no varying-axes metadata.  Caller must have passed
    pallas_mesh_ok.
    """
    from jax.sharding import PartitionSpec as P

    q_ax = ("tp", "tq") if mesh.shape.get("tq", 1) > 1 else "tp"
    kernel = functools.partial(
        paged_decode_attention, page_size=page_size, interpret=interpret
    ) if window is None else functools.partial(
        paged_decode_attention_window, window=window, page_size=page_size,
        interpret=interpret,
    )
    fn = shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(None, q_ax, None), P(None, "tp"), P(None, "tp"),
                  P(None, None), P(None)),
        out_specs=P(None, q_ax, None),
        check_vma=False,
    )
    return fn(q, k_pool, v_pool, page_table, seq_lens)


def _decode_kernel_int8(
    # scalar prefetch
    page_table_ref,  # [B, P] i32
    seq_lens_ref,    # [B] i32
    # inputs
    q_ref,        # [1, Hq, Hkv*D] VMEM — block-diagonal expanded q
    ksw_ref,      # [1, NC, chunk] f32 — k per-slot scales, chunk-major
    vsw_ref,      # [1, NC, chunk] f32 — v per-slot scales
    k_pages_hbm,  # [num_pages, ps, Hkv*D] int8 in HBM/ANY
    v_pages_hbm,  # [num_pages, ps, Hkv*D] int8
    out_ref,      # [1, Hq, Hkv*D] VMEM
    # scratch
    kbuf,     # [2, CP*ps, Hkv*D] int8
    vbuf,     # [2, CP*ps, Hkv*D] int8
    ksem,
    vsem,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size: int,
    pages_per_chunk: int,
    scale: float,
):
    """Int8-KV variant of _decode_kernel: pages DMA as int8 (HALF the HBM
    traffic of the bf16 kernel — the whole point), and the per-slot
    dequant scales fold into the math instead of materializing dequantized
    K/V: score[h,j] = (qx . k_q^T)[h,j] * s_k[j] and the PV product uses
    pexp * s_v — exactly runtime/kv_cache.py's `q * s` dequant, fused.
    The scales arrive pre-gathered in LOGICAL window order (chunk-major
    [NC, chunk] so chunk c is one static-shape sublane row — Mosaic-safe
    dynamic indexing, no in-kernel reshape across tiles)."""
    b = pl.program_id(0)
    ps, cp = page_size, pages_per_chunk
    chunk = cp * ps
    n_valid = seq_lens_ref[b] + 1
    n_pages = pl.cdiv(n_valid, ps)
    n_chunks = pl.cdiv(n_pages, cp)

    def issue(c, slot):
        for j in range(cp):
            @pl.when(c * cp + j < n_pages)
            def _():
                page = page_table_ref[b, c * cp + j]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page],
                    kbuf.at[slot, pl.ds(j * ps, ps)],
                    ksem.at[slot, j],
                ).start()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page],
                    vbuf.at[slot, pl.ds(j * ps, ps)],
                    vsem.at[slot, j],
                ).start()

    def wait(c, slot):
        for j in range(cp):
            @pl.when(c * cp + j < n_pages)
            def _():
                page = page_table_ref[b, c * cp + j]
                pltpu.make_async_copy(
                    k_pages_hbm.at[page],
                    kbuf.at[slot, pl.ds(j * ps, ps)],
                    ksem.at[slot, j],
                ).wait()
                pltpu.make_async_copy(
                    v_pages_hbm.at[page],
                    vbuf.at[slot, pl.ds(j * ps, ps)],
                    vsem.at[slot, j],
                ).wait()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    issue(0, 0)

    def body(c, carry):
        slot = jax.lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            issue(c + 1, jax.lax.rem(c + 1, 2))

        wait(c, slot)

        remaining = n_valid - c * chunk
        local = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), dimension=1)
        slot_mask = local < remaining
        local_col = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), dimension=0)
        col_mask = local_col < remaining

        ksw = ksw_ref[0, c, :][None, :]  # [1, chunk] f32
        vsw = vsw_ref[0, c, :][None, :]
        kc = kbuf[slot].astype(jnp.float32)  # int8 -> f32
        # never-DMA'd rows hold stale int8 garbage, but int8 cannot be
        # NaN/inf: K garbage is masked to NEG_INF scores, V garbage is
        # zeroed like the dense kernel
        vc = jnp.where(col_mask, vbuf[slot].astype(jnp.float32), 0.0)
        qx = q_ref[0].astype(jnp.float32)
        s = (
            jax.lax.dot_general(
                qx, kc,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        ) * ksw  # fused per-slot k dequant
        s = jnp.where(slot_mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)
        pexp = jnp.where(slot_mask, pexp, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp * vsw, vc,  # fused per-slot v dequant
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_chunks, body, 0)
    denom = jnp.maximum(l_ref[...], 1e-30)
    out_ref[0, :, :] = (acc_ref[...] / denom).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "pages_per_chunk", "scale", "interpret"),
)
def paged_decode_attention_int8(
    q: jnp.ndarray,            # [B, Hq, D]
    k_q: jnp.ndarray,          # [TOTAL_SLOTS, Hkv*D] int8 rows
    k_s: jnp.ndarray,          # [TOTAL_SLOTS, 1] f32 per-slot scales
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    page_table: jnp.ndarray,   # [B, P]
    seq_lens: jnp.ndarray,     # [B]
    *,
    page_size: int,
    pages_per_chunk: int = 8,
    scale: float | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention straight off the int8-quantized paged pool
    (runtime/kv_cache.py kv_quantize="int8": QTensor rows + per-slot
    scales).  The kernel streams HALF the KV bytes of the bf16 kernel;
    the scales ride as an XLA page-granular pre-gather (4 B/slot — noise
    next to the row bytes) shaped chunk-major for Mosaic-safe indexing.
    Same contract as paged_decode_attention otherwise."""
    B, Hq, D = q.shape
    HD = k_q.shape[1]
    Hkv = HD // D
    G = Hq // Hkv
    P = page_table.shape[1]
    if scale is None:
        scale = D**-0.5
    cp = min(pages_per_chunk, P)
    nc = -(-P // cp)  # chunks per window
    k_pages = k_q.reshape(-1, page_size, HD)
    v_pages = v_q.reshape(-1, page_size, HD)

    def window_scales(s):
        # [SLOTS, 1] -> [B, NC, chunk] in logical window order: page-
        # granular gather (16x fewer descriptors than per-slot), pages
        # padded up to nc*cp so every chunk row is full width
        sp = s.reshape(-1, page_size)[page_table]      # [B, P, ps]
        pad = nc * cp - P
        if pad:
            sp = jnp.pad(sp, ((0, 0), (0, pad), (0, 0)))
        return sp.reshape(B, nc, cp * page_size).astype(jnp.float32)

    ksw = window_scales(k_s)
    vsw = window_scales(v_s)

    kv_of_q = jnp.repeat(jnp.arange(Hkv), G)
    qx = jnp.zeros((B, Hq, Hkv, D), q.dtype)
    qx = qx.at[:, jnp.arange(Hq), kv_of_q].set(q)
    qx = qx.reshape(B, Hq, HD)

    chunk = cp * page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, HD), lambda b, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, nc, chunk), lambda b, pt, sl: (b, 0, 0)),
            pl.BlockSpec((1, nc, chunk), lambda b, pt, sl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, HD), lambda b, pt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, HD), k_q.dtype),
            pltpu.VMEM((2, chunk, HD), v_q.dtype),
            pltpu.SemaphoreType.DMA((2, cp)),
            pltpu.SemaphoreType.DMA((2, cp)),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, HD), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel_int8,
        page_size=page_size,
        pages_per_chunk=cp,
        scale=scale,
    )
    out_wide = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, HD), q.dtype),
        interpret=interpret,
    )(page_table, seq_lens, qx, ksw, vsw, k_pages, v_pages)
    return out_wide.reshape(B, Hq, Hkv, D)[:, jnp.arange(Hq), kv_of_q]


def paged_decode_attention_int8_sharded(
    mesh,
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_s: jnp.ndarray,
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    page_table: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Int8 kernel on a tp(/tq) mesh — same layout contract as
    paged_decode_attention_sharded; the per-slot scales are head-agnostic
    ([SLOTS, 1]) and ride replicated."""
    from jax.sharding import PartitionSpec as P

    q_ax = ("tp", "tq") if mesh.shape.get("tq", 1) > 1 else "tp"
    fn = shard_map(
        functools.partial(
            paged_decode_attention_int8,
            page_size=page_size, interpret=interpret,
        ),
        mesh=mesh,
        in_specs=(P(None, q_ax, None),
                  P(None, "tp"), P(None, None),
                  P(None, "tp"), P(None, None),
                  P(None, None), P(None)),
        out_specs=P(None, q_ax, None),
        check_vma=False,
    )
    return fn(q, k_q, k_s, v_q, v_s, page_table, seq_lens)
