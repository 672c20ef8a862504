"""What the kernels that update a recurrent state IN PLACE in its slot share
(ops/pallas/gated_delta.py, ops/pallas/ssd.py): how a pass's StatePlan
(models/hybrid.py) becomes the scalar-prefetch arguments of a chunk kernel,
and the DMAs that bring a lane's state from its `src` slot into VMEM scratch
and send it to `dst` and `snap`, through a leaf [layers, n_slots, rows, cols]
that is aliased in and out of the call: HBM sees three copies of a lane's
block a launch and no copy of the leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def kernel_form(kernel: bool, cached: bool, S: int, own_slots: bool, chunk,
                tiles: bool) -> str:
    """Which form a pass of S rows a lane takes of a state's recurrence,
    "kernel" or "xla": the one rule of `gated_delta`, `gdn` and `ssd`, from
    what is known when the program is traced.  `kernel`: the Pallas backend;
    `cached`: there is a leaf to update; `own_slots`: a launch that names its
    lanes' slots (a prefill: one row of it is not decode's step); `chunk`:
    the rows of a chunk the module's chunk kernel takes of S > 1 rows (None:
    it does not tile them); `tiles`: the geometry is one the CHIP's compiler
    takes (the interpreter takes any)."""
    if (not kernel or not cached or (S > 1 and chunk is None)
            or (S == 1 and own_slots)
            or (jax.default_backend() == "tpu" and not tiles)):
        return "xla"
    return "kernel"


def chunk_slots(plan, B: int):
    """(src, dst, snap, flag), [B] int32 each, of a prefill pass: the slot a
    lane's state comes from and the two it goes to (decode's plan, all None:
    lane i is slot i), and flag: 1 = the lane has real rows, 2 = it starts
    from zeros."""
    lanes = jnp.arange(B, dtype=jnp.int32)
    src = lanes if plan.src is None else plan.src
    dst = lanes if plan.dst is None else plan.dst
    snap = dst if plan.snap is None else plan.snap
    flag = (plan.lens > 0).astype(jnp.int32)
    if plan.fresh is not None:
        flag = flag + 2 * plan.fresh.astype(jnp.int32)
    return src, dst, snap, flag


def load_state(leaf_in, layer, slot_ref, lane, rows, scratch, sem, flag):
    """Inside a kernel: `scratch` <- zeros where `flag` says fresh, else rows
    `rows` of slot `slot_ref[lane]` of layer `layer` of the leaf, by one DMA
    (the slot id is read where it is used)."""
    @pl.when((flag & 2) == 2)
    def _():
        scratch[...] = jnp.zeros_like(scratch)

    @pl.when((flag & 2) == 0)
    def _():
        cp = pltpu.make_async_copy(
            leaf_in.at[layer, slot_ref[lane], rows], scratch, sem.at[0])
        cp.start()
        cp.wait()


def store_state(leaf_out, layer, slot_refs, lane, rows, scratch, sem):
    """Inside a kernel: `scratch` -> rows `rows` of slot `ref[lane]` of layer
    `layer` for every ref of `slot_refs`, the DMAs in flight together."""
    out = [pltpu.make_async_copy(
        scratch, leaf_out.at[layer, ref[lane], rows], sem.at[i])
        for i, ref in enumerate(slot_refs)]
    for cp in out:
        cp.start()
    for cp in out:
        cp.wait()
