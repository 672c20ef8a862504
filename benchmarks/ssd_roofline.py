"""Bytes and matmul flops the two SSD kernels (`kafka_tpu/ops/pallas/ssd.py`,
Mamba-2's recurrence with the state updated in place in its slot) must move
and do for ONE call, from the shapes of the call's own operands (roofline.py
is a yardstick file that a `model_config` PR does not edit): the same work
whatever implements it.

The shapes are read from the call's HLO text, the event's name on the
device's op line (`kernel_calls.shapes(text, "operands")`), in the order the
kernels take their operands: the scalar-prefetch vectors first, then dt x
[lanes, rows, heads x P], B and C [lanes, rows, groups x N], the log-decay
laid out a grid step's heads together [lanes, steps, rows, heads a step], the
state leaf [layers, n_slots, heads x P, N] last.

`ssd_step` (decode, one row a lane): every lane's state is read and written
once, 2 x heads x P x N x 4 B, beside its rows in and one out.  Its
arithmetic is five operations a state value on the VPU: the bound is
bandwidth.

`ssd_chunk` (prefill, `rows` a lane in chunks of 128): the float32 row
operands in and the output out, and a lane's state once in and twice out (its
slot and the snapshot's).  The state never leaves VMEM between a lane's
chunks: that is the kernel's point, and why its traffic is not chunks x
state.  The matmuls a chunk of C rows cannot avoid, as multiply-adds x 2: C
B^T once a GROUP (C x C x N), and a head (L * C B^T) X (C x C x P), (C exp G)
S_0 (C x N x P) and the state's update (C x P x N).  The exponentials (C x C a
head) are stated nowhere in the share: what is counted is the algorithm's
need, at the bf16 peak although the kernel multiplies in float32, so the
share errs low.  A lane without real rows is skipped by the kernel and
counted here all the same (the shapes do not say which lanes were active):
batched prefill launches with idle lanes read a little high, never over what
a full launch reads.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

CHUNK = 128  # rows of one chunk (ssd.CHUNK)


def _sizes(dims: Sequence[Tuple[int, ...]]):
    """(lanes, rows, heads, P, groups, N) of a call, or None where the
    operands are not the kernels'."""
    rows = [d for d in dims if len(d) == 3]
    four = [d for d in dims if len(d) == 4]
    if len(rows) < 3 or len(four) < 2:
        return None
    (lanes, n, wide), grouped = rows[0], rows[1][2]
    decay, leaf = four[0], four[-1]
    heads, N = decay[1] * decay[3], leaf[3]
    if leaf[2] != wide or wide % heads or grouped % N:
        return None
    return lanes, n, heads, wide // heads, grouped // N, N


def step_call(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one `ssd_step` call whose operands have the shapes
    `dims`; None where they are not the kernel's."""
    sizes = _sizes(dims)
    if sizes is None:
        return None
    lanes, _, heads, P, groups, N = sizes
    state = heads * P * N
    rows = 2 * heads * P + 2 * groups * N + heads  # dt x, y; B, C; the decay
    return 5.0 * lanes * state, 4.0 * lanes * (2 * state + rows)


def chunk_call(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one `ssd_chunk` call whose operands have the shapes
    `dims`; None where they are not the kernel's."""
    sizes = _sizes(dims)
    if sizes is None:
        return None
    lanes, n, heads, P, groups, N = sizes
    c = min(CHUNK, n)
    per_chunk = 2.0 * (groups * c * c * N
                       + heads * (c * c * P + 2 * c * P * N))
    flops = lanes * (n // c) * per_chunk
    nbytes = 4.0 * lanes * (n * (2 * heads * P + 2 * groups * N + heads)
                            + 3 * heads * P * N)
    return flops, nbytes
