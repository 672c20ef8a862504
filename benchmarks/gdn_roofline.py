"""Bytes and matmul flops the two Gated DeltaNet kernels
(`kafka_tpu/ops/pallas/gdn.py`) must move and do for ONE call, from the shapes
of the call's own operands (roofline.py is a yardstick file that a
`model_config` PR does not edit; `delta_roofline.py` counts another operand
order, a decay [.., heads x d_k] and a leaf laid [heads x d_v, d_k]).

The shapes are read from the call's HLO text, the event's name on the
device's op line (`kernel_calls.shapes(text, "operands")`), in the order the
kernels take their operands: the scalar-prefetch vectors first, then the
float32 row operands (q and k a head to whole 128-lane tiles, then the rows
over heads x d_v), the state leaf [layers, n_slots, d_k, heads x d_v] LAST.
What is counted is the PUBLISHED state and rows, not the operands as padded:
q and k at d_k values a head, whatever tile they were handed in.

`gdn_step` (decode, one row a lane): every lane's state is read and written
once, 2 x heads x d_k x d_v x 4 B, beside its rows: q and k (heads x d_k
each), beta v and the output (heads x d_v each), one decay and one beta a
head.  Its arithmetic is a handful of multiply-adds a state value and runs on
the VPU: the bound is bandwidth.

`gdn_chunk` (prefill, `rows` a lane in chunks of 64): the rows in (q, k, beta
v, the log-decay and beta a head) and the output out, and a lane's state once
in and twice out (its slot and the snapshot's); the state never leaves VMEM
between a lane's chunks.  The matmuls the SCALAR-decay algorithm needs for a
chunk of C rows of one head, as multiply-adds x 2: A and P (2 x C x C x d_k),
the two products with the incoming state (2 x C x d_k x d_v), the triangular
system applied to the right-hand side and P U (2 x C x C x d_v), the state's
update (C x d_k x d_v).  The inverse's own doubling products, the exponentials
and what the kernel multiplies over a whole GROUP of heads' lanes for each of
its heads are stated nowhere in the share: what is counted is the algorithm's
need, at the bf16 peak although the kernel multiplies in float32, so the
share errs low.  A lane without real rows is skipped by the kernel and
counted here all the same.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

CHUNK = 64  # rows of one triangular system (gated_delta.CHUNK)
LANES = 128


def _geometry(dims: Sequence[Tuple[int, ...]]):
    """(lanes, rows, heads, d_k, d_v) of a call, or None where the operands
    are not the kernels'."""
    rows = [d for d in dims if len(d) == 3]
    if len(rows) < 3 or len(dims[-1]) != 4:
        return None
    d_k, wide_v = dims[-1][2:]
    tile = -(-d_k // LANES) * LANES
    lanes, n, wide_k = rows[0]
    if wide_k % tile or rows[2][2] != wide_v:
        return None
    heads = wide_k // tile
    return lanes, n, heads, d_k, wide_v // heads


def step_call(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one `gdn_step` call whose operands have the shapes
    `dims`; None where they are not the kernel's."""
    geo = _geometry(dims)
    if geo is None:
        return None
    lanes, _, heads, d_k, d_v = geo
    state = heads * d_k * d_v
    rows = heads * (2 * d_k + 2 * d_v + 2)
    return 8.0 * lanes * state, 4.0 * lanes * (2 * state + rows)


def chunk_call(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one `gdn_chunk` call whose operands have the shapes
    `dims`; None where they are not the kernel's."""
    geo = _geometry(dims)
    if geo is None:
        return None
    lanes, n, heads, d_k, d_v = geo
    c = min(CHUNK, n)
    per_chunk = 2.0 * (2 * c * c * d_k + 2 * c * d_k * d_v
                       + 2 * c * c * d_v + c * d_k * d_v)
    flops = lanes * heads * (n // c) * per_chunk
    nbytes = 4.0 * lanes * (n * heads * (2 * d_k + 2 * d_v + 2)
                            + 3 * heads * d_k * d_v)
    return flops, nbytes
