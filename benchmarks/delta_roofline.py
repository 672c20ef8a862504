"""Bytes and matmul flops the two gated-delta kernels
(`kafka_tpu/ops/pallas/gated_delta.py`) must move and do for ONE call, from
the shapes of the call's own operands (roofline.py is a yardstick file that a
`model_config` PR does not edit).

The shapes are read from the call's HLO text, the event's name on the
device's op line (`kernel_calls.shapes(text, "operands")`), in the order the
kernels take their operands: the scalar-prefetch vectors first, then the
float32 row operands, the state leaf [layers, n_slots, heads x d_v, d_k] last.

`gated_delta_step` (decode, one row a lane): every lane's state is read and
written once, 2 x heads x d_v x d_k x 4 B, beside five rows in and one out.
Its arithmetic is a handful of multiply-adds a state value and runs on the
VPU: the bound is bandwidth.

`gated_delta_chunk` (prefill, `rows` a lane in chunks of 64): the float32 row
operands (q, k, beta k, beta v, the cumulative log-decay) in and the output
out, and a lane's state once in and twice out (its slot and the snapshot's).
The state never leaves VMEM between a lane's chunks: that is the kernel's
point, and why its traffic is not chunks x state.  The matmuls a chunk of C
rows of one head cannot avoid, as multiply-adds x 2: A and P (2 x C x C x
d_k), the two products with the incoming state (2 x C x d_k x d_v), the
triangular system applied to the right-hand side and P U (2 x C x C x d_v),
the state's update (C x d_k x d_v).  The inverse's own doubling products and
the exponentials (one per row and key channel per sub-block) are stated
nowhere in the share: what is counted is the algorithm's need, at the bf16
peak although the kernel multiplies in float32, so the share errs low.  A
lane without real rows is skipped by the kernel and counted here all the
same (the shapes do not say which lanes were active): batched prefill
launches with idle lanes read a little high, never over what a full launch
reads.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

CHUNK = 64  # rows of one triangular system (gated_delta.CHUNK)


def _leaf(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[int, int]]:
    """(heads x d_v, d_k) of the state leaf, the one 4-d operand."""
    leaf = [d for d in dims if len(d) == 4]
    return (leaf[0][2], leaf[0][3]) if leaf else None


def step_call(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one `gated_delta_step` call whose operands have the
    shapes `dims`; None where they are not the kernel's."""
    rows = [d for d in dims if len(d) == 3]
    leaf = _leaf(dims)
    if len(rows) < 5 or leaf is None:
        return None
    lanes = rows[0][0]
    width = sum(d[2] for d in rows[:5]) + rows[3][2]  # five in, o out
    state = leaf[0] * leaf[1]
    return 8.0 * lanes * state, 4.0 * lanes * (2 * state + width)


def chunk_call(dims: Sequence[Tuple[int, ...]]) -> Optional[Tuple[float, float]]:
    """(flops, bytes) of one `gated_delta_chunk` call whose operands have the
    shapes `dims`; None where they are not the kernel's."""
    rows = [d for d in dims if len(d) == 3]
    leaf = _leaf(dims)
    if len(rows) < 5 or leaf is None:
        return None
    lanes, n, wide_k = rows[0]
    wide_v, d_k = leaf
    heads = wide_k // d_k
    d_v = wide_v // heads
    c = min(CHUNK, n)
    per_chunk = 2.0 * (2 * c * c * d_k + 2 * c * d_k * d_v
                       + 2 * c * c * d_v + c * d_k * d_v)
    flops = lanes * heads * (n // c) * per_chunk
    nbytes = 4.0 * lanes * (n * (4 * wide_k + 2 * wide_v)
                            + 3 * wide_v * d_k)
    return flops, nbytes
