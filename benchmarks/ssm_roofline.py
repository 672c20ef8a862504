"""Bytes a selective-scan call (Mamba-1's recurrence over a prefill chunk,
`kafka_tpu/ops/pallas/selective_scan.py`) must move and the exponentials it
makes, from its shapes (roofline.py is a yardstick file that a `model_config`
PR does not edit).

The recurrence h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t, y_t = C_t h_t +
D x_t has no matmul form, so it has no MXU work to count: the bound is
bandwidth.  What MUST move, float32 as the kernel takes it: x and dt in and y
out ([rows, d_inner] each), B and C ([rows, d_state] each: the need, not the
128-lane broadcast the kernel is handed), the state in and out ([d_state,
d_inner] each), A and D once.  The state itself never leaves VMEM between
rows: that is the kernel's point, and why its traffic is not rows x state.

The exponentials are the other cost a call cannot avoid: one per row, state
index and channel.  They are stated, not put into the share: the chip's
transcendental rate is not in `roofline.PEAKS`.
"""

from __future__ import annotations

from typing import Tuple


def scan_call(lanes: int, rows: int, d_inner: int, d_state: int,
              dtype_bytes: int = 4) -> Tuple[float, float]:
    """(exponentials, bytes) of ONE selective-scan call (one Mamba layer of
    one prefill launch of `lanes` x `rows`)."""
    exps = float(lanes) * rows * d_state * d_inner
    nbytes = dtype_bytes * (
        3.0 * lanes * rows * d_inner       # x, dt in; y out
        + 2.0 * lanes * rows * d_state     # B, C
        + 2.0 * lanes * d_state * d_inner  # the state in and out
        + d_state * d_inner + d_inner)     # A, D
    return exps, nbytes
