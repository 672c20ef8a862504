"""Bytes and flops ONE mapping site of a widened residual stream
(`kafka_tpu/models/llama.py` `_hc_in` / `_hc_out`: `hc_mult` = n rows of C a
token, per-token mappings around a sublayer) must move and do over `rows`
rows, from n, C and the stream's dtype alone: the same work whatever
implements it, a chain of XLA fusions today or one fused kernel later
(roofline.py is a yardstick file that a `model_config` PR does not edit).

Bytes a row: the stream read once (n C: the mappings' norm and product and
the pre-mix can share the read, and a kernel that holds a row's n C values on
the chip across the sublayer need not read them again for the res-mix), u
written (C), the sublayer's y read (C), the stream written (n C): (2n + 2) C
values.  Phi [nC, n + n + n^2] and the stream norm's weight [nC] are read
once a site a pass, whatever the rows.  The mappings themselves ([rows, n + n
+ n^2] float32) never need to leave the chip and are not counted.

Flops a row: the product with Phi, 2 nC (2n + n^2); the three mixes, 2 nC +
2 n^2 C + 2 nC; the norm's square-and-sum, 2 nC.  Sinkhorn's rounds are a few
hundred operations on 16 numbers a row and are left out: the site is bound by
bytes or, at few rows, by latency, which no roofline states (a share in the
single digits at 32 rows says exactly that).
"""

from __future__ import annotations

from typing import Tuple


def site(rows: int, n: int, c: int, value_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of one site over `rows` rows of one pass."""
    maps = 2 * n + n * n
    nbytes = value_bytes * (rows * (2 * n + 2) * c + n * c * (maps + 1))
    flops = rows * (2.0 * n * c * maps + 2.0 * n * c * (n + 3))
    return flops, float(nbytes)
