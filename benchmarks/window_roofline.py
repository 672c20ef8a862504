"""Operations and bytes a SLIDING-WINDOW paged-decode attention call needs,
from its shapes (roofline.py, which holds the global call's, is a yardstick
file that a `model_config` PR does not edit).

A windowed layer's query at position n attends keys n - window < k <= n: it
needs min(n + 1, window) keys whatever the context, K and V each, fetched in
the kernel's DMA unit (a chunk of `pages_per_chunk` pages), plus q in and
out.  The kernel may touch one chunk more than this (a window that straddles
chunk boundaries reads ceil((window + chunk) / chunk) of them): that extra
chunk is the kernel's cost, not the algorithm's need, so it is not counted
here and the share stays under 100%."""

from __future__ import annotations

from typing import Iterable, Tuple


def windowed_decode(seq_lens: Iterable[int], window: int, num_heads: int,
                    num_kv_heads: int, head_dim: int, page_size: int,
                    pages_per_chunk: int = 8,
                    dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ONE windowed paged-decode call (one layer)."""
    chunk = page_size * pages_per_chunk
    flops = nbytes = 0.0
    for n in seq_lens:
        keys = min(int(n) + 1, int(window))
        if keys <= 0:
            continue
        rows = -(-keys // chunk) * chunk
        flops += 4.0 * keys * num_heads * head_dim
        nbytes += 2.0 * rows * num_kv_heads * head_dim * dtype_bytes
        nbytes += 2.0 * num_heads * head_dim * dtype_bytes
    return flops, nbytes
