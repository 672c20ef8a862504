"""Operations and bytes the two decode reads need that dots3-note-prev adds,
from their shapes (roofline.py and mla_roofline.py, which hold the calls the
benchmark had, are files a `model_config` PR does not edit).

`chosen_rows_decode`: one full layer's attention over the rows its indexer
KEPT, absorbed form.  A lane with n cached tokens keeps min(n, topk) rows;
each is read once as stored (`row_values`: the latent lanes and the rotary
tile, lane padding included) and costs, per head, 2 flops a lane of the score
(latent + rope) and of the weighted sum (latent).  The indexer's own reads
and the top-k are NOT here: they are what choosing costs (`dev_index_share`),
this is what the chosen read needs.

`latent_window_decode`: one sliding layer's windowed latent decode call.  The
query at position n attends min(n + 1, window) keys, fetched in the kernel's
DMA unit (a chunk of `pages_per_chunk` pages), one stored row each.  The
kernel may touch one chunk more (a window that straddles chunk boundaries):
that is the kernel's cost, not the algorithm's need, so it is not counted and
the share stays under 100%.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def chosen_rows_decode(seq_lens: Iterable[int], topk: int, num_heads: int,
                       latent: int, rope: int, row_values: int,
                       dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ONE full layer's read of and attention over its
    chosen rows."""
    flops = nbytes = 0.0
    for n in seq_lens:
        kept = min(int(n), int(topk))
        if kept <= 0:
            continue
        flops += 2.0 * kept * num_heads * ((latent + rope) + latent)
        nbytes += float(kept * row_values * dtype_bytes)
        nbytes += float(num_heads * ((latent + rope) + latent) * dtype_bytes)
    return flops, nbytes


def latent_window_decode(seq_lens: Iterable[int], window: int, num_heads: int,
                         latent: int, rope: int, row_values: int,
                         page_size: int, pages_per_chunk: int = 8,
                         dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ONE windowed latent paged-decode call (one layer)."""
    chunk = page_size * pages_per_chunk
    flops = nbytes = 0.0
    for n in seq_lens:
        keys = min(int(n) + 1, int(window))
        if keys <= 0:
            continue
        rows = -(-keys // chunk) * chunk
        flops += 2.0 * keys * num_heads * ((latent + rope) + latent)
        nbytes += float(rows * row_values * dtype_bytes)
        nbytes += float(num_heads * ((latent + rope) + latent) * dtype_bytes)
    return flops, nbytes
