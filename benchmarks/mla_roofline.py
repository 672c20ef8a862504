"""Operations and bytes ONE latent (MLA) paged-decode attention call needs, in
the absorbed form, from its shapes (roofline.py, which holds the GQA call's,
is a yardstick file that a `model_config` PR does not edit).

A lane with n cached tokens attends n keys.  Each key is one stored row
shared by all heads: `latent` values that are key AND value, and the rotary
key part; per head the score runs over latent + rope lanes and the weighted
sum over the latent lanes, 2 flops a lane each: 2 x heads x ((latent + rope) +
latent) flops a key.  The row is read ONCE, as it is stored (`row_values`: the
pool's allocated lanes, lane padding included, because that is what a page
holds and a page DMA moves), in whole pages; plus the absorbed query in
(latent + rope lanes a head) and the latent result out.  No value is read
beyond the latent row.  The share is taken against the larger of the byte
time and the flop time (`roofline.roofline_share`): with 32 heads the call
sits near the ridge of a chip whose MXU wants 128 rows.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def latent_decode(seq_lens: Iterable[int], num_heads: int, latent: int,
                  rope: int, row_values: int, page_size: int,
                  dtype_bytes: int = 2) -> Tuple[float, float]:
    """(flops, bytes) of ONE latent paged-decode call (one layer)."""
    flops = nbytes = 0.0
    for n in seq_lens:
        n = int(n)
        if n <= 0:
            continue
        rows = -(-n // page_size) * page_size
        flops += 2.0 * n * num_heads * ((latent + rope) + latent)
        nbytes += float(rows * row_values * dtype_bytes)
        nbytes += float(num_heads * ((latent + rope) + latent) * dtype_bytes)
    return flops, nbytes
