"""Operations a grouped-query prefill chunk's attention NEEDS, from its
bounds (roofline.py, which holds the causal call's, is a yardstick file that a
`model_config` PR does not edit, and it has no windowed form).

The count is the MODEL's: 2 flops a value of the score and 2 of the weighted
sum for every (query, key) pair the mask lets through, over every query head.
It does not change with the kernel's form: a block-diagonal kernel that
multiplies Hkv x the lanes, a masked tile that is computed and thrown away,
padded rows of a bucket and f32 passes on the MXU are the kernel's cost, and
show as a LOW share of the peak, never as work done."""

from __future__ import annotations

from typing import Optional


def attended_pairs(chunk_len: int, start: int,
                   window: Optional[int] = None) -> float:
    """(query, key) pairs of `chunk_len` queries at positions start..: each
    attends its own position and those before it, a sliding layer the last
    `window` of them."""
    s, c = int(chunk_len), int(start)
    if s <= 0:
        return 0.0
    if not window:
        return s * c + s * (s + 1) / 2.0
    w = int(window)
    # query at position p attends min(p + 1, w) keys
    short = max(0, min(s, w - 1 - c))  # queries with fewer than w keys
    first = c + 1                      # keys of the first query
    return short * (2 * first + short - 1) / 2.0 + (s - short) * w


def gqa_prefill(chunk_len: int, start: int, num_heads: int, head_dim: int,
                window: Optional[int] = None) -> float:
    """Flops of ONE layer's attention over one prefill chunk."""
    return 4.0 * attended_pairs(chunk_len, start, window) * num_heads * head_dim
