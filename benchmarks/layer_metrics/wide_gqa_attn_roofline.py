"""Pallas paged-decode kernel at a WIDE grouped-query geometry (64 query / 8
KV heads x 128: a merged row of 1,024 lanes), the one full-attention layer's
decode read at long context: the least time the chip could take for the decode
programs' `paged_decode_attention` calls, one a pass, over their measured
device time.  `cross_attn_roofline`'s reading (calls as wide as `max_batch`
only, by the lanes in each call's own result shape, so a narrower launch's
calls are left out; `roofline.paged_decode` bytes at the window's mean context
x `decode_batch_occupancy` lanes x the counted calls) under a name of this
cell's own, because `paged_attn_roofline`'s list of cells is a `benchmark`
PR's to edit and its pattern also matches the windowed kernel, which here is
`narrow_window_attn_roofline`'s.  A capture without the kernel has nothing to
read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "cross_attn_roofline").read
