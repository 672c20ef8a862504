"""Pallas paged-decode kernel at 32 query / 8 KV heads x 64 (a merged row of
512 lanes), the conv layout's three attention layers' decode reads: the least
time the chip could take for the decode programs' `paged_decode_attention`
calls, three a pass, over their measured device time.  `cross_attn_roofline`'s
reading (calls as wide as `max_batch` only, by the lanes in each call's own
result shape; `roofline.paged_decode` bytes at the window's mean context x
`decode_batch_occupancy` lanes x the counted calls) under a name of this
cell's own, because `paged_attn_roofline`'s list of cells is a `benchmark`
PR's to edit (ROADMAP R1 folds these twins).  A capture without the kernel
has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "cross_attn_roofline").read
