"""Pallas paged-decode kernel at 32 query / 2 KV heads x 128 (a group of
SIXTEEN query rows a KV head, where every other cell has 4, 5 or 8; a merged
row of 256 lanes), the two attention layers' decode read at ~8.3k keys of
unrotated rows: the least time the chip could take for the decode programs'
`paged_decode_attention` calls, two a pass, over their measured device time.
`paged_attn_roofline`'s reader (`roofline.paged_decode` bytes at the window's
mean context x `decode_batch_occupancy` lanes x the counted calls) under a
name of this cell's own, because that metric's list of cells is a `benchmark`
PR's to edit (ROADMAP R1 folds these twins).  A capture without the kernel
has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "paged_attn_roofline").read
