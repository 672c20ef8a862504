"""Pallas paged-decode kernel at 64 query / 8 KV heads x 128 (a merged row of
1,024 lanes), the two gated softmax layers' decode reads at ~8.3k keys: the
least time the chip could take for the decode programs'
`paged_decode_attention` calls, two a pass, over their measured device time.
`wide_gqa_attn_roofline`'s reader (`cross_attn_roofline`'s reading: calls as
wide as `max_batch` only, `roofline.paged_decode` bytes at the window's mean
context x `decode_batch_occupancy` lanes x the counted calls) under a name of
this cell's own, because that metric's list of cells is a `benchmark` PR's to
edit (ROADMAP R1 folds these twins).  A capture without the kernel has nothing
to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "cross_attn_roofline").read
