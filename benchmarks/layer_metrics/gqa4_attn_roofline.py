"""Pallas paged-decode kernel at 32 query / 8 KV heads x 128 (four query rows a
KV head, a merged row of 1,024 lanes) under a PUBLISHED softmax scale
(`attention_multiplier` 1 / 128 handed to the kernel in place of 128^-1/2),
the ONE attention layer's decode read at ~8.3k keys of unrotated rows: the
least time the chip could take for the decode programs'
`paged_decode_attention` calls, one a pass, over their measured device time.
`paged_attn_roofline`'s reader (`roofline.paged_decode` bytes at the window's
mean context x `decode_batch_occupancy` lanes x the counted calls) under a
name of this cell's own, because that metric's list of cells is a `benchmark`
PR's to edit (ROADMAP R1 folds these twins).  A capture without the kernel
has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "paged_attn_roofline").read
