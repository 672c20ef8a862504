"""Pallas paged-decode kernel at 20 query / 4 KV heads x 128 (a group of FIVE
query rows a KV head, where every other cell has 4, 5 of 40 / 20 x 64, or 8;
a merged row of 512 lanes), every layer's decode read at ~8.3k keys beside
the same layer's SSD step: the least time the chip could take for the decode
programs' `paged_decode_attention` calls, seven a pass, over their measured
device time.  `paged_attn_roofline`'s reader (`roofline.paged_decode` bytes
at the window's mean context x `decode_batch_occupancy` lanes x the counted
calls) under a name of this cell's own, because that metric's list of cells
is a `benchmark` PR's to edit (ROADMAP R1 folds these twins).  A capture
without the kernel has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "paged_attn_roofline").read
