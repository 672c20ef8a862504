"""Pallas paged-decode kernel at 30 query / 30 KV heads x 128: MULTI-HEAD
attention, ONE query row a KV head, a merged row of 3,840 lanes (3.75 x the
widest before it), four calls a decode pass over ~8.3k keys of unrotated
rows: the least time the chip could take for the decode programs'
`paged_decode_attention` calls over their measured device time.  The step is
256 keys, not 512 (`paged_attention.step_rows`: the ring of K and V buffers
within VMEM), and q is expanded block-diagonally to [30, 3,840], so the MXU
multiplies 30 x what the scores need.  `cross_attn_roofline`'s reader
(`roofline.paged_decode` bytes at the window's mean context x
`decode_batch_occupancy` lanes x the calls whose result is `max_batch` lanes
wide) under a name of this cell's own.  A capture without the kernel has
nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "cross_attn_roofline").read
