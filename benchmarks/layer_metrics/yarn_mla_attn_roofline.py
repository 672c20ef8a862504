"""Pallas latent (MLA) paged-decode kernel under a YaRN-scaled rotation and a
softmax scale of 192^-1/2 x 2.0048 (Xing4.0: 32 heads, a query low-rank of 768
ahead of the absorb, every lane's keys at 7.7k-8.9k, past the rotation's
original 4,096): the least time the chip could take for the decode programs'
`paged_decode_attention_latent` calls, eight a pass, over their measured
device time.  `mla_attn_roofline`'s reader (`mla_roofline.latent_decode` at
the window's mean context x `decode_batch_occupancy` lanes x the counted
calls; the stored row's width is what the program says it allocates) under a
name of this cell's own, because that metric's list of cells is a `benchmark`
PR's to edit (ROADMAP R1 folds these twins).  The scale and the rotation
change no byte and no flop of the kernel: the share should read what
Kanana-2's does.  A capture without the kernel has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "mla_attn_roofline").read
