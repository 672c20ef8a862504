"""Pallas windowed paged-decode kernel at a NARROW window (128 keys: one DMA
chunk of 8 pages of 16), five calls a pass: `window_attn_roofline`'s reading
(the kernel `paged_decode_attention_window`, `window_roofline.windowed_decode`
bytes at min(mean context, `sliding_window`) x busy lanes x calls over the
calls' device time) under a name of this cell's own, because that metric's
list of cells is a `benchmark` PR's to edit.  A window that straddles a chunk
boundary makes the kernel touch two chunks where the count needs one, so at
this window the share reads how much of a call is fixed cost and the second
chunk."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "window_attn_roofline").read
