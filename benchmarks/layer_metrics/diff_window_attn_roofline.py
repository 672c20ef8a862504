"""Pallas windowed paged-decode kernel of a hybrid decoder, its sliding
differential layers' decode read: `window_attn_roofline`'s reading (the
kernel `paged_decode_attention_window`, `window_roofline.windowed_decode`
bytes at min(mean context, `sliding_window`) x busy lanes x calls over the
calls' device time) under a name of this cell's own, because that metric's
list of cells is a `benchmark` PR's to edit.  The differential output, twice
as wide as q, is counted there at q's width (0.2% of a call's bytes at a
512-key window), so the share errs low."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "window_attn_roofline").read
