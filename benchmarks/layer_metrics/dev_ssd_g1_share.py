"""Step programs: % of the capture's device busy time in the Mamba-2 (SSD)
mixers that stand at the head of nine of Granite-4.0-H's ten layers (128 heads
of 64 x 128 in ONE group, a routed feed-forward behind each), the four
`ssd_*` scopes over all programs: the two projections and the mixer's
residual add with its `residual_multiplier` (`ssd_proj`), the short
convolution over 8,448 channels with its (8, 3168) tail's read and write
(`ssd_conv`), the elementwise decay, gate and the norm over all 8,192 channels
(`ssd_gate`) and the recurrence itself, the two Pallas kernels at 64 heads a
grid step or the XLA scan (`ssd_scan`).  `dev_ssd_share`'s reader under a name
this cell can be listed on: that metric's list of cells is a `benchmark` PR's
to edit (ROADMAP R1 folds the twins).  The configuration lists the scopes
(`scopes`).  A capture without them (the parent, a configuration without such
layers) has nothing to read: None, not 0."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "dev_ssd_share").read
