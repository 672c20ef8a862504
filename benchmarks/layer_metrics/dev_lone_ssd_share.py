"""Step programs: % of the capture's device busy time in the Mamba-2 (SSD)
mixers that stand ALONE in their layers (Nemotron-H's M layers: no attention
beside them, no feed-forward behind them), the four `ssd_*` scopes over all
programs: the two projections and the layer's residual add (`ssd_proj`), the
short convolution with its tail's read and write (`ssd_conv`), the elementwise
decay, gate and grouped norm (`ssd_gate`) and the recurrence itself, the two
Pallas kernels at heads of 64 x 128 or the XLA scan (`ssd_scan`).
`dev_ssd_share`'s reader under a name this cell can be listed on: that
metric's list of cells is a `benchmark` PR's to edit (ROADMAP R1 folds the
twins).  The configuration lists the scopes (`scopes`).  A capture without
them (the parent, a configuration without such layers) has nothing to read:
None, not 0."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "dev_ssd_share").read
