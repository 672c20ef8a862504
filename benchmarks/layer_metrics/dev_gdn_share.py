"""Step programs: % of the capture's device busy time in Olmo-Hybrid's Gated
DeltaNet layers (12 of the cut's 16: 30 heads of 96 x 192 under ONE decay a
head), the four `kda_*` scopes over all programs, the block being the shared
one (`models/mixers/state._delta_attention_block`): the projections, the
post-norm of the mixer's output and the residual add (`kda_proj`), the three
short convolutions over 11,520 channels with their (8, 4320) tail's read and
write (`kda_conv`), the elementwise gates and norms (`kda_gate`) and the
recurrence itself, `gdn_step` / `gdn_chunk` or the XLA scan (`kda_delta`).
`dev_kda_share`'s reader under a name this cell can be listed on: that
metric's list of cells is a `benchmark` PR's to edit (ROADMAP R1 folds the
twins).  The configuration lists the scopes (`scopes`).  A capture without
them (the parent, a configuration without such layers) has nothing to read:
None, not 0."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "dev_kda_share").read
