"""Step programs: % of the capture's device busy time in QK-norm at 32 / 8
heads x 64 (the `qk_norm` scope over all programs; the configuration lists
it under `scopes`).  `dev_qk_norm_share`'s reading under a name of this
cell's own, because that metric's list of cells is a `benchmark` PR's to edit
(ROADMAP R1 folds these twins).  A capture without the scope has nothing to
read: None, not 0."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "dev_qk_norm_share").read
