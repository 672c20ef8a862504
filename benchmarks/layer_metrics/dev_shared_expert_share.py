"""Step programs: % of the capture's device busy time in the always-on shared
expert beside the held routed ones, the `moe_shared` scope over all programs:
`dev_moe_shared_share`'s reading under a name of this cell's own, because
that metric's list of cells is a `benchmark` PR's to edit."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "dev_moe_shared_share").read
