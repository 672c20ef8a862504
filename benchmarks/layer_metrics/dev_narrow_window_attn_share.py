"""Step programs: % of the capture's device busy time in the attention proper
of the 128-key sliding layers, the `attn_window` scope over all programs:
`dev_window_attn_share`'s reading under a name of this cell's own, because
that metric's list of cells is a `benchmark` PR's to edit."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "dev_window_attn_share").read
