"""Prefix cache / pool: of the KV rows live lanes hold when the window closes,
the % no later query can attend (five of six layers keep 128 of ~29k rows):
`kv_window_dead_share`'s reading of `/metrics`
`engine.kv_window_dead_share` under a name of this cell's own, because that
metric's list of cells is a `benchmark` PR's to edit."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "kv_window_dead_share").read
