"""Routed feed-forward block at decode, one of TWO chips' share under the
SOFTMAX rule (36 of 72 experts held, top-10: the widest pick and the
narrowest experts, f = 768, in the benchmark): of the held experts over the
ten routed layers of the window's decode passes, the share whose weights a
pass READ, in % (`moe_experts_read_share`'s reader and counters,
`engine.moe_experts_read` / `engine.moe_experts_held`, under a name this cell
can be listed on: that metric's list of cells is a `benchmark` PR's to edit;
ROADMAP R1 folds the twins).  With 16 lanes an expert goes unpicked with
probability (1 - 10 / 72)^16 = 0.091, so token dispatch reads ~91% of the held
experts a pass.  A program without the counters has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "moe_experts_read_share").read
