"""Routed feed-forward block at decode, one of TWO chips' share (64 of 128
experts held, top-6, ungated squared-ReLU experts of two matrices): of the
held experts over the seven routed layers of the window's decode passes, the
share whose weights a pass READ, in % (`moe_experts_read_share`'s reader and
counters, `engine.moe_experts_read` / `engine.moe_experts_held`, under a name
this cell can be listed on: that metric's list of cells is a `benchmark` PR's
to edit; ROADMAP R1 folds the twins).  With 32 lanes an expert goes unpicked
with probability (1 - 6 / 128)^32 = 0.215, so token dispatch reads ~78% of
the held experts a pass.  A program without the counters has nothing to read:
None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "moe_experts_read_share").read
