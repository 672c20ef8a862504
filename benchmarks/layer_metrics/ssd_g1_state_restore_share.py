"""Prefix cache of Granite-4.0-H (nine Mamba-2 layers, one attention layer):
over the window's admissions, the tokens a SNAPSHOT of the SSD state and the
convolution's tail let the prefill skip over the tokens the PAGES matched, in
% (`/metrics` `state.state_tokens_skipped` / `state.state_tokens_matched`,
window deltas).  `state_restore_share`'s counters under this cell's name: that
metric lists its cells and a new cell cannot be appended to the list (ROADMAP
R1 folds the twins).  ~100 where the shared prefix's boundary snapshot, 38.7
MB copied slot to slot (the largest slot in the benchmark), is found by every
admission.  A server without the section (the parent, a model without state)
has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "state_restore_share").read
