"""Prefix cache of the Gated DeltaNet layout (Olmo-Hybrid): over the window's
admissions, the tokens a SNAPSHOT of the delta state and the convolutions'
tails let the prefill skip over the tokens the PAGES matched, in % (`/metrics`
`state.state_tokens_skipped` / `state.state_tokens_matched`, window deltas).
`state_restore_share`'s counters under this cell's name: that metric lists
its cells and a new cell cannot be appended to the list (ROADMAP R1 folds the
twins).  ~100 where the shared prefix's boundary snapshot, 28.2 MB copied
slot to slot, is found by every admission.  A server without the section (the
parent, a model without state) has nothing to read: None."""
import os

import named

read = named.load((os.path.dirname(os.path.dirname(__file__)),),
                  "layer_metrics", "state_restore_share").read
