"""Prefix cache of the conv layout (LFM2): over the window's admissions, the
tokens a SNAPSHOT of the conv tails let the prefill skip over the tokens the
PAGES matched, in % (`/metrics` `state.state_tokens_skipped` /
`state.state_tokens_matched`, window deltas).  `state_restore_share`'s
counters under this cell's name: that metric lists its cells and a new cell
cannot be appended to the list (ROADMAP R1 folds the two).  ~100 where the
shared prefix's boundary snapshot is found by every admission; under it the
page cache holds prefixes no snapshot covers and they are prefilled again.  A
server without the section (the parent, a model without state) has nothing to
read: None."""
import readers


def read(ctx):
    matched = readers.counter_delta(ctx, "state", "state_tokens_matched")
    skipped = readers.counter_delta(ctx, "state", "state_tokens_skipped")
    if not matched or skipped is None:
        return None
    return 100.0 * skipped / matched
