"""Prefix cache of a model with a recurrent state: over the window's
admissions, the tokens a SNAPSHOT let the prefill skip over the tokens the
PAGES matched, in % (`/metrics` `state.state_tokens_skipped` /
`state.state_tokens_matched`, window deltas).  Pages can be shared from any
page boundary, a recurrence only from where a snapshot stands: under 100 the
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
