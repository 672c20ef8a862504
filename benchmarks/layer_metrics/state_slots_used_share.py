"""State slots of a model with a recurrent state: slots in use (seated lanes
+ standing snapshots) over all slots when the window closes, in %
(`/metrics` `state.state_slots_live` / `state.state_slots_total`).  Read
beside `kv_pool_used_share`: slots the traffic never fills are memory
reserved, not state; at 100 the oldest snapshots are being dropped.  A server
without the section has nothing to read: None."""


def read(ctx):
    state = ctx["after"].get("state") or {}
    total = state.get("state_slots_total", 0)
    if total <= 0 or "state_slots_live" not in state:
        return None
    return 100.0 * state["state_slots_live"] / total
