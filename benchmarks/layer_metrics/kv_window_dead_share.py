"""Prefix cache / pool: of the KV rows live lanes hold (tokens x layers) when
the window closes, the % no later query can attend: sliding-window layers'
rows older than the window, which the uniform pool keeps and a window-aware
allocator would give back.  The engine counts it
(`/metrics` `engine.kv_window_dead_share`); the fullest replica's.  A
program without the counter (the parent) has nothing to read: None."""


def read(ctx):
    shares = [(rep.get("engine") or {}).get("kv_window_dead_share")
              for rep in ctx["after"].get("replicas") or [ctx["after"]]]
    shares = [s for s in shares if s is not None]
    return 100.0 * max(shares) if shares else None
