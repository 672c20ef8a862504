"""Prefix cache / pool: bytes one cached token holds in the KV pool over all
layers, as the engine allocated them (both pools, lane padding and any scales
included): what a page, a prefix-cache hit and a shipped page cost per token.
The engine exports it (`/metrics` `engine.kv_bytes_per_token`); replicas are
alike, the first one's is read.  A program without the gauge (the parent) has
nothing to read: None."""


def read(ctx):
    eng = (ctx["after"].get("replicas") or [ctx["after"]])[0].get("engine")
    value = (eng or {}).get("kv_bytes_per_token")
    return float(value) if value else None
