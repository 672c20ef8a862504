"""Device: 1 - union of op intervals / traced window, the worst chip's."""


def read(ctx):
    t = ctx.get("trace")
    return None if not t else 100.0 * t["worst_idle_share"]
