"""Load generator: p99 of send time - due time, the parent's clock."""


def read(ctx):
    return ctx["summary"]["late_p99_ms"]
