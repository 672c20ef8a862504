"""Whole service, client side: median per-request TPOT over the window
(e2e.py), for the cell where it is too unsteady to carry a bound as
`tpot_p50_ms`."""


def read(ctx):
    return ctx["summary"]["tpot_p50_ms"]
