"""Whole service, client side: median of due time -> first content delta
over the window (e2e.py), for the cells where ~30-70 requests a window make
it too unsteady to carry a bound as `ttft_p50_ms`."""


def read(ctx):
    return ctx["summary"]["ttft_p50_ms"]
