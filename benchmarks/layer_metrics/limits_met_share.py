"""Whole service, client side: share of the window's attempted requests that
met the cell's TTFT and TPOT limits (a failed, refused or shed request
misses both), in %."""


def read(ctx):
    return 100.0 * ctx["summary"]["limits_met_share"]
