"""Prefix cache: pages of the KV pool in use (live lanes + what the radix
cache retains) over the pool's pages when the window closes, in %; the
fullest replica's.  Read beside `hbm_peak_gb`: a pool the traffic never
fills is memory reserved, not state."""


def read(ctx):
    shares = []
    for rep in ctx["after"].get("replicas") or [ctx["after"]]:
        eng = rep.get("engine") or {}
        total = eng.get("pages_total", 0) - 1  # page 0 is the trash page
        if total > 0 and "pages_in_use" in eng:
            shares.append(100.0 * eng["pages_in_use"] / total)
    return max(shares) if shares else None
