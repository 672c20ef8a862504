"""The arithmetic of the end-to-end metrics, from the client log alone.

Every time is the load generator's `time.monotonic()`.  TTFT runs from a
request's DUE time (not its send time) to its first content delta.  TPOT is
per request (last content delta - first content delta) / (completion_tokens
- 1) over requests of 8+ tokens; deltas arrive in bursts (`multi_step` 16,
UTF-8 holdback), so single gaps are not used.  `out_tok_s` counts the
characters of content deltas that arrived inside the window: under the byte
tokenizer padded with one-letter filler ids a token is one character
(`token_char_ratio` checks it against `usage.completion_tokens`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

MIN_TPOT_TOKENS = 8
MIN_TAIL_SAMPLES = 100  # a p90 needs 10 samples beyond it
LATE_MS = 10.0  # a send this long after its due time counts as late


def percentile(samples: List[float], p: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default)."""
    if not samples:
        return None
    s = sorted(samples)
    k = (len(s) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def completion_tokens(rec: Dict[str, Any]) -> Optional[int]:
    return (rec.get("usage") or {}).get("completion_tokens")


def cached_tokens(rec: Dict[str, Any]) -> int:
    u = rec.get("usage") or {}
    return int((u.get("prompt_tokens_details") or {}).get("cached_tokens", 0))


def ok(rec: Dict[str, Any]) -> bool:
    return bool(rec.get("done")) and not rec.get("error")


def ttft_ms(rec: Dict[str, Any]) -> Optional[float]:
    if rec.get("t_first") is None:
        return None
    return (rec["t_first"] - rec["due"]) * 1e3


def tpot_ms(rec: Dict[str, Any]) -> Optional[float]:
    n = completion_tokens(rec)
    if not ok(rec) or n is None or n < MIN_TPOT_TOKENS:
        return None
    return (rec["t_last"] - rec["t_first"]) * 1e3 / (n - 1)


def warm_turns(log: List[Dict[str, Any]], page: int) -> Optional[float]:
    """Share of turns >= 2 that found the thread's previous turn cached:
    cached_tokens >= previous prompt_tokens less one page."""
    by_session: Dict[str, Dict[int, Dict[str, Any]]] = {}
    for r in log:
        if "session" in r and ok(r) and r.get("usage"):
            by_session.setdefault(r["session"], {})[r["turn"]] = r
    warm = total = 0
    for turns in by_session.values():
        for n, r in turns.items():
            prev = turns.get(n - 1)
            if n < 2 or prev is None or not r["in_window"]:
                continue
            total += 1
            if cached_tokens(r) >= prev["usage"]["prompt_tokens"] - page:
                warm += 1
    return warm / total if total else None


def summarize(log: List[Dict[str, Any]], kind: str, t0: float, t1: float,
              limits: Dict[str, float]) -> Dict[str, Any]:
    """Counts and end-to-end values of one window.  `kind` is the traffic
    kind: an open loop counts a request unfinished after the drain as failed,
    a closed loop cuts what is in flight at the window's end."""
    inw = [r for r in log if r["in_window"]]
    if kind == "closed_loop":
        failed = [r for r in inw if r.get("error")]
        # TPOT over every request that FINISHED inside the window
        tpot_pool = [r for r in log
                     if ok(r) and t0 <= (r.get("t_end") or -1) < t1]
    else:
        failed = [r for r in inw if not ok(r)]
        tpot_pool = [r for r in inw if ok(r)]
    ttfts = [v for v in (ttft_ms(r) for r in inw) if v is not None]
    tpots = [v for v in (tpot_ms(r) for r in tpot_pool) if v is not None]
    finished = [r for r in inw if ok(r)]
    met = 0
    for r in finished:
        a, b = ttft_ms(r), tpot_ms(r)
        if (a is not None and a <= limits["ttft_ms"]
                and (b is None or b <= limits["tpot_ms"])):
            met += 1
    chars = sum(r.get("chars_in_window", 0) for r in log)
    done_tok = sum(completion_tokens(r) or 0 for r in log if ok(r))
    done_chars = sum(r["chars"] for r in log
                     if ok(r) and completion_tokens(r) is not None)
    lates = [(r["t_send"] - r["due"]) * 1e3 for r in log
             if r.get("t_send") is not None and not r.get("shed")]
    every = [r for r in log if ok(r)]  # lead-in requests count here too
    at_length = [r for r in every
                 if r.get("finish_reason") == "length"
                 and completion_tokens(r) == r.get("max_tokens")]
    return {
        "attempted": len(inw),
        "failed": len(failed),
        "finished": len(finished),
        "cut_in_flight": sum(1 for r in inw
                             if not r.get("error") and not r.get("done")),
        "ttft_samples": len(ttfts),
        "tpot_samples": len(tpots),
        "ttft_p50_ms": percentile(ttfts, 50),
        "ttft_p90_ms": (percentile(ttfts, 90)
                        if len(ttfts) >= MIN_TAIL_SAMPLES else None),
        "tpot_p50_ms": percentile(tpots, 50),
        "out_tok_s": chars / (t1 - t0),
        "limits_met_share": met / len(inw) if inw else 0.0,
        "at_length_share": len(at_length) / len(every) if every else 0.0,
        "token_char_ratio": done_tok / done_chars if done_chars else None,
        "late_p99_ms": percentile(lates, 99),
        "late_max_ms": max(lates) if lates else None,
        "late_share": (sum(1 for x in lates if x > LATE_MS) / len(lates)
                       if lates else None),
    }
