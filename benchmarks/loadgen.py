"""The one general traffic generator and the HTTP/SSE client that drives it.

A traffic mix is a data file, `benchmarks/traffic/<mix>.json`; a cell file's
`params` override single keys of it (the fixed rate, the session cap).  Two
kinds of mix exist:

- `open_sessions`: sessions arrive at `request_rate` / E[turns] a second, one
  to each slot of that length at a random place in it (a Poisson process'
  rate without its run-to-run variance in the amount of work).  A session is one thread: turn 1 is due at the
  arrival, turn n+1 is due `think_s` after turn n ended (an agent waits for
  its reply).  `request_rate` x `expected_session_s` / E[turns] sessions with
  part of their turns left arrive at the start of the lead-in: the sessions
  that are alive when the window opens.  At most `max_alive` sessions live at once; one that arrives
  above the cap is shed and its first turn counts as attempted and failed.
- `closed_loop`: `clients` callers, each sending its next one-shot request (a
  thread of its own) when the previous one completed.

Both start `lead_s` before the measured window opens, so that the window
sees a steady state (live sessions mid-life, decode lanes out of phase); the
lead-in is set-up.  Everything is drawn from `--seed`; time is
`time.monotonic()` of the one asyncio loop that sends every request.
"""

from __future__ import annotations

import asyncio
import functools
import json
import math
import random
import statistics
import time
from typing import Any, Dict, List, Optional

import aiohttp

LETTERS = "abcdefghijklmnopqrstuvwxyz"
DRAIN_S = 15.0  # an open-loop request may finish this long after the window


# --------------------------------------------------------------------------
# schedule: pure functions of (seed, params)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def geometric_pmf(mean: float, lo: int, hi: int) -> tuple:
    """p(k) ~ r^(k-lo) on lo..hi with r solved so that the mean is `mean`."""
    def mean_of(r: float) -> float:
        w = [r ** (k - lo) for k in range(lo, hi + 1)]
        return sum(k * x for k, x in zip(range(lo, hi + 1), w)) / sum(w)

    a, b = 1e-6, 50.0
    for _ in range(200):
        mid = (a + b) / 2
        if mean_of(mid) < mean:
            a = mid
        else:
            b = mid
    r = (a + b) / 2
    w = [r ** (k - lo) for k in range(lo, hi + 1)]
    t = sum(w)
    return tuple(x / t for x in w)


def quantile(spec: Any, q: float) -> float:
    """The q-quantile (0 < q < 1) of a distribution spec of a traffic file."""
    if not isinstance(spec, dict):
        return spec
    dist, lo, hi = spec["dist"], spec.get("min"), spec.get("max")
    if dist == "const":
        v = spec["value"]
    elif dist == "uniform":
        v = lo + q * (hi - lo)
    elif dist == "lognormal":
        v = spec["median"] * math.exp(
            spec["sigma"] * statistics.NormalDist().inv_cdf(q))
    elif dist == "geometric":
        acc, v = 0.0, hi
        for k, p in zip(range(lo, hi + 1),
                        geometric_pmf(spec["mean"], lo, hi)):
            acc += p
            if q <= acc:
                v = k
                break
    else:
        raise ValueError(f"unknown dist {dist!r}")
    if lo is not None:
        v = max(lo, v)
    if hi is not None:
        v = min(hi, v)
    return int(round(v)) if spec.get("int", False) else v


def stratified(rng: random.Random, spec: Any, n: int) -> List[float]:
    """n values of the distribution, one from each of its n equal-probability
    strata, in random order.  The marginal is the distribution's; the sum over
    a run is nearly the same for every seed, so that two runs differ by what
    the system does with the work and not by how much work they drew."""
    out = [quantile(spec, (j + rng.random()) / n) for j in range(n)]
    rng.shuffle(out)
    return out


def mean_turns(spec: Any) -> float:
    if not isinstance(spec, dict):
        return float(spec)
    if spec["dist"] == "geometric":
        pmf = geometric_pmf(spec["mean"], spec["min"], spec["max"])
        return sum(k * p for k, p in zip(
            range(spec["min"], spec["max"] + 1), pmf))
    if spec["dist"] == "const":
        return float(spec["value"])
    raise ValueError("turns must be const or geometric")


def text(rng: random.Random, n_bytes: int) -> str:
    """n_bytes of lower-case words: one byte-token each, no '<' (specials),
    no leading '{' or '[' (the provider's tool-call buffering)."""
    out: List[str] = []
    left = n_bytes
    while left > 0:
        w = "".join(rng.choices(LETTERS, k=min(left, rng.randint(2, 9))))
        out.append(w)
        left -= len(w)
        if left > 0:
            out.append(" ")
            left -= 1
    return "".join(out)


def bodies(model: str, rng: random.Random, params: Dict[str, Any],
           n: int) -> List[Dict[str, Any]]:
    """n request bodies whose lengths are stratified over the n."""
    sizes = stratified(rng, params["message_bytes"], n)
    outs = stratified(rng, params["max_tokens"], n)
    return [{
        "model": model, "stream": True,
        "temperature": params.get("temperature", 0),
        "max_tokens": int(outs[i]),
        "messages": [{"role": "user", "content": text(rng, int(sizes[i]))}],
    } for i in range(n)]


def schedule(seed: int, params: Dict[str, Any], seconds: float,
             model: str) -> Dict[str, Any]:
    """The whole run's traffic as data.  Times are seconds relative to the
    window's opening (negative: lead-in).  Every quantity is stratified
    (`stratified`): arrivals fall one to a slot of 1 / rate seconds, at a
    random place in it, and turn counts, lengths and think times are one from
    each stratum of their distribution, so a run's amount of work hardly
    depends on the seed while its order and timing do."""
    rng = random.Random(f"{seed}:{params['kind']}")
    lead = float(params.get("lead_s", 0.0))
    if params["kind"] == "open_sessions":
        rate = float(params["request_rate"]) / mean_turns(params["turns"])
        # the sessions that are mid-life when the window opens: by Little's
        # law rate x expected_session_s of them, arriving in the first
        # seconds of the lead-in with an even spread of their turns left
        n0 = round(rate * float(params.get("expected_session_s", 0)))
        n1 = int((lead + seconds) * rate)
        arrive = ([-lead + rng.uniform(0.0, min(3.0, lead)) for _ in range(n0)]
                  + [-lead + (k + rng.random()) / rate for k in range(n1)])
        turns = [int(t) for t in stratified(rng, params["turns"], n0 + n1)]
        left = stratified(rng, {"dist": "uniform", "min": 0.0, "max": 1.0}, n0)
        for j in range(n0):
            turns[j] = max(1, math.ceil(turns[j] * left[j]))
        total = sum(turns)
        reqs = bodies(model, rng, params, total)
        think = stratified(rng, params["think_s"], total)
        sessions, at = [], 0
        for i, (t, n) in enumerate(zip(arrive, turns)):
            sessions.append({
                "id": f"s{seed}-{i}", "arrive_s": t,
                "turns": [{"body": reqs[at + j], "think_s": float(think[at + j])}
                          for j in range(n)],
            })
            at += n
        return {"kind": "open_sessions", "sessions": sessions, "lead_s": lead}
    if params["kind"] == "closed_loop":
        per_client, n = int(params.get("requests_per_client", 64)), int(
            params["clients"])
        # the k-th requests of all clients are one stratified set
        waves = [bodies(model, rng, params, n) for _ in range(per_client)]
        clients = [{
            "id": f"c{seed}-{c}",
            "start_s": -lead + c * float(params.get("stagger_s", 0.05)),
            "requests": [waves[k][c] for k in range(per_client)],
        } for c in range(n)]
        return {"kind": "closed_loop", "clients": clients, "lead_s": lead}
    raise ValueError(f"unknown traffic kind {params['kind']!r}")


# --------------------------------------------------------------------------
# the client: one request over SSE, timed on this loop's clock
# --------------------------------------------------------------------------

async def one_request(http: aiohttp.ClientSession, base: str, path: str,
                      body: Dict[str, Any], rec: Dict[str, Any],
                      window: tuple) -> Dict[str, Any]:
    """POST a streamed completion.  `rec` is filled in place, so a request
    cancelled at the window's end keeps what it had received."""
    w0, w1 = window
    rec.update(t_send=time.monotonic(), t_first=None, t_last=None, chars=0,
               chars_in_window=0, usage=None, finish_reason=None,
               status=None, error=None, t_end=None, done=False,
               max_tokens=body["max_tokens"])
    try:
        async with http.post(base + path, json=body) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = f"http {resp.status}"
                return rec
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = time.monotonic()
                data = raw[5:].strip()
                if data == b"[DONE]":
                    rec["done"] = True
                    break
                ev = json.loads(data)
                if ev.get("type") == "error" or "error" in ev:
                    rec["error"] = json.dumps(ev)[:300]
                    continue
                if ev.get("object") != "chat.completion.chunk":
                    continue
                choice = (ev.get("choices") or [{}])[0]
                content = (choice.get("delta") or {}).get("content")
                if content:
                    if rec["t_first"] is None:
                        rec["t_first"] = now
                    rec["t_last"] = now
                    rec["chars"] += len(content)
                    if w0 <= now < w1:
                        rec["chars_in_window"] += len(content)
                if choice.get("finish_reason"):
                    rec["finish_reason"] = choice["finish_reason"]
                if ev.get("usage"):
                    rec["usage"] = ev["usage"]
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        rec["t_end"] = time.monotonic()
    if not rec["done"] and rec["error"] is None:
        rec["error"] = "stream ended without [DONE]"
    return rec


def request_path(thread_id: str) -> str:
    """Every request is a turn of a thread (its own, for a one-shot request):
    only requests that carry a thread's prefix key read or feed the radix
    prefix cache; the stateless endpoint bypasses it (PERF.md section 6)."""
    return f"/v1/threads/{thread_id}/chat/completions"


async def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        await asyncio.sleep(left)


class Driver:
    """Runs one schedule against one server and keeps the client log."""

    def __init__(self, base: str, plan: Dict[str, Any], params: Dict[str, Any],
                 t_open: float, seconds: float):
        self.base, self.plan, self.params = base, plan, params
        self.t0, self.t1 = t_open, t_open + seconds
        self.log: List[Dict[str, Any]] = []
        self.alive = 0
        self.alive_peak = 0

    def _rec(self, **kw) -> Dict[str, Any]:
        rec = dict(kw)
        rec["in_window"] = self.t0 <= rec["due"] < self.t1
        self.log.append(rec)
        return rec

    async def _session(self, http, sess) -> None:
        arrive = self.t0 + sess["arrive_s"]
        await sleep_until(arrive)
        if self.alive >= int(self.params.get("max_alive", 1 << 30)):
            rec = self._rec(session=sess["id"], turn=1, due=arrive, shed=True)
            rec.update(error="shed: max_alive", t_send=time.monotonic())
            return
        self.alive += 1
        self.alive_peak = max(self.alive_peak, self.alive)
        try:
            due = arrive
            for n, turn in enumerate(sess["turns"], start=1):
                if due >= self.t1:
                    return  # the window's end cuts the session
                await sleep_until(due)
                rec = self._rec(session=sess["id"], turn=n, due=due)
                await one_request(
                    http, self.base, request_path(sess["id"]),
                    turn["body"], rec, (self.t0, self.t1))
                if rec["error"]:
                    return
                due = rec["t_end"] + turn["think_s"]
        finally:
            self.alive -= 1

    async def _client(self, http, client) -> None:
        await sleep_until(self.t0 + client["start_s"])
        for k, body in enumerate(client["requests"]):
            due = time.monotonic()
            if due >= self.t1:
                return
            rec = self._rec(client=client["id"], due=due)
            await one_request(http, self.base,
                              request_path(f"{client['id']}-{k}"),
                              body, rec, (self.t0, self.t1))
            if rec["error"]:
                await asyncio.sleep(0.2)  # do not spin on a refusing server

    async def run(self, http: aiohttp.ClientSession,
                  side_tasks: Optional[List] = None) -> None:
        if self.plan["kind"] == "open_sessions":
            tasks = [asyncio.ensure_future(self._session(http, s))
                     for s in self.plan["sessions"]]
            deadline = self.t1 + DRAIN_S
        else:
            tasks = [asyncio.ensure_future(self._client(http, c))
                     for c in self.plan["clients"]]
            deadline = self.t1  # in-flight requests are cut, not failed
        side = [asyncio.ensure_future(t) for t in (side_tasks or [])]
        try:
            while time.monotonic() < deadline:
                open_in_window = any(
                    r["in_window"] and r.get("t_end") is None
                    for r in self.log)
                if (time.monotonic() >= self.t1 and not open_in_window
                        and self.plan["kind"] == "open_sessions"):
                    break
                await asyncio.sleep(0.05)
        finally:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            for s in side:
                if not s.done():
                    s.cancel()
            results = await asyncio.gather(*side, return_exceptions=True)
            for r in results:
                if isinstance(r, Exception) and not isinstance(
                        r, asyncio.CancelledError):
                    raise r
