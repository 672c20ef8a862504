"""Flight-recorder viewer: pretty-print the scheduler's dispatch timeline.

Reads either a LIVE ring from a running server::

    python scripts/flightview.py --url http://localhost:8000 --replica 0

or a postmortem dump (written next to the persisted traces on engine
failure / quarantine / failed recovery)::

    python scripts/flightview.py /path/to/postmortem.*.flight.json
    python scripts/flightview.py --latest          # newest dump in the
                                                   # configured dump dir

Output: one line per scheduler iteration — seq, wall time, inter-
iteration gap, dispatch kinds, batch composition, queue/page pressure,
modeled vs measured dispatch time, cause codes — followed by the anomaly
state and (for postmortems) the active-lane table, headline metrics and
the engine thread's account by phase (`sched`)
(including the live-HBM ``memory`` section when present, ISSUE 18).
The record schema and cause-code table are documented in README
"Flight recorder".

``--url ... --compiles`` switches to the compile observatory's ring
(GET /debug/compiles): one line per XLA compilation — label, phase,
cache hit/miss/off, wall seconds — plus storm state and totals.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional


def _fetch_live(url: str, replica: int,
                token: Optional[str] = None) -> Dict[str, Any]:
    return _fetch(url, f"/debug/flight/{replica}", token)


def _fetch(url: str, path: str,
           token: Optional[str] = None) -> Dict[str, Any]:
    from urllib.request import Request, urlopen

    req = Request(
        f"{url.rstrip('/')}{path}",
        headers={"Authorization": f"Bearer {token}"} if token else {},
    )
    with urlopen(req, timeout=10) as r:
        return json.load(r)


def _load_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _fmt_t(t: Optional[float]) -> str:
    if not t:
        return "-"
    return time.strftime("%H:%M:%S", time.localtime(t)) + f".{int(t % 1 * 1e3):03d}"


def _fmt_kinds(kinds: List[str]) -> str:
    short = {"prefill": "P", "decode": "D", "multi": "M",
             "verify": "V", "mixed": "X"}
    return "".join(short.get(k, "?") for k in kinds) or "-"


def _fmt_causes(causes: Dict[str, int]) -> str:
    if not causes:
        return ""
    return " ".join(f"{k}x{n}" if n > 1 else k
                    for k, n in sorted(causes.items()))


def print_records(records: List[Dict[str, Any]], tail: int) -> None:
    if tail > 0:
        records = records[-tail:]
    hdr = (f"{'seq':>7} {'time':>12} {'gap':>8} {'disp':>5} "
           f"{'lanes':>5} {'toks':>5} {'pf.tk':>5} {'spec':>4} "
           f"{'q':>3} {'act':>3} {'park':>4} {'pend':>4} "
           f"{'pg.free':>7} {'model ms':>8} {'meas ms':>8}  causes")
    print(hdr)
    print("-" * len(hdr))
    for r in records:
        print(
            f"{r['seq']:>7} {_fmt_t(r.get('t')):>12} "
            f"{r.get('gap_ms', 0):>7.1f}m {_fmt_kinds(r.get('kinds', [])):>5} "
            f"{r.get('lanes', 0):>5} {r.get('toks', 0):>5} "
            f"{r.get('prefill_toks', 0):>5} {r.get('spec_cands', 0):>4} "
            f"{r.get('queue_depth', 0):>3} {r.get('active', 0):>3} "
            f"{r.get('parked', 0):>4} {r.get('pending', 0):>4} "
            f"{r.get('pages_free', 0):>7} "
            f"{r.get('modeled_ms', 0):>8.3f} {r.get('measured_ms', 0):>8.3f}"
            f"  {_fmt_causes(r.get('causes', {}))}"
        )


def print_anomalies(anomalies: Dict[str, Any]) -> None:
    active = anomalies.get("active") or []
    if isinstance(anomalies, dict) and not active:
        # postmortem shape: {kind: {active, since, detail}}
        active = [
            {"kind": k, **v} for k, v in anomalies.items()
            if isinstance(v, dict) and v.get("active")
        ]
    if active:
        print("\nACTIVE ANOMALIES:")
        for a in active:
            rep = f" replica={a['replica']}" if "replica" in a else ""
            print(f"  !! {a['kind']}{rep} since {_fmt_t(a.get('since'))}: "
                  f"{a.get('detail')}")
    else:
        print("\nno active anomalies")


def print_lanes(lanes: List[Dict[str, Any]]) -> None:
    if not lanes:
        return
    print(f"\nLANES ({len(lanes)}):")
    hdr = (f"  {'request_id':<28} {'state':<10} {'slot':>4} {'age s':>7} "
           f"{'out':>5} {'disp':>5} {'drain':>5} {'pages':>5}  flags")
    print(hdr)
    for ln in lanes:
        flags = []
        if ln.get("grammar"):
            flags.append("grammar")
        if ln.get("host_constrained"):
            flags.append("host-mask")
        if ln.get("spec_ahead"):
            flags.append(f"spec+{ln['spec_ahead']}")
        if ln.get("cached_tokens"):
            flags.append(f"cached:{ln['cached_tokens']}"
                         f"({ln.get('cache_source')})")
        if ln.get("prefetch_staged_bytes"):
            flags.append(f"prefetch:{ln['prefetch_staged_bytes']}B")
        if ln.get("background"):
            flags.append("bg")
        if ln.get("awaiting_tool"):
            # mid-tool-call gap (ISSUE 20): lingering = demote timer
            # still running; demoted = pages already moved down-tier
            flags.append("await-tool" + ("(linger)" if ln.get("lingering")
                                         else ""))
            if ln.get("demoted_pages"):
                flags.append(f"demoted:{ln['demoted_pages']}pg")
        print(
            f"  {ln.get('request_id', '?'):<28} {ln.get('state', '?'):<10} "
            f"{ln.get('slot', -1):>4} {ln.get('age_s') or 0:>7.2f} "
            f"{ln.get('output_tokens', 0):>5} {ln.get('dispatched', 0):>5} "
            f"{ln.get('drained', 0):>5} {ln.get('pages', 0):>5}  "
            f"{' '.join(flags)}"
        )


def print_metrics_headline(m: Dict[str, Any]) -> None:
    if not m:
        return
    print("\nMETRICS AT CAPTURE:")
    req = m.get("requests") or {}
    print(f"  requests: {req}")
    slo = m.get("slo") or {}
    if slo:
        print(f"  slo: attainment={slo.get('slo_attainment')} "
              f"1m={slo.get('slo_attainment_1m')} "
              f"goodput_tok_s={slo.get('goodput_tok_s')}")
    util = m.get("utilization") or {}
    for kind in ("prefill", "decode", "verify"):
        u = util.get(kind) or {}
        if u.get("dispatches"):
            print(f"  {kind}: dispatches={u['dispatches']} "
                  f"mfu={u.get('mfu')} skew={u.get('model_skew')} "
                  f"measured_s={u.get('measured_busy_s')}")
    print_memory(m.get("memory") or {})
    print_sched(m.get("sched") or {})


def print_sched(sched: Dict[str, Any]) -> None:
    """The engine thread's account (ISSUE 52) — the `sched` metrics section:
    seconds by phase, largest first, each with the device's starved seconds
    charged to it."""
    phases = sorted(((k[:-2], v) for k, v in sched.items()
                     if k.endswith("_s") and f"starved_{k}" in sched),
                    key=lambda kv: -kv[1])
    if not phases:
        return
    total = sum(v for _, v in phases)
    print(f"  sched: {total:.3f}s over {sched.get('threads', 1)} thread(s); "
          f"dev_starved={sched.get('dev_starved_s')}s.."
          f"{sched.get('dev_starved_hi_s')}s in "
          f"{sched.get('dev_starved_gaps')} gaps; "
          f"wait_over={sched.get('wait_over_s')}s "
          f"delivered={sched.get('delivered')}")
    for name, secs in phases:
        if secs:
            print(f"    {name:<11} {secs:>12.6f}s {100 * secs / total:>6.2f}%"
                  f"  starved {sched.get(f'starved_{name}_s', 0.0):.6f}s"
                  f"..{sched.get(f'starved_hi_{name}_s', 0.0):.6f}s")


def print_memory(mem: Dict[str, Any]) -> None:
    """Live HBM accounting (ISSUE 18) — the `memory` metrics section."""
    if not mem or mem.get("source") == "none":
        return
    mib = 1 / (1024 * 1024)
    print(f"  memory[{mem.get('source')}]: "
          f"in_use={mem.get('hbm_bytes_in_use', 0) * mib:.1f}MiB "
          f"peak={mem.get('hbm_bytes_peak', 0) * mib:.1f}MiB "
          f"limit={mem.get('hbm_bytes_limit', 0) * mib:.1f}MiB "
          f"headroom={mem.get('hbm_headroom_bytes', 0) * mib:.1f}MiB "
          f"plan_skew={mem.get('hbm_plan_skew')} "
          f"pressure={mem.get('hbm_pressure', 0)}")
    comp = mem.get("hbm_component_bytes") or {}
    if comp:
        parts = " ".join(f"{k}={v * mib:.1f}MiB"
                         for k, v in comp.items())
        print(f"    components: {parts}")


def print_compiles(payload: Dict[str, Any], tail: int) -> None:
    """The compile observatory ring (GET /debug/compiles, ISSUE 18)."""
    totals = payload.get("totals") or {}
    storm = payload.get("storm") or {}
    print(f"ring: {len(payload.get('records', []))} records "
          f"(size {payload.get('ring_size')}, "
          f"{payload.get('next_seq')} total)  phase: "
          f"{payload.get('phase')}  cache_dir: "
          f"{payload.get('cache_dir') or '-'}")
    print(f"totals: {totals.get('compiles', 0)} compiles, "
          f"{totals.get('seconds', 0.0):.2f}s  "
          f"by_cache={totals.get('by_cache')}  "
          f"by_phase={totals.get('by_phase')}")
    if storm.get("active"):
        print(f"!! COMPILE STORM ACTIVE (threshold {storm.get('n')} in "
              f"{storm.get('window_s')}s; {storm.get('storms_total')} "
              f"storm(s) total)")
    records = payload.get("records") or []
    if tail > 0:
        records = records[-tail:]
    hdr = (f"{'seq':>6} {'time':>12} {'phase':>13} {'cache':>5} "
           f"{'secs':>8}  label")
    print(hdr)
    print("-" * len(hdr))
    for r in records:
        print(f"{r.get('seq', 0):>6} {_fmt_t(r.get('t')):>12} "
              f"{r.get('phase', '?'):>13} {r.get('cache', '?'):>5} "
              f"{r.get('seconds', 0.0):>8.3f}  {r.get('label', '?')}")


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Pretty-print a flight-recorder ring or postmortem")
    ap.add_argument("path", nargs="?",
                    help="postmortem JSON file (or - for stdin)")
    ap.add_argument("--url", help="fetch the live ring from a server")
    ap.add_argument("--replica", type=int, default=0,
                    help="replica index for --url (default 0)")
    ap.add_argument("--token", default=os.environ.get("KAFKA_TPU_API_TOKEN"),
                    help="bearer token for --url against a server with an "
                         "API token configured (default: "
                         "$KAFKA_TPU_API_TOKEN)")
    ap.add_argument("--latest", action="store_true",
                    help="open the newest postmortem in the dump dir")
    ap.add_argument("--compiles", action="store_true",
                    help="with --url: show the compile observatory ring "
                         "(GET /debug/compiles) instead of the flight "
                         "ring")
    ap.add_argument("-n", "--tail", type=int, default=64,
                    help="show only the last N records (0 = all)")
    ap.add_argument("--json", action="store_true",
                    help="dump the raw payload instead of the table")
    args = ap.parse_args()

    if args.url and args.compiles:
        payload = _fetch(args.url, "/debug/compiles", args.token)
        if args.json:
            json.dump(payload, sys.stdout, indent=2)
            print()
            return
        print("== COMPILE OBSERVATORY ==")
        print_compiles(payload, args.tail)
        return
    if args.compiles:
        ap.error("--compiles needs --url (it reads the live ring)")
        return
    if args.url:
        payload = _fetch_live(args.url, args.replica, args.token)
        title = f"LIVE ring, replica {payload.get('replica')}"
    elif args.latest:
        import sys as _sys

        _sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from kafka_tpu.runtime.flight_recorder import list_postmortems

        paths = list_postmortems()
        if not paths:
            print("no postmortem dumps found (set KAFKA_TPU_FLIGHT_DIR "
                  "or KAFKA_TPU_TRACE_PERSIST_DIR)", file=sys.stderr)
            raise SystemExit(1)
        payload = _load_file(paths[0])
        title = f"POSTMORTEM {paths[0]}"
    elif args.path:
        if args.path == "-":
            payload = json.load(sys.stdin)
            title = "POSTMORTEM <stdin>"
        else:
            payload = _load_file(args.path)
            title = f"POSTMORTEM {args.path}"
    else:
        ap.error("give a postmortem file, --latest, or --url")
        return

    if args.json:
        json.dump(payload, sys.stdout, indent=2)
        print()
        return

    print(f"== {title} ==")
    if payload.get("reason"):
        print(f"reason: {payload['reason']}  replica: "
              f"{payload.get('replica')}  pid: {payload.get('pid')}  "
              f"at: {_fmt_t(payload.get('t_wall'))}")
    print(f"ring: {len(payload.get('records', []))} records "
          f"(size {payload.get('ring_size')}, "
          f"{payload.get('next_seq')} total)")
    print_records(payload.get("records", []), args.tail)
    print_anomalies(payload.get("anomalies") or {})
    print_lanes(payload.get("lanes") or [])
    print_metrics_headline(payload.get("metrics") or {})


if __name__ == "__main__":
    main()
