"""Device time by component and by program, read from the capture itself.

    python benchmarks/scope_reduce.py <trace_dir>     # the account, as JSON
    python benchmarks/scope_reduce.py --table <trace_dir>   # as a table
    python benchmarks/scope_reduce.py --table --config <configuration file> \
        <trace_dir>                 # with the configuration's own `scopes`

The program names its device work (PR 24): `jax.named_scope` components in
`models/llama.py` and the engine's step programs (`kafka_tpu.tracing.
DEVICE_SCOPES`), and one jitted function name per step program
(`runtime/engine.program_name`).  A v5e capture carries both: every `XLA Ops`
event's METADATA has the stats `tf_op` (the HLO `op_name`, e.g.
`jit(fn_multi_decode_16)/step_ctl/while/body/layers/while/body/closed_call/
attn_core/dot_general:`), `hlo_category`, `bytes_accessed` and `program_id`
(the fingerprint in the `XLA Modules` event name `jit_fn_multi_decode_16(<id>)`).
`jax.profiler.ProfileData` does not expose an event's metadata stats, so this
file parses the xplane protobuf with the `xplane_pb2` that ships inside the
installed tensorflow wheel, loaded by path: importing `tensorflow` itself
takes 9 s and is not needed.

Attribution.  An op's component is the INNERMOST scope of `SCOPES` in its
`tf_op` path; `layers` itself (an op of the layer scan's body under no leaf
scope: the scan's slicing and write-back of its stacked inputs, and the
`while` op's own time) is reported as `scan_plumbing`; a `tf_op` with no known
scope is `other`; no `tf_op` at all (compiler-inserted copies and the like) is
`unscoped`.  A fusion carries the `tf_op` of its root instruction: XLA fuses
across scope boundaries (an RMSNorm's multiply into the matmul that consumes
it), so a component's seconds are those of the fusions ROOTED in it.  Time is
self time, as in `trace_reduce.self_times` (ops nest under `while`), so the
components of a capture sum to its busy time and nothing is counted twice.
Names are metadata, and JAX's persistent compile cache leaves metadata out of
its key: a program that two trees build alike but for its scopes, under the
same function name, is compiled once, and the capture shows the names of
whichever tree compiled it (my chip run D, PR 24: the parent's `jit_body` read
the scopes of this PR's).  So a capture in which a step program with 1% of the
busy time names no component has no shares (`unnamed_programs`), and the
program's single decode step is `jit_body_decode`, a name the parent never
compiled.
The table of scopes is the benchmark's own copy (tests/test_tracing.py holds
`SCOPES` to be a subset of the program's registry).  A scope the program adds
later for a new block is listed by the configuration that runs the block, under
`scopes` in its own file: `component` then stops at it as at one of `SCOPES`,
and it is a component of its own, a row of the table and part of no existing
share.  A scope nobody lists stays with the enclosing component, and directly
under the layer scan that is `scan_plumbing`, which `dev_kv_move_share` sums:
so a configuration lists every scope of its block.  With no `scopes` key the
account is what it was.

The arithmetic (`account`) works on plain lists and is tested without a
profile; `load_ops` is checked against the recorded v5e capture
`benchmarks/tests/recorded/tiny_scoped_v5e.xplane.pb`.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
import sys
from typing import Any, Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import trace_reduce  # noqa: E402

SCAN_SCOPE = "layers"
SCOPES = (
    "embed", SCAN_SCOPE, "attn_norm", "attn_qkv", "kv_write", "attn_core",
    "attn_gather", "attn_out", "mlp_norm", "mlp", "moe_router", "moe_experts",
    "head", "sample", "fsm", "step_ctl",
)
SCAN_PLUMBING, OTHER, UNSCOPED = "scan_plumbing", "other", "unscoped"
PREFILL_PROGRAM = re.compile(r"^jit_fn_b?prefill")
# the engine's step programs (`runtime/engine.program_name`); one with this
# share of the busy time and no named op leaves the capture without shares
STEP_PROGRAM = re.compile(r"^jit_(body|fn)")
UNNAMED_MIN = 0.01
# a launch on the `XLA Modules` line: `jit_fn_prefill_2048(<program id>)`
FINGERPRINT = re.compile(r"^(.*)\((\d+)\)$")
TOP = 10

# name (HLO text), start_ps, duration_ps, tf_op, hlo_category, program_id,
# bytes_accessed
Op = Tuple[str, int, int, Optional[str], Optional[str], Optional[int],
           Optional[int]]


@functools.lru_cache(maxsize=None)
def component(tf_op: Optional[str], extra: Tuple[str, ...] = ()) -> str:
    """`extra`: the configuration's own scopes, beside `SCOPES`."""
    if not tf_op:
        return UNSCOPED
    for seg in reversed(tf_op.rstrip(":").split("/")):
        if seg in SCOPES or seg in extra:
            return SCAN_PLUMBING if seg == SCAN_SCOPE else seg
    return OTHER


def config_scopes(config: Dict[str, Any]) -> Tuple[str, ...]:
    """The `scopes` a configuration file lists for its own block."""
    extra = tuple(config.get("scopes") or ())
    clash = set(extra) & {SCAN_PLUMBING, OTHER, UNSCOPED}
    if clash:
        raise ValueError(f"`scopes` may not list {sorted(clash)}")
    return extra


def account(planes: List[Dict[str, Any]],
            scopes: Tuple[str, ...] = ()) -> Optional[Dict[str, Any]]:
    """planes: [{"name", "ops": [Op], "modules": [(name, start_ps, dur_ps)]}],
    one per chip.  Seconds are summed over the chips.  An op's program is
    the launch whose fingerprint its `program_id` gives; an op without one
    (no v5e capture so far has any) is under the program `?`.  `scopes` are
    the configuration's own, components beside those of `SCOPES`."""
    table: Dict[str, Dict[str, float]] = {}
    # (op label, category, component) -> [seconds, calls, bytes a call]
    loose: Dict[Tuple[str, str, str], List[Any]] = {}
    scoped = False
    for plane in planes:
        by_id = {int(m.group(2)): m.group(1) for m in
                 (FINGERPRINT.match(e[0]) for e in plane["modules"]) if m}
        keyed = []
        for op in plane["ops"]:
            comp = component(op[3], scopes)
            scoped = scoped or comp not in (UNSCOPED, OTHER)
            label = trace_reduce.op_label(op[0])
            keyed.append(((by_id.get(op[5], "?"), comp, label, op[4] or ""),
                          op[1], op[2]))
            if comp in (UNSCOPED, OTHER):
                t = loose.setdefault((label, op[4] or "", comp),
                                     [0.0, 0, op[6] or 0])
                t[1] += 1
        for (prog, comp, label, cat), ps in \
                trace_reduce.self_times(keyed).items():
            row = table.setdefault(prog, {})
            row[comp] = row.get(comp, 0.0) + ps / 1e12
            if comp in (UNSCOPED, OTHER):
                loose[(label, cat, comp)][0] += ps / 1e12
    if not table:
        return None
    by_component: Dict[str, float] = {}
    for row in table.values():
        for comp, s in row.items():
            by_component[comp] = by_component.get(comp, 0.0) + s
    top = sorted(loose.items(), key=lambda kv: -kv[1][0])[:TOP]
    busy = sum(by_component.values())
    return {
        "busy_s": busy,
        "scoped": scoped,
        "unnamed_programs": sorted(
            p for p, r in table.items()
            if STEP_PROGRAM.match(p) and sum(r.values()) >= UNNAMED_MIN * busy
            and not set(r) - {UNSCOPED, OTHER}),
        "by_component": by_component,
        "by_program": {p: sum(r.values()) for p, r in table.items()},
        "table": table,
        "top_unattributed": [
            {"op": label, "category": cat, "component": comp,
             "seconds": t[0], "calls": t[1], "bytes_accessed": t[2]}
            for (label, cat, comp), t in top],
    }


def share(acc: Optional[Dict[str, Any]], components: Iterable[str] = (),
          programs: Optional[re.Pattern] = None) -> Optional[float]:
    """Percent of the capture's device busy time in the named components
    (over all programs) or in the programs whose name matches.  None where
    the capture names no component at all, or a step program with 1% of the
    busy time names none: a program from before the scopes has no account, not an
    account of zeros, and a capture that mixes the two has no shares."""
    if (not acc or not acc["scoped"] or acc["unnamed_programs"]
            or acc["busy_s"] <= 0):
        return None
    if programs is not None:
        part = sum(s for p, s in acc["by_program"].items()
                   if programs.search(p))
    else:
        part = sum(acc["by_component"].get(c, 0.0) for c in components)
    return 100.0 * part / acc["busy_s"]


@functools.lru_cache(maxsize=1)
def xplane_pb2():
    """The generated protobuf module for xplane.proto, or None.  It lives
    in the tensorflow wheel and needs only `google.protobuf`."""
    try:
        spec = importlib.util.find_spec("tensorflow")
        base = list(spec.submodule_search_locations)[0]
        path = os.path.join(base, "tsl", "profiler", "protobuf",
                            "xplane_pb2.py")
        mod_spec = importlib.util.spec_from_file_location(
            "_bench_xplane_pb2", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod
    except Exception as e:  # no wheel, no file, protobuf too old: no account
        print(f"scope_reduce: no xplane_pb2 to parse the capture with "
              f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)
        return None


def load_ops(path: str) -> Optional[List[Dict[str, Any]]]:
    """The device planes of an `.xplane.pb` as `account` wants them."""
    pb2 = xplane_pb2()
    if pb2 is None:
        return None
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        meta: Dict[int, Tuple] = {}

        def describe(mid: int) -> Tuple:
            if mid not in meta:
                md = plane.event_metadata[mid]
                st: Dict[str, Any] = {}
                for s in md.stats:
                    key = stat_name.get(s.metadata_id)
                    if key in ("tf_op", "hlo_category"):
                        st[key] = (s.str_value
                                   or stat_name.get(s.ref_value, ""))
                    elif key in ("program_id", "bytes_accessed"):
                        st[key] = s.uint64_value or s.int64_value
                meta[mid] = (md.name, st.get("tf_op") or None,
                             st.get("hlo_category") or None,
                             st.get("program_id"), st.get("bytes_accessed"))
            return meta[mid]

        ops: List[Op] = []
        modules: List[Tuple[str, int, int]] = []
        for line in plane.lines:
            t0 = line.timestamp_ns * 1000
            if line.name == trace_reduce.OPS_LINE:
                for ev in line.events:
                    name, tf_op, cat, pid, nbytes = describe(ev.metadata_id)
                    ops.append((name, t0 + ev.offset_ps, ev.duration_ps,
                                tf_op, cat, pid, nbytes))
            elif line.name == trace_reduce.MODULES_LINE:
                modules = [(plane.event_metadata[ev.metadata_id].name,
                            t0 + ev.offset_ps, ev.duration_ps)
                           for ev in line.events]
        if ops:
            planes.append({"name": plane.name, "ops": ops,
                           "modules": modules})
    return planes


def account_dir(trace_dir: str,
                scopes: Tuple[str, ...] = ()) -> Optional[Dict[str, Any]]:
    path = trace_reduce.find_xplane(trace_dir)
    planes = load_ops(path) if path else None
    return account(planes, scopes) if planes else None


def of_ctx(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The account of the run a reader is called for, computed once.  The
    capture is where run.py told the server to put it:
    `<checkout>/.bench_out/<cell name>/trace`."""
    if "scope_account" not in ctx:
        acc = ctx["scope_account"] = (
            None if not ctx.get("trace") else account_dir(
                os.path.join(ROOT, ".bench_out", ctx["cell"].name, "trace"),
                config_scopes(ctx["cell"].config)))
        if acc and acc["scoped"] and acc["unnamed_programs"]:
            print("scope_reduce: no shares, these programs name no "
                  f"component: {acc['unnamed_programs']}", file=sys.stderr,
                  flush=True)
    return ctx["scope_account"]


def table_lines(acc: Dict[str, Any]) -> List[str]:
    """The component x program table in percent of busy time, as markdown."""
    progs = sorted(acc["by_program"], key=lambda p: -acc["by_program"][p])
    comps = sorted(acc["by_component"], key=lambda c: -acc["by_component"][c])
    pct = lambda s: f"{100.0 * s / acc['busy_s']:.2f}"
    out = ["| component | " + " | ".join(progs) + " | all |",
           "|---|" + "---|" * (len(progs) + 1)]
    for c in comps:
        out.append(f"| `{c}` | " + " | ".join(
            pct(acc["table"][p].get(c, 0.0)) for p in progs)
            + f" | {pct(acc['by_component'][c])} |")
    out.append("| all | " + " | ".join(
        pct(acc["by_program"][p]) for p in progs) + " | 100.00 |")
    return out


if __name__ == "__main__":
    argv = sys.argv[1:]
    as_table = "--table" in argv
    own: Tuple[str, ...] = ()
    if "--config" in argv:
        with open(argv[argv.index("--config") + 1]) as f:
            own = config_scopes(json.load(f))
    result = account_dir(argv[-1], own)
    if result is None:
        print("scope_reduce: no device ops in " + argv[-1],
              file=sys.stderr)
        raise SystemExit(1)
    if as_table:
        print(f"busy {result['busy_s']:.4f} s, scoped {result['scoped']}, "
              f"unnamed programs {result['unnamed_programs']}")
        print("\n".join(table_lines(result)))
        for row in result["top_unattributed"]:
            print(json.dumps(row))
    else:
        print(json.dumps(result, indent=1))
