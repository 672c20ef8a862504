"""The scheduler-account and boot readers (PR 52): `sched_account.py`'s
overlap arithmetic on plain lists, every reader on synthetic snapshots (and
on the parent's, which have no account: None, never a raise), their entries
in BENCHMARK.json, and the CPU rehearsal of the tiny cell under
`benchmarks/tests/sched_account/`, whose traced line must carry every reader
that does not need a device plane."""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import named  # noqa: E402
import sched_account  # noqa: E402

TWIN = os.path.join(HERE, "sched_account")
PHASES = ("idle_wait", "hold_wait", "inbox", "paused", "house", "drain",
          "admit", "prefill", "hold_check", "decode", "flush", "flight",
          "deliver")
NEW = {
    "sched_busy_share": ("%", "program_counter",
                         "admission, parking, batching", "out_tok_s"),
    "sched_deliver_share": ("%", "program_counter",
                            "HTTP + agent loop + provider", "out_tok_s"),
    "sched_wait_over_share": ("%", "program_counter",
                              "admission, parking, batching", "out_tok_s"),
    "sched_admit_iter_ms_p50": ("ms", "program_span",
                                "admission, parking, batching",
                                "tpot_p50_ms"),
    "dev_starved_share": ("%", "program_counter", "device", "tpot_p50_ms"),
    "idle_unnamed_share": ("%", "device_trace", "device", "tpot_p50_ms"),
    "boot_weights_s": ("s", "program_counter", "boot", "setup_s"),
    "boot_warmup_s": ("s", "program_counter", "boot", "setup_s"),
    "boot_trace_lower_s": ("s", "program_counter", "boot", "setup_s"),
}


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def section(seconds=None, starved=None, **kw):
    out = {"threads": 1, "wait_over_s": 0.0, "delivered": 0,
           "dev_starved_s": 0.0, "dev_starved_hi_s": 0.0,
           "dev_starved_gaps": 0}
    for p in PHASES:
        out[p + "_s"] = (seconds or {}).get(p, 0.0)
        out["starved_" + p + "_s"] = (starved or {}).get(p, 0.0)
        out["starved_hi_" + p + "_s"] = 2 * (starved or {}).get(p, 0.0)
    out.update(kw)
    return out


def hist(counts):
    le = [0.25 * 2 ** (i / 2) for i in range(len(counts))]
    return {"le": le, "counts": counts, "count": sum(counts),
            "sum": float(sum(c * b for c, b in zip(counts, le))), "max": le[-1]}


def ctx_of(before, after, **kw):
    return dict({"before": before, "after": after, "t_open": 10.0,
                 "t_close": 61.0, "wall_open": 1000.0, "wall_close": 1051.0},
                **kw)


# --------------------------------------------------------------------------
# the overlap arithmetic
# --------------------------------------------------------------------------

@pytest.mark.parametrize("gaps,spans,want", [
    # one gap under three phases that touch, and one outside it
    ([(0, 10)], [("a", 0, 4), ("b", 4, 9), ("a", 9, 30), ("c", 40, 50)],
     {"a": 5, "b": 5, "c": 0}),
    # a phase that covers two gaps and the busy time between them
    ([(0, 10), (20, 30)], [("a", 5, 25)], {"a": 10}),
    # a gap no span reaches: nothing charged, all of it unnamed
    ([(100, 110)], [("a", 0, 50)], {"a": 0}),
    # spans given out of order, gaps too
    ([(20, 30), (0, 10)], [("a", 25, 40), ("a", 0, 5), ("b", 5, 25)],
     {"a": 10, "b": 10}),
    ([], [("a", 0, 5)], {"a": 0}),
    ([(0, 10)], [], {}),
])
def test_gaps_are_charged_by_overlap(gaps, spans, want):
    assert sched_account.charge_gaps(gaps, spans) == want


def planes_of(ops, notes):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [("%f = fusion()", s, d)
                                           for s, d in ops]},
            {"name": "XLA Modules", "events": []}]},
        {"name": "/host:CPU", "lines": [
            {"name": "engine", "events": [(n, s, e - s)
                                          for n, s, e in notes]},
            {"name": "loop", "events": [
                ("kafka.metrics.snapshot", 1_450, 100),
                ("$engine.py:10 step", 0, 5_000)]}]},
    ]


def test_idle_seconds_go_to_the_phases_that_cover_them():
    """Busy 0-1000 and 2000-3000 ns, idle 1000-2000: 300 ns under `decode`
    (which began while the chip was busy), 500 under `deliver`, 100 under
    `hold_wait`, and 100 ns that no phase covers."""
    planes = planes_of(
        ops=[(0, 1000), (2000, 1000)],
        notes=[("kafka.sched.decode", 700, 1300),
               ("kafka.sched.deliver", 1300, 1800),
               ("kafka.sched.hold_wait", 1900, 2600),
               ("kafka.decode[ab12]", 800, 900)])  # not a phase
    got = sched_account.idle_by_phase(planes)
    assert got["idle_s"] == pytest.approx(1000e-9)
    assert got["by_phase_s"] == pytest.approx(
        {"deliver": 500e-9, "decode": 300e-9, "hold_wait": 100e-9})
    assert got["unnamed_s"] == pytest.approx(100e-9)
    assert got["unnamed_share"] == pytest.approx(10.0)
    assert got["beside_s"] == pytest.approx(
        {"kafka.metrics.snapshot": 100e-9})
    assert got["phase_spans"] == 3 and got["gaps"] == 1


def test_a_chip_that_never_idles_reads_zero_not_nothing():
    planes = planes_of(ops=[(0, 1000), (1000, 1000)],
                       notes=[("kafka.sched.decode", 0, 2000)])
    got = sched_account.idle_by_phase(planes)
    assert got["idle_s"] == 0.0 and got["unnamed_share"] == 0.0
    ctx = {"idle_by_phase": got, "trace": {}, "profile": None}
    assert reader("idle_unnamed_share").read(ctx) == 0.0


def test_a_capture_without_the_annotations_reads_nothing():
    """The parent's capture (and the recorded v5e one): idle gaps and no
    `kafka.sched.*` span to hold them against."""
    planes = planes_of(ops=[(0, 1000), (2000, 1000)],
                       notes=[("kafka.decode[ab12]", 800, 900)])
    got = sched_account.idle_by_phase(planes)
    assert got["phase_spans"] == 0 and got["unnamed_share"] == 100.0
    assert reader("idle_unnamed_share").read(
        {"idle_by_phase": got, "trace": {}}) is None
    assert reader("idle_unnamed_share").read(
        {"idle_by_phase": None, "trace": None}) is None
    import trace_reduce

    recorded = trace_reduce.load_xplane(
        os.path.join(HERE, "recorded", "tiny_v5e.xplane.pb"))
    got = sched_account.idle_by_phase(recorded)
    assert got is not None and got["phase_spans"] == 0
    assert got["idle_s"] > 0.0 and got["window_s"] > got["idle_s"]


# --------------------------------------------------------------------------
# the readers on synthetic snapshots
# --------------------------------------------------------------------------

def window():
    before = {"uptime_s": 100.0, "sched": section(
        {"idle_wait": 50.0, "decode": 30.0, "deliver": 5.0, "drain": 15.0},
        starved={"drain": 0.5}, dev_starved_s=0.5, dev_starved_hi_s=0.9,
        dev_starved_gaps=40, delivered=1000, wait_over_s=1.0)}
    after = {"uptime_s": 151.0, "sched": section(
        {"idle_wait": 50.0, "hold_wait": 20.4, "decode": 45.0,
         "deliver": 10.1, "drain": 25.5},
        starved={"drain": 1.5, "deliver": 2.0}, dev_starved_s=3.5,
        dev_starved_hi_s=5.9, dev_starved_gaps=400, delivered=52000,
        wait_over_s=2.02)}
    return before, after


def test_the_window_is_the_sum_of_its_phases():
    d = sched_account.window(ctx_of(*window()))
    assert d["interval_s"] == pytest.approx(51.0)
    assert d["by_phase"]["hold_wait"] == pytest.approx(20.4)
    assert d["starved_by_phase"]["deliver"] == pytest.approx(2.0)
    assert d["starved_hi_by_phase"]["deliver"] == pytest.approx(4.0)
    assert d["dev_starved_gaps"] == 360


@pytest.mark.parametrize("name,want", [
    ("sched_busy_share", 100.0 * (51.0 - 20.4) / 51.0),
    ("sched_deliver_share", 100.0 * 5.1 / 51.0),
    ("sched_wait_over_share", 100.0 * 1.02 / 51.0),
    ("dev_starved_share", 100.0 * 3.0 / 51.0),
])
def test_shares_of_the_window(name, want, capsys):
    assert reader(name).read(ctx_of(*window())) == pytest.approx(want)
    assert "sched_account: " in capsys.readouterr().err or \
        name == "sched_wait_over_share"


def test_two_threads_share_by_the_thread():
    before, after = window()
    for snap in (before, after):
        snap["sched"] = {k: (2 * v if k != "threads" else 2)
                         for k, v in snap["sched"].items()}
    ctx = ctx_of(before, after)
    assert sched_account.window(ctx)["interval_s"] == pytest.approx(51.0)
    assert reader("sched_deliver_share").read(ctx) == pytest.approx(10.0)


def test_dev_starved_share_leaves_the_captures_bracket_out(capsys):
    """The capture's bracket (at_start .. at_stop_return) holds 20 s of the
    51 and 2.4 of the 3 starved seconds: what is left is 0.6 s in 31."""
    before, after = window()
    b0 = section({"decode": 40.0}, dev_starved_s=1.0, dev_starved_hi_s=2.0)
    b1 = section({"decode": 45.0}, dev_starved_s=1.4, dev_starved_hi_s=2.9)
    b2 = section({"decode": 60.0}, dev_starved_s=3.4, dev_starved_hi_s=4.0)
    profile = {"sched_window": {
        "at_start": {"t": 1017.0, "sched": b0},
        "at_stop_call": {"t": 1022.0, "sched": b1},
        "at_stop_return": {"t": 1037.0, "sched": b2}}}
    read = reader("dev_starved_share").read
    assert read(ctx_of(before, after, profile=profile)) == pytest.approx(
        100.0 * 0.6 / 31.0)
    said = json.loads(capsys.readouterr().err.split("starved ", 1)[1])
    assert said["whole_window"]["share"] == pytest.approx(100 * 3.0 / 51.0)
    assert said["capture_bracket"]["dev_starved_s"] == pytest.approx(2.4)
    assert said["less_bracket"]["seconds"] == pytest.approx(31.0)
    # a bracket that ends after the window closed: the whole window
    profile["sched_window"]["at_stop_return"]["t"] = 1060.0
    assert read(ctx_of(before, after, profile=profile)) == pytest.approx(
        100.0 * 3.0 / 51.0)
    # a reply without the marks (the parent's): the whole window
    assert read(ctx_of(before, after, profile={"flight_window": {}})) == \
        pytest.approx(100.0 * 3.0 / 51.0)
    # the reply came after the window closed (stop_trace outlasted it):
    # the marks taken so far ride on the last /metrics snapshot, and what
    # came before the capture is the part of the window nobody disturbed:
    # 40 - 30 = 10 s of `decode` since the opening, 1.0 - 0.5 s starved
    opened = {"idle_wait": 50.0, "decode": 30.0, "deliver": 5.0,
              "drain": 15.0}
    b0 = section(dict(opened, decode=40.0), dev_starved_s=1.0,
                 dev_starved_hi_s=2.0, starved={"drain": 0.5})
    b1 = section(dict(opened, decode=45.0), dev_starved_s=1.4,
                 dev_starved_hi_s=2.9, starved={"drain": 0.5})
    late = dict(after, sched_window={
        "at_start": {"t": 1017.0, "sched": b0},
        "at_stop_call": {"t": 1022.0, "sched": b1}})
    assert read(ctx_of(before, late, profile=None)) == pytest.approx(
        100.0 * 0.5 / 10.0)
    said = json.loads(capsys.readouterr().err.rsplit("starved ", 1)[1])
    assert said["before_capture"]["seconds"] == pytest.approx(10.0)
    brackets = sched_account.capture_brackets(ctx_of(before, late))
    assert set(brackets) == {"traced", "before"}
    traced = sched_account.capture_brackets(
        ctx_of(before, after, profile=profile))["traced"]
    assert traced["interval_s"] == pytest.approx(5.0)
    assert traced["dev_starved_s"] == pytest.approx(0.4)


def test_admit_iterations_median_over_the_window(capsys):
    names = [f"sched_iter_{c}_ms" for c in
             ("admit", "prefill", "multi", "decode", "held")]
    zero = {n: hist([0] * 12) for n in names}
    after = dict(zero, sched_iter_admit_ms=hist([0] * 9 + [4, 0, 0]),
                 sched_iter_multi_ms=hist([0, 0, 7] + [0] * 9))
    read = reader("sched_admit_iter_ms_p50").read
    got = read({"before": {"histograms": zero},
                "after": {"histograms": after}})
    le = hist([0] * 12)["le"]
    assert le[8] <= got <= le[9]
    said = json.loads(capsys.readouterr().err.split("iterations ", 1)[1])
    assert said["admit"]["n"] == 4 and said["multi"]["n"] == 7
    assert said["held"] == {"n": 0, "p50_ms": None}
    # the histograms are there and no iteration of the window admitted: 0.0
    assert read({"before": {"histograms": after},
                 "after": {"histograms": after}}) == 0.0


def test_boot_readers():
    boot = {"import_s": 1.5, "weights_s": 20.25, "engine_build_s": 3.0,
            "grammar_s": 2.0, "warmup_s": 61.5, "rest_s": 0.5}
    compiles = {
        "trace_seconds_by_phase": {"boot": 1.0, "warmup": 6.5,
                                   "first_traffic": 100.0},
        "lower_seconds_by_phase": {"boot": 0.25, "warmup": 12.0}}
    ctx = {"after": {"boot": boot, "compiles": compiles}}
    assert reader("boot_weights_s").read(ctx) == 20.25
    assert reader("boot_warmup_s").read(ctx) == 61.5
    assert reader("boot_trace_lower_s").read(ctx) == 19.75


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_parent_has_nothing_to_read(name):
    """A program without the account, the boot section or the stage sums:
    every reader says None and none raises (the driver lays these files
    over the parent's checkout too)."""
    snap = {"uptime_s": 5.0, "engine": {"fetch_blocked_s": 0.1},
            "histograms": {"ttft_ms": hist([1, 2])},
            "compiles": {"compiles_total": 3, "by_phase": {"boot": 3}}}
    ctx = ctx_of(snap, dict(snap, uptime_s=56.0), trace=None, log=[],
                 profile={"flight_window": {"t_start": 1.0, "t_end": 9.0}},
                 cell=types.SimpleNamespace(name="none.at-all"))
    assert reader(name).read(ctx) is None
    assert reader(name).read(ctx_of({}, None)) is None


# --------------------------------------------------------------------------
# BENCHMARK.json and the rehearsal
# --------------------------------------------------------------------------

def test_the_entries_ask_every_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    tail = real["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)  # appended, in order
    e2e = {m["name"] for m in real["end_to_end"]}
    for m in tail:
        unit, source, layer, moves = NEW[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": source, "layer": layer, "moves": moves}
        assert moves in e2e and m in twin["per_layer"]
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))


def test_rehearsal_of_the_tiny_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", "tiny-dense.chat-decode", "--seed", "3000000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    got = line["metrics"]
    # the CPU has no device plane: the capture has no idle gap to charge
    assert set(got) >= set(NEW) - {"idle_unnamed_share"}
    for name in set(NEW) & set(got):
        assert got[name]["unit"] == NEW[name][0]
    assert 0.0 < got["sched_busy_share"]["value"] <= 100.0
    assert 0.0 < got["sched_deliver_share"]["value"] < 50.0
    assert got["sched_admit_iter_ms_p50"]["value"] > 0.0
    assert got["boot_warmup_s"]["value"] > got["boot_weights_s"]["value"] > 0
    assert got["boot_trace_lower_s"]["value"] > 0.0
    said = next(ln for ln in p.stderr.splitlines()
                if ln.startswith("sched_account: window "))
    tile = json.loads(said.split("window ", 1)[1])
    # the phases tile the thread's time: their window delta is the window
    assert abs(tile["sum_vs_uptime_pct"]) < 2.0  # uptime_s rounds to 0.1 s
    assert tile["sum_s"] == pytest.approx(6.0, abs=0.3)
