"""The six fetch-pipeline readers (PR 35) on synthetic `ctx`s: window means
of the stage histograms and that they tile `ttft_fetch_ms`; the run-ahead
and blocked-read counters; and the capture-side device wait on hand-built
xplane-shaped planes, with launches from before the capture at its head and
one launch cut by its end.  Each reader reads None from a program that lacks
what it reads (the parent commit)."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import fetch_stages  # noqa: E402
import named  # noqa: E402


def reader(name):
    return named.load((BENCH,), "layer_metrics", name)


def hist(count, total):
    return {"le": [1.0, 2.0], "counts": [count, 0, 0], "count": count,
            "sum": total, "max": 0.0}


POPS = {"aged": 0, "depth": 0, "blocking": 0, "now": 0}


def window(before, after, **engine):
    """A ctx whose window saw `after - before` of every histogram, and the
    engine counters in `engine` as (before, after) pairs."""
    return {
        "before": {"histograms": {k: hist(*v) for k, v in before.items()},
                   "engine": {k: v[0] for k, v in engine.items()}},
        "after": {"histograms": {k: hist(*v) for k, v in after.items()},
                  "engine": {k: v[1] for k, v in engine.items()}},
        "t_open": 100.0, "t_close": 151.0,
    }


BEFORE = {"ttft_dev_wait_ms": (10, 5000.0), "ttft_dev_exec_ms": (10, 2000.0),
          "ttft_hold_ms": (10, 1000.0), "ttft_emit_ms": (10, 100.0),
          "ttft_fetch_ms": (10, 8100.0)}
AFTER = {"ttft_dev_wait_ms": (45, 40000.0), "ttft_dev_exec_ms": (45, 9000.0),
         "ttft_hold_ms": (45, 4500.0), "ttft_emit_ms": (45, 450.0),
         "ttft_fetch_ms": (45, 53950.0)}


@pytest.mark.parametrize("name,mean", [
    ("ttft_dev_wait_ms_mean", 1000.0),
    ("ttft_dev_exec_ms_mean", 200.0),
    ("ttft_hold_ms_mean", 100.0),
])
def test_a_stage_mean_is_the_ratio_of_two_window_deltas(name, mean, capsys):
    assert reader(name).read(window(BEFORE, AFTER)) == pytest.approx(mean)
    # a window without a first token, and a program without the histogram
    assert reader(name).read(window(BEFORE, BEFORE)) is None
    bare = {"ttft_fetch_ms": (10, 8100.0)}
    assert reader(name).read(window(bare, {"ttft_fetch_ms": (45, 1.0)})) is None
    capsys.readouterr()


def test_the_four_means_tile_the_fetch_phase_and_the_tile_is_printed(capsys):
    ctx = window(BEFORE, AFTER)
    tile = fetch_stages.tile(ctx)
    assert sum(tile[s] for s in fetch_stages.STAGES) == pytest.approx(
        tile["ttft_fetch_ms"])
    assert tile["tile_error_pct"] == pytest.approx(0.0, abs=1e-9)
    reader("ttft_dev_wait_ms_mean").read(ctx)
    err = capsys.readouterr().err
    assert "fetch_stages: tile " in err and '"ttft_emit_ms": 10.0' in err
    # the parent: nothing to tile, nothing raised
    assert fetch_stages.tile(window({}, {}))["tile_error_pct"] is None


def test_run_ahead_is_steps_per_sampled_dispatch():
    r = reader("fetch_depth_steps_mean")
    ctx = window({}, {}, fetch_depth_steps_sum=(1000, 13000),
                 fetch_depth_samples=(100, 300))
    assert r.read(ctx) == pytest.approx(60.0)
    assert r.read(window({}, {}, fetch_depth_steps_sum=(5, 5),
                         fetch_depth_samples=(3, 3))) is None
    assert r.read(window({}, {})) is None  # the parent's /metrics


def test_blocked_share_is_of_the_window_and_per_replica(capsys):
    r = reader("sched_fetch_blocked_share")
    ctx = window({}, {}, fetch_blocked_s=(2.0, 12.2),
                 fetch_pops=(POPS, dict(POPS, aged=40, depth=7)))
    assert r.read(ctx) == pytest.approx(20.0)  # 10.2 s of 51
    assert '"depth": 7' in capsys.readouterr().err
    ctx["after"]["replicas"] = [{}, {}, {}, {}]
    assert r.read(ctx) == pytest.approx(5.0)
    assert r.read(window({}, {})) is None


# --- the capture side ------------------------------------------------------

MS = 1_000_000


def planes(notes, launches, extra_host=(), extra_device=()):
    """xplane-shaped lists as trace_reduce.load_xplane gives them: one host
    plane with the annotations on a thread line, one device plane with the
    launches on its `XLA Modules` line.  Times in ms."""
    return [
        {"name": "/host:CPU", "lines": [{"name": "scheduler", "events": [
            (n, int(s * MS), int(d * MS)) for n, s, d in
            list(notes) + list(extra_host)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": []},
            {"name": "XLA Modules", "events": [
                (n, int(s * MS), int(d * MS)) for n, s, d in
                list(launches) + list(extra_device)]}]},
    ]


# the host dispatches D P D D P D P; the device is two launches behind when
# the capture begins (a decode and a prefill dispatched before it), runs
# everything in order, and the capture ends before the last prefill starts
NOTES = [
    ("kafka.decode[ab12cd34]", 10, 1), ("kafka.prefill[ab12cd34]", 20, 2),
    ("kafka.decode[]", 30, 1), ("kafka.decode[]", 40, 1),
    ("kafka.prefill[ef567890,ab12cd34]", 50, 3), ("kafka.decode[]", 60, 1),
    ("kafka.prefill[]", 70, 2),
]
LAUNCHES = [
    ("jit_fn_multi_decode_16(111)", 5, 40),      # dispatched before the capture
    ("jit_fn_prefill_2048(222)", 45, 30),        # likewise
    ("jit_body_decode(333)", 75, 10),            # <- kafka.decode @10
    ("jit_fn_prefill_512(444)", 85, 20),         # <- kafka.prefill @20: +63
    ("jit_fn_multi_decode_16(111)", 105, 40),    # <- kafka.decode @30
    ("jit_body_decode(333)", 145, 10),           # <- kafka.decode @40
    ("jit_fn_bprefill_512x4(555)", 155, 25),     # <- kafka.prefill @50: +102
    ("jit_fn_decode_fsm(666)", 180, 10),         # <- kafka.decode @60
]                                                # kafka.prefill @70: cut


def test_prefill_waits_pair_in_dispatch_order_and_drop_the_cut_launch():
    found = fetch_stages.prefill_dev_waits(planes(
        NOTES, LAUNCHES,
        # what else a capture holds and the pairing must not see
        extra_host=[("$engine.py:2513 _drain", 12, 1),
                    ("kafka.fetch[prefill]", 13, 1)],
        extra_device=[("jit_scatter(777)", 76, 1),
                      ("jit_convert_element_type(888)", 150, 1)]))
    assert found["shift"] == 2 and found["pairs"] == 6
    assert found["paired_by"] == "all dispatches"
    assert found["waits_ms"] == pytest.approx([85 - 22, 155 - 53])
    assert found["dropped"] == 1
    assert found["annotations"] == 7 and found["launches"] == 8


def test_host_seconds_inside_each_kind_of_annotation():
    secs = fetch_stages.annotation_seconds(planes(
        NOTES, LAUNCHES, extra_host=[("kafka.fetch[decode]", 12, 6),
                                     ("$engine.py:2513 _drain", 12, 7)]))
    assert secs == pytest.approx({"decode": 0.004, "prefill": 0.007,
                                  "fetch": 0.006, "span": 0.062})


def test_the_greedy_reading_would_pair_with_a_launch_from_before_the_capture():
    """Why the pairing counts every dispatch: the first prefill launch that
    starts after the first prefill annotation began (45 > 20) belongs to a
    dispatch from before the capture, and would read a wait of 23 ms where
    the launch's own is 63."""
    greedy = fetch_stages.pair_dispatches(
        [("prefill", 20 * MS, 22 * MS)], [("prefill", 45 * MS, 75 * MS)])
    assert greedy == (0, 1)
    found = fetch_stages.prefill_dev_waits(planes(NOTES, LAUNCHES))
    assert found["waits_ms"][0] == pytest.approx(63.0)


def test_an_unannotated_launch_falls_back_to_the_prefill_lists_alone():
    # a step program nobody annotated, in mid-capture: no shift lines the
    # whole lists up, the prefill lists alone still pair by causality
    stray = LAUNCHES[:5] + [("jit_fn_verify(999)", 144, 1)] + LAUNCHES[5:]
    found = fetch_stages.prefill_dev_waits(planes(NOTES, stray))
    assert found["paired_by"] == "prefill launches alone"
    assert len(found["waits_ms"]) >= 2 and all(
        w >= 0 for w in found["waits_ms"])


def test_no_launch_may_start_before_its_annotation_began():
    notes = [("prefill", 50 * MS, 51 * MS), ("decode", 60 * MS, 61 * MS)]
    launches = [("prefill", 40 * MS, 45 * MS), ("decode", 70 * MS, 75 * MS)]
    # shift 0 agrees in kind and breaks causality (40 < 50)
    assert fetch_stages.pair_dispatches(notes, launches)[0] != 0


def test_the_reader_takes_the_mean_prints_the_pairing_and_reads_none_bare(
        capsys):
    r = reader("prefill_dev_wait_ms_mean")
    cell = types.SimpleNamespace(name="synthetic")
    ctx = {"cell": cell, "trace": {"window_s": 5.0},
           "prefill_dev_waits": fetch_stages.prefill_dev_waits(
               planes(NOTES, LAUNCHES))}
    assert r.read(ctx) == pytest.approx((63.0 + 102.0) / 2)
    err = capsys.readouterr().err
    assert '"dropped": 1' in err and '"shift": 2' in err
    # an untraced run, and a capture without a prefill launch
    assert r.read({"cell": cell, "trace": None}) is None
    assert r.read({"cell": cell, "trace": {"window_s": 5.0},
                   "prefill_dev_waits": fetch_stages.prefill_dev_waits(
                       planes(NOTES[:1], LAUNCHES[2:3]))}) is None


def test_kinds_of_the_step_programs():
    kinds = {n: fetch_stages.module_kind(n) for n in (
        "jit_body_decode(1)", "jit_fn_decode_fsm(1)",
        "jit_fn_multi_decode_16_fsm(1)", "jit_fn_verify_fsm(1)",
        "jit_fn_prefill_2048(1)", "jit_fn_bprefill_512x4(1)",
        "jit_scatter(1)", "jit_fn(1)", "jit_body(1)")}
    assert kinds == {
        "jit_body_decode(1)": "decode", "jit_fn_decode_fsm(1)": "decode",
        "jit_fn_multi_decode_16_fsm(1)": "decode",
        "jit_fn_verify_fsm(1)": "verify",
        "jit_fn_prefill_2048(1)": "prefill",
        "jit_fn_bprefill_512x4(1)": "prefill",
        "jit_scatter(1)": None, "jit_fn(1)": None, "jit_body(1)": None}
