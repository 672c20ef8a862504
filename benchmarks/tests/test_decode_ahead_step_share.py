"""`decode_ahead_step_share` (PR 62): the reader on synthetic counters and its
entry in BENCHMARK.json.  (The counters against the kernel's own arithmetic,
on a live engine of both backends, are tests/test_metrics.py's.)"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import named  # noqa: E402

NAME = "decode_ahead_step_share"


def engine(ahead, every):
    return {"engine": {"decode_steps_ahead": ahead,
                       "decode_steps_all": every}}


@pytest.mark.parametrize("before,after,want", [
    (engine(10, 100), engine(310, 2820), 100.0 * 300 / 2720),  # 2 of ~17
    (engine(0, 50), engine(0, 450), 0.0),           # one lane a call
    (engine(0, 0), engine(0, 0), None),             # an XLA cell
    (engine(7, 90), engine(7, 90), None),           # no decode step in it
    ({"engine": {"decode_steps_walked": 5}},
     {"engine": {"decode_steps_walked": 9}}, None),  # the parent: no counter
    ({}, None, None),
])
def test_the_reader_reads_the_window_or_nothing(before, after, want):
    value = named.load((BENCH,), "layer_metrics", NAME).read(
        {"before": before, "after": after})
    assert value == (want if want is None else pytest.approx(want))


def test_the_entry_lists_the_cells_decode_run_step_share_lists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    by_name = {m["name"]: m for m in real["per_layer"]}
    entry = dict(by_name[NAME])
    assert entry.pop("workloads") == by_name["decode_run_step_share"][
        "workloads"]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": "tpot_p50_ms"}
