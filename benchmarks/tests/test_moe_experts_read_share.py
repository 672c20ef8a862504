"""`moe_experts_read_share` (PR 48): the reader on synthetic counters, its
entry in BENCHMARK.json, and the CPU rehearsal of three tiny cells under
`benchmarks/tests/experts_read/`: a routed model whose decode passes dispatch
by token (the share under 100), one whose passes keep the dense einsums (100),
and a model with no routed block (the counters stay 0: the line lacks the
metric)."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import named  # noqa: E402

TWIN = os.path.join(HERE, "experts_read")
NAME = "moe_experts_read_share"
ROUTED = ["mixtral-8x7b", "mellum2-12b-a2.5b", "kanana-2-30b-a3b",
          "dots3-note-prev", "k-exaone-236b-a23b", "lfm2-8b-a1b"]


def engine(read, held):
    return {"engine": {"moe_experts_read": read, "moe_experts_held": held}}


@pytest.mark.parametrize("before,after,want", [
    (engine(100, 160), engine(1320, 2160), 61.0),   # token dispatch
    (engine(512, 512), engine(4608, 4608), 100.0),  # every held expert read
    (engine(0, 0), engine(0, 0), None),             # no routed block
    (engine(7, 9), engine(7, 9), None),             # no decode pass in it
    ({"engine": {}}, {"engine": {}}, None),         # the parent: no counters
    ({}, None, None),
])
def test_the_reader_reads_the_window_or_nothing(before, after, want):
    value = named.load((BENCH,), "layer_metrics", NAME).read(
        {"before": before, "after": after})
    assert value == (want if want is None else pytest.approx(want))


def test_the_entry_lists_the_routed_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    entry = next(m for m in real["per_layer"] if m["name"] == NAME)
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "jitted step programs",
        "moves": "tpot_p50_ms",
        "workloads": [c + ".chat-decode" for c in ROUTED]}
    cells = {w["name"]: w["config"] for w in real["workloads"]}
    files = {c["name"]: c["file"] for c in real["configs"]}
    for cell, config in cells.items():
        with open(os.path.join(ROOT, files[config])) as f:
            raw = json.load(f)
        routed = any(k in raw for k in ("num_local_experts", "num_experts",
                                        "n_routed_experts"))
        assert (cell in entry["workloads"]) == routed, cell
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    listed = dict(entry)
    del listed["workloads"]  # the twin asks every cell: a reader says None
    assert listed in twin["per_layer"]


@pytest.mark.parametrize("cell,share", [
    ("tiny-unread.chat-decode", (1.0, 15.0)),  # 1-2 lanes x top-2 of 64
    ("tiny-allread.chat-decode", (100.0, 100.0)),
    ("tiny-dense.chat-decode", None),
])
def test_rehearsal_of_the_tiny_cells(cell, share):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", cell, "--seed", "3000000019", "--seconds", "6",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=400, capture_output=True, text=True)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    if share is None:
        assert NAME not in line["metrics"]
        return
    value = line["metrics"][NAME]
    assert value["unit"] == "%" and share[0] <= value["value"] <= share[1]
