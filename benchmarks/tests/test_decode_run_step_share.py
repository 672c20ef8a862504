"""`decode_run_step_share` (PR 53): the reader on synthetic counters, its
entry in BENCHMARK.json, and the CPU rehearsal of two tiny cells under
`benchmarks/tests/decode_run/`: the Pallas decode walk (interpreted) under a
system prompt of two whole 512-key softmax steps, which the first request
lays down as one ascending run of pages (the share well above 0), and the
same model on the XLA backend (no kernel walks, the counters stay 0: the
line lacks the metric)."""

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

TWIN = os.path.join(HERE, "decode_run")
NAME = "decode_run_step_share"


def engine(run, walked):
    return {"engine": {"decode_steps_run": run,
                       "decode_steps_walked": walked}}


@pytest.mark.parametrize("before,after,want", [
    (engine(100, 120), engine(1500, 1720), 87.5),   # 14 of 16 whole steps
    (engine(0, 50), engine(0, 450), 0.0),           # every step scattered
    (engine(0, 0), engine(0, 0), None),             # an XLA cell
    (engine(7, 9), engine(7, 9), None),             # no decode step in it
    ({"engine": {"decode_keys_walked": 5}},
     {"engine": {"decode_keys_walked": 9}}, None),  # the parent: no counter
    ({}, None, None),
])
def test_the_reader_reads_the_window_or_nothing(before, after, want):
    value = named.load((BENCH,), "layer_metrics", NAME).read(
        {"before": before, "after": after})
    assert value == (want if want is None else pytest.approx(want))


def test_the_entry_lists_the_cells_whose_global_layers_walk_in_the_kernel():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    assert real["per_layer"][-1]["name"] == NAME   # appended, nothing moved
    entry = real["per_layer"][-1]
    listed = dict(entry)
    cells = listed.pop("workloads")
    assert listed == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "Pallas kernels",
        "moves": "tpot_p50_ms"}
    files = {c["name"]: c["file"] for c in real["configs"]}
    for cell in real["workloads"]:
        with open(os.path.join(ROOT, files[cell["config"]])) as f:
            config = json.load(f)
        # StepPrograms.decode_steps' rule: the Pallas backend, and no
        # indexer (its full layers read chosen rows in XLA)
        walks = (config["expect"]["attention_backend"] == "pallas"
                 and not config.get("index_topk"))
        assert (cell["name"] in cells) == walks, cell["name"]
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    assert listed in twin["per_layer"]  # the twin asks every cell


@pytest.mark.parametrize("cell,share", [
    # ~3.6k keys a lane, the rendered prompt a run from page 1 on
    ("tiny-dense-run.chat-decode", (80.0, 100.0)),
    ("tiny-dense.chat-decode", None),
])
def test_rehearsal_of_the_tiny_cells(cell, share):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--root", TWIN,
         "--workload", cell, "--seed", "3000000053", "--seconds", "6",
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
