"""`decode_shared_key_share` (PR 51): the reader on synthetic counters, its
entry in BENCHMARK.json, and the CPU rehearsal of three tiny cells under
`benchmarks/tests/decode_shared/`: the XLA decode walk under a system prompt
longer than two of its trips (the share well above 0), the same model whose
threads share less than a trip (a lane shares a trip only with itself, where
it decodes alone), and a latent model (decode does not walk in XLA, the
counters stay 0: the line lacks the metric)."""

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

TWIN = os.path.join(HERE, "decode_shared")
NAME = "decode_shared_key_share"


def engine(shared, walked):
    return {"engine": {"decode_keys_shared": shared,
                       "decode_keys_walked": walked}}


@pytest.mark.parametrize("before,after,want", [
    (engine(1000, 1200), engine(15000, 18200), 82.352941),  # 14 of 17 trips
    (engine(0, 500), engine(0, 4500), 0.0),            # nothing in common
    (engine(0, 0), engine(0, 0), None),                # a Pallas cell
    (engine(7, 9), engine(7, 9), None),                # no decode step in it
    ({"engine": {"decode_keys_walked": 5}},
     {"engine": {"decode_keys_walked": 9}}, None),     # the parent: no counter
    ({}, None, None),
])
def test_the_reader_reads_the_window_or_nothing(before, after, want):
    value = named.load((BENCH,), "layer_metrics", NAME).read(
        {"before": before, "after": after})
    assert value == (want if want is None else pytest.approx(want))


def test_the_entry_lists_the_cell_that_walks_in_xla():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    entry, = [m for m in real["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "jitted step programs",
        "moves": "tpot_p50_ms", "workloads": ["mixtral-8x7b.chat-decode"]}
    # the cells whose decode is the XLA walk: the XLA backend, not latent;
    # the same cells as the walk's other counter metric
    window, = [m for m in real["per_layer"]
               if m["name"] == "decode_window_read_share"]
    assert entry["workloads"] == window["workloads"]
    files = {c["name"]: c["file"] for c in real["configs"]}
    for cell in real["workloads"]:
        with open(os.path.join(ROOT, files[cell["config"]])) as f:
            config = json.load(f)
        walks = (config["expect"]["attention_backend"] == "xla"
                 and "kv_lora_rank" not in config)
        assert (cell["name"] in entry["workloads"]) == walks, cell["name"]
    with open(os.path.join(TWIN, "BENCHMARK.json")) as f:
        twin = json.load(f)
    listed = dict(entry)
    del listed["workloads"]  # the twin asks every cell: a reader says None
    assert listed in twin["per_layer"]


@pytest.mark.parametrize("cell,share", [
    # ~1.4k keys a lane, 1,024 of them two shared trips of three
    ("tiny-dense-shared.chat-decode", (50.0, 100.0)),
    ("tiny-dense.chat-decode", (0.0, 100.0)),
    ("tiny-shared.chat-decode", None),
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
