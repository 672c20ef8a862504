"""CPU rehearsal of run.py end to end: tiny configurations under
benchmarks/tests/tiny, the real served path (serve.py -> create_app), the real
generator, the real verdict.  The line it prints is contract-shaped, its
device says cpu and its `correct` is false; without --rehearse the same
command finds no TPU and prints no result."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
       "--root", os.path.join(HERE, "tiny")]


def run(args, timeout=400):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(RUN + args, cwd=ROOT, env=env, timeout=timeout,
                          capture_output=True, text=True)


@pytest.mark.parametrize("cell,trace,expect", [
    ("tiny-dense.agent-sessions", 0, {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}),
    ("tiny-moe.chat-decode", 1, {"loadgen_late_p99_ms", "prefix_hit_share",
                                 "decode_batch_occupancy",
                                 "client_ttft_p50_ms",
                                 "kv_pool_used_share"}),
    ("tiny-dense-dp2.agent-sessions", 1, {"router_warm_share",
                                          "replica_req_spread",
                                          "limits_met_share",
                                          "client_tpot_p50_ms",
                                          "kv_pool_used_share"}),
    # the shape of yi-1.5-9b-dp4.chat-decode: a closed loop of dp_size x
    # max_batch clients across the router; lanes per step summed over the
    # replicas (readers.batch_occupancy)
    ("tiny-dense-dp2.chat-decode", 1, {"replica_req_spread",
                                       "queue_wait_p90_ms",
                                       "decode_batch_occupancy",
                                       "prefix_hit_share",
                                       "kv_pool_used_share"}),
])
def test_rehearsal_prints_a_contract_shaped_line(cell, trace, expect):
    p = run(["--workload", cell, "--seed", "3", "--seconds", "6",
             "--trace", str(trace), "--rehearse"])
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert expect <= set(line["metrics"])
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]
    # device metrics never come from a CPU run
    assert not {"device_idle", "decode_step_dev_ms", "paged_attn_roofline",
                "hbm_peak_gb"} & set(line["metrics"])
    assert "busy_s" not in line["device"]


def test_without_a_tpu_there_is_no_result():
    p = run(["--workload", "tiny-dense.agent-sessions", "--seed", "3",
             "--seconds", "2", "--trace", "0"], timeout=120)
    assert p.returncode == 3
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
