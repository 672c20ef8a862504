"""The reducer against a small trace recorded on the v5e
(benchmarks/tests/record_trace.py: two jitted programs, `body` and `fn`,
launched four times each inside `kafka.decode[...]` annotations, with host
sleeps of 10 and 20 ms between them)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402

TRACE = os.path.join(HERE, "recorded", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_planes(trace_reduce.load_xplane(TRACE))


def test_programs_and_launches(reduced):
    mods = {trace_reduce.instr_name(k): v for k, v in reduced["modules"].items()}
    assert set(mods) == {"jit_body", "jit_fn"}
    assert mods["jit_body"]["count"] == 4 and mods["jit_fn"]["count"] == 4
    # fn scans body eight times: about eight times the device time
    ratio = mods["jit_fn"]["total_s"] / mods["jit_body"]["total_s"]
    assert 4.0 < ratio < 12.0
    assert len(reduced["devices"]) == 1
    # `fn` is one scan: one innermost loop a launch; `body` has none
    assert mods["jit_fn"]["loops"] == 4 and mods["jit_body"]["loops"] == 0


def test_busy_is_the_union_not_the_sum(reduced):
    # the `while` of `fn` covers its body's fusions: summing would count twice
    summed = sum(reduced["op_self_s"].values())
    assert reduced["busy_s"] == pytest.approx(summed, rel=0.05)
    assert reduced["busy_s"] == pytest.approx(0.000477, rel=0.02)
    assert 0.08 < reduced["window_s"] < 0.1103
    assert reduced["worst_idle_share"] > 0.99
    assert reduced["op_self_s"]["while"] < 0.01 * reduced["op_self_s"]["fusion"]


def test_idle_gaps_are_labelled_by_the_host_span(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert set(gaps) == {"host in kafka.decode",
                         "host outside engine.py and kafka.* spans"}
    # 4 x sleep(0.01) inside the annotation, 4 x sleep(0.02) outside it
    assert 0.03 < gaps["host in kafka.decode"] + gaps[
        "host outside engine.py and kafka.* spans"] < 0.2
    assert reduced["longest_gaps_s"][0] > 0.02
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops[0][0].startswith("fusion")
    assert all(len(name) < 80 for name, _ in ops)


def test_names():
    hlo = ('%paged_decode_attention.6 = bf16[16,32,512]{2,1,0} custom-call('
           's32[16,1024]{1,0} %x), custom_call_target="tpu_custom_call"')
    assert trace_reduce.base_name(hlo) == "paged_decode_attention"
    assert trace_reduce.op_label(hlo) == "paged_decode_attention.6 bf16[16,32,512]"
    assert trace_reduce.is_kernel(hlo)
    assert trace_reduce.instr_name("jit_fn(8520029511854486875)") == "jit_fn"
    assert trace_reduce.host_label("$engine.py:2513 _drain") == "engine.py _drain"
    assert trace_reduce.host_label("kafka.decode[ab,cd]") == "kafka.decode"
    assert trace_reduce.host_label("$selectors.py:451 select") is None
