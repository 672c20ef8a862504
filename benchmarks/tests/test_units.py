"""Traffic generation, the trace arithmetic on plain lists, the roofline
table and the end-to-end arithmetic: no JAX, no server."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import e2e  # noqa: E402
import loadgen  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402


def mixes():
    d = os.path.join(BENCH, "traffic")
    return sorted(f[:-5] for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("mix", mixes())
def test_same_seed_same_schedule_other_seed_another(mix):
    with open(os.path.join(BENCH, "traffic", mix + ".json")) as f:
        params = json.load(f)
    if params.get("request_rate", 1) is None:
        params["request_rate"] = 5.0
    a = loadgen.schedule(7, params, 20.0, "m")
    assert a == loadgen.schedule(7, params, 20.0, "m")
    assert a != loadgen.schedule(8, params, 20.0, "m")
    bodies = ([t["body"] for s in a["sessions"] for t in s["turns"]]
              if a["kind"] == "open_sessions"
              else [b for c in a["clients"] for b in c["requests"]])
    assert bodies
    lo, hi = params["message_bytes"]["min"], params["message_bytes"]["max"]
    for b in bodies:
        n = len(b["messages"][0]["content"].encode())
        assert lo <= n <= hi
        assert (params["max_tokens"]["min"] <= b["max_tokens"]
                <= params["max_tokens"]["max"])
        assert "<" not in b["messages"][0]["content"]


def test_open_sessions_offer_the_stated_request_rate():
    with open(os.path.join(BENCH, "traffic", "agent-sessions.json")) as f:
        params = json.load(f)
    params["request_rate"] = 6.0
    plan = loadgen.schedule(1, params, 2000.0, "m")
    turns = sum(len(s["turns"]) for s in plan["sessions"])
    assert turns / (2000.0 + params["lead_s"]) == pytest.approx(6.0, rel=0.05)
    assert loadgen.mean_turns(params["turns"]) == pytest.approx(4.0, abs=1e-6)


def test_roofline_unknown_device_kind_raises():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.roofline_share(1.0, 1.0, 1.0, "cpu")


def test_roofline_shapes():
    # one lane, 8192 tokens, Yi geometry: K and V of 4 heads x 128, bf16
    flops, nbytes = roofline.paged_decode([8192], 32, 4, 128, 16)
    assert nbytes == 2 * 8192 * 4 * 128 * 2 + 2 * 32 * 128 * 2
    assert flops == 4 * 8192 * 32 * 128
    share, bound = roofline.roofline_share(flops, nbytes, nbytes / 819e9,
                                           "TPU v5 lite")
    assert bound == "bandwidth" and share == pytest.approx(100.0)
    f2, _ = roofline.flash_prefill(512, 8000, 32, 4, 128)
    assert f2 == 4 * (512 * 8000 + 512 * 513 / 2) * 32 * 128
    yi = {"hidden_size": 4096, "intermediate_size": 11008, "vocab_size": 64000,
          "num_attention_heads": 32, "num_key_value_heads": 4,
          "num_hidden_layers": 20}
    _, step_bytes = roofline.decode_step(yi, [8192] * 16, 16)
    assert 12e9 < step_bytes < 15e9  # 7.97 GB of weights + 5.4 GB of KV


def test_union_self_time_and_reduce_on_plain_lists():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)])[0] == 30
    ev = [("while.1", 0, 100), ("fusion.2", 10, 30), ("fusion.3", 50, 40),
          ("copy.4", 120, 10)]
    own = trace_reduce.self_times(ev)
    assert own == {"while.1": 30, "fusion.2": 30, "fusion.3": 40, "copy.4": 10}
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ev},
            {"name": "XLA Modules", "events": [("jit_body(1)", 0, 100),
                                                ("jit_fn(2)", 120, 10)]}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ("kafka.decode[abc]", 95, 30), ("other", 0, 200)]}]},
    ]
    red = trace_reduce.reduce_planes(planes)
    assert red["window_s"] == pytest.approx(130e-9)  # the device lines' extent
    assert red["busy_s"] == pytest.approx(110e-9)
    assert red["worst_idle_share"] == pytest.approx(20 / 130)
    assert red["modules"]["jit_body(1)"]["count"] == 1
    assert red["modules"]["jit_body(1)"]["loops"] == 1
    assert red["op_count"]["fusion"] == 2
    assert red["op_self_s"]["fusion"] == pytest.approx(70e-9)
    assert red["breakdown"]["idle_gaps"][0][0] == "host in kafka.decode"
    assert red["breakdown"]["idle_gaps"][0][1] == pytest.approx(20e-9)
    assert trace_reduce.reduce_planes([planes[1]]) is None


def test_e2e_arithmetic():
    assert e2e.percentile([1, 2, 3, 4], 50) == 2.5
    assert e2e.percentile([], 50) is None

    def rec(due, first, last, n, **kw):
        return dict(due=due, t_send=due + 0.001, t_first=first, t_last=last,
                    t_end=last, done=True, error=None, chars=n,
                    chars_in_window=n, finish_reason="length", max_tokens=n,
                    usage={"prompt_tokens": 100, "completion_tokens": n},
                    in_window=True, **kw)

    log = [rec(0.0, 0.1, 1.1, 11), rec(1.0, 1.3, 2.3, 21)]
    s = e2e.summarize(log, "open_sessions", 0.0, 10.0,
                      {"ttft_ms": 250, "tpot_ms": 150})
    assert s["attempted"] == 2 and s["failed"] == 0
    assert s["ttft_p50_ms"] == pytest.approx(200.0)
    assert s["tpot_p50_ms"] == pytest.approx(75.0)  # (100 + 50) / 2
    assert s["out_tok_s"] == pytest.approx(3.2)
    assert s["limits_met_share"] == 0.5  # the second missed TTFT
    assert s["ttft_p90_ms"] is None  # under 100 samples: no tail
    log.append(dict(due=2.0, t_send=2.0, t_first=None, t_last=None,
                    t_end=None, done=False, error=None, chars=0,
                    chars_in_window=0, in_window=True))
    assert e2e.summarize(log, "open_sessions", 0, 10,
                         {"ttft_ms": 1, "tpot_ms": 1})["failed"] == 1
    assert e2e.summarize(log, "closed_loop", 0, 10,
                         {"ttft_ms": 1, "tpot_ms": 1})["failed"] == 0


def test_warm_turns():
    def turn(sess, n, prompt, cached):
        return dict(session=sess, turn=n, done=True, error=None,
                    in_window=True,
                    usage={"prompt_tokens": prompt, "completion_tokens": 8,
                           "prompt_tokens_details": {"cached_tokens": cached}})

    log = [turn("a", 1, 8000, 7900), turn("a", 2, 8400, 7990),
           turn("b", 1, 8000, 7900), turn("b", 2, 8300, 7900)]
    assert e2e.warm_turns(log, 16) == 0.5


def test_kv_pool_used_share_takes_the_fullest_replica():
    import run

    def snap(in_use, total):
        return {"engine": {"pages_in_use": in_use, "pages_total": total}}

    one = {"after": snap(50, 101)}
    assert run.read_layer_metric(BENCH, "kv_pool_used_share", one) == 50.0
    dp = {"after": {"replicas": [snap(10, 101), snap(80, 101)]}}
    assert run.read_layer_metric(BENCH, "kv_pool_used_share", dp) == 80.0
    assert run.read_layer_metric(BENCH, "kv_pool_used_share",
                                 {"after": {}}) is None
