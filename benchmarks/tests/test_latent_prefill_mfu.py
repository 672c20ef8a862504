"""The `latent_prefill_mfu` reader (PR 34) on synthetic captures: the MXU
work of a call from its operands' shapes in the event's HLO text, both ways
the text may carry them; the share against the bf16 peak; None where the
capture holds no such kernel; the calls found on the device's op line by the
kernel's name alone."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import named  # noqa: E402
import trace_reduce  # noqa: E402

mfu = named.load((BENCH,), "layer_metrics", "latent_prefill_mfu")

# the call as the v5e's compiler prints it (a described-v5e compile of the
# walk at the registered shapes, its Mosaic body cut): operands by name,
# their shapes under operand_layout_constraints
COMPILED = (
    '%latent_prefill_fold.6 = (f32[1,128,1,512]{3,2,1,0:T(1,128)S(1)}, '
    'f32[1,128,1,512]{3,2,1,0:T(1,128)S(1)}, f32[1,128,128,512]{3,2,1,0:'
    'T(8,128)S(1)}) custom-call(%get-tuple-element.338, %get-tuple-element'
    '.339, %convolution_bitcast_fusion.4, %slice_select_fusion.2, '
    '%convolution_bitcast_fusion.5, /*index=5*/%select_bitcast_fusion.2, '
    '%bitcast.73, %bitcast.70, %get-tuple-element.307), custom_call_target='
    '"tpu_custom_call", operand_layout_constraints={bf16[1,128,128,512]'
    '{3,2,1,0}, bf16[1,128,64,512]{3,2,1,0}, bf16[1,128,1024,128]{3,2,1,0}, '
    'bf16[1,1024,64]{2,1,0}, bf16[1,128,128,1024]{3,2,1,0}, f32[1,1024,512]'
    '{2,1,0}, f32[1,128,1,512]{3,2,1,0}, f32[1,128,1,512]{3,2,1,0}, '
    'f32[1,128,128,512]{3,2,1,0}}, output_to_operand_aliasing={{0}: (6, {})}')
FULL_FLOPS = 2.0 * 1 * 128 * 512 * 1024 * (128 + 64 + 128)


def traced(b, n, s, t, dn, dr, dv, i=1):
    """The same call as the profiler prints an op: operands with shapes."""
    return (
        f"%latent_prefill_fold.{i} = (f32[{b},{n},1,{s}]{{3,2,1,0}}, "
        f"f32[{b},{n},1,{s}]{{3,2,1,0}}, f32[{b},{n},{dv},{s}]{{3,2,1,0}}) "
        f"custom-call(bf16[{b},{n},{dn},{s}]{{3,2,1,0:T(8,128)(2,1)}} %a, "
        f"bf16[{b},{n},{dr},{s}]{{3,2,1,0}} %b, bf16[{b},{n},{t},{dn}]"
        f"{{3,2,1,0}} %c, bf16[{b},{t},{dr}]{{2,1,0}} %d, "
        f"bf16[{b},{n},{dv},{t}]{{3,2,1,0}} %e, f32[{b},{t},{s}]{{2,1,0}} %f, "
        f"f32[{b},{n},1,{s}]{{3,2,1,0}} %g, f32[{b},{n},1,{s}]{{3,2,1,0}} %h, "
        f"f32[{b},{n},{dv},{s}]{{3,2,1,0}} %i), "
        'custom_call_target="tpu_custom_call"')


def ctx(calls, **kw):
    return dict({"latent_fold_calls": calls, "info": {"kind": "TPU v5 lite"},
                 "cell": types.SimpleNamespace(name="synthetic")}, **kw)


def test_work_of_a_call_is_read_from_its_own_shapes():
    assert mfu.call_flops(COMPILED) == FULL_FLOPS
    assert mfu.call_flops(traced(1, 128, 512, 1024, 128, 64, 128)) == FULL_FLOPS
    # a sliding layer, a 64-row bucket padded to a lane tile, four lanes
    assert mfu.call_flops(traced(4, 64, 128, 1024, 192, 64, 128)) == (
        2.0 * 4 * 64 * 128 * 1024 * (192 + 64 + 128))
    assert mfu.call_flops("%latent_prefill_fold.1 = f32[8] custom-call()") is None
    assert mfu.call_flops("%fusion.3 = f32[1,128,512,128] fusion(...)") is None


def test_share_is_executed_work_over_time_and_peak():
    least = FULL_FLOPS / 197e12          # a trip at the bf16 peak: 218 us
    assert least == pytest.approx(218.0e-6, rel=2e-3)
    calls = [(traced(1, 128, 512, 1024, 128, 64, 128, i), 2 * least)
             for i in range(29)]
    assert mfu.read(ctx(calls)) == pytest.approx(50.0)
    # geometries mix by the work each executed
    slide = traced(1, 64, 512, 1024, 192, 64, 128)
    both = calls + [(slide, mfu.call_flops(slide) / 197e12)] * 3
    want = 100.0 * (29 + 3 * 0.6) / (58 + 3 * 0.6)
    assert mfu.read(ctx(both)) == pytest.approx(want)
    # a call whose shapes cannot be read voids the reading: no guess
    assert mfu.read(ctx(calls + [("%latent_prefill_fold.9 = f32[8] "
                                  "custom-call()", 1e-4)])) is None
    with pytest.raises(KeyError, match="no peaks"):
        mfu.read(ctx(calls, info={"kind": "TPU v9"}))


def test_a_capture_without_the_kernel_reads_none():
    assert mfu.read(ctx([])) is None         # the parent, the xla backend
    assert mfu.read(ctx(None)) is None       # no capture on disk
    assert mfu.read({"trace": None, "info": {"kind": "TPU v5 lite"},
                     "cell": types.SimpleNamespace(name="synthetic")}) is None


def test_calls_are_found_by_name_on_the_device_op_line(monkeypatch, tmp_path):
    fold = traced(1, 128, 512, 1024, 128, 64, 128)
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                (fold, 0, 400_000),
                ("%paged_decode_attention_latent_window.2 = bf16[32,64,1024] "
                 "custom-call(...)", 500_000, 150_000),
                ("%fusion.7 = f32[1,128,512,128] fusion(...)", 700_000, 9),
                (fold.replace("fold.1", "fold.12"), 800_000, 360_000)]},
            {"name": "XLA Modules", "events": [
                ("jit_fn_prefill_512(123)", 0, 2_000_000)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [(fold, 0, 5)]}]},
    ]
    seen = []
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda d: seen.append(d) or str(tmp_path / "x.pb"))
    monkeypatch.setattr(trace_reduce, "load_xplane", lambda path: planes)
    c = {"trace": {"busy_s": 1.0}, "info": {"kind": "TPU v5 lite"},
         "cell": types.SimpleNamespace(name="dots3-note-prev.chat-decode")}
    got = mfu.read(c)
    assert seen[0].endswith(
        os.path.join(".bench_out", "dots3-note-prev.chat-decode", "trace"))
    assert [s for _, s in c["latent_fold_calls"]] == [400e-6, 360e-6]
    assert got == pytest.approx(100.0 * 2 * FULL_FLOPS / 197e12 / 760e-6)
    assert got < 100.0
    # the other readers' patterns do not match the kernel's name
    for other in ("paged_prefill", "paged_decode"):
        assert other not in fold.split(" = ")[0]


def test_the_metric_is_registered_for_the_one_cell():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    assert entry == {
        "name": "latent_prefill_mfu", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Pallas kernels",
        "moves": "tpot_p50_ms", "workloads": ["dots3-note-prev.chat-decode"]}
