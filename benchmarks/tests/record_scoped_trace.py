"""Record the small SCOPED device trace that test_scope_reduce.py holds
scope_reduce.load_ops to.  Run on the chip (one process, ~40 s); writes
<out>/tiny_scoped.xplane.pb.

    python benchmarks/tests/record_scoped_trace.py chiprun_out/tiny_scoped

record_trace.py's method (Python tracer off, a few launches inside the
capture) on the program's own step programs: a 2-layer engine at toy widths
on the XLA attention path serves one request alone and then three at once, so
the capture holds `jit_fn_prefill_8`, `jit_fn_bprefill_8x4`, `jit_body_decode`
and `jit_fn_multi_decode_4` with the component scopes of
kafka_tpu.tracing.DEVICE_SCOPES in their ops' `tf_op`.  Only the device planes
are kept, without the `source` / `source_stack` stats (file paths of the
machine that recorded it).
"""
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import scope_reduce  # noqa: E402
import trace_reduce  # noqa: E402
from kafka_tpu.models import ModelConfig, init_params  # noqa: E402
from kafka_tpu.runtime import (  # noqa: E402
    EngineConfig, GenRequest, InferenceEngine)


def traffic(eng, tag: str) -> None:
    for i in range(4):
        eng.submit(GenRequest(request_id=f"{tag}{i}",
                              prompt_ids=[5, 9, 23, 4, 7, 11][: 3 + i],
                              max_new_tokens=12))
        if i == 0:
            eng.step()
    eng.run_to_completion()


def strip(src: str, dst: str) -> None:
    pb2 = scope_reduce.xplane_pb2()
    space = pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    keep = pb2.XSpace()
    for plane in space.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        drop = {k for k, v in plane.stat_metadata.items()
                if v.name in ("source", "source_stack")}
        for md in plane.event_metadata.values():
            kept = [s for s in md.stats if s.metadata_id not in drop]
            del md.stats[:]
            md.stats.extend(kept)
        keep.planes.append(plane)
    with open(dst, "wb") as f:
        f.write(keep.SerializeToString())


def main(out: str) -> None:
    os.makedirs(out, exist_ok=True)
    cfg = ModelConfig(name="tiny-scoped", vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, dtype="float32")
    eng = InferenceEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(7)),
        EngineConfig(max_batch=4, page_size=8, num_pages=64,
                     max_pages_per_seq=8, prefill_buckets=(8, 16),
                     multi_step=4, attention_backend="xla"),
        kv_dtype=jnp.float32)
    traffic(eng, "warm")  # compiles everything the capture will launch
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    traffic(eng, "traced")
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    dst = os.path.join(out, "tiny_scoped.xplane.pb")
    strip(src[0], dst)
    shutil.rmtree(tmp)
    print("wrote", dst, os.path.getsize(dst), "bytes from",
          jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1])
