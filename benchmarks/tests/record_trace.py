"""Record the small device trace that test_trace_reduce.py holds the reducer
to.  Run on the chip (one process, ~10 s); writes <out>/tiny.xplane.pb.

    python benchmarks/tests/record_trace.py chiprun_out/tiny_trace

Two jitted programs (`body`, a matmul chain; `fn`, a scan of it) launched a
few times inside `kafka.decode[...]` annotations with host sleeps between
them, so the capture has device ops, program launches, annotations and idle
gaps of known order.  The Python tracer is off to keep the file small.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    os.makedirs(out, exist_ok=True)
    x = jnp.ones((1024, 1024), jnp.bfloat16)

    def body(a):
        return jnp.tanh(a @ a) * 0.5

    def fn(a):
        return jax.lax.scan(lambda c, _: (body(c), None), a, None, length=8)[0]

    jb, jf = jax.jit(body), jax.jit(fn)
    jb(x).block_until_ready()
    jf(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = os.path.join(out, "raw")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(4):
        with jax.profiler.TraceAnnotation(f"kafka.decode[{i:08x}]"):
            jb(x).block_until_ready()
            jf(x).block_until_ready()
            time.sleep(0.01)
        time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copy(src[0], os.path.join(out, "tiny.xplane.pb"))
    shutil.rmtree(tmp)
    print("wrote", os.path.join(out, "tiny.xplane.pb"),
          os.path.getsize(os.path.join(out, "tiny.xplane.pb")), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
