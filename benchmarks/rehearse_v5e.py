"""Compile for a DESCRIBED v5e (no chip attached; on-chip-measurement guide,
rehearsal 3): the Pallas kernels at a configuration's geometry and one decode
step and one prefill chunk of the whole configuration at its serving shapes,
with `memory_analysis()`.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_v5e.py <config> [<config> ...]

A compile that passes is not a chip run: this says what the chip's compiler
refuses and how much memory one program needs, nothing about times.

`kafka_tpu.models.llama` picks interpret mode from `jax.default_backend()`,
which is the CPU here, so this script makes that one call answer "tpu" while
it lowers (the guide: steer such code from the script, not through an option
of the program).
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import paged_step  # noqa: E402
from kafka_tpu.models import config as model_registry  # noqa: E402
from kafka_tpu.models.llama import init_params  # noqa: E402


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    gb = 1e9
    return {"args_gb": round(m.argument_size_in_bytes / gb, 3),
            "out_gb": round(m.output_size_in_bytes / gb, 3),
            "temp_gb": round(m.temp_size_in_bytes / gb, 3),
            "alias_gb": round(m.alias_size_in_bytes / gb, 3)}


def rehearse(name: str) -> None:
    path = os.path.join(HERE, "configs", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    srv = spec["serving"]
    backend = spec["expect"]["attention_backend"]
    cfg = model_registry.config_from_hf_json(path).replace(
        name=name, dtype=srv["dtype"], attention_backend=backend)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ps, P, B = srv["page_size"], srv["max_pages_per_seq"], srv["max_batch"]
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pool = sds((cfg.num_layers, srv["num_pages"] * ps,
                cfg.num_kv_heads * cfg.head_dim), cfg.activation_dtype)
    i32 = jnp.int32
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # see the module docstring
    try:
        t0 = time.monotonic()
        dec = jax.jit(paged_step.decode_step, static_argnums=(1,),
                      static_argnames=("page_size",), donate_argnums=(2, 3)
                      ).lower(params, cfg, pool, pool, sds((B, P), i32),
                              sds((B,), i32), sds((B,), i32),
                              sds((B,), jnp.bool_), page_size=ps).compile()
        text = dec.as_text()
        print(json.dumps({
            "config": name, "program": f"decode step B={B} window={P * ps}",
            "backend": backend, "kernel_in_hlo": "tpu_custom_call" in text,
            "compile_s": round(time.monotonic() - t0, 1), **mem(dec)}),
            flush=True)
        for bucket in srv["prefill_buckets"]:
            t0 = time.monotonic()
            pre = jax.jit(paged_step.prefill_chunk, static_argnums=(1,),
                          static_argnames=("page_size",),
                          donate_argnums=(2, 3)
                          ).lower(params, cfg, pool, pool, sds((P,), i32),
                                  sds((bucket,), i32), sds((), i32),
                                  sds((), i32), page_size=ps).compile()
            print(json.dumps({
                "config": name, "program": f"prefill chunk S={bucket}",
                "backend": backend,
                "kernel_in_hlo": "tpu_custom_call" in pre.as_text(),
                "compile_s": round(time.monotonic() - t0, 1), **mem(pre)}),
                flush=True)
    finally:
        jax.default_backend = real_backend


if __name__ == "__main__":
    for config_name in sys.argv[1:] or ["yi-1.5-9b", "mixtral-8x7b"]:
        rehearse(config_name)
