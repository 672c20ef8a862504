"""Does a program copy a state leaf?  Two ways to ask.

    python scripts/leaf_copies.py <trace_dir> "f32[7,129,4096,256]"

Lists, by program, every device op of a capture whose result or operands
name that shape, with its calls and seconds, and exits 1 if one of them is a
`copy` (an in-place kernel's point is that there is none: PERF.md section 7,
ROADMAP M3).  The capture is a traced benchmark run's
(`.bench_out/<cell>/trace`).

    python scripts/leaf_copies.py --compiled <config> [<program> ...]

No chip and no capture: compiles the ENGINE's own step programs
(`runtime/step_programs.py`: `decode`, `multi_decode[16]`, each `prefill`
bucket, each `bprefill` the engine would fuse) of `benchmarks/configs/
<config>.json` at its serving shapes for a DESCRIBED v5e (on-chip-measurement
guide, rehearsal 3; ~10 s a program, a compile and NOT a timing) and lists
every `copy` of the compiled text whose result has the shape of a state leaf
of the v pool, with the computation it sits in and whether that computation
is (reached from) the body of a `while`: a copy there runs once a trip of the
layer scan, one in the entry computation once a launch.  Exits 1 if a copy
sits in a `while` body.  `<program>` picks labels (`prefill[512]`); default
all.
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# an instruction that names other computations, and the attributes it does
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=(%?[\w.\-]+)|branch_computations=\{([^}]*)\}")


def hlo_shape(aval) -> str:
    """`f32[9,129,16,5120]`: an array's shape as compiled text writes it."""
    short = {"float32": "f32", "bfloat16": "bf16"}[str(aval.dtype)]
    return f"{short}[{','.join(map(str, aval.shape))}]"


def computations(text: str) -> dict:
    """{computation: its instruction lines} of a compiled module's text."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*\(.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1).lstrip("%")
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line.strip())
    return out


def in_while(comps: dict) -> set:
    """The computations a `while` runs a trip: its body and condition and
    whatever they call, transitively."""
    calls = {name: set() for name in comps}
    roots = set()
    for name, lines in comps.items():
        for line in lines:
            called = set()
            for one, many in _CALLED.findall(line):
                called.update(c.strip().lstrip("%")
                              for c in (many.split(",") if many else [one]))
            calls[name] |= called
            if re.search(r"\bwhile\(", line):
                roots |= called
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            todo.extend(calls[name])
    return seen


def leaf_copies(text: str, shapes) -> list:
    """Every `copy` of compiled `text` whose result has one of `shapes`, a
    `copy-done` among them (the leaf moved between memories whole: XLA
    prefetches a custom call's operand into fast memory where it fits, PERF.md
    section 6, PR 64): [{shape, op, computation, in_while}].  (A copy inside a
    fusion is the fusion's own loop, not a pass over the leaf by itself:
    fused computations are not searched.)"""
    comps = computations(text)
    loops = in_while(comps)
    found = []
    for name, lines in comps.items():
        if name.startswith("fused_computation"):
            continue
        for line in lines:
            m = re.match(r"^(?:ROOT\s+)?(%?[\w.\-]+) = (\w+\[[\d,]*\])\S* "
                         r"copy(?:-done)?\(", line)
            if m and m.group(2) in shapes:
                found.append({"shape": m.group(2),
                              "op": m.group(1).lstrip("%"),
                              "computation": name,
                              "in_while": name in loops})
    return found


def engine_programs(cfg, srv: dict, sharding=None) -> tuple:
    """{label: (fn, abstract args)} of the step programs an engine over
    `cfg` (a model with a recurrent state: text only) with the serving shapes
    `srv` launches, and the state leaves' shapes: ({...}, {leaf: hlo
    shape})."""
    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import init_params
    from kafka_tpu.runtime import step_programs as sp
    from kafka_tpu.runtime.kv_cache import (
        default_state_slots, make_kv_pool_arrays)

    ps, P, B = srv["page_size"], srv["max_pages_per_seq"], srv["max_batch"]

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: of(a.dtype, *a.shape), tree)

    params = described(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pools = described(jax.eval_shape(lambda: make_kv_pool_arrays(
        cfg, srv["num_pages"], ps, state_slots=default_state_slots(B))))
    leaves = {k: hlo_shape(v) for k, v in pools[1].items() if k != "v"}
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    lanes = sp.Lanes(
        page_table=of(i32, B, P), last_tokens=of(i32, B), seq_lens=of(i32, B),
        active=of(jnp.bool_, B), temps=of(f32, B), top_ks=of(i32, B),
        top_ps=of(f32, B), seeds=of(u32, B))
    steps = 16  # (`EngineConfig.multi_step`, which no configuration sets)
    out = {
        "decode": (sp._decode_fn(cfg, None, ps),
                   (params, *pools, lanes, None, None, None)),
        f"multi_decode[{steps}]": (
            sp._multi_decode_fn(cfg, None, ps, steps),
            (params, *pools, lanes)),
    }
    W = min(4, B)
    for bucket in srv["prefill_buckets"]:
        # (the last two of each: the lanes' state slots and snapshot slots)
        out[f"prefill[{bucket}]"] = (
            sp._prefill_fn(cfg, None, ps, bucket),
            (params, *pools, of(i32, P), of(i32, bucket), of(i32), of(i32),
             of(f32), of(i32), of(f32), of(u32, 1),
             of(jnp.bool_, 1, cfg.vocab_size), of(i32), of(i32)))
        # (runtime/engine.py batches_prefill: where the flash kernel serves
        # the single chunk, only small buckets fuse)
        if W >= 2 and (cfg.attention_backend != "pallas" or bucket <= 128):
            out[f"bprefill[{bucket}x{W}]"] = (
                sp._batched_prefill_fn(cfg, None, ps, bucket),
                (params, *pools, of(i32, W, P), of(i32, W, bucket),
                 of(i32, W), of(i32, W), of(f32, W), of(i32, W), of(f32, W),
                 of(u32, W), of(jnp.bool_, W), of(i32, W), of(i32, W)))
    return out, leaves


def compile_for(fn, args, label: str):
    """`fn` jitted as the engine jits a step program (pools donated) and
    compiled for the devices its abstract `args` are placed on.  The model
    picks interpret mode from `jax.default_backend()`, the CPU here: that one
    call answers "tpu" while this lowers (benchmarks/rehearse_v5e.py)."""
    import jax

    from kafka_tpu.runtime import step_programs as sp

    fn.__name__ = fn.__qualname__ = sp.program_name(label)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        return jax.jit(fn, donate_argnums=(1, 2)).lower(*args).compile()
    finally:
        jax.default_backend = real


def compiled(name: str, only) -> int:
    import time

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from kafka_tpu.models import config as model_registry

    path = os.path.join(ROOT, "benchmarks", "configs", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    srv = spec["serving"]
    cfg = model_registry.config_from_hf_json(path).replace(
        name=name, dtype=srv["dtype"],
        attention_backend=spec["expect"]["attention_backend"])
    if not cfg.has_state:
        print(f"leaf_copies: {name} holds no recurrent state",
              file=sys.stderr)
        return 2
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    programs, leaves = engine_programs(
        cfg, srv, SingleDeviceSharding(topo.devices[0]))
    looped = total = 0
    for label, (fn, args) in programs.items():
        if only and label not in only:
            continue
        t0 = time.monotonic()
        exe = compile_for(fn, args, label)
        found = leaf_copies(exe.as_text(), set(leaves.values()))
        for row in found:
            print(json.dumps({"config": name, "program": label, **row}))
        inside = sum(row["in_while"] for row in found)
        looped += inside
        total += len(found)
        print(json.dumps({
            "config": name, "program": label, "copies": len(found),
            "in_while": inside,
            "temp_gb": round(
                exe.memory_analysis().temp_size_in_bytes / 1e9, 3),
            "compile_s": round(time.monotonic() - t0, 1)}), flush=True)
    print(json.dumps({"config": name, "leaves": leaves, "copies": total,
                      "in_while": looped}))
    return 1 if looped else 0


def traced(trace_dir: str, shape: str) -> int:
    import trace_reduce

    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        print(f"leaf_copies: no capture under {trace_dir}", file=sys.stderr)
        return 2
    seen = collections.defaultdict(lambda: [0, 0.0])
    for plane in trace_reduce.load_xplane(path):
        if not trace_reduce.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != trace_reduce.OPS_LINE:
                continue
            for name, _, dur in line["events"]:
                if shape in name:
                    op = re.sub(r"\s+", " ", name)[:160]
                    seen[op][0] += 1
                    seen[op][1] += dur / 1e9
    copies = 0
    for op, (calls, seconds) in sorted(seen.items(), key=lambda kv: -kv[1][1]):
        is_copy = bool(re.match(r"^%?copy(-done)?[.\d]* = ", op))
        copies += is_copy
        print(json.dumps({"op": op, "calls": calls,
                          "seconds": round(seconds, 6), "copy": is_copy}))
    print(json.dumps({"shape": shape, "ops": len(seen), "copies": copies}))
    return 1 if copies else 0


def main() -> int:
    if sys.argv[1] == "--compiled":
        return compiled(sys.argv[2], set(sys.argv[3:]))
    return traced(sys.argv[1], sys.argv[2])


if __name__ == "__main__":
    raise SystemExit(main())
