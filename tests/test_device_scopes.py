"""Device-side names (ISSUE 24): the component scopes of tracing.DEVICE_SCOPES
reach the compiled HLO of the engine's own step programs, and every step
program is jitted under the name its /debug/compiles label gives.

A device profile prints an op's `op_name` as its `tf_op` stat and a
program launch as its HLO module name; benchmarks/scope_reduce.py and the
trace readers under benchmarks/layer_metrics/ attribute device time by
both.  These tests are the CPU-side guard for what they match."""

import re

import pytest

import jax
import jax.numpy as jnp

from kafka_tpu import tracing
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine
from kafka_tpu.runtime import compile_log
from kafka_tpu.runtime import step_programs

LEAF_SCOPES = set(tracing.DEVICE_SCOPES) - {"layers"}
# `%dot.3 = f32[2,8]{1,0} dot(...)`, `ROOT %x = (f32[..]) custom-call(...)`
INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? ([a-z][\w\-]*)\(")
SCOPED_OPCODES = ("dot", "custom-call", "scatter", "gather")


def _abstract(args):
    """Shapes of a call's arguments (the k/v pools are donated, so the
    arrays themselves are gone after the call)."""
    return jax.tree.map(
        lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                   if hasattr(a, "shape") else a), args)


def _build(cfg, drive, **ecfg_kw):
    """Every step program an engine builds while `drive(engine)` runs:
    {label: {"jit": the jax.jit object, "args": abstract args of its first
    call or None}}.  The process-wide program cache is emptied for the
    duration, so nothing is reused from another test's engine."""
    built = {}

    def spy(label, jitted):
        rec = built.setdefault(label, {"jit": jitted, "args": None})

        def call(*args):
            if rec["args"] is None:
                rec["args"] = _abstract(args)
            return jitted(*args)

        return call

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compile_log, "instrument", spy)
        mp.setattr(step_programs, "_PROGRAMS", {})
        params = init_params(cfg, jax.random.PRNGKey(7))
        defaults = dict(max_batch=4, page_size=8, num_pages=64,
                        max_pages_per_seq=8, prefill_buckets=(8, 16),
                        multi_step=4)
        defaults.update(ecfg_kw)
        eng = InferenceEngine(cfg, params, EngineConfig(**defaults),
                              kv_dtype=jnp.float32)
        drive(eng)
    return built


def _traffic(eng):
    """One request alone, then three more at once: single and batched
    prefill, the single decode step and the fused multi-step scan (>= 3
    busy lanes)."""
    for i in range(4):
        eng.submit(GenRequest(request_id=f"r{i}",
                              prompt_ids=[5, 9, 23, 4, 7, 11][: 3 + i],
                              max_new_tokens=12))
        if i == 0:
            eng.step()
    eng.run_to_completion()


def _tiny(name, **kw):
    return ModelConfig(name=name, vocab_size=kw.pop("vocab_size", 128),
                       hidden_size=64, intermediate_size=128, num_layers=2,
                       num_heads=4, num_kv_heads=2, head_dim=16,
                       dtype="float32", **kw)


@pytest.fixture(scope="module")
def programs():
    cache = {}

    def get(family):
        if family not in cache:
            kw = ({"num_experts": 4, "num_experts_per_tok": 2}
                  if family == "moe" else {})
            cache[family] = _build(_tiny(f"scope-{family}", **kw), _traffic)
        return cache[family]

    return get


@pytest.mark.parametrize("phase", ["prefill", "decode"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_compiled_ops_carry_a_leaf_scope(programs, family, phase):
    """In the compiled HLO of the engine's own prefill chunk and fused
    decode programs, every dot / custom-call / scatter / gather that carries
    an `op_name` names a registered leaf scope: matmuls, KV writes, window
    gathers and sampling are attributable from a device trace.  (XLA's CPU
    passes rewrite a few dots without metadata; a device profile shows
    those with no `tf_op`, which the account reports as `unscoped`.)"""
    built = programs(family)
    label = "prefill[8]" if phase == "prefill" else "multi_decode[4]"
    rec = built[label]
    assert rec["args"] is not None, f"{label} was built but never ran"
    text = rec["jit"].lower(*rec["args"]).compile().as_text()
    assert text.startswith(
        f"HloModule jit_{step_programs.program_name(label)},")
    checked, bare, seen = 0, 0, set()
    for line in text.splitlines():
        m = INSTR.match(line)
        if not m or m.group(1) not in SCOPED_OPCODES:
            continue
        checked += 1
        name = re.search(r'op_name="([^"]*)"', line)
        if name is None:
            bare += 1
            continue
        leaf = LEAF_SCOPES & set(name.group(1).split("/"))
        assert leaf, f"no leaf scope on: {line.strip()[:200]}"
        seen |= leaf
    assert checked >= 10 and bare <= checked // 5, (checked, bare)
    ffn = {"moe_router", "moe_experts"} if family == "moe" else {"mlp"}
    assert ffn | {"embed", "attn_qkv", "kv_write", "attn_core",
                  "attn_out", "head"} <= seen, seen
    # the layer scan's own plumbing and the fused program's scan over steps
    # are told apart by the scope that wraps each scan
    assert "/layers/while" in text
    if phase == "decode":
        assert "/step_ctl/while/body/" in text


def test_step_programs_have_distinct_module_names():
    """Every step program the engine builds lowers to a module name that
    starts `jit_body` (the single decode step) or `jit_fn_`, is unique, and is
    `jit_` + program_name(label): the prefixes are what
    benchmarks/layer_metrics/decode_step_dev_ms.py matches, and labels in
    /debug/compiles map one-to-one onto module names in a device trace."""
    from kafka_tpu.llm.constrained import compile_tool_call_grammar
    from kafka_tpu.models.tokenizer import ByteTokenizer

    tools = [{"type": "function", "function": {
        "name": "get_time",
        "parameters": {"type": "object", "properties": {}}}}]

    def drive(eng):
        _traffic(eng)
        eng.warmup_verify()
        eng.warmup_grammar(
            compile_tool_call_grammar(ByteTokenizer(), tools, vocab_size=262))
        eng._programs.multi_decode(4, eng._fsm(True))  # built, not run

    built = _build(_tiny("scope-names", vocab_size=262), drive,
                   speculative_k=2)
    assert {"decode", "decode_fsm", "prefill[8]", "multi_decode[4]",
            "multi_decode[4]_fsm", "verify", "verify_fsm"} <= set(built)
    assert any(lb.startswith("bprefill[") for lb in built)
    modules = {}
    for label, rec in built.items():
        name = step_programs.program_name(label)
        assert name == "body_decode" or name.startswith("fn_"), (label, name)
        assert re.fullmatch(r"\w+", name), name
        assert rec["jit"].__name__ == name
        if rec["args"] is not None:
            low = rec["jit"].lower(*rec["args"]).as_text()
            assert f"module @jit_{name} " in low.split("\n", 1)[0], label
        modules.setdefault("jit_" + name, []).append(label)
    assert all(len(v) == 1 for v in modules.values()), modules
    # one `jit_body*` program, and not the bare `jit_body` of the trees
    # before the scopes (the compile cache would hand back their executable)
    assert [m for m in modules if m.startswith("jit_body")] \
        == ["jit_body_decode"]
    assert modules["jit_body_decode"] == ["decode"]

