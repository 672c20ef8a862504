#!/usr/bin/env python
"""Do two checkouts lower the engine's step programs to the same text?

    python scripts/lowered_programs.py dump OUT    # in each checkout (cwd)
    python scripts/lowered_programs.py dump OUT tiny-gqa tiny-kexaone
                       # those presets only: seven presets in one process
                       # ran XLA's CPU compiler out of memory maps (PR 44)
    python scripts/lowered_programs.py diff A B

`dump` drives an engine per tiny preset (`tiny-gqa`, `tiny-moe`, the tiny
Mellum2, Kanana-2, dots3, Phi-4-flash, K-EXAONE, LFM2, Solar-Open2,
Falcon-H1 and Xing4.0 under
benchmarks/tests/)
and attention backend (`xla`,
`pallas`, which lowers in interpret mode off the chip) through single and
batched prefill, the decode step (plain, host-masked, forced tokens), the
fused multi-step scan, speculative verify (not on the windowed preset, which
refuses it) and the grammar-FSM form of each, and writes for every program
and every argument signature it was called with:

    <preset>.<backend>.<label>.<signature>.mlir   jit.lower(...).as_text()
    <preset>.<backend>.<label>.<signature>.ops    the compiled module's
        instructions in order: result shape, opcode, op_name (the device
        scopes an op sits under)

`diff` compares the two directories file by file, byte for byte.  Equal
.mlir = the same executable and a warm compile cache across the two trees
(the cache key is made from this text); equal .ops = the same scopes on the
same ops.  A refactor of runtime/step_programs.py that means to change no
program shows it here before it costs chip time.
"""

import hashlib
import pathlib
import re
import sys


def _signature(args):
    import jax

    leaves = jax.tree.leaves(args)
    sig = ",".join(f"{getattr(a, 'dtype', type(a).__name__)}"
                   f"{list(getattr(a, 'shape', ()))}" for a in leaves)
    return hashlib.sha1(sig.encode()).hexdigest()[:8]


def _presets():
    import dataclasses

    from kafka_tpu.models.config import config_from_hf_json, get_config

    mellum = config_from_hf_json(
        "benchmarks/tests/mellum2/configs/tiny-mellum2.json")
    kanana = config_from_hf_json(
        "benchmarks/tests/kanana2/configs/tiny-kanana2.json")
    dots3 = config_from_hf_json(
        "benchmarks/tests/dots3/configs/tiny-dots3.json")
    phi4flash = config_from_hf_json(
        "benchmarks/tests/phi4flash/configs/tiny-phi4flash.json")
    kexaone = config_from_hf_json(
        "benchmarks/tests/kexaone/configs/tiny-kexaone.json")
    lfm2moe = config_from_hf_json(
        "benchmarks/tests/lfm2moe/configs/tiny-lfm2moe.json")
    solar = config_from_hf_json(
        "benchmarks/tests/solar_open2/configs/tiny-solaropen2.json")
    falcon = config_from_hf_json(
        "benchmarks/tests/falcon_h1/configs/tiny-falconh1.json")
    xing4 = config_from_hf_json(
        "benchmarks/tests/xing4/configs/tiny-xing4.json")
    for name, cfg in (("tiny-gqa", get_config("tiny-gqa")),
                      ("tiny-moe", get_config("tiny-moe")),
                      ("tiny-mellum2", mellum),
                      ("tiny-kanana2", kanana),
                      ("tiny-dots3", dots3),
                      ("tiny-phi4flash", phi4flash),
                      ("tiny-kexaone", kexaone),
                      ("tiny-lfm2moe", lfm2moe),
                      ("tiny-solaropen2", solar),
                      ("tiny-falconh1", falcon),
                      ("tiny-xing4", xing4)):
        for backend in ("xla", "pallas"):
            yield name, backend, dataclasses.replace(
                cfg, attention_backend=backend)


def _engine(cfg):
    import jax
    import jax.numpy as jnp

    from kafka_tpu.models import init_params
    from kafka_tpu.runtime import EngineConfig, InferenceEngine

    return InferenceEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(7)),
        EngineConfig(max_batch=4, page_size=8, num_pages=96,
                     max_pages_per_seq=16, prefill_buckets=(8, 16),
                     multi_step=4,
                     # "auto" resolves to xla off the chip whatever the
                     # model config says: name the preset's backend
                     attention_backend=cfg.attention_backend,
                     # windowed and latent models, and one with a
                     # recurrent state, refuse speculative verify
                     speculative_k=0 if (cfg.is_windowed or cfg.is_latent
                                         or cfg.has_state) else 2),
        kv_dtype=jnp.float32)


def _drive_plain(cfg):
    """One request alone, then three more at once: single and batched
    prefill, the decode step, the fused scan; then the verify program."""
    from kafka_tpu.runtime import GenRequest

    eng = _engine(cfg)
    for i in range(4):
        eng.submit(GenRequest(request_id=f"r{i}",
                              prompt_ids=[5, 9, 23, 4, 7, 11][: 3 + i],
                              max_new_tokens=12))
        if i == 0:
            eng.step()
    eng.run_to_completion()
    eng.warmup_verify()


def _drive_fsm(cfg, tok, tools):
    """Four forced tool calls at once: decode_fsm, verify_fsm (warm-up),
    and the fused scan with the automaton in its carry.  Then one whose
    mask stays on the host: the decode step with a [B, V] mask and with
    forced tokens."""
    from kafka_tpu.llm.constrained import (
        ToolCallMaskFn,
        compile_tool_call_grammar,
    )
    from kafka_tpu.runtime import GenRequest

    grammar = compile_tool_call_grammar(tok, tools,
                                        vocab_size=cfg.vocab_size)
    eng = _engine(cfg)
    eng.warmup_grammar(grammar)
    for i in range(4):
        eng.submit(GenRequest(
            request_id=f"g{i}",
            prompt_ids=tok.encode("call a tool" + "!" * i),
            max_new_tokens=40, stop_token_ids=tuple(tok.stop_ids),
            logits_mask_fn=ToolCallMaskFn(tok, tools), grammar=grammar))
    eng.run_to_completion()
    # two tools, so that the name is a choice (a mask) and the rest forced
    choice = tools + [{"type": "function", "function": {
        "name": "get_date",
        "parameters": {"type": "object", "properties": {}}}}]
    eng.submit(GenRequest(
        request_id="h", prompt_ids=tok.encode("call a tool"),
        max_new_tokens=40, stop_token_ids=tuple(tok.stop_ids),
        logits_mask_fn=ToolCallMaskFn(tok, choice)))
    eng.run_to_completion()


def dump(out, only=()):
    import dataclasses

    import jax

    from kafka_tpu.models.tokenizer import ByteTokenizer
    from kafka_tpu.runtime import compile_log

    out = pathlib.Path(out)
    out.mkdir(parents=True, exist_ok=True)
    seen = {}
    tag = [""]

    def spy(label, jitted):
        def call(*args):
            key = (tag[0], label, _signature(args))
            if key not in seen:
                seen[key] = (jitted, jax.tree.map(
                    lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype)
                               if hasattr(a, "shape") else a), args))
            return jitted(*args)

        return call

    compile_log.instrument = spy
    tok = ByteTokenizer()
    tools = [{"type": "function", "function": {
        "name": "get_time",
        "parameters": {"type": "object", "properties": {}}}}]
    for name, backend, cfg in _presets():
        if only and name not in only:
            continue
        tag[0] = f"{name}.{backend}"
        _drive_plain(cfg)
        if cfg.vocab_size < tok.vocab_size:
            # the grammar needs the byte tokenizer's 262 ids: the fsm
            # programs are lowered at that vocab, and the file name says so
            cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size)
            tag[0] = f"{name}+v{tok.vocab_size}.{backend}"
        _drive_fsm(cfg, tok, tools)
    for (t, label, sig), (jitted, args) in sorted(seen.items()):
        low = jitted.lower(*args)
        stem = f"{t}.{label}.{sig}"
        (out / f"{stem}.mlir").write_text(low.as_text())
        (out / f"{stem}.ops").write_text(
            _scoped_ops(low.compile().as_text()))
        print(stem, flush=True)


# `%dot.3 = f32[2,8]{1,0} dot(...)`, `ROOT %x = (f32[..]) custom-call(...)`
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_QUALNAME = re.compile(r"[\w.]*<locals>[\w.<>]*")


def _scoped_ops(hlo):
    """One line per instruction of a compiled module, in order: result
    shape, opcode, op_name.  Left out is what names the source and not the
    work: instruction and operand names (a parameter's is its argument's
    name), source positions, a parameter's op_name (the argument's name
    again) and the Python qualname of a closure inside an op_name (jax
    spells the function a cached inner jit was first traced from)."""
    lines = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = _OP_NAME.search(line)
        name = name.group(1) if name and "/" in name.group(1) else ""
        lines.append(f"{m.group(1)} {m.group(2)} "
                     f"{_QUALNAME.sub('<fn>', name)}\n")
    return "".join(lines)


def diff(a, b):
    a, b = pathlib.Path(a), pathlib.Path(b)
    names = sorted({p.name for p in a.iterdir()} | {p.name for p in b.iterdir()})
    bad = 0
    for n in names:
        if not ((a / n).exists() and (b / n).exists()):
            verdict = "MISSING in " + (str(b) if (a / n).exists() else str(a))
        else:
            same = (a / n).read_bytes() == (b / n).read_bytes()
            verdict = "equal" if same else "DIFFERENT"
        bad += verdict != "equal"
        print(f"{verdict:10s} {n}")
    print(f"{len(names) - bad} of {len(names)} equal")
    return 1 if bad else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "dump":
        sys.path.insert(0, ".")
        dump(sys.argv[2], sys.argv[3:])
    elif len(sys.argv) == 4 and sys.argv[1] == "diff":
        sys.exit(diff(sys.argv[2], sys.argv[3]))
    else:
        sys.exit(__doc__)
