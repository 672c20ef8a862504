"""runtime/step_programs.py (ISSUE 29): the paged index plan against its
arithmetic written out in plain numpy, the builders' labels and cache keys,
and the module's one-way dependency.

The plan is the pool's addressing format: a page table and positions
become flat slot indices (`PagedView`).  The references below are loops
over lanes and positions, written from the format's definition and not
from the plan's code: slot = page * page_size + offset, page 0 is the trash
page, a lane's window is its pages in order."""

import ast
import pathlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import kafka_tpu
from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.runtime import compile_log, step_programs
from kafka_tpu.runtime.kv_cache import TRASH_PAGE, make_kv_pool_arrays
from kafka_tpu.runtime.step_programs import Fsm, Lanes, StepPrograms

PS = 4  # page size
P = 4   # pages per sequence
C = P * PS


def _window(page_table):
    """read_idx, kv_positions [B, C]: lane b's pages in order, slot by
    slot."""
    B = len(page_table)
    read = np.zeros((B, C), np.int64)
    for b in range(B):
        for p in range(P):
            for o in range(PS):
                read[b, p * PS + o] = page_table[b][p] * PS + o
    return read, np.tile(np.arange(C), (B, 1))


def _slot(page_row, pos):
    return page_row[pos // PS] * PS + pos % PS


def _trash(offset):
    return TRASH_PAGE * PS + offset % PS


def _check(paged, write, read, kvp, valid):
    np.testing.assert_array_equal(np.asarray(paged.write_idx), write)
    np.testing.assert_array_equal(np.asarray(paged.read_idx), read)
    np.testing.assert_array_equal(np.asarray(paged.kv_positions), kvp)
    np.testing.assert_array_equal(np.asarray(paged.kv_valid), valid)
    assert paged.page_size == PS


TABLE = [[1, 2, 3, 4], [0, 0, 0, 0], [8, 7, 6, 5], [9, 10, 0, 0]]


@pytest.mark.parametrize("seq_lens,active", [
    # mid-page, an inactive lane, a page's last slot, a page's first slot
    ([5, 3, 15, 4], [True, False, True, True]),
    # everything inactive (a warm-up dispatch): every write is trash
    ([5, 3, 15, 4], [False, False, False, False]),
    # position 0, and the window's last slot
    ([0, 0, 15, 7], [True, True, True, False]),
])
def test_decode_plan(seq_lens, active):
    """A lane writes its new token at position seq_len and attends to
    positions 0..seq_len, the new token included; an inactive lane writes
    the trash page and attends to nothing."""
    positions, paged = step_programs.decode_plan(
        jnp.asarray(TABLE, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
        jnp.asarray(active), PS)
    B = len(TABLE)
    write = np.zeros((B, 1), np.int64)
    valid = np.zeros((B, C), bool)
    for b in range(B):
        n = seq_lens[b]
        write[b, 0] = _slot(TABLE[b], n) if active[b] else _trash(n)
        for c in range(C):
            valid[b, c] = active[b] and c <= n
    read, kvp = _window(TABLE)
    _check(paged, write, read, kvp, valid)
    np.testing.assert_array_equal(
        np.asarray(positions), np.asarray(seq_lens)[:, None])
    np.testing.assert_array_equal(np.asarray(paged.seq_lens), seq_lens)
    np.testing.assert_array_equal(np.asarray(paged.page_table), TABLE)
    # the boundary token: valid at seq_len, not one past it
    for b in range(B):
        if active[b] and seq_lens[b] + 1 < C:
            assert paged.kv_valid[b, seq_lens[b]]
            assert not paged.kv_valid[b, seq_lens[b] + 1]


def _chunk_reference(rows, starts, chunk_lens, lane_active, S):
    W = len(rows)
    write = np.zeros((W, S), np.int64)
    valid = np.zeros((W, C), bool)
    pos = np.zeros((W, S), np.int64)
    for w in range(W):
        for i in range(S):
            pos[w, i] = starts[w] + i
            real = lane_active[w] and i < chunk_lens[w]
            write[w, i] = _slot(rows[w], starts[w] + i) if real else _trash(i)
        for c in range(C):
            valid[w, c] = lane_active[w] and c < starts[w] + chunk_lens[w]
    return pos, write, valid


@pytest.mark.parametrize("starts,chunk_lens,lane_active", [
    # a mid-page start, a full chunk from 0, a short tail, an idle lane
    ([6, 0, 8, 0], [5, 8, 1, 0], [True, True, True, False]),
    # a lane whose chunk_len is nonzero but which is inactive: all trash
    ([2, 4, 0, 0], [8, 8, 8, 8], [True, False, True, True]),
])
def test_chunk_plan(starts, chunk_lens, lane_active):
    """Rows of a chunk past chunk_len, and every row of an inactive lane,
    write the trash page; the window is valid up to the chunk's last real
    token and no further."""
    S = 8
    pos, paged = step_programs.chunk_plan(
        jnp.asarray(TABLE, jnp.int32), jnp.asarray(starts, jnp.int32),
        jnp.asarray(chunk_lens, jnp.int32), jnp.asarray(lane_active), S, PS)
    want_pos, write, valid = _chunk_reference(
        TABLE, starts, chunk_lens, lane_active, S)
    read, kvp = _window(TABLE)
    _check(paged, write, read, kvp, valid)
    np.testing.assert_array_equal(np.asarray(pos), want_pos)
    assert paged.seq_lens is None and paged.start is None
    for w in range(len(TABLE)):
        end = starts[w] + chunk_lens[w]
        if lane_active[w] and 0 < end < C:
            assert paged.kv_valid[w, end - 1] and not paged.kv_valid[w, end]


@pytest.mark.parametrize("start,chunk_len", [
    (0, 8), (6, 5), (8, 1), (5, 0), (3, 8)])
def test_one_row_chunk_plan_is_the_single_sequence_plan(start, chunk_len):
    """`prefill_plan` is `chunk_plan` for one active row: the same slots,
    window and mask.  It differs in what it carries besides (scalar start
    and chunk_len, for the flash prefill kernel), which is why both forms
    exist."""
    S, row = 8, [9, 2, 11, 5]
    pos1, one = step_programs.prefill_plan(
        jnp.asarray(row, jnp.int32), jnp.int32(start), jnp.int32(chunk_len),
        S, PS)
    posw, many = step_programs.chunk_plan(
        jnp.asarray([row], jnp.int32), jnp.asarray([start], jnp.int32),
        jnp.asarray([chunk_len], jnp.int32), jnp.asarray([True]), S, PS)
    np.testing.assert_array_equal(np.asarray(pos1), np.asarray(posw))
    for a, b in zip(one[:4], many[:4]):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(one.page_table), np.asarray(many.page_table))
    assert int(one.start) == start and int(one.chunk_len) == chunk_len
    want_pos, write, valid = _chunk_reference(
        [row], [start], [chunk_len], [True], S)
    _check(one, write, *_window([row]), valid)


@pytest.mark.parametrize("seq_lens,cand_lens,active", [
    # a full proposal, a non-proposer (an ordinary decode step), an inactive
    # lane, a proposal that crosses a page boundary
    ([5, 3, 9, 2], [2, 0, 2, 1], [True, True, False, True]),
    # a run that would index past the last page: the page lookup clamps,
    # and those rows are padding (cand_len 0) so they write trash anyway
    ([14, 15, 0, 13], [1, 0, 2, 2], [True, True, True, True]),
])
def test_verify_plan(seq_lens, cand_lens, active):
    """K+1 rows per lane at seq_len..seq_len+K: rows 0..cand_len are real,
    the rest write the trash page; the window is valid through the last
    candidate."""
    S = 3
    pos, paged = step_programs.verify_plan(
        jnp.asarray(TABLE, jnp.int32), jnp.asarray(seq_lens, jnp.int32),
        jnp.asarray(cand_lens, jnp.int32), jnp.asarray(active), S, PS)
    B = len(TABLE)
    write = np.zeros((B, S), np.int64)
    valid = np.zeros((B, C), bool)
    for b in range(B):
        for i in range(S):
            real = active[b] and i <= cand_lens[b]
            write[b, i] = (_slot(TABLE[b], seq_lens[b] + i) if real
                           else _trash(i))
        for c in range(C):
            valid[b, c] = active[b] and c <= seq_lens[b] + cand_lens[b]
    read, kvp = _window(TABLE)
    _check(paged, write, read, kvp, valid)
    np.testing.assert_array_equal(
        np.asarray(pos), np.asarray(seq_lens)[:, None] + np.arange(S))
    np.testing.assert_array_equal(
        np.asarray(paged.chunk_len), np.asarray(cand_lens) + 1)


# ----------------------------------------------------------------------
# builders: labels, names, cache keys
# ----------------------------------------------------------------------


@pytest.mark.parametrize("label,name", [
    ("decode", "body_decode"),
    ("decode_fsm", "fn_decode_fsm"),
    ("multi_decode[16]", "fn_multi_decode_16"),
    ("multi_decode[16]_fsm", "fn_multi_decode_16_fsm"),
    ("verify", "fn_verify"),
    ("prefill[2048]", "fn_prefill_2048"),
    ("bprefill[512x4]", "fn_bprefill_512x4"),
])
def test_program_name_from_label(label, name):
    assert step_programs.program_name(label) == name


def _cfg(name="stepprog", **kw):
    return ModelConfig(name=name, vocab_size=64, hidden_size=32,
                       intermediate_size=64, num_layers=2, num_heads=4,
                       num_kv_heads=2, head_dim=8, dtype="float32", **kw)


@pytest.fixture
def labels(monkeypatch):
    """Labels handed to the compile observatory, in order, with the
    process-wide cache emptied for the test."""
    seen = []

    def spy(label, jitted):
        seen.append(label)
        return jitted

    monkeypatch.setattr(compile_log, "instrument", spy)
    monkeypatch.setattr(step_programs, "_PROGRAMS", {})
    return seen


FSM_KEY = (64, 32, 8)  # the grammar tables' padded (states, classes, live)


def _fsm(states=64):
    """An `Fsm` whose tables have the padded shape (states, 32, 8); a
    builder reads nothing but that shape."""
    lane = np.zeros(3, np.int32)
    return Fsm(lane, lane, lane, np.zeros((8, 64), np.int32),
               np.zeros((states, 32), np.int32), np.zeros(states, np.int32),
               np.int32(0))


@pytest.mark.parametrize("kind,args,plain,fsm", [
    ("decode", (), "decode", "decode_fsm"),
    ("multi_decode", (4,), "multi_decode[4]", "multi_decode[4]_fsm"),
    ("verify", (2,), "verify", "verify_fsm"),
])
def test_builder_lands_on_plain_and_fsm_labels(labels, kind, args, plain,
                                               fsm):
    """One builder per kind: handed `None` it builds the plain program,
    handed an `Fsm` the `_fsm` program, each under its own label, name and
    cache key (the tables' padded shape), and each once."""
    cfg = _cfg()
    progs = StepPrograms(cfg, None, PS, 3, P)
    build = getattr(progs, kind)
    assert _fsm().key == FSM_KEY
    f_plain, f_fsm = build(*args), build(*args, _fsm())
    assert labels == [plain, fsm]
    assert f_plain is not f_fsm
    assert f_plain.__name__ == step_programs.program_name(plain)
    assert f_fsm.__name__ == step_programs.program_name(fsm)
    assert build(*args) is f_plain and build(*args, _fsm()) is f_fsm
    assert labels == [plain, fsm]
    assert set(progs.built) == {(plain, None), (fsm, FSM_KEY)}
    geometry = (cfg, PS, C, 3, None)
    want = {
        "decode": [("decode",) + geometry,
                   ("decode_fsm",) + geometry + (FSM_KEY,)],
        "multi_decode": [("multi_decode",) + geometry + (4, None),
                         ("multi_decode",) + geometry + (4, FSM_KEY)],
        "verify": [("verify",) + geometry + (2, None),
                   ("verify",) + geometry + (2, FSM_KEY)],
    }[kind]
    assert list(step_programs._PROGRAMS) == want
    # a second engine of the same shape compiles nothing, a grown grammar
    # table (another key) builds its own program under the same label
    again = StepPrograms(cfg, None, PS, 3, P)
    assert getattr(again, kind)(*args, _fsm()) is f_fsm
    assert labels == [plain, fsm]
    grown = getattr(again, kind)(*args, _fsm(states=128))
    assert grown is not f_fsm and labels == [plain, fsm, fsm]


def test_prefill_builders_and_clear(labels):
    cfg = _cfg()
    progs = StepPrograms(cfg, None, PS, 3, P)
    one, many = progs.prefill(8), progs.batched_prefill(8, 2)
    assert labels == ["prefill[8]", "bprefill[8x2]"]
    assert list(step_programs._PROGRAMS) == [
        ("prefill", cfg, 8, PS, C, P, None),
        ("bprefill", cfg, 8, 2, PS, C, P, None),
    ]
    assert progs.prefill(8) is one and progs.batched_prefill(8, 2) is many
    step_programs.clear()
    assert not step_programs._PROGRAMS
    # this engine keeps what it was handed; a new one builds afresh
    assert progs.prefill(8) is one
    assert StepPrograms(cfg, None, PS, 3, P).prefill(8) is not one
    assert labels == ["prefill[8]", "bprefill[8x2]", "prefill[8]"]


def test_programs_run_without_an_engine():
    """The programs are pure functions of device arrays: prefill a prompt,
    then four single decode steps and one fused 4-step dispatch from the
    same state give the same tokens; and a program's result ends with the
    automaton's (state, budget) exactly when it was given one."""
    cfg = _cfg("stepprog-run")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B = 3
    progs = StepPrograms(cfg, None, PS, B, P)
    table = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]], np.int32)
    prompts = [[5, 9, 23, 4, 7], [11, 3]]

    def prefilled():
        k, v = make_kv_pool_arrays(cfg, 12, PS, jnp.float32)
        last = []
        for row, prompt in zip(table, prompts):
            chunk = np.zeros(8, np.int32)
            chunk[:len(prompt)] = prompt
            k, v, tok = progs.prefill(8)(
                params, k, v, row, chunk, np.int32(0),
                np.int32(len(prompt)), np.float32(0), np.int32(0),
                np.float32(1), np.asarray([0], np.uint32),
                np.ones((1, cfg.vocab_size), bool))
            last.append(int(tok))
        return k, v, Lanes(
            jnp.asarray(table), jnp.asarray(last + [0], jnp.int32),
            jnp.asarray([5, 2, 0], jnp.int32),
            jnp.asarray([True, True, False]), jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32), jnp.ones(B, jnp.float32),
            jnp.zeros(B, jnp.uint32))

    k, v, lanes = prefilled()
    singles = []
    for _ in range(4):
        out = progs.decode()(params, k, v, lanes, None)
        # (last: the experts its routed blocks read; None, no routed block)
        assert len(out) == 5 and out[-1] is None
        k, v, toks, lens, _ = out
        lanes = lanes._replace(last_tokens=toks, seq_lens=lens)
        singles.append(np.asarray(toks))
    k, v, lanes = prefilled()
    out = progs.multi_decode(4)(params, k, v, lanes)
    assert len(out) == 6 and out[-1] is None
    np.testing.assert_array_equal(
        np.asarray(out[2])[:, :2], np.stack(singles)[:, :2])
    np.testing.assert_array_equal(np.asarray(out[4]), [9, 6, 0])

    # an automaton whose every state allows every token: the tokens are the
    # plain program's, and its state and budget come back advanced
    k, v, lanes = prefilled()
    fsm = Fsm(jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
              jnp.full(B, 100, jnp.int32),
              jnp.zeros((1, cfg.vocab_size), jnp.int32),
              jnp.zeros((4, 2), jnp.int32), jnp.zeros(4, jnp.int32),
              jnp.int32(0))
    out = progs.multi_decode(4, fsm)(params, k, v, lanes, fsm)
    assert len(out) == 8 and out[-1] is None
    np.testing.assert_array_equal(
        np.asarray(out[2])[:, :2], np.stack(singles)[:, :2])
    np.testing.assert_array_equal(np.asarray(out[6]), [96, 96, 100])


# ----------------------------------------------------------------------
# the boundary
# ----------------------------------------------------------------------

ROOT = pathlib.Path(kafka_tpu.__file__).parent


def _imports(path):
    """Every module a file imports, at any depth (an import inside a
    function counts), relative ones with their dots."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield from (f"{'.' * node.level}{node.module or ''}.{a.name}"
                        for a in node.names)


def test_step_programs_does_not_import_the_engine():
    """One direction only: engine -> step_programs.  Every import in the
    module names models, ops, parallel, compile_log or program_store."""
    for name in _imports(ROOT / "runtime" / "step_programs.py"):
        assert "engine" not in name, name
        if name.startswith("."):
            assert re.match(
                r"\.\.(models|ops|parallel)\.|\.\.(compile_log|program_store)$",
                name
            ), name


def test_runtime_does_not_import_the_llm_tier():
    """One direction only: llm -> runtime.  The grammar-table budget and
    the pending-compile gauge are runtime quantities (device bytes, a
    queue depth) that llm/constrained.py imports, not the reverse."""
    for path in sorted((ROOT / "runtime").glob("*.py")):
        for name in _imports(path):
            assert not re.match(r"(\.\.|kafka_tpu\.)llm(\.|$)", name), (
                f"{path.name} imports {name}")


def test_the_index_plan_has_one_home():
    """`PagedView(` is constructed by the plan functions, by models/cache.py
    (which defines it) and by parallel/pipeline.py's re-wrap of arrays it
    is handed; the engine holds no program, jit or cache of its own but
    the `_fsm_advance` helper."""
    homes = {str(p.relative_to(ROOT)) for p in ROOT.rglob("*.py")
             if "PagedView(" in p.read_text()}
    assert homes == {"runtime/step_programs.py", "models/cache.py",
                     "parallel/pipeline.py"}
    engine = (ROOT / "runtime" / "engine.py").read_text()
    assert "_jit_step" not in engine and "_FN_CACHE" not in engine
    assert engine.count("jax.jit") == 1
