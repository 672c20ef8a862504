"""The device-time account: its arithmetic on plain lists, its readers
without a trace, and `load_ops` against a small scoped trace recorded on the
v5e (benchmarks/tests/record_scoped_trace.py: a 2-layer engine at toy widths
serving one request alone, then three at once)."""

import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402
import scope_reduce  # noqa: E402

TRACE = os.path.join(HERE, "recorded", "tiny_scoped_v5e.xplane.pb")
US = 1_000_000  # ps

FUSED = "jit(fn_multi_decode_4)/step_ctl/while/body/"
LAYER = FUSED + "layers/while/body/closed_call/"


def op(name, start_us, dur_us, tf_op, cat="fusion", pid=7, nbytes=0):
    return (f"%{name} = bf16[4,64]{{1,0}} fusion(%x)", start_us * US,
            dur_us * US, tf_op, cat, pid, nbytes)


@pytest.mark.parametrize("tf_op,expected", [
    (LAYER + "attn_core/dot_general:", "attn_core"),
    # the innermost scope wins: the gather inside attention proper
    (LAYER + "attn_core/attn_gather/gather:", "attn_gather"),
    (LAYER + "attn_core/jit(paged_decode_attention)/pallas_call:",
     "attn_core"),
    # the layer scan's body under no leaf scope, and the scan itself
    (FUSED + "layers/while/body/dynamic_slice:", "scan_plumbing"),
    (FUSED + "layers/while:", "scan_plumbing"),
    # the fused program's scan over steps is not the layer scan
    (FUSED + "add:", "step_ctl"),
    ("jit(fn_multi_decode_4)/step_ctl/while:", "step_ctl"),
    ("jit(fn_prefill_8)/head/dot_general:", "head"),
    # a scope this table does not list stays with the enclosing one
    (LAYER + "mlp/some_new_scope/mul:", "mlp"),
    ("jit(body)/dot_general:", "other"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_component_of_a_tf_op(tf_op, expected):
    assert scope_reduce.component(tf_op) == expected
    # a configuration's own scopes move no op that is not under one of them
    assert scope_reduce.component(tf_op, ("moe_dispatch",)) == expected


NEW_BLOCK = "jit(f)/layers/while/body/moe_dispatch/dot_general"


@pytest.mark.parametrize("tf_op,listed,expected", [
    # pinned as today's behaviour: a scope nobody lists, directly under the
    # layer scan, is the scan's plumbing, which dev_kv_move_share sums
    (NEW_BLOCK, (), "scan_plumbing"),
    (NEW_BLOCK, ("qk_norm",), "scan_plumbing"),
    (NEW_BLOCK, ("moe_dispatch", "qk_norm"), "moe_dispatch"),
    (NEW_BLOCK + ":", ("moe_dispatch",), "moe_dispatch"),
    # the innermost wins among the listed and the built-in alike
    (LAYER + "attn_core/qk_norm/mul:", ("qk_norm",), "qk_norm"),
    (LAYER + "moe_dispatch/mlp/dot_general:", ("moe_dispatch",), "mlp"),
])
def test_a_configurations_own_scope_is_its_own_component(tf_op, listed,
                                                         expected):
    assert scope_reduce.component(tf_op, listed) == expected


def test_a_listed_scope_is_a_row_of_its_own_and_in_no_existing_share():
    ops = [
        op("fusion.1", 0, 30, NEW_BLOCK),
        op("fusion.2", 30, 10, "jit(f)/layers/while/body/dynamic_slice:"),
        op("fusion.3", 40, 60, "jit(f)/layers/while/body/mlp/dot_general:"),
    ]
    planes = [{"name": "/device:TPU:0", "ops": ops,
               "modules": [("jit_fn_f(7)", 0, 100 * US)]}]
    kv = ("kv_write", scope_reduce.SCAN_PLUMBING)
    plain = scope_reduce.account(planes)
    assert "moe_dispatch" not in plain["by_component"]
    assert scope_reduce.share(plain, kv) == pytest.approx(40.0)
    own = scope_reduce.account(planes, ("moe_dispatch",))
    assert own["by_component"]["moe_dispatch"] == pytest.approx(30e-6)
    assert own["busy_s"] == pytest.approx(plain["busy_s"])
    assert scope_reduce.share(own, kv) == pytest.approx(10.0)
    for comps in (("attn_core", "attn_gather"),
                  ("mlp", "moe_router", "moe_experts"), ("unscoped",)):
        assert scope_reduce.share(own, comps) == scope_reduce.share(
            plain, comps)
    assert any(ln.startswith("| `moe_dispatch` | 30.00 |")
               for ln in scope_reduce.table_lines(own))


def test_the_readers_take_the_scopes_from_the_cells_configuration(
        monkeypatch):
    seen = []

    def fake_account_dir(trace_dir, scopes=()):
        seen.append((os.path.basename(os.path.dirname(trace_dir)), scopes))
        return None

    monkeypatch.setattr(scope_reduce, "account_dir", fake_account_dir)

    class Cell:
        name = "some-cell"
        config = {"scopes": ["moe_dispatch", "qk_norm"]}

    assert scope_reduce.of_ctx({"trace": {"busy_s": 1.0},
                                "cell": Cell()}) is None
    Cell.config = {}
    scope_reduce.of_ctx({"trace": {"busy_s": 1.0}, "cell": Cell()})
    assert seen == [("some-cell", ("moe_dispatch", "qk_norm")),
                    ("some-cell", ())]
    with pytest.raises(ValueError):
        scope_reduce.config_scopes({"scopes": ["unscoped"]})


def test_account_nests_attributes_and_sums():
    """A `while` of 100 us holds a slice (10), a fusion rooted in attn_core
    (50) and a compiler copy with no tf_op (20): the while's own 20 us are
    scan plumbing, nothing is counted twice, and a second program's ops are
    found by program id or, without one, by the launch that covers them."""
    fused = [
        op("while.1", 0, 100, FUSED + "layers/while:", "while"),
        op("slice.2", 0, 10, FUSED + "layers/while/body/dynamic_slice:"),
        op("fusion.3", 10, 50, LAYER + "attn_core/dot_general:"),
        op("copy.4", 60, 20, None, "data formatting", nbytes=4096),
        op("fusion.5", 100, 30, LAYER + "mlp/dot_general:"),
    ]
    prefill = [
        op("fusion.9", 200, 40, "jit(fn_prefill_8)/layers/while/body/"
           "closed_call/attn_core/dot_general:", pid=9),
        # no program_id: under the program `?`, not guessed from the time
        op("fusion.10", 240, 10, "jit(fn_prefill_8)/iota:", pid=None),
    ]
    acc = scope_reduce.account([{
        "name": "/device:TPU:0", "ops": fused + prefill,
        "modules": [("jit_fn_multi_decode_4(7)", 0, 130 * US),
                    ("jit_fn_prefill_8(9)", 200 * US, 50 * US)]}])
    us = lambda s: round(s * 1e6, 6)
    assert us(acc["busy_s"]) == 180
    assert {k: us(v) for k, v in acc["by_component"].items()} == {
        "scan_plumbing": 30, "attn_core": 90, "unscoped": 20, "mlp": 30,
        "other": 10}
    assert {k: us(v) for k, v in acc["by_program"].items()} == {
        "jit_fn_multi_decode_4": 130, "jit_fn_prefill_8": 40, "?": 10}
    assert us(acc["table"]["jit_fn_prefill_8"]["attn_core"]) == 40
    assert acc["scoped"] is True and acc["unnamed_programs"] == []
    top = acc["top_unattributed"]
    assert [t["op"] for t in top] == ["copy.4 bf16[4,64]",
                                      "fusion.10 bf16[4,64]"]
    assert top[0]["category"] == "data formatting" and top[0]["calls"] == 1
    assert top[0]["bytes_accessed"] == 4096
    assert top[0]["component"] == "unscoped" and top[1]["component"] == "other"
    # the five metrics' arithmetic
    assert scope_reduce.share(acc, ("attn_core", "attn_gather")) == 50.0
    assert scope_reduce.share(
        acc, ("kv_write", "scan_plumbing")) == pytest.approx(100 * 30 / 180)
    assert scope_reduce.share(
        acc, programs=scope_reduce.PREFILL_PROGRAM) == pytest.approx(
            100 * 40 / 180)
    assert scope_reduce.share(acc, ("unscoped",)) == pytest.approx(
        100 * 20 / 180)
    lines = scope_reduce.table_lines(acc)
    assert lines[0].startswith("| component | jit_fn_multi_decode_4 | "
                               "jit_fn_prefill_8 | ? | all |")
    assert lines[-1] == "| all | 72.22 | 22.22 | 5.56 | 100.00 |"


def test_two_chips_are_summed():
    plane = lambda n: {"name": f"/device:TPU:{n}", "modules": [
        ("jit_body(7)", 0, 10 * US)],
        "ops": [op("fusion.1", 0, 10, "jit(body)/head/dot_general:")]}
    acc = scope_reduce.account([plane(0), plane(1)])
    assert acc["by_component"]["head"] == pytest.approx(20e-6)


def test_a_program_without_scopes_has_no_account():
    """The parent of PR 24: every op is `other` or `unscoped`.  The shares
    are then None, not zeros."""
    acc = scope_reduce.account([{
        "name": "/device:TPU:0", "modules": [("jit_fn(7)", 0, 20 * US)],
        "ops": [op("fusion.1", 0, 10, "jit(fn)/dot_general:"),
                op("copy.2", 10, 10, None)]}])
    assert acc["scoped"] is False
    assert scope_reduce.share(acc, ("unscoped",)) is None
    assert scope_reduce.share(acc, programs=scope_reduce.PREFILL_PROGRAM) \
        is None
    assert scope_reduce.share(None, ("mlp",)) is None
    assert scope_reduce.account([]) is None


def test_a_capture_that_mixes_named_and_unnamed_programs_has_no_shares():
    """A cached executable keeps the names of the tree that compiled it
    (chip run D, PR 24: the parent's `jit_body` carried this PR's scopes
    beside its own bare `jit_fn` programs).  A program with 1% of the busy
    time and no named op leaves the shares out; a small one does not."""
    named = op("fusion.1", 0, 60, "jit(body)/layers/while/body/mlp/dot:",
               pid=7)
    bare = op("fusion.2", 60, 40, "jit(fn)/dot_general:", pid=8)
    small = op("copy.3", 100, 0.5, None, pid=9)
    modules = [("jit_body(7)", 0, 60 * US), ("jit_fn(8)", 60 * US, 40 * US),
               ("jit_scatter(9)", 100 * US, US)]
    acc = scope_reduce.account([{"name": "/device:TPU:0",
                                 "modules": modules,
                                 "ops": [named, bare, small]}])
    assert acc["scoped"] is True
    assert acc["unnamed_programs"] == ["jit_fn"]
    assert scope_reduce.share(acc, ("mlp",)) is None
    assert scope_reduce.share(acc, programs=scope_reduce.PREFILL_PROGRAM) \
        is None
    acc = scope_reduce.account([{"name": "/device:TPU:0",
                                 "modules": modules, "ops": [named, small]}])
    assert acc["unnamed_programs"] == []
    assert scope_reduce.share(acc, ("mlp",)) == pytest.approx(
        100 * 60 / 60.5)


NEW_READERS = ["dev_attn_share", "dev_kv_move_share", "dev_ffn_share",
               "dev_prefill_share", "dev_unscoped_share"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_returns_none_without_a_trace(name):
    class Cell:
        name = "no-such-cell"
        config = {}

    for ctx in ({"trace": None, "cell": Cell()},
                # a reduced trace but no capture on disk (nothing to parse)
                {"trace": {"busy_s": 1.0}, "cell": Cell()}):
        assert run.read_layer_metric(run.HERE, name, ctx) is None


def test_a_metric_lists_cells_only_where_some_cell_cannot_report_it():
    """BENCHMARK.json's rule (benchmarks/README.md): a per-layer metric that
    every cell can report has no `workloads` key, so a new cell is an entry
    of its own and no edit of these; a list stands only where a reader finds
    nothing in some cell (a kernel one backend does not run).  The driver
    refuses a traced line that lacks a metric without the key, which is why
    such a metric keeps it."""
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cells = {w["name"] for w in bench["workloads"]}
    held = {m["name"] for m in bench["end_to_end"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        m = by_name[name]
        assert "workloads" not in m, name
        assert m["source"] == "device_trace" and m["unit"] == "%"
        assert m["layer"] == "jitted step programs"
        assert m["moves"] == "tpot_p50_ms"
    # an end-to-end metric applies to every cell: none lists any
    assert not [m["name"] for m in bench["end_to_end"] if "workloads" in m]
    listed = {n: m["workloads"] for n, m in by_name.items()
              if "workloads" in m}
    # Mixtral runs no paged_decode kernel
    assert listed == {"paged_attn_roofline": ["yi-1.5-9b.chat-decode"]}
    for name, m in by_name.items():
        assert set(m.get("workloads", cells)) <= cells, name
        assert m["moves"] in held, name  # a mistyped `moves` drops nothing
        assert os.path.exists(os.path.join(  # its reader, found by name
            run.HERE, "layer_metrics", name + ".py")), name


def test_the_tiny_root_lists_the_same_device_account():
    """tests/tiny/BENCHMARK.json carries the five entries as the real file
    does, so `run.py --rehearse` on the tiny root calls the five readers
    (test_rehearse.py; on the CPU each returns None)."""
    tiny = run.load_json(os.path.join(HERE, "tiny", "BENCHMARK.json"))
    real = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for name in NEW_READERS:
        entry = next(m for m in real["per_layer"] if m["name"] == name)
        assert entry in tiny["per_layer"], name


# --------------------------------------------------------------------------
# the recorded v5e capture
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    planes = scope_reduce.load_ops(TRACE)
    assert planes is not None, "no xplane_pb2 in this installation"
    return planes, scope_reduce.account(planes)


def test_recorded_programs_are_named(recorded):
    _, acc = recorded
    progs = set(acc["by_program"])
    assert {"jit_body_decode", "jit_fn_prefill_8",
            "jit_fn_multi_decode_4"} <= progs
    assert any(re.match(r"^jit_fn_bprefill_8x\d+$", p) for p in progs)
    assert not {"?", "jit_fn", "jit_body"} & progs
    assert acc["unnamed_programs"] == []
    assert scope_reduce.share(acc, programs=scope_reduce.PREFILL_PROGRAM) > 0


def test_recorded_ops_carry_the_scopes(recorded):
    planes, acc = recorded
    assert acc["scoped"] is True
    comps = set(acc["by_component"])
    assert {"attn_qkv", "attn_core", "attn_out", "mlp", "head", "sample",
            "scan_plumbing"} <= comps
    assert comps <= set(scope_reduce.SCOPES) - {scope_reduce.SCAN_SCOPE} | {
        "scan_plumbing", "other", "unscoped"}
    # every op names its program: the fingerprint in the module's name
    ids = {o[5] for o in planes[0]["ops"]}
    assert None not in ids and len(ids) >= 4
    # the fused program keeps its two scans apart
    fused = acc["table"]["jit_fn_multi_decode_4"]
    assert fused.get("scan_plumbing", 0) > 0 and fused.get("step_ctl", 0) > 0
    assert sum(acc["by_component"].values()) == pytest.approx(acc["busy_s"])
    assert sum(acc["by_program"].values()) == pytest.approx(acc["busy_s"])


def test_recorded_busy_time_agrees_with_trace_reduce(recorded):
    """The same capture through the benchmark's first reducer: the sum of
    self times is the union of the op intervals."""
    import trace_reduce

    _, acc = recorded
    red = trace_reduce.reduce_planes(trace_reduce.load_xplane(TRACE))
    assert acc["busy_s"] == pytest.approx(red["busy_s"], rel=0.02)
    # every op found its launch (a launch may hold no op of its own)
    names = {trace_reduce.instr_name(k) for k in red["modules"]}
    assert set(acc["by_program"]) <= names


@pytest.mark.parametrize("extra", [[], ["--config", os.path.join(
    run.HERE, "configs", "yi-1.5-9b.json")]], ids=["plain", "no-scopes-key"])
def test_recorded_capture_reduces_to_the_same_table_byte_for_byte(
        tmp_path, extra):
    """`recorded/tiny_scoped_v5e.table.txt` is what the parent of PR 26
    printed for this capture: with no `scopes` the account is what it was."""
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    shutil.copy(TRACE, d / "tiny.xplane.pb")
    p = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "scope_reduce.py"),
         "--table"] + extra + [str(tmp_path)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    with open(os.path.join(HERE, "recorded", "tiny_scoped_v5e.table.txt"),
              "rb") as f:
        assert p.stdout == f.read()
