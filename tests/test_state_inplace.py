"""A prefill launch writes a lane's state into its slot in place (ISSUE 59):
`models/cache._read_state` hands the lanes' incoming rows on as a value of
their own and `_write_state` writes one slot a lane, so that the layer scan
stops copying the whole stacked leaf ahead of every write.

(a) SEMANTICS.  On the tiny twin of each of the four configurations with a
recurrent state, one two-lane prefill launch through the new accessors is
bit for bit the launch through the PARENT's (the gather and the two scatters
of f4d1a60, kept here as the reference and swapped in where the forward
passes name theirs): the logits, the rows, and every slot of every state
leaf.  The cases are the ones the rewrite could break; each also says what
the case means on the slots themselves.

(b) THE COMPILED TEXT.  A tiny hybrid prefill compiled for a DESCRIBED v5e
holds no `copy` with a state leaf's shape, in or out of a `while` body, and
the parent's accessors compiled the same way do hold one inside the scan
(the test would notice if it had stopped looking).  The topology is
described inside a fixture (on-chip-measurement guide, section 2).
"""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import forward, hybrid, init_params, llama
from kafka_tpu.models.cache import KVCache, StatePlan
from kafka_tpu.models.config import config_from_hf_json
from kafka_tpu.runtime import step_programs
from kafka_tpu.runtime.kv_cache import make_kv_pool_arrays

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TWINS = {
    "phi4flash": "phi4flash/configs/tiny-phi4flash.json",
    "lfm2moe": "lfm2moe/configs/tiny-lfm2moe.json",
    "solar_open2": "solar_open2/configs/tiny-solaropen2.json",
    "falcon_h1": "falcon_h1/configs/tiny-falconh1.json",
}
PS, PAGES, S, W = 8, 4, 16, 2
# state slots: two lanes', the trash slot, two snapshots
LANE0, LANE1, TRASH, SNAP0, SNAP1, N_SLOTS = 0, 1, 2, 3, 4, 5


def _parent_read(leaf, layer, plan, batch):
    """`_read_state` of f4d1a60."""
    if plan.src is None:
        return jax.lax.dynamic_slice(
            leaf, (layer, 0, 0, 0), (1, batch) + leaf.shape[2:])[0]
    rows = leaf[layer, plan.src]
    if plan.fresh is not None:
        fresh = plan.fresh & (plan.lens > 0)
        rows = jnp.where(fresh[:, None, None], 0.0, rows)
    return rows


def _parent_write(leaf, layer, plan, new, old):
    """`_write_state` of f4d1a60."""
    new = jnp.where((plan.lens > 0)[:, None, None], new, old).astype(leaf.dtype)
    if plan.dst is None:
        return jax.lax.dynamic_update_slice(leaf, new[None], (layer, 0, 0, 0))
    leaf = leaf.at[layer, plan.dst].set(new)
    if plan.snap is not None:
        leaf = leaf.at[layer, plan.snap].set(new)
    return leaf


def _parents(mp):
    for mod in (llama, hybrid):
        mp.setattr(mod, "_read_state", _parent_read)
        mp.setattr(mod, "_write_state", _parent_write)


def _launch_fn(cfg):
    def launch(params, k_pool, v_pool, page_rows, chunks, starts, lens, src,
               dst, snap, fresh):
        """A two-lane prefill launch with the StatePlan spelled out (the
        engine's programs pass one array as `src` and `dst`)."""
        pos, paged = step_programs.chunk_plan(
            page_rows, starts, lens, lens > 0, S, PS)
        paged = paged._replace(state=StatePlan(
            lens=lens, src=src, dst=dst, snap=snap, fresh=fresh))
        logits, cache = forward(params, cfg, chunks, pos,
                                kv_cache=KVCache(k_pool, v_pool), paged=paged)
        return logits, cache.k, cache.v

    return launch


class Twin:
    """One tiny twin: its weights, a pool whose every state slot holds
    values of its own, and the launch compiled through both accessors."""

    def __init__(self, name):
        path = os.path.join(ROOT, "benchmarks", "tests", TWINS[name])
        self.cfg = cfg = config_from_hf_json(path).replace(
            attention_backend="xla")
        self.params = init_params(cfg, jax.random.PRNGKey(0))
        k_pool, v_pool = make_kv_pool_arrays(
            cfg, W * PAGES + 1, PS, state_slots=N_SLOTS)
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
        self.pools = (k_pool, {
            leaf: (a if leaf == "v"
                   else jax.random.normal(next(keys), a.shape, a.dtype))
            for leaf, a in v_pool.items()})
        self.leaves = [leaf for leaf in v_pool if leaf != "v"]
        rng = np.random.default_rng(2)
        self.page_rows = jnp.asarray(
            1 + np.arange(W * PAGES).reshape(W, PAGES), jnp.int32)
        self.chunks = jnp.asarray(
            rng.integers(1, cfg.vocab_size, (W, S)), jnp.int32)
        args = self.args(dict(starts=[0, 0], lens=[S, S], src=[0, 1],
                              dst=[0, 1], snap=[2, 2], fresh=[True, True]))
        launch = _launch_fn(cfg)
        self.new = jax.jit(launch).lower(*args).compile()
        with pytest.MonkeyPatch.context() as mp:
            _parents(mp)
            self.parent = jax.jit(launch).lower(*args).compile()

    def args(self, case):
        i32 = jnp.int32
        return (self.params, *self.pools, self.page_rows, self.chunks,
                jnp.asarray(case["starts"], i32),
                jnp.asarray(case["lens"], i32), jnp.asarray(case["src"], i32),
                jnp.asarray(case["dst"], i32), jnp.asarray(case["snap"], i32),
                jnp.asarray(case["fresh"], bool))


@pytest.fixture(scope="module")
def twins():
    built = {}

    def get(name):
        if name not in built:
            built[name] = Twin(name)
        return built[name]

    return get


# what each case asks of the slots: `same` hold what they held, bit for bit;
# `moved` were written (a lane's 11-16 real rows move every leaf); `pairs`
# hold the same values as each other
CASES = {
    # the benchmark driver's second launch: the state comes from a snapshot
    # slot, goes to the lane's own, and no snapshot is wanted
    "src_ne_dst_snap_trash": dict(
        starts=[PS, 0], lens=[11, 0], src=[SNAP0, LANE1], dst=[LANE0, LANE1],
        snap=[TRASH, SNAP1], fresh=[False, False],
        same=[SNAP0, LANE1], moved=[LANE0],
        pairs=[(LANE0, TRASH), (LANE1, SNAP1)]),
    # a lane with no rows in a batched launch: its slot passes through
    "idle_lane": dict(
        starts=[PS, PS], lens=[0, S], src=[LANE0, LANE1], dst=[LANE0, LANE1],
        snap=[SNAP0, SNAP1], fresh=[False, False],
        same=[LANE0, TRASH], moved=[LANE1], pairs=[(LANE1, SNAP1)]),
    # ... also where the plan calls it fresh: not zeroed
    "fresh_idle_lane": dict(
        starts=[0, 0], lens=[0, 13], src=[LANE0, LANE1], dst=[LANE0, LANE1],
        snap=[TRASH, SNAP1], fresh=[True, True],
        same=[LANE0, SNAP0], moved=[LANE1], pairs=[(LANE1, SNAP1)]),
    # two lanes of one launch, one from zeros and one resumed, a snapshot
    # each (the engine's launch: src is dst)
    "two_lanes": dict(
        starts=[0, 2 * PS], lens=[S, 12], src=[LANE0, LANE1],
        dst=[LANE0, LANE1], snap=[SNAP0, SNAP1], fresh=[True, False],
        same=[TRASH], moved=[LANE0, LANE1],
        pairs=[(LANE0, SNAP0), (LANE1, SNAP1)]),
    # two lanes that both throw their snapshot away
    "two_lanes_snap_trash": dict(
        starts=[PS, 0], lens=[S, 14], src=[LANE0, LANE1], dst=[LANE0, LANE1],
        snap=[TRASH, TRASH], fresh=[False, True],
        same=[SNAP0, SNAP1], moved=[LANE0, LANE1], pairs=[]),
    # a lane resumed from ANOTHER lane's slot while that lane is rewritten:
    # every read is of the pool as the launch found it
    "src_is_the_other_lanes_dst": dict(
        starts=[PS, PS], lens=[S, S], src=[LANE1, LANE0], dst=[LANE0, LANE1],
        snap=[SNAP0, TRASH], fresh=[False, False],
        same=[SNAP1], moved=[LANE0, LANE1], pairs=[(LANE0, SNAP0)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", sorted(TWINS))
def test_a_prefill_launch_equals_the_parents(twins, name, case):
    twin, spec = twins(name), CASES[case]
    args = twin.args(spec)
    logits, k_new, v_new = twin.new(*args)
    want_logits, want_k, want_v = twin.parent(*args)
    active = np.asarray(spec["lens"]) > 0  # (equal logits: equal tokens)
    assert np.array_equal(np.asarray(logits)[active],
                          np.asarray(want_logits)[active])
    assert np.array_equal(k_new, want_k)
    assert np.array_equal(v_new["v"], want_v["v"])
    before = twin.pools[1]
    # (two lanes that write the trash slot leave it undefined: a scatter
    # with a repeated index; nobody reads it)
    defined = [slot for slot in range(N_SLOTS) if slot != TRASH
               or (spec["dst"] + spec["snap"]).count(TRASH) < 2]
    for leaf in twin.leaves:
        got = np.asarray(v_new[leaf])
        assert got.dtype == np.float32
        assert np.array_equal(got[:, defined],
                              np.asarray(want_v[leaf])[:, defined]), leaf
        for slot in spec["same"]:
            assert np.array_equal(got[:, slot], before[leaf][:, slot]), (
                leaf, slot)
        for slot in spec["moved"]:
            assert not np.array_equal(got[:, slot], before[leaf][:, slot]), (
                leaf, slot)
        for a, b in spec["pairs"]:
            assert np.array_equal(got[:, a], got[:, b]), (leaf, a, b)


@pytest.mark.parametrize("name", sorted(TWINS))
def test_a_fresh_lane_starts_from_zeros_whatever_its_slot_held(twins, name):
    """`fresh` with rows: the launch over a slot full of noise equals the
    launch over a slot of zeros, on the lane's slot and its logits."""
    twin = twins(name)
    case = dict(starts=[0, 0], lens=[S, 0], src=[LANE0, TRASH],
                dst=[LANE0, TRASH], snap=[SNAP0, TRASH], fresh=[True, False])
    args = list(twin.args(case))
    logits, _, v_new = twin.new(*args)
    args[2] = {leaf: (a if leaf == "v" else a.at[:, LANE0].set(0.0))
               for leaf, a in args[2].items()}
    want, _, v_want = twin.new(*args)
    assert np.array_equal(logits[0], want[0])
    for leaf in twin.leaves:
        for slot in (LANE0, SNAP0):
            assert np.array_equal(v_new[leaf][:, slot], v_want[leaf][:, slot])


# ----------------------------------------------------------------------
# (b) compiled for a described v5e
# ----------------------------------------------------------------------


def _leaf_copies():
    path = os.path.join(ROOT, "scripts", "leaf_copies.py")
    spec = importlib.util.spec_from_file_location("leaf_copies", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# serving shapes of a tiny hybrid: 4 lanes (17 slots), two prefill buckets
SRV = {"page_size": 16, "max_pages_per_seq": 8, "max_batch": 4,
       "num_pages": 64, "prefill_buckets": [64, 128]}
HYBRIDS = {"phi4flash": ("prefill[128]", "bprefill[64x4]"),
           "falcon_h1": ("prefill[128]",),
           "solar_open2": ("prefill[128]",)}


DECODES = dict(TWINS, nemotronh="nemotronh/configs/tiny-nemotronh.json")


def _compiled_copies(lc, name, label, sharding, only=None):
    path = os.path.join(ROOT, "benchmarks", "tests", DECODES[name])
    # (the XLA forms: the Pallas kernels do not tile a twin's widths for
    # the chip, and under them EVERY state leaf goes through the accessors,
    # the ones a kernel of the served path updates in place too)
    cfg = config_from_hf_json(path).replace(
        dtype="bfloat16", attention_backend="xla")
    programs, leaves = lc.engine_programs(cfg, SRV, sharding)
    assert len(leaves) == 2
    fn, args = programs[label]
    text = lc.compile_for(fn, args, label).as_text()
    assert " while(" in text  # the layer scan is there to look into
    return lc.leaf_copies(
        text, {leaves[only]} if only else set(leaves.values()))


@pytest.mark.parametrize("name,label", [
    (name, label) for name in sorted(HYBRIDS) for label in HYBRIDS[name]])
def test_no_compiled_prefill_copies_a_state_leaf(one_chip, name, label):
    found = _compiled_copies(_leaf_copies(), name, label, one_chip)
    if name == "solar_open2":
        # (the twin's `delta` leaf [.., 64, 16] is narrower than a lane
        # tile and the chip re-lays it out at the program's entry, a LAYOUT
        # copy, PERF.md section 7's own item: none inside the scan)
        found = [row for row in found if row["in_while"]]
    assert found == []


@pytest.mark.parametrize("name,label", [
    (name, label) for name in ("nemotronh", "solar_open2")
    for label in ("decode", "multi_decode[16]")])
def test_no_compiled_decode_program_copies_the_conv_leaf(
        one_chip, name, label):
    """ISSUE 64: the tail's slot is read and written where it lies at decode
    too, in the single step and in the fused one, as at the parent."""
    assert _compiled_copies(
        _leaf_copies(), name, label, one_chip, only="conv") == []


# channels a row, state layers, lanes, bias: the three served tails that
# `ops/pallas/tail_conv.tiles` takes
SERVED_TAILS = {"solar-open2": (24576, 6, 32, False),
                "nemotron-3-nano": (6144, 7, 32, True),
                "falcon-h1": (5120, 7, 32, True)}


@pytest.mark.parametrize("name", sorted(SERVED_TAILS))
def test_the_tail_kernel_compiles_for_the_chip_and_copies_no_leaf(
        one_chip, name):
    """`tail_conv_step` at a served geometry, compiled for a described v5e
    (what the interpreter cannot show: every slice a whole tile, the blocks
    inside VMEM): one custom call, the leaf aliased through, no copy."""
    from kafka_tpu.models.config import _tail_layout
    from kafka_tpu.ops.pallas import tail_conv

    C, layers, lanes, bias = SERVED_TAILS[name]
    slot = _tail_layout(3, C)
    assert tail_conv.tiles(4, C, slot)

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    leaf = of(f32, layers, 129, *slot)
    args = [leaf, of(i32), of(i32, lanes), of(f32, lanes, C), of(f32, 4, C)]
    if bias:
        args.append(of(f32, C))
    text = jax.jit(
        lambda *a: tail_conv.tail_conv_step(*a, interpret=False),
        donate_argnums=0).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "tail_conv_step" in text
    assert _leaf_copies().leaf_copies(
        text, {_leaf_copies().hlo_shape(leaf)}) == []
    assert "input_output_alias" in text


def test_the_served_falcon_h1_decode_step_moves_no_state_leaf(one_chip):
    """The engine's decode program at Falcon-H1's PUBLISHED widths, compiled
    for a described v5e: its `conv` leaf is 55 MB, small enough to fit the
    chip's fast memory beside a kernel's VMEM, and XLA prefetched it whole
    ahead of `tail_conv_step` in every layer and copied it back (`copy-start`
    / `copy-done` in the scan's body, +0.85 ms a pass on the chip: PERF.md
    section 6, PR 64) until the kernel's result held the leaf in HBM."""
    lc = _leaf_copies()
    path = os.path.join(ROOT, "benchmarks", "configs", "falcon-h1-34b.json")
    with open(path) as f:
        spec = json.load(f)
    cfg = config_from_hf_json(path).replace(
        dtype=spec["serving"]["dtype"],
        attention_backend=spec["expect"]["attention_backend"])
    assert cfg.attention_backend == "pallas"
    programs, leaves = lc.engine_programs(cfg, spec["serving"], one_chip)
    # (the paged-decode kernel does not compile under the suite's "highest")
    with jax.default_matmul_precision("default"):
        text = lc.compile_for(*programs["decode"], "decode").as_text()
    assert "tail_conv_step" in text and "ssd_step" in text
    assert lc.leaf_copies(text, set(leaves.values())) == []


def test_the_parents_accessors_do_copy_a_leaf_inside_the_scan(
        one_chip, monkeypatch):
    _parents(monkeypatch)
    found = _compiled_copies(
        _leaf_copies(), "phi4flash", "prefill[128]", one_chip)
    assert any(row["in_while"] for row in found), found


def test_leaf_copies_reads_a_modules_text():
    """The reader itself, on a module written by hand: a copy in the entry
    computation, one in a computation the while body calls, one of another
    shape, one inside a fusion."""
    lc = _leaf_copies()
    text = """HloModule m

%fused_computation.1 (p: f32[2,5,3,8]) -> f32[2,5,3,8] {
  %p = f32[2,5,3,8]{3,2,1,0} parameter(0)
  ROOT %copy.9 = f32[2,5,3,8]{3,2,1,0:T(8,128)} copy(%p)
}

%inner (q: f32[2,5,3,8]) -> f32[2,5,3,8] {
  %q = f32[2,5,3,8]{3,2,1,0} parameter(0)
  ROOT %copy.2 = f32[2,5,3,8]{3,2,1,0:T(8,128)} copy(%q)
}

%body.1 (c: (s32[], f32[2,5,3,8])) -> (s32[], f32[2,5,3,8]) {
  %c = (s32[], f32[2,5,3,8]{3,2,1,0}) parameter(0)
  %g = f32[2,5,3,8]{3,2,1,0} get-tuple-element(%c), index=1
  %x = f32[2,5,3,8]{3,2,1,0} call(%g), to_apply=%inner
  %y = f32[4,4]{1,0} copy(%z)
  %cs = (f32[2,5,3,8]{3,2,1,0:S(1)}, f32[2,5,3,8]{3,2,1,0}, u32[]) copy-start(%g)
  %copy-done.3 = f32[2,5,3,8]{3,2,1,0:T(8,128)S(1)} copy-done(%cs)
  ROOT %t = (s32[], f32[2,5,3,8]{3,2,1,0}) tuple(%i, %x)
}

%cond.1 (c: (s32[], f32[2,5,3,8])) -> pred[] {
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.7 (a: f32[2,5,3,8]) -> f32[2,5,3,8] {
  %a = f32[2,5,3,8]{3,2,1,0} parameter(0)
  %copy.1 = f32[2,5,3,8]{3,2,1,0:T(8,128)} copy(%a)
  %w = (s32[], f32[2,5,3,8]{3,2,1,0}) while(%init), condition=%cond.1, body=%body.1
  ROOT %r = f32[2,5,3,8]{3,2,1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1
}
"""
    rows = lc.leaf_copies(text, {"f32[2,5,3,8]"})
    assert sorted((r["op"], r["computation"], r["in_while"])
                  for r in rows) == [("copy-done.3", "body.1", True),
                                     ("copy.1", "main.7", False),
                                     ("copy.2", "inner", True)]


@pytest.mark.parametrize("kernel", ["gdn_step", "gdn_chunk"])
def test_the_gdn_kernels_compile_for_the_chip_at_the_published_heads(
        one_chip, kernel):
    """ISSUE 66: `gdn_step` (16 lanes, a lane's whole [96, 5760] block a grid
    step) and `gdn_chunk` (512 rows, a pair of heads a grid step) at
    Olmo-Hybrid's 30 heads x 96 x 192, where no head starts on a lane tile,
    compiled for a described v5e (what the interpreter cannot show: every
    slice a whole tile, the blocks inside VMEM): one custom call, the leaf
    aliased through, no copy."""
    from kafka_tpu.ops.pallas import gdn

    H, dk, dv = 30, 96, 192
    assert gdn.tiles(H, dk, dv)

    def of(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32, i32 = jnp.float32, jnp.int32
    leaf = of(f32, 12, 65, dk, H * dv)
    if kernel == "gdn_step":
        B = 16
        args = [leaf, of(i32), of(i32, B), of(f32, B, H * 128),
                of(f32, B, H * 128)] + [of(f32, B, H * dv)] * 3
        fn = lambda *a: gdn.gdn_step(*a, dv=dv, interpret=False)  # noqa: E731
    else:
        B, S = 1, 512
        args = ([leaf, of(i32)] + [of(i32, B)] * 4
                + [of(f32, B, S, H * 128)] * 2
                + [of(f32, B, S, H * dv), of(f32, B, S, H), of(f32, B, S, H)])
        fn = lambda *a: gdn.gdn_chunk(  # noqa: E731
            *a, dv=dv, chunk=64, interpret=False)
    text = jax.jit(fn, donate_argnums=0).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and kernel in text
    assert _leaf_copies().leaf_copies(
        text, {_leaf_copies().hlo_shape(leaf)}) == []
    assert "input_output_alias" in text


def test_the_served_olmo_hybrid_decode_step_runs_kernels_on_its_state(
        one_chip):
    """The engine's decode program at Olmo-Hybrid's PUBLISHED widths,
    compiled for a described v5e: the recurrence is `gdn_step` (no row-by-row
    scan's `while` over the state), attention is `paged_decode_attention` at
    a merged row of 3,840 lanes with its ring inside the VMEM the call asks
    for, and neither state leaf is copied (the tail's XLA body too reads and
    writes its slot where it lies)."""
    lc = _leaf_copies()
    path = os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b.json")
    with open(path) as f:
        spec = json.load(f)
    cfg = config_from_hf_json(path).replace(
        dtype=spec["serving"]["dtype"],
        attention_backend=spec["expect"]["attention_backend"])
    assert cfg.attention_backend == "pallas"
    programs, leaves = lc.engine_programs(cfg, spec["serving"], one_chip)
    assert leaves == {"conv": "f32[12,65,8,4320]",
                      "delta": "f32[12,65,96,5760]"}
    # (the paged-decode kernel does not compile under the suite's "highest")
    with jax.default_matmul_precision("default"):
        text = lc.compile_for(*programs["decode"], "decode").as_text()
    assert "gdn_step" in text and "paged_decode_attention" in text
    assert "tail_conv_step" not in text  # pieces of 11.25 lane tiles
    assert lc.leaf_copies(text, set(leaves.values())) == []
