"""The chunk plan (ISSUE 55): how a prompt's uncached suffix is cut into
prefill launches.  `planner.prefill_launches` covers the remainder at the
least modeled price, `DispatchCostModel.prefill_launch_cost` is the price of
one launch, and the engine asks the plan's first bucket
(`InferenceEngine._first_bucket`).  No chip and no timing: the prices here
are v5e's datasheet peaks over the registered configurations' shapes, or
injected.  ISSUE 57: the price is the configuration the engine RUNS (the
resolved backend), and a latent launch pays its walk over the context."""

import dataclasses
import json
import os

import pytest

import jax.numpy as jnp

from kafka_tpu.models.config import GLOBAL, WINDOWED, config_from_hf_json
from kafka_tpu.runtime import planner
from kafka_tpu.runtime.planner import (
    PREFILL_SPLIT_MIN_SAVING,
    first_fit_bucket as todays_bucket,  # `_prefill_bucket_for` before the plan
    first_fit_launches,
    prefill_launches,
)

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "benchmarks", "configs")
LADDERS = ((64, 512, 2048), (64, 256, 512), (128, 256, 512))
START = 7424  # the registered cells' shared prefix, in whole pages


def served(name):
    """(ModelConfig on the backend the cell expects, ladder, page size) of a
    registered configuration."""
    path = os.path.join(CONFIGS, name + ".json")
    with open(path) as f:
        raw = json.load(f)
    cfg = config_from_hf_json(path).replace(
        attention_backend=raw["expect"]["attention_backend"])
    serving = raw["serving"]
    return cfg, tuple(serving["prefill_buckets"]), serving["page_size"]


def own_prefix(name):
    """The shared prefix of a registered configuration's cells, in whole
    pages: START is the 4,175-byte system prompt's (one token a byte, the
    builtin tools and the persona round it), and dots3's and K-EXAONE's own
    prompt is 24,994 bytes, so their cells prefill from ~28.2k."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        serving = json.load(f)["serving"]
    ps = serving["page_size"]
    return (START - 4175 + len(serving["system_prompt"].encode())) // ps * ps


def tiny(latent):
    from kafka_tpu.models import ModelConfig

    if not latent:
        return ModelConfig(name="plan", vocab_size=128, dtype="float32")
    return ModelConfig(
        name="plan-latent", vocab_size=128, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=8, intermediate_size=96,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, dtype="float32")


def tiny_engine(cfg, ladder, backend="auto"):
    """(a CPU engine of `cfg` over float32 pages, its parameters)."""
    import jax

    from kafka_tpu.models import init_params
    from kafka_tpu.runtime import EngineConfig, InferenceEngine

    params = init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=2, page_size=16, num_pages=32,
                     max_pages_per_seq=8, prefill_buckets=ladder,
                     attention_backend=backend),
        kv_dtype=jnp.float32), params


def v5e_price(cfg):
    return planner.dispatch_cost_model(cfg).launch_price(
        *planner.CHIP_PEAKS["v5e"])


# ---------------------------------------------------------------------------
# (a) no roofline: the plan is the first bucket that holds the remainder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ladder", LADDERS)
def test_without_a_price_the_plan_is_first_fit(ladder):
    for r in range(1, 2201):
        plan = prefill_launches(r, ladder)
        assert plan == first_fit_launches(r, ladder)
        assert plan[0][0] == todays_bucket(r, ladder)
        assert sum(t for _, t in plan) == r


@pytest.mark.parametrize("ladder", LADDERS)
def test_an_engine_without_a_roofline_asks_first_fit(ladder):
    """On the CPU `device_peaks` is unknown: no price, no memo, today's
    bucket for every remainder, and nothing counted as split."""
    eng, _ = tiny_engine(tiny(latent=False), ladder)
    assert eng._launch_price is None and not eng._have_roofline
    for r in range(1, 2201):
        assert eng._first_bucket(r, 0) == (todays_bucket(r, ladder), False)
    assert eng._first_buckets == {}


# ---------------------------------------------------------------------------
# (b) v5e's peaks over the registered configurations: the decisions, pinned
# ---------------------------------------------------------------------------

REMAINDERS = (300, 512, 513, 600, 800, 1024, 1084, 1600, 2048, 2500)

# the buckets of each plan's launches from position START (first-fit's where
# the two agree: "=")
DECISIONS = {
    # (600: the band the engine cut 512 + 64 + 64 while it priced pad rows'
    # attention on every backend: 592-655 tokens, ISSUE 57)
    "yi-1.5-9b": {
        513: (512, 64), 600: (512, 512), 800: (512, 512), 1024: (512, 512),
        1084: (512, 512, 64), 1600: (512, 512, 512, 64)},
    "phi-4-mini-flash-reasoning": {
        513: (512, 64), 600: (512, 512), 800: (512, 512), 1024: (512, 512),
        1084: (512, 512, 64)},
    "lfm2-8b-a1b": {},
    "mellum2-12b-a2.5b": {},
    "mixtral-8x7b": {},
    "kanana-2-30b-a3b": {},
    "falcon-h1-34b": {},
    "dots3-note-prev": {},
    "k-exaone-236b-a23b": {},
    "solar-open2-250b": {},
    "xing4.0-29b-a4b": {},
}


@pytest.mark.parametrize("name", sorted(DECISIONS))
def test_decisions_at_v5e_peaks_are_pinned(name):
    cfg, ladder, ps = served(name)
    price = v5e_price(cfg)
    for r in REMAINDERS:
        plan = prefill_launches(r, ladder, price, START, ps)
        want = DECISIONS[name].get(
            r, tuple(b for b, _ in first_fit_launches(r, ladder)))
        assert tuple(b for b, _ in plan) == want, (name, r, plan)


@pytest.mark.parametrize("where", ["empty", "short", "own"])
@pytest.mark.parametrize("name", [
    "mixtral-8x7b", "kanana-2-30b-a3b", "falcon-h1-34b", "dots3-note-prev",
    "k-exaone-236b-a23b", "solar-open2-250b", "xing4.0-29b-a4b"])
def test_a_ladder_that_tops_at_512_keeps_first_fit(name, where):
    """The seven cells whose ladder tops at 512 are the control: a remainder
    over 512 already goes out as full 512s, and under it no 256 + 64 split
    is 10% cheaper (a routed block under 384 rows multiplies every held
    expert by every row; a dense one pays the weights' read twice; a latent
    one walks the whole context again), from an empty context, from START
    and from the prefix the configuration's own cell prefills behind (dots3,
    K-EXAONE: ~28.2k, where an empty row's attention would be six times its
    dense products if the walk's fold skipped none and the price forgot the
    walk: the plan PR 55's engine ran cut a third of dots3's requests)."""
    cfg, ladder, ps = served(name)
    assert ladder[-1] == 512
    price = v5e_price(cfg)
    start = {"empty": 0, "short": START, "own": own_prefix(name)}[where]
    for r in range(1, 1101):
        assert prefill_launches(r, ladder, price, start, ps) == \
            first_fit_launches(r, ladder), (name, r, start)


def test_the_cells_own_prefixes():
    assert own_prefix("yi-1.5-9b") == own_prefix("kanana-2-30b-a3b") == START
    assert own_prefix("dots3-note-prev") == 28240
    assert own_prefix("k-exaone-236b-a23b") == 28240


# ---------------------------------------------------------------------------
# (c) what every plan is
# ---------------------------------------------------------------------------

def rows_price(rows, tokens, start):
    """A price with no floor a launch: every split wins."""
    return float(rows)


def plans():
    for name in ("yi-1.5-9b", "lfm2-8b-a1b", "mixtral-8x7b"):
        cfg, ladder, ps = served(name)
        yield ladder, ps, v5e_price(cfg)
    for ladder in LADDERS + ((16, 64, 256),):
        yield ladder, 16, rows_price


def test_every_plan_covers_the_remainder_in_full_launches_on_pages():
    for ladder, ps, price in plans():
        for r in list(range(1, 1200, 7)) + [2048, 2049, 2500, 5000]:
            plan = prefill_launches(r, ladder, price, START, ps)
            assert sum(t for _, t in plan) == r
            at = START
            for rows, tokens in plan[:-1]:
                assert rows in ladder and tokens == rows  # full
                at += tokens
                assert at % ps == 0                       # on a page
            rows, tokens = plan[-1]
            rest = r - sum(t for _, t in plan[:-1])
            assert tokens == rest
            # the last launch: the first bucket that holds the rest
            assert rows == todays_bucket(rest, ladder) >= rest


def test_a_bucket_off_the_page_grid_splits_nothing():
    """A full launch of 24 rows would end between pages of 16."""
    ladder = (24, 256)
    assert prefill_launches(150, ladder, rows_price, 0, 16) == ((256, 150),)
    assert prefill_launches(150, ladder, rows_price, 0, 8) == \
        ((24, 24),) * 6 + ((24, 6),)


# ---------------------------------------------------------------------------
# (d) the threshold, and ties
# ---------------------------------------------------------------------------

def table_price(table):
    return lambda rows, tokens, start: table[rows]


@pytest.mark.parametrize("two_small, splits", [
    (0.91, False), (0.90, True), (0.89, True)])
def test_a_split_must_be_a_tenth_cheaper(two_small, splits):
    assert PREFILL_SPLIT_MIN_SAVING == 0.10
    price = table_price({64: two_small / 2, 512: 1.0})
    plan = prefill_launches(128, (64, 512), price)
    assert plan == (((64, 64), (64, 64)) if splits else ((512, 128),))


def test_ties_go_to_fewer_launches():
    # four launches of 32 and two of 64 cost the same
    price = table_price({32: 0.2, 64: 0.4, 512: 1.0})
    assert prefill_launches(128, (32, 64, 512), price) == ((64, 64),) * 2
    # and a cheaper one with more launches still wins
    price = table_price({32: 0.19, 64: 0.4, 512: 1.0})
    assert prefill_launches(128, (32, 64, 512), price) == ((32, 32),) * 4


def test_the_rest_of_a_split_is_planned_too():
    # 300 = 256 + 44: the rest goes out in the first bucket that holds it
    # (64), or in two of 16 and one that holds 12 where that is cheaper
    price = table_price({16: 0.3, 64: 1.0, 256: 2.0, 1024: 10.0})
    assert prefill_launches(300, (16, 64, 256, 1024), price) == \
        ((256, 256), (16, 16), (16, 16), (16, 12))
    price = table_price({16: 0.4, 64: 1.0, 256: 2.0, 1024: 10.0})
    assert prefill_launches(300, (16, 64, 256, 1024), price) == \
        ((256, 256), (64, 44))


# ---------------------------------------------------------------------------
# (e) the price of one launch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(DECISIONS))
def test_price_is_monotone_in_rows_and_tokens(name):
    cfg, ladder, _ = served(name)
    cm = planner.dispatch_cost_model(cfg)
    price = v5e_price(cfg)
    for rows in (512, 2048):  # one form of the routed block
        costs = [cm.prefill_launch_cost(rows, t, START)
                 for t in range(64, rows + 1, 64)]
        assert costs == sorted(costs)
        assert [f for f, _ in costs] == sorted(f for f, _ in costs)
    for tokens in (1, 300, 512):
        f_small, b_small = cm.prefill_launch_cost(512, tokens, START)
        f_large, b_large = cm.prefill_launch_cost(2048, tokens, START)
        assert f_large > f_small and b_large >= b_small
        assert price(2048, tokens, START) >= price(512, tokens, START)


def test_pad_rows_pay_the_dense_products_and_nothing_else():
    """Yi (dense, Pallas): 1,536 more rows cost 1,536 x the per-row
    products and not one attention pair; LFM2 (routed, token dispatch from
    384 rows): not one expert pick either; the bytes do not move."""
    for name in ("yi-1.5-9b", "lfm2-8b-a1b", "mellum2-12b-a2.5b"):
        cfg, _, _ = served(name)
        cm = planner.dispatch_cost_model(cfg)
        f_small, b_small = cm.prefill_launch_cost(512, 400, START)
        f_large, b_large = cm.prefill_launch_cost(2048, 400, START)
        assert f_large - f_small == pytest.approx(1536 * cm.row_flops)
        assert b_large == b_small
    # the XLA prefill computes the rows past chunk_len: they attend
    cfg, _, _ = served("mixtral-8x7b")
    cm = planner.dispatch_cost_model(cfg)
    assert cm.attend_row_tile == 1
    f_few, _ = cm.prefill_launch_cost(512, 100, START)
    f_all, _ = cm.prefill_launch_cost(512, 512, START)
    pairs = sum(p for p, _ in cm.attn_kinds)
    assert f_all - f_few == pytest.approx(
        412 * cm.moe[2] * cm.pick_flops)  # the picks alone
    assert f_few > 512 * (START + 256) * pairs


def test_the_weights_are_read_once_a_launch():
    cfg, _, _ = served("yi-1.5-9b")
    cm = planner.dispatch_cost_model(cfg)
    # dense, untied: every leaf but the table the launch gathers rows of
    table = cfg.vocab_size * cfg.hidden_size * 2
    assert cm.launch_bytes == cm.weight_bytes - table
    for rows in (64, 512, 2048):
        _, bytes_ = cm.prefill_launch_cost(rows, 64, 0)
        assert bytes_ == cm.launch_bytes + 128 * cm.kv_bytes_per_token
    # routed: under dense dispatch every held expert, under token dispatch
    # those some token picked (all of 32 at top-4 from a few dozen tokens on)
    cfg, _, _ = served("lfm2-8b-a1b")
    cm = planner.dispatch_cost_model(cfg)
    kv = cm.kv_bytes_per_token
    assert cm.launch_bytes + cm.expert_bytes <= cm.weight_bytes
    _, dense = cm.prefill_launch_cost(64, 1, 0)
    assert dense == cm.launch_bytes + cm.expert_bytes + 2 * kv
    _, one = cm.prefill_launch_cost(512, 1, 0)
    assert one == pytest.approx(
        cm.launch_bytes + cm.expert_bytes * 4 / 32 + 2 * kv)
    _, many = cm.prefill_launch_cost(512, 400, 0)
    assert many == pytest.approx(
        cm.launch_bytes + cm.expert_bytes + 800 * kv, rel=1e-6)


def test_a_hybrid_decoders_second_half_is_priced_at_one_row():
    cfg, _, _ = served("phi-4-mini-flash-reasoning")
    cm = planner.dispatch_cost_model(cfg)
    wb = 2
    second = planner._hybrid_second_half_bytes(cfg, wb) / wb
    table = cfg.vocab_size * cfg.hidden_size
    assert cm.lane_flops == pytest.approx(2.0 * (second + table))
    total = planner.weight_bytes_per_device(cfg) / wb
    assert cm.row_flops == pytest.approx(2.0 * (total - second - table))
    # 14 of the 32 layers, every one with its MLP
    assert second > 14 * 3 * cfg.hidden_size * cfg.intermediate_size


# ---------------------------------------------------------------------------
# (f) the price is of the configuration the engine runs (ISSUE 57)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("latent, asked, resolved, tile", [
    (False, "auto", "xla", 1), (False, "pallas", "pallas", 0),
    (True, "auto", "xla", 1), (True, "pallas", "pallas", 128)])
def test_an_engine_prices_the_backend_it_resolved(latent, asked, resolved,
                                                  tile):
    """The constructor's ModelConfig says "xla" (the default) whatever the
    engine resolves: the cost model is built from `engine.cfg`, so "auto"
    on the CPU prices XLA's rows and a pinned "pallas" the kernels'."""
    from kafka_tpu.models.quant import param_bytes

    cfg = tiny(latent)
    assert cfg.attention_backend == "xla"
    eng, params = tiny_engine(cfg, (64,), asked)
    assert eng.cfg.attention_backend == resolved
    assert eng._cost_model == planner.dispatch_cost_model(
        eng.cfg, weight_bytes_total=param_bytes(params), kv_dtype_bytes=4)
    assert eng._cost_model.attend_row_tile == tile
    assert bool(eng._cost_model.walk_kinds) == latent


def test_the_rows_that_attend_are_the_paths():
    """XLA prefill: every bucket row.  Flash prefill (GQA on Pallas, one
    device, a pool that is not int8): the rows that hold a token.  The
    latent walk: every bucket row, in whole lane tiles of 128 where the
    kernel folds."""
    yi, _, _ = served("yi-1.5-9b")
    assert planner.dispatch_cost_model(yi).attend_row_tile == 0
    assert planner.dispatch_cost_model(yi, n_devices=4).attend_row_tile == 1
    assert planner.dispatch_cost_model(yi, kv_dtype_bytes=1
                                       ).attend_row_tile == 1
    assert planner.dispatch_cost_model(
        yi.replace(attention_backend="xla")).attend_row_tile == 1
    dots, _, _ = served("dots3-note-prev")
    assert planner.dispatch_cost_model(dots).attend_row_tile == 128
    on_xla = planner.dispatch_cost_model(dots.replace(attention_backend="xla"))
    assert on_xla.attend_row_tile == 1
    # a 64-row latent launch folds 128 rows on the kernel path: the pairs of
    # a 128-row one (what is left of the difference is 64 rows' products)
    # (the routed block apart: its form changes between the two buckets)
    cm = dataclasses.replace(planner.dispatch_cost_model(dots), moe=None)
    on_xla = dataclasses.replace(on_xla, moe=None)
    start = own_prefix("dots3-note-prev")
    f64, _ = cm.prefill_launch_cost(64, 64, start)
    f128, _ = cm.prefill_launch_cost(128, 64, start)
    assert f128 - f64 == pytest.approx(64 * cm.row_flops)
    x64, _ = on_xla.prefill_launch_cost(64, 64, start)
    x128, _ = on_xla.prefill_launch_cost(128, 64, start)
    assert x128 == pytest.approx(f128)
    assert x128 - x64 > 5 * 64 * cm.row_flops  # there 64 more rows attend


# ---------------------------------------------------------------------------
# (g) a latent launch's walk (ISSUE 57)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["yi-1.5-9b", "mixtral-8x7b",
                                  "falcon-h1-34b"])
def test_a_model_without_latent_rows_walks_nothing(name):
    cfg, _, _ = served(name)
    cm = planner.dispatch_cost_model(cfg)
    assert cm.walk_kinds == ()
    assert cm.walk_flops(512, START) == 0.0
    pf, pb = planner.CHIP_PEAKS["v5e"]
    flops, bytes_ = cm.prefill_launch_cost(512, 300, START)
    assert cm.launch_price(pf, pb)(512, 300, START) == \
        max(flops / pf, bytes_ / pb)


@pytest.mark.parametrize("name", ["dots3-note-prev", "kanana-2-30b-a3b",
                                  "xing4.0-29b-a4b"])
def test_the_walk_is_linear_in_the_context_and_blind_to_rows(name):
    cfg, _, _ = served(name)
    cm = planner.dispatch_cost_model(cfg)
    g = cfg.geometry_of()
    a_key = 2.0 * cfg.layers_of(GLOBAL) * g.kv_lora_rank * g.num_heads * (
        g.qk_nope_head_dim + g.v_head_dim)
    full = [(k, w) for k, w in cm.walk_kinds if w is None]
    assert full == [(a_key, None)]
    # past the sliding layers' window every key more is one more key through
    # every full layer, whatever the bucket
    far = cm.walk_flops(64, 20000)
    assert cm.walk_flops(64, 28192) - far == pytest.approx(8192 * a_key)
    assert cm.walk_flops(512, 20000) - far == pytest.approx(448 * sum(
        k for k, _ in cm.walk_kinds))
    for rows in (64, 256, 512):
        f, _ = cm.prefill_launch_cost(rows, 64, 28192)
        f0, _ = cm.prefill_launch_cost(rows, 64, 20000)
        q = rows + -rows % 128
        pairs = sum(p for p, w in cm.attn_kinds if w is None)
        assert f - f0 == pytest.approx(8192 * a_key + q * 8192 * pairs)
    # and it is paid in series with the launch's two bounds
    pf, pb = planner.CHIP_PEAKS["v5e"]
    flops, bytes_ = cm.prefill_launch_cost(64, 16, START)
    walk = cm.walk_flops(16, START)
    assert 0 < walk < flops
    assert cm.launch_price(pf, pb)(64, 16, START) == pytest.approx(
        max((flops - walk) / pf, bytes_ / pb) + walk / pf)


def test_a_sliding_layers_walk_is_bounded_by_its_window():
    """dots3's sliding kind (513 keys): walked from the chunk that holds the
    first query's window, so never more than the window, the launch's own
    tokens and one chunk of the walk."""
    from kafka_tpu.models.mixers.latent import PREFILL_WALK_KEYS

    cfg, _, _ = served("dots3-note-prev")
    cm = planner.dispatch_cost_model(cfg)
    (a_key, window), = [(k, w) for k, w in cm.walk_kinds if w is not None]
    assert window == cfg.window_of(WINDOWED) == 513
    g = cfg.geometry_of(WINDOWED)
    assert a_key == 2.0 * cfg.layers_of(WINDOWED) * g.kv_lora_rank \
        * g.num_heads * (g.qk_nope_head_dim + g.v_head_dim)
    only = dataclasses.replace(cm, walk_kinds=((a_key, window),))
    assert cm.walk_chunk_keys == PREFILL_WALK_KEYS
    for start in (0, 400, 1024, 7424, 28240, 31000):
        for tokens in (16, 512):
            keys = only.walk_flops(tokens, start) / a_key
            assert keys == pytest.approx(
                start + tokens
                - max(start - window + 1, 0) // PREFILL_WALK_KEYS
                * PREFILL_WALK_KEYS)
            assert min(start, window - 1) + tokens <= round(keys) \
                < window + tokens + PREFILL_WALK_KEYS
    # the engine's own count of the trips (StepPrograms.prefill_walk_trips)
    # is the same walk: whole chunks of it
    assert only.walk_flops(512, 28240) / a_key == 28752 - 27 * 1024
