"""The engine's two prefill-row counters (ISSUE 28): rows of every prefill
program dispatched (lanes x bucket) and the rows of them that held a token,
on `/metrics` under `engine.`, and the benchmark's reader of their window
delta (`benchmarks/layer_metrics/prefill_pad_share.py`)."""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.models import ModelConfig, init_params
from kafka_tpu.models.config import config_from_hf_json
from kafka_tpu.runtime import EngineConfig, GenRequest, InferenceEngine

READER = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                      "benchmarks", "layer_metrics", "prefill_pad_share.py")


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig(name="rows", vocab_size=128, dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def read():
    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(os.path.dirname(os.path.dirname(READER)))  # readers
        spec = importlib.util.spec_from_file_location("prefill_pad_share",
                                                      READER)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return mod.read


def make_engine(model, **kw):
    cfg, params = model
    return InferenceEngine(
        cfg, params,
        EngineConfig(max_batch=4, page_size=8, num_pages=64,
                     max_pages_per_seq=24, prefill_buckets=(16, 64), **kw),
        kv_dtype=jnp.float32)


def rows(eng):
    snap = eng.metrics.snapshot(eng)["engine"]
    assert snap["prefill_rows_dispatched"] == eng.prefill_rows_dispatched
    assert snap["prefill_rows_filled"] == eng.prefill_rows_filled
    return eng.prefill_rows_dispatched, eng.prefill_rows_filled


@pytest.mark.parametrize("n_prompt, chunks", [
    (5, [(16, 5)]),                        # the small bucket, mostly padding
    (16, [(16, 16)]),                      # a full bucket: no padding
    (40, [(64, 40)]),                      # the first bucket that holds it
    (150, [(64, 64), (64, 64), (64, 22)]),  # full chunks, then the rest
])
def test_single_prefill_counts_bucket_and_chunk_len(model, n_prompt, chunks):
    eng = make_engine(model)
    assert rows(eng) == (0, 0)
    prompt = list(np.random.RandomState(n_prompt).randint(1, 128,
                                                          size=n_prompt))
    eng.submit(GenRequest(request_id="a", prompt_ids=prompt,
                          max_new_tokens=2))
    eng.run_to_completion()
    assert rows(eng) == (sum(b for b, _ in chunks),
                         sum(n for _, n in chunks))
    assert sum(n for _, n in chunks) == n_prompt


def test_batched_prefill_counts_every_lane_of_the_program(model,
                                                           monkeypatch):
    """Three same-bucket prompts admitted together fuse into ONE launch of
    the 4-lane batched program: 4 x 16 rows on the device, the fourth lane
    empty."""
    eng = make_engine(model)
    launches = []
    batch = eng._advance_prefill_batch
    monkeypatch.setattr(
        eng, "_advance_prefill_batch",
        lambda bucket, reqs, W: (launches.append((bucket, len(reqs), W)),
                                 batch(bucket, reqs, W)))
    rng = np.random.RandomState(1)
    lens = (5, 9, 12)
    for i, n in enumerate(lens):
        eng.submit(GenRequest(request_id=f"r{i}",
                              prompt_ids=list(rng.randint(1, 128, size=n)),
                              max_new_tokens=2))
    eng.run_to_completion()
    assert launches == [(16, 3, 4)]
    assert rows(eng) == (4 * 16, sum(lens))


def test_reader_gives_the_windows_share_or_nothing(model, read):
    eng = make_engine(model)
    rng = np.random.RandomState(2)

    def turn(rid, n):
        eng.submit(GenRequest(request_id=rid,
                              prompt_ids=list(rng.randint(1, 128, size=n)),
                              max_new_tokens=2))
        eng.run_to_completion()

    turn("warm", 33)  # before the window: must not count
    before = eng.metrics.snapshot(eng)
    assert read({"before": before, "after": before}) is None  # no launch
    turn("a", 40)     # 64 rows, 40 real
    turn("b", 4)      # 16 rows, 4 real
    after = eng.metrics.snapshot(eng)
    assert read({"before": before, "after": after}) == pytest.approx(
        100.0 * (1 - 44 / 80))
    # the parent's /metrics has no such counters
    for snap in (before, after):
        snap = dict(snap, engine={k: v for k, v in snap["engine"].items()
                                  if not k.startswith("prefill_rows")})
        assert read({"before": snap, "after": snap}) is None


# ---------------------------------------------------------------------------
# The chunk plan (ISSUE 55): with a price the engine cuts a remainder into
# full launches of a smaller bucket where that is modeled cheaper, counts the
# requests it did that to, and the tokens are those of the one-launch plan.
# ---------------------------------------------------------------------------

LADDER = (16, 64, 256)
# a launch of 256 rows costs over four of 64; two of 16 more than one of 64
PRICE = {16: 40.0, 64: 70.0, 256: 300.0}


def plan_engine(model, split, **kw):
    cfg, params = model
    eng = InferenceEngine(
        cfg, params,
        EngineConfig(**dict(dict(max_batch=4, page_size=16, num_pages=96,
                                 max_pages_per_seq=40, multi_step=4,
                                 prefill_buckets=LADDER), **kw)),
        kv_dtype=jnp.float32)
    assert eng._launch_price is None  # the CPU has no roofline
    if split:
        eng._launch_price = lambda rows, tokens, start: PRICE[rows]
    return eng


def plans(eng):
    snap = eng.metrics.snapshot(eng)["engine"]
    assert snap["prefill_plans"] == eng.prefill_plans
    assert snap["prefill_plans_split"] == eng.prefill_plans_split
    return eng.prefill_plans, eng.prefill_plans_split


@pytest.mark.parametrize("n_prompt, chunks, split", [
    (150, [(64, 64), (64, 64), (64, 22)], True),  # 210 against 300
    (64, [(64, 64)], False),                      # a full bucket
    (70, [(64, 64), (16, 6)], True),              # 110 against 300
    (40, [(64, 40)], False),                      # 16, 16 and 8 cost 120
    (256, [(256, 256)], False),                   # four of 64: 280, 7% less
    (300, [(256, 256), (64, 44)], False),         # as without a price
    (420, [(256, 256), (64, 64), (64, 64), (64, 36)], True),  # the rest is
    (520, [(256, 256), (256, 256), (16, 8)], False),  # 640: 64s cost 600
])
def test_split_plan_launches_and_counters(model, monkeypatch, n_prompt,
                                          chunks, split):
    eng = plan_engine(model, split=True)
    assert plans(eng) == (0, 0)
    launched = []
    prefill = eng._programs.prefill
    monkeypatch.setattr(eng._programs, "prefill",
                        lambda b: (launched.append(b), prefill(b))[1])
    prompt = list(np.random.RandomState(n_prompt).randint(1, 128,
                                                          size=n_prompt))
    eng.submit(GenRequest(request_id="a", prompt_ids=prompt,
                          max_new_tokens=2))
    eng.run_to_completion()
    assert launched == [b for b, _ in chunks]
    assert rows(eng) == (sum(b for b, _ in chunks), n_prompt)
    assert sum(n for _, n in chunks) == n_prompt
    assert plans(eng) == (1, int(split))


def test_flight_note_carries_rows_and_split(model):
    from kafka_tpu.runtime.flight_recorder import FlightRecorder

    eng = plan_engine(model, split=True)
    eng.flight = FlightRecorder(size=64)
    eng.generate(list(range(1, 151)), max_new_tokens=2, temperature=0.0)
    notes = [r for r in eng.flight.records() if r["prefill_lanes"]]
    assert [(r["prefill_rows"], r["prefill_toks"], r["prefill_split"])
            for r in notes] == [(64, 64, 1), (64, 64, 1), (64, 22, 1)]


TWINS = os.path.join(os.path.dirname(READER), os.pardir, "tests")
# the benchmark's tiny twins of Phi-4-flash (a Mamba state and a conv tail a
# slot) and LFM2 (a conv tail, routed experts), float32
PRESETS = {
    "gqa": lambda: ModelConfig(name="rows", vocab_size=128, dtype="float32"),
    "phi4flash": lambda: config_from_hf_json(os.path.join(
        TWINS, "phi4flash", "configs", "tiny-phi4flash.json")),
    "lfm2moe": lambda: config_from_hf_json(os.path.join(
        TWINS, "lfm2moe", "configs", "tiny-lfm2moe.json")),
}


@pytest.fixture(scope="module", params=sorted(PRESETS))
def preset(request):
    cfg = PRESETS[request.param]()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _generate(eng, prompts, n=8):
    reqs = [GenRequest(request_id=f"r{i}", prompt_ids=p, max_new_tokens=n,
                       temperature=0.0, prefix_key=f"k{i}")
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    assert eng.self_check() == []
    return [r.output_ids for r in reqs]


@pytest.mark.parametrize("lanes", [1, 3], ids=["single", "batched"])
def test_split_plan_gives_the_one_launch_plans_tokens(preset, lanes):
    """The same prompts through the first bucket that holds them (one launch
    of 256 rows each) and through the split plan (64 + 64 + what is left):
    greedy tokens equal, a recurrent state handed across the new boundaries
    (the conv tail with it), alone and in the batched prefill program."""
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, 128, size=150 + 9 * i)]
               for i in range(lanes)]
    whole = plan_engine(preset, split=False)
    want = _generate(whole, prompts)
    assert whole.prefill_rows_dispatched == (256 if lanes == 1 else 4 * 256)
    assert plans(whole) == (lanes, 0)
    cut = plan_engine(preset, split=True)
    assert _generate(cut, prompts) == want
    assert plans(cut) == (lanes, lanes)
    # three launches of 64 rows: one lane wide, or the 4-lane program's
    assert cut.prefill_rows_dispatched == 3 * 64 * (1 if lanes == 1 else 4)
    assert cut.prefill_rows_filled == sum(map(len, prompts))
    labels = {k[0] for k in cut._programs.built}
    assert ("bprefill[64x4]" in labels) == (lanes == 3)
    if cut.state_pool is not None:
        # the split's chunks end on page boundaries (64, 128) and leave no
        # snapshot there: the one launch they stand for left none
        stored = [e.state_section()["state_snapshots_stored"]
                  for e in (whole, cut)]
        assert stored[0] == stored[1] == 0
