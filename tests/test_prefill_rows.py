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
