"""Zero-host-copy KV movement (ISSUE 19): device-to-device page
shipping, wake prefetch, and multipart object puts.

The load-bearing claims:
  * the DeviceShipper round-trips page runs byte-exact (float32 + bf16,
    single- and multi-chunk) with the same torn-chunk chaos contract as
    the host transport (kv.ship fires once per chunk, error:nth=2
    raises mid-run),
  * KAFKA_TPU_SHIP_TRANSPORT resolves conservatively: unset/unknown ->
    host, auto -> device only when BOTH owners' pools are in-process
    jax arrays, explicit modes taken at their word,
  * host and device transports land byte-identical destination pools,
    and only the host path ever arms the process-wide staging
    accounting (device ship pins zero host bytes),
  * the WakePrefetcher is an overlap optimization, never a correctness
    dependency: single-flight per content key, staged payloads are the
    same bytes the sync fetch returns, queued-unstarted entries are
    reclaimed for the sync path, failures/cancellations degrade with no
    staged residue, the byte budget evicts oldest-ready-first, and a
    tripped store breaker stops scheduling entirely,
  * HTTPObjectStore puts above KAFKA_TPU_KV_OBJECT_MULTIPART_MB go
    initiate/part/complete, abort server-side on failure (no orphan
    object, no orphan upload), and reland identically under StoreGuard
    retry,
  * with every knob unset the three legs are bit-identical to the old
    behavior: host transport, no prefetcher, monolithic puts.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kafka_tpu.runtime import failpoints
from kafka_tpu.runtime.kv_tier import (
    ENV_SHIP_TRANSPORT,
    CrossReplicaPageShipper,
    DeviceShipper,
    resolve_ship_transport,
    ship_staging_bytes,
    ship_staging_peak,
    ship_transport_from_env,
)
from kafka_tpu.runtime.object_tier import (
    ENV_OBJECT_MULTIPART_MB,
    ENV_WAKE_PREFETCH_MB,
    HTTPObjectStore,
    LocalFSObjectStore,
    ObjectTier,
    WakePrefetcher,
    object_multipart_bytes,
)
from kafka_tpu.runtime.store_guard import (
    BREAKER_OPEN,
    CircuitBreaker,
    StoreGuard,
)

from objstore_stub import StubS3Server

MiB = 1 << 20


class _Owner:
    """Minimal pool-array holder standing in for a replica engine (the
    shipper only needs mutable k_pool/v_pool)."""

    def __init__(self, num_pages, page_size, layers=2, width=8, seed=0,
                 dtype=np.float32):
        rng = np.random.default_rng(seed)
        shape = (layers, num_pages * page_size, width)
        self.k_pool = jnp.asarray(
            rng.normal(size=shape).astype(np.float32)
        ).astype(dtype)
        self.v_pool = jnp.asarray(
            rng.normal(size=shape).astype(np.float32)
        ).astype(dtype)


class _HostOwner:
    """An owner whose pools are NOT jax arrays (a cross-process
    transport stub holding opaque handles): auto must pick host."""

    def __init__(self, num_pages, page_size, layers=1, width=4):
        shape = (layers, num_pages * page_size, width)
        self.k_pool = np.zeros(shape, np.float32)
        self.v_pool = np.zeros(shape, np.float32)


def _rows(owner, pages, page_size, pool="k"):
    arr = np.asarray(owner.k_pool if pool == "k" else owner.v_pool)
    return np.concatenate(
        [arr[:, p * page_size:(p + 1) * page_size] for p in pages], axis=1
    )


# ---------------------------------------------------------------------------
# leg (a): device-to-device ship transport
# ---------------------------------------------------------------------------


class TestDeviceShipper:
    @pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
    def test_round_trip_byte_exact(self, dtype):
        if dtype == "bfloat16":
            import ml_dtypes

            dtype = ml_dtypes.bfloat16
        ps = 4
        src = _Owner(16, ps, seed=11, dtype=dtype)
        dst = _Owner(16, ps, seed=12, dtype=dtype)
        ship = CrossReplicaPageShipper(src, dst, ps, transport="device")
        assert ship.transport == "device"
        src_pages, dst_pages = [3, 7, 5], [9, 2, 11]
        want_k = _rows(src, src_pages, ps, "k")
        want_v = _rows(src, src_pages, ps, "v")
        nbytes = ship.ship(src_pages, dst_pages)
        assert nbytes == len(src_pages) * ship.bytes_per_page()
        np.testing.assert_array_equal(
            _rows(dst, dst_pages, ps, "k").view(np.uint8),
            want_k.view(np.uint8),
        )
        np.testing.assert_array_equal(
            _rows(dst, dst_pages, ps, "v").view(np.uint8),
            want_v.view(np.uint8),
        )

    def test_multi_chunk_round_trip(self):
        # 67 pages exceed the largest SHIP_BUCKET (64): two chunks
        ps = 2
        src = _Owner(80, ps, layers=1, width=4, seed=13)
        dst = _Owner(80, ps, layers=1, width=4, seed=14)
        ship = CrossReplicaPageShipper(src, dst, ps, transport="device")
        src_pages = list(range(1, 68))
        dst_pages = list(range(10, 77))
        want = _rows(src, src_pages, ps, "k")
        ship.ship(src_pages, dst_pages)
        np.testing.assert_array_equal(
            _rows(dst, dst_pages, ps, "k"), want
        )

    def test_torn_chunk_raises(self):
        # the kv.ship failpoint must fire once per chunk on the device
        # path too, so chaos rules behave identically across transports
        ps = 2
        src = _Owner(80, ps, layers=1, width=4, seed=15)
        dst = _Owner(80, ps, layers=1, width=4, seed=16)
        ship = CrossReplicaPageShipper(src, dst, ps, transport="device")
        with failpoints.armed("kv.ship", "error", "torn", nth=2):
            with pytest.raises(failpoints.FailpointError):
                ship.ship(list(range(1, 68)), list(range(10, 77)))

    def test_host_device_parity(self):
        # both transports are the same copy: identical destination bytes
        ps = 4
        src = _Owner(16, ps, seed=21)
        dst_h = _Owner(16, ps, seed=22)
        dst_d = _Owner(16, ps, seed=22)
        pages, dest = [1, 9, 4, 12], [3, 8, 0, 14]
        nb_h = CrossReplicaPageShipper(
            src, dst_h, ps, transport="host"
        ).ship(pages, dest)
        nb_d = CrossReplicaPageShipper(
            src, dst_d, ps, transport="device"
        ).ship(pages, dest)
        assert nb_h == nb_d
        np.testing.assert_array_equal(
            _rows(dst_h, dest, ps, "k").view(np.uint8),
            _rows(dst_d, dest, ps, "k").view(np.uint8),
        )
        np.testing.assert_array_equal(
            _rows(dst_h, dest, ps, "v").view(np.uint8),
            _rows(dst_d, dest, ps, "v").view(np.uint8),
        )

    def test_device_ship_pins_no_host_bytes(self):
        ps = 4
        src = _Owner(16, ps, seed=31)
        dst = _Owner(16, ps, seed=32)
        ship_staging_peak(reset=True)
        CrossReplicaPageShipper(src, dst, ps, transport="device").ship(
            [1, 2, 3], [5, 6, 7]
        )
        assert ship_staging_peak() == 0
        assert ship_staging_bytes() == 0
        # the host path DOES arm the peak (and releases on completion)
        CrossReplicaPageShipper(src, dst, ps, transport="host").ship(
            [1, 2, 3], [5, 6, 7]
        )
        assert ship_staging_peak(reset=True) > 0
        assert ship_staging_bytes() == 0


class TestTransportResolution:
    def test_env_knob_defaults_to_host(self, monkeypatch):
        monkeypatch.delenv(ENV_SHIP_TRANSPORT, raising=False)
        assert ship_transport_from_env() == "host"
        monkeypatch.setenv(ENV_SHIP_TRANSPORT, "carrier-pigeon")
        assert ship_transport_from_env() == "host"
        for mode in ("auto", "host", "device", " DEVICE "):
            monkeypatch.setenv(ENV_SHIP_TRANSPORT, mode)
            assert ship_transport_from_env() == mode.strip().lower()

    def test_auto_picks_device_for_jax_pools(self):
        src, dst = _Owner(4, 2), _Owner(4, 2)
        assert resolve_ship_transport(src, dst, "auto") == "device"

    def test_auto_picks_host_for_foreign_pools(self):
        # either side off-process (non-jax pools) forces the wire path
        jx, hp = _Owner(4, 2), _HostOwner(4, 2)
        assert resolve_ship_transport(jx, hp, "auto") == "host"
        assert resolve_ship_transport(hp, jx, "auto") == "host"
        assert resolve_ship_transport(hp, hp, "auto") == "host"

    def test_explicit_modes_taken_at_word(self):
        src, dst = _Owner(4, 2), _Owner(4, 2)
        assert resolve_ship_transport(src, dst, "host") == "host"
        assert resolve_ship_transport(src, dst, "device") == "device"

    def test_shipper_reads_env(self, monkeypatch):
        src, dst = _Owner(4, 2), _Owner(4, 2)
        monkeypatch.delenv(ENV_SHIP_TRANSPORT, raising=False)
        assert CrossReplicaPageShipper(src, dst, 2).transport == "host"
        monkeypatch.setenv(ENV_SHIP_TRANSPORT, "auto")
        assert CrossReplicaPageShipper(src, dst, 2).transport == "device"
        monkeypatch.setenv(ENV_SHIP_TRANSPORT, "device")
        shp = CrossReplicaPageShipper(src, dst, 2)
        assert shp.transport == "device"
        assert isinstance(shp._device, DeviceShipper)


# ---------------------------------------------------------------------------
# leg (b): wake prefetch
# ---------------------------------------------------------------------------


def _leaves(seed=7):
    rng = np.random.default_rng(seed)
    return ([rng.normal(size=(2, 8, 4)).astype(np.float32)],
            [rng.normal(size=(2, 8, 4)).astype(np.float32)])


def _archive_two_runs(tmp_path):
    """A tier with one thread's 2-run manifest archived: 16 tokens at
    page_size=4, runs of 8 tokens / 2 pages each (path-addressed like
    the real sleep path writes them)."""
    tier = ObjectTier(LocalFSObjectStore(str(tmp_path)),
                      fingerprint="zc", page_size=4)
    toks = list(range(100, 116))
    k1, v1 = _leaves(1)
    k2, v2 = _leaves(2)
    key1 = tier.put_run(toks[:8], k1, v1, 2)
    key2 = tier.put_run(toks, k2, v2, 2)
    assert key1 and key2
    assert tier.write_manifest("thr", toks, [
        {"key": key1, "tokens": 8, "pages": 2},
        {"key": key2, "tokens": 8, "pages": 2},
    ])
    return tier, key1, key2


def _wait(cond, timeout=5.0):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition not met in time")
        time.sleep(0.005)


class _GatedStore:
    """LocalFS wrapper whose GETs block on an event (deterministic
    queued-vs-started staging states without wall-clock sleeps)."""

    def __init__(self, inner):
        self.inner = inner
        self.gate = threading.Event()
        self.gate.set()

    def get(self, key):
        assert self.gate.wait(timeout=10.0)
        return self.inner.get(key)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class TestWakePrefetcher:
    def test_from_env(self, tmp_path, monkeypatch):
        tier = ObjectTier(LocalFSObjectStore(str(tmp_path)))
        monkeypatch.delenv(ENV_WAKE_PREFETCH_MB, raising=False)
        assert WakePrefetcher.from_env(tier) is None
        monkeypatch.setenv(ENV_WAKE_PREFETCH_MB, "not-a-number")
        assert WakePrefetcher.from_env(tier) is None
        monkeypatch.setenv(ENV_WAKE_PREFETCH_MB, "8")
        pre = WakePrefetcher.from_env(tier)
        assert pre is not None and pre.budget_bytes == 8 * MiB

    def test_fetch_run_without_prefetcher_is_get_run(self, tmp_path):
        tier, key1, _ = _archive_two_runs(tmp_path)
        assert tier.prefetcher is None
        got = tier.fetch_run(key1)
        assert got is not None and got[2] == 2
        assert tier.prefetch_hits == 0 and tier.prefetch_bytes == 0

    def test_staged_payload_matches_sync_fetch(self, tmp_path):
        tier, key1, key2 = _archive_two_runs(tmp_path)
        want = tier.get_run(key1)
        tier.prefetcher = pre = WakePrefetcher(tier, 64 * MiB)
        pre.stage_runs([key1, key2], "thr")
        # a staged run whose worker has not started yet is handed back to
        # the sync path (test_take_reclaims_queued_unstarted) and counts
        # no hit: let both start before taking them
        _wait(lambda: all(pre._staged[k].started for k in (key1, key2)))
        got = tier.fetch_run(key1)  # waits out the inflight fetch
        assert got is not None
        for a, b in zip(want[0] + want[1], got[0] + got[1]):
            np.testing.assert_array_equal(
                a.view(np.uint8), b.view(np.uint8)
            )
        assert got[2:] == want[2:]
        assert tier.fetch_run(key2) is not None
        assert tier.prefetch_hits == 2
        assert tier.prefetch_bytes > 0
        assert pre.staged_bytes() == 0  # both consumed

    def test_single_flight_per_content_key(self, tmp_path):
        tier, key1, _ = _archive_two_runs(tmp_path)
        store = _GatedStore(tier.store)
        tier.store = store
        store.gate.clear()
        pre = WakePrefetcher(tier, 64 * MiB, workers=2)
        assert pre._begin(key1, "thr") is True
        assert pre._begin(key1, "thr") is False  # already staged
        pre.stage_runs([key1], "thr")  # idempotent too
        with pre._lock:
            assert len(pre._staged) == 1
        store.gate.set()
        assert pre.take(key1) is not None
        assert tier.prefetch_hits == 1

    def test_take_reclaims_queued_unstarted(self, tmp_path):
        # one worker, gated store: key1 starts and blocks, key2 stays
        # queued — take(key2) must hand it to the sync path, never wait
        tier, key1, key2 = _archive_two_runs(tmp_path)
        store = _GatedStore(tier.store)
        tier.store = store
        store.gate.clear()
        pre = WakePrefetcher(tier, 64 * MiB, workers=1)
        pre.stage_runs([key1, key2], "thr")
        _wait(lambda: pre._staged[key1].started)
        assert not pre._staged[key2].started
        assert pre.take(key2) is None  # reclaimed, not awaited
        with pre._lock:
            assert key2 not in pre._staged
        store.gate.set()
        assert pre.take(key1) is not None
        assert tier.prefetch_hits == 1
        # the doomed key2 worker run stages nothing when it drains
        _wait(lambda: pre.inflight() == 0)
        assert pre.staged_bytes() == 0

    def test_budget_evicts_oldest_ready_first(self, tmp_path):
        tier, key1, key2 = _archive_two_runs(tmp_path)
        n1 = tier.get_run(key1)[3]
        tier.prefetcher = pre = WakePrefetcher(tier, n1 + 1)
        pre.stage_runs([key1, key2], "thr")
        _wait(lambda: pre.staged_bytes() <= n1 + 1 and
              all(e.event.is_set() for e in list(pre._staged.values())))
        # both landed; the budget holds one: key1 (oldest) was evicted
        assert tier.prefetch_wasted == 1
        assert pre.take(key1) is None
        assert pre.take(key2) is not None

    def test_budget_full_rejects_new_staging(self, tmp_path):
        tier, key1, key2 = _archive_two_runs(tmp_path)
        n1 = tier.get_run(key1)[3]
        pre = WakePrefetcher(tier, n1)  # exactly one run fits
        assert pre._begin(key1, "thr") is True
        _wait(lambda: pre.staged_bytes() >= n1)
        assert pre._begin(key2, "thr") is False  # staging full
        assert pre.take(key2) is None  # caller falls back to sync

    def test_cancel_thread_drops_ready_payloads(self, tmp_path):
        tier, key1, key2 = _archive_two_runs(tmp_path)
        tier.prefetcher = pre = WakePrefetcher(tier, 64 * MiB)
        pre.stage_runs([key1, key2], "thr")
        _wait(lambda: pre.staged_bytes() > 0 and pre.inflight() == 0)
        pre.cancel_thread("thr")
        assert tier.prefetch_wasted == 2
        assert pre.staged_bytes() == 0
        assert pre.take(key1) is None and pre.take(key2) is None
        # degrade is clean: the sync path still serves the wake
        assert tier.fetch_run(key1) is not None

    def test_failed_fetch_degrades_to_sync(self, tmp_path):
        tier, key1, _ = _archive_two_runs(tmp_path)
        tier.prefetcher = pre = WakePrefetcher(tier, 64 * MiB)
        with failpoints.armed("kv.prefetch", "error", "boom"):
            assert pre._begin(key1, "thr") is True
            _wait(lambda: key1 not in pre._staged)
        assert pre.staged_bytes() == 0  # no residue
        assert tier.prefetch_hits == 0
        got = tier.fetch_run(key1)  # sync path, exactly today's
        assert got is not None and got[2] == 2

    def test_breaker_open_stops_scheduling(self, tmp_path):
        class _DeadStore:
            def get(self, key):
                raise OSError("store down")

        guard = StoreGuard(
            _DeadStore(), retries=0, backoff_s=0.0,
            breaker=CircuitBreaker(failure_threshold=1,
                                   open_window_s=60.0),
        )
        tier = ObjectTier(guard, fingerprint="zc", page_size=4)
        assert tier.get_run("deadbeef") is None  # trips the breaker
        assert guard.breaker.state == BREAKER_OPEN
        assert tier.available() is False
        pre = WakePrefetcher(tier, 64 * MiB)
        assert pre.prefetch_thread("thr") is False  # degrade at the gate

    def test_prefetch_thread_stages_manifest_runs(self, tmp_path):
        tier, key1, key2 = _archive_two_runs(tmp_path)
        tier.prefetcher = pre = WakePrefetcher(tier, 64 * MiB)
        assert pre.prefetch_thread("thr") is True
        _wait(lambda: pre.staged_bytes() > 0 and pre.inflight() == 0
              and len(pre._staged) == 2)
        assert tier.fetch_run(key1) is not None
        assert tier.fetch_run(key2) is not None
        assert tier.prefetch_hits == 2

    def test_prefetch_thread_skips_locally_covered_runs(self, tmp_path):
        # min_depth = the replica's radix match: run1 (8 tokens) is
        # wholly covered, so a wake would skip it — prefetch must too
        tier, key1, key2 = _archive_two_runs(tmp_path)
        tier.prefetcher = pre = WakePrefetcher(tier, 64 * MiB)
        assert pre.prefetch_thread("thr", min_depth=8) is True
        _wait(lambda: pre.inflight() == 0 and len(pre._staged) == 1)
        with pre._lock:
            assert key1 not in pre._staged and key2 in pre._staged
        assert pre.take(key2) is not None


# ---------------------------------------------------------------------------
# leg (c): multipart object puts
# ---------------------------------------------------------------------------


def _body(n, seed=5):
    return bytes(np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8
    ))


class TestMultipartPut:
    def test_threshold_routes_large_puts(self):
        with StubS3Server() as srv:
            st = HTTPObjectStore(srv.url)
            st.multipart_bytes = 256 * 1024
            small = _body(64 * 1024, 1)
            big = _body(600 * 1024, 2)  # 3 parts of <=256K
            st.put("runs/small", small)
            assert st.multipart_puts == 0  # at/below threshold: simple
            st.put("runs/big", big)
            assert st.multipart_puts == 1
            assert srv.completed_uploads == 1
            assert srv.uploads == {}  # no orphan upload state
            assert st.get("runs/small") == small
            assert st.get("runs/big") == big
            h = st.head("runs/big")
            assert h is not None and h[0] == len(big)

    def test_part_failure_aborts_server_side(self):
        with StubS3Server() as srv:
            st = HTTPObjectStore(srv.url)
            st.multipart_bytes = 256 * 1024
            srv.fail_parts = 1
            with pytest.raises(OSError):
                st.put("runs/torn", _body(600 * 1024, 3))
            assert st.multipart_aborts == 1
            assert st.multipart_puts == 0
            assert st.get("runs/torn") is None  # no partial object
            assert srv.uploads == {}  # aborted, not orphaned

    def test_guard_retry_relands_identically(self):
        with StubS3Server() as srv:
            st = HTTPObjectStore(srv.url)
            st.multipart_bytes = 256 * 1024
            g = StoreGuard(st, retries=2, backoff_s=0.0)
            srv.fail_parts = 1
            data = _body(600 * 1024, 4)
            g.put("runs/retry", data)  # attempt 1 aborts, attempt 2 lands
            assert g.retries_total >= 1
            assert st.multipart_aborts == 1
            assert st.multipart_puts == 1
            assert srv.completed_uploads == 1
            assert srv.uploads == {}
            assert st.get("runs/retry") == data

    def test_put_deadline_scales_with_request_count(self, monkeypatch):
        monkeypatch.setenv(ENV_OBJECT_MULTIPART_MB, "4")
        assert StoreGuard._put_deadline_scale(1 * MiB) == 1
        assert StoreGuard._put_deadline_scale(4 * MiB) == 1
        assert StoreGuard._put_deadline_scale(10 * MiB) == 3
        monkeypatch.delenv(ENV_OBJECT_MULTIPART_MB, raising=False)
        assert StoreGuard._put_deadline_scale(10 * MiB) == 1


# ---------------------------------------------------------------------------
# disabled-knob bit-identity
# ---------------------------------------------------------------------------


class TestKnobsOffBitIdentity:
    def test_all_three_legs_default_off(self, tmp_path, monkeypatch):
        for knob in (ENV_SHIP_TRANSPORT, ENV_WAKE_PREFETCH_MB,
                     ENV_OBJECT_MULTIPART_MB):
            monkeypatch.delenv(knob, raising=False)
        # (a) host transport, exactly the pre-ISSUE-19 path
        src, dst = _Owner(4, 2), _Owner(4, 2)
        assert CrossReplicaPageShipper(src, dst, 2).transport == "host"
        # (b) no prefetcher attaches; fetch_run degenerates to get_run
        tier, key1, _ = _archive_two_runs(tmp_path)
        assert WakePrefetcher.from_env(tier) is None
        assert tier.fetch_run(key1) is not None
        assert tier.prefetch_hits == 0
        # (c) monolithic puts only
        assert object_multipart_bytes() == 0
        with StubS3Server() as srv:
            st = HTTPObjectStore(srv.url)
            assert st.multipart_bytes == 0
            st.put("runs/x", _body(600 * 1024, 6))
            assert st.multipart_puts == 0 and srv.completed_uploads == 0
