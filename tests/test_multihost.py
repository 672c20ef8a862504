"""REAL multi-process jax.distributed coverage (SURVEY §2.2 "distributed
communication backend").

`init_distributed` was previously exercised only as a single-process
no-op; here two OS processes form a 2-host topology over CPU: both join
the coordination service, see the global device view, rendezvous at a
coordination-service barrier, and run a shard_map psum over their LOCAL
devices token-exact.  (This jaxlib's CPU backend cannot execute
multiprocess XLA computations — "Multiprocess computations aren't
implemented on the CPU backend" — so the cross-process data plane is
TPU-only; what IS portable, and what multi-host fault tolerance actually
lives on, is the coordination plane tested here.)

Cross-process chaos (ISSUE 2): the `chaos`+`slow` tests kill one process
of the 2-process topology mid-psum (via an inherited
`dist.step=exit(..)` failpoint) and assert the SURVIVOR surfaces a clean
`DistributedStepError` through `guarded_collective` instead of hanging —
the crash-only contract at the mesh boundary.  Tier-1 runs the fast
single-process subset (watchdog + dist.init failpoint semantics).
"""

import os
import subprocess
import sys
import textwrap
import threading

import pytest


_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, %(repo)r)
    from kafka_tpu.parallel.distributed import barrier, init_distributed

    assert init_distributed(), "env-driven init did not activate"
    assert jax.process_count() == 2
    assert len(jax.devices()) == 8          # global view: 2 procs x 4
    assert len(jax.local_devices()) == 4    # local view

    # coordination plane: both processes must arrive (a dead peer would
    # time this out — that failure mode is the chaos matrix below)
    assert barrier("multihost-smoke", timeout_s=60), "barrier inactive"

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    # data plane over the LOCAL slice (this jaxlib cannot run
    # multiprocess XLA computations on CPU; on TPU the same MeshConfig
    # code paths span hosts)
    mesh = Mesh(np.array(jax.local_devices()).reshape(1, 4), ("dp", "tp"))

    def f(x):
        return jax.lax.psum(x, "tp")

    g = jax.jit(shard_map(f, mesh=mesh,
                          in_specs=P("dp", "tp"), out_specs=P("dp", "tp")))
    base = 8.0 * jax.process_index()
    x = jax.device_put(
        base + jnp.arange(4.0).reshape(1, 4),
        NamedSharding(mesh, P("dp", "tp")),
    )
    out = np.asarray(g(x))
    np.testing.assert_allclose(out, np.full((1, 4), 4 * base + 6.0))
    print("MULTIHOST_OK", jax.process_index(), flush=True)
""")


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_mesh():
    port = _free_port()  # per-run coordinator port: no cross-run collisions
    procs = []
    try:
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                KAFKA_TPU_COORDINATOR=f"localhost:{port}",
                KAFKA_TPU_NUM_PROCESSES="2",
                KAFKA_TPU_PROCESS_ID=str(pid),
            )
            # JAX children of a JAX parent: fine on CPU (this suite), but
            # on an accelerator the parent would hold the chip and these
            # workers would fail or hang -- one process per chip there
            env.pop("PYTHONPATH", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-c",
                 _WORKER % {"repo": os.path.dirname(os.path.dirname(
                     os.path.abspath(__file__)))}],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            ))
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=220)
            assert p.returncode == 0, err.decode()[-2000:]
            outs.append(out.decode())
        assert "MULTIHOST_OK 0" in outs[0] + outs[1]
        assert "MULTIHOST_OK 1" in outs[0] + outs[1]
    finally:
        for p in procs:  # never leak a worker pinning the rendezvous port
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)


class TestGuardedCollectiveSingleProcess:
    """Fast tier-1 subset: the watchdog + failpoint semantics that do not
    need a second OS process."""

    def test_passthrough_result_and_errors(self):
        from kafka_tpu.parallel import guarded_collective

        assert guarded_collective(lambda a, b: a + b, 2, 3,
                                  timeout_s=5) == 5
        with pytest.raises(ZeroDivisionError):
            guarded_collective(lambda: 1 / 0, timeout_s=5)

    def test_hang_becomes_terminal_error(self):
        from kafka_tpu.parallel import (
            DistributedStepError,
            guarded_collective,
        )

        gate = threading.Event()
        with pytest.raises(DistributedStepError, match="peer process"):
            guarded_collective(gate.wait, timeout_s=0.2, label="psum")
        gate.set()  # release the watchdog thread

    def test_dist_init_failpoint_gates_on_multihost(self):
        """dist.init fires only when multi-host init is actually
        requested — a single-process run must not trip an armed rule."""
        from kafka_tpu.parallel.distributed import init_distributed
        from kafka_tpu.runtime import failpoints as fp

        with fp.armed("dist.init", "error", "init-chaos"):
            assert init_distributed() is False  # no env: no-op, no fire
            with pytest.raises(fp.FailpointError, match="init-chaos"):
                init_distributed(
                    coordinator_address="127.0.0.1:1",
                    num_processes=2, process_id=0,
                )

    def test_dist_step_failpoint_fires_in_guard(self):
        from kafka_tpu.parallel import guarded_collective
        from kafka_tpu.runtime import failpoints as fp

        with fp.armed("dist.step", "error", "step-chaos"):
            with pytest.raises(fp.FailpointError, match="step-chaos"):
                guarded_collective(lambda: 1, timeout_s=5)


class TestTopologyReformation:
    """ISSUE 13 satellite (PR 2 follow-up): a missed collective deadline
    attempts ONE barrier-coordinated re-formation over the survivors
    before fail-stop — a transient stall (peer alive, merely wedged)
    completes the ORIGINAL in-flight collective inside one post-reform
    grace window (never a second execution: the wedged daemon thread is
    still inside the runtime collective, and re-entering it locally
    would pair an extra op against peers participating once); a dead
    peer still surfaces the clean DistributedStepError (the
    dist.step=exit chaos kill matrix exercises that branch across real
    processes)."""

    def test_transient_stall_reforms_and_completes_in_place(
            self, monkeypatch):
        from kafka_tpu.parallel import distributed as dist

        monkeypatch.setattr(dist, "_INITIALIZED", True)
        barriers = []
        gate = threading.Event()

        def healing_barrier(name, timeout_s=60.0):
            barriers.append(name)
            gate.set()  # the stall heals while the survivors rendezvous
            return True

        monkeypatch.setattr(dist, "barrier", healing_barrier)
        calls = []

        def fn():
            calls.append(1)
            gate.wait()  # wedges past the first watchdog window
            return 42

        before = dict(dist.reform_stats)
        try:
            assert dist.guarded_collective(fn, timeout_s=0.2,
                                           label="psum") == 42
        finally:
            gate.set()
        assert len(calls) == 1  # the original attempt, never re-executed
        assert len(barriers) == 1 and barriers[0].startswith("kafka-reform-")
        assert dist.reform_stats["attempts"] == before["attempts"] + 1
        assert dist.reform_stats["successes"] == before["successes"] + 1

    def test_reformed_but_still_stuck_fail_stops(self, monkeypatch):
        """Every peer answers the barrier but the collective still never
        materializes: the grace window expires and the process
        fail-stops — one re-formation, never a loop."""
        from kafka_tpu.parallel import DistributedStepError
        from kafka_tpu.parallel import distributed as dist

        monkeypatch.setattr(dist, "_INITIALIZED", True)
        barriers = []
        monkeypatch.setattr(
            dist, "barrier",
            lambda name, timeout_s=60.0: barriers.append(name) or True,
        )
        gate = threading.Event()
        calls = []

        def fn():
            calls.append(1)
            gate.wait()

        try:
            with pytest.raises(DistributedStepError, match="peer process"):
                dist.guarded_collective(fn, timeout_s=0.2, label="psum")
        finally:
            gate.set()
        assert len(calls) == 1
        assert len(barriers) == 1

    def test_dead_peer_barrier_failure_fail_stops(self, monkeypatch):
        from kafka_tpu.parallel import DistributedStepError
        from kafka_tpu.parallel import distributed as dist

        monkeypatch.setattr(dist, "_INITIALIZED", True)

        def dead_barrier(name, timeout_s=60.0):
            raise RuntimeError("DEADLINE_EXCEEDED: barrier timed out")

        monkeypatch.setattr(dist, "barrier", dead_barrier)
        gate = threading.Event()
        calls = []

        def fn():
            calls.append(1)
            gate.wait()

        try:
            with pytest.raises(DistributedStepError, match="peer process"):
                dist.guarded_collective(fn, timeout_s=0.2, label="psum")
        finally:
            gate.set()
        assert len(calls) == 1  # no retry against a dead topology

    def test_single_process_never_reforms(self):
        """_INITIALIZED False (no multi-host): the pre-existing behavior
        is untouched — straight to the terminal error, no barrier."""
        from kafka_tpu.parallel import DistributedStepError
        from kafka_tpu.parallel import distributed as dist

        gate = threading.Event()
        before = dict(dist.reform_stats)
        try:
            with pytest.raises(DistributedStepError, match="peer process"):
                dist.guarded_collective(gate.wait, timeout_s=0.2,
                                        label="psum")
        finally:
            gate.set()
        assert dist.reform_stats == before

    def test_env_disable(self, monkeypatch):
        from kafka_tpu.parallel import distributed as dist

        monkeypatch.setattr(dist, "_INITIALIZED", True)
        monkeypatch.setenv("KAFKA_TPU_DIST_REFORM", "0")

        def must_not_run(name, timeout_s=60.0):  # pragma: no cover
            raise AssertionError("reform barrier ran while disabled")

        monkeypatch.setattr(dist, "barrier", must_not_run)
        assert dist.reform_topology("psum") is False


# Worker for the kill matrix: both processes run guarded steps in
# lockstep — each step is a local psum plus a coordination-service
# rendezvous (the cross-process sync point a multi-host decode step
# rides on).  The victim's inherited `dist.step=exit(..)` failpoint
# kills it at step 2, and the survivor must convert the resulting
# missing-peer stall into a clean terminal error and exit with a
# distinct code — never hang.
_CHAOS_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, %(repo)r)
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map
    from kafka_tpu.parallel import (
        DistributedStepError, barrier, guarded_collective,
        init_distributed,
    )

    assert init_distributed(), "env-driven init did not activate"
    mesh = Mesh(np.array(jax.local_devices()).reshape(1, 4), ("dp", "tp"))
    g = jax.jit(shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                          in_specs=P("dp", "tp"),
                          out_specs=P("dp", "tp")))
    x = jax.device_put(jnp.arange(4.0).reshape(1, 4),
                       NamedSharding(mesh, P("dp", "tp")))

    step = 0

    def one_step():
        jax.block_until_ready(g(x))          # device work
        barrier("chaos-step-%%d" %% step, timeout_s=10)  # peer rendezvous

    try:
        for step in range(4):
            # the victim's dist.step=exit rule fires inside this call on
            # its nth evaluation; the survivor's next psum then has a
            # dead peer and must hit the watchdog deadline
            guarded_collective(one_step, timeout_s=15, label="psum")
            print("STEP_OK", step, flush=True)
    except DistributedStepError as e:
        print("SURVIVOR_CLEAN", jax.process_index(), str(e)[:80],
              flush=True)
        # a watchdog thread is still stuck inside the dead collective:
        # hard-exit the way a supervised server would after failing its
        # in-flight requests
        os._exit(17)
    except Exception as e:
        # some transports DETECT the dead peer instead of hanging (reset
        # connection / coordination-service heartbeat): that is also a
        # clean terminal error, not a hang — same survivor contract
        print("SURVIVOR_CLEAN", jax.process_index(),
              type(e).__name__, str(e)[:80], flush=True)
        os._exit(17)
    print("ALL_STEPS_DONE", jax.process_index(), flush=True)
""")


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("victim", [0, 1],
                         ids=["kill-coordinator", "kill-worker"])
def test_killed_process_mid_psum_survivor_fails_clean(victim):
    """Kill the coordinator (process 0) or a worker (process 1) mid-step:
    the survivor must TERMINATE within the watchdog budget — never hang.

    Worker kill: the coordinator-side process sees the barrier deadline,
    guarded_collective surfaces the clean DistributedStepError path, and
    the survivor exits 17.  Coordinator kill: the jax runtime's own
    missed-heartbeat policy may hard-abort the survivor from C++ before
    the clean Python path wins the race — fail-stop, which still honors
    crash-only semantics (die loudly rather than serve from a headless
    mesh); both terminations are accepted, a hang never is."""
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    try:
        for pid in range(2):
            env = dict(os.environ)
            env.update(
                KAFKA_TPU_COORDINATOR=f"localhost:{port}",
                KAFKA_TPU_NUM_PROCESSES="2",
                KAFKA_TPU_PROCESS_ID=str(pid),
            )
            env.pop("PYTHONPATH", None)
            if pid == victim:
                # failpoint env inheritance: the kill rule rides the
                # environment into the worker process and fires at its
                # 2nd guarded step — a crash mid-topology, not at boot
                env["KAFKA_TPU_FAILPOINTS"] = "dist.step=exit(31):nth=2"
            else:
                env.pop("KAFKA_TPU_FAILPOINTS", None)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _CHAOS_WORKER % {"repo": repo}],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            ))
        outs = {}
        for pid, p in enumerate(procs):
            out, err = p.communicate(timeout=220)
            outs[pid] = (p.returncode, out.decode(), err.decode())
        survivor = 1 - victim
        vrc, vout, _ = outs[victim]
        src, sout, serr = outs[survivor]
        # the victim died by the injected exit, after at least one step
        assert vrc == 31, outs[victim]
        assert "STEP_OK 0" in vout, outs[victim]
        # the survivor terminated (communicate() above bounds the wait:
        # a hang would TimeoutExpired).  Worker kill must take the clean
        # DistributedStepError path; coordinator kill may also be
        # fail-stopped by the runtime's heartbeat abort.
        if victim == 0:
            assert src != 0, (src, sout, serr[-2000:])
            assert src == 17 or "SURVIVOR_CLEAN" in sout or src < 0, (
                src, sout, serr[-2000:]
            )
        else:
            assert src == 17, (src, sout, serr[-2000:])
            assert "SURVIVOR_CLEAN" in sout, (sout, serr[-2000:])
    finally:
        for p in procs:  # never leak a worker pinning the rendezvous port
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
