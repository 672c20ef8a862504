"""chip_smoke.py on CPU: the rehearsal passes and cannot say PASS, the real
command refuses a machine without a chip, and the two decisions the smoke
leans on - where the compile cache lives, which roofline a device gets -
are made in one place each.
"""

import os
import socket
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _cpu_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # conftest's 8 virtual devices would switch the four-chip legs on
    env.pop("XLA_FLAGS", None)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_rehearsal_passes_and_never_says_pass(tmp_path):
    proc = subprocess.run(
        [sys.executable, SMOKE, "--rehearse", "--out", str(tmp_path),
         "--port", str(_free_port())],
        env=_cpu_env(), capture_output=True, text=True, timeout=300,
    )
    out = proc.stdout
    assert proc.returncode == 0, out[-3000:] + proc.stderr[-2000:]
    lines = out.strip().splitlines()
    assert lines[-1].startswith("verdict: REHEARSAL")
    assert "PASS" not in out
    # a rehearsal prints no result object: only a chip run may
    assert not any(ln.startswith("{") for ln in lines)
    assert any(ln.startswith("fact chip_smoke.parent_imported_jax = False")
               for ln in lines)
    # the legs ran one after the other, and the four-chip ones said why not
    assert any(ln.startswith("fact kernel.paged_verify_attention = ok")
               for ln in lines)
    assert any(ln.startswith("fact serve.compiles.first_traffic = 0")
               for ln in lines)
    assert any(ln.startswith("fact serve.sigterm_exit_code = 0")
               for ln in lines)
    assert any(ln.startswith("skip tp4:") for ln in lines)
    assert any(ln.startswith("skip dp4:") for ln in lines)


def test_no_chip_exits_nonzero_naming_the_platform(tmp_path):
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path)],
        env=_cpu_env(), capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no accelerator" in proc.stdout and "'cpu'" in proc.stdout
    assert "verdict" not in proc.stdout and '"ok"' not in proc.stdout


def test_compile_cache_dir_is_decided_in_one_place(monkeypatch, tmp_path):
    from kafka_tpu.runtime import compile_log

    placed = str(tmp_path / "elsewhere")
    monkeypatch.setenv(compile_log.CACHE_DIR_ENV, placed)
    assert compile_log.compile_cache_dir() == placed
    assert not os.path.exists(placed)  # reported, not created or touched
    monkeypatch.delenv(compile_log.CACHE_DIR_ENV)
    first = compile_log.compile_cache_dir()
    assert first == os.path.join(ROOT, ".jax_cache")
    assert compile_log.compile_cache_dir() == first  # no pid, time or temp

    monkeypatch.setenv(compile_log.CACHE_SWITCH_ENV, "0")
    assert not compile_log.compile_cache_enabled()
    monkeypatch.delenv(compile_log.CACHE_SWITCH_ENV)
    assert compile_log.compile_cache_enabled()


def _fake_device(platform: str, kind: str):
    return types.SimpleNamespace(platform=platform, device_kind=kind,
                                 memory_stats=lambda: None)


def test_unknown_tpu_kind_is_an_error_not_a_guess(monkeypatch):
    from kafka_tpu.runtime import planner

    monkeypatch.delenv(planner.PEAK_TFLOPS_ENV, raising=False)
    monkeypatch.delenv(planner.PEAK_HBM_GBPS_ENV, raising=False)
    v9 = _fake_device("tpu", "TPU v9")
    with pytest.raises(ValueError, match="TPU v9"):
        planner.device_peaks(v9)
    with pytest.raises(ValueError, match="TPU v9"):
        planner.hbm_for_device(v9)

    v5e = _fake_device("tpu", "TPU v5 lite")
    assert planner.device_peaks(v5e) == (*planner.CHIP_PEAKS["v5e"],
                                         "datasheet")
    assert planner.hbm_for_device(v5e) == planner.HBM_BYTES["v5e"]
    # v5p reports plain "TPU v5": an exact key, not a substring of v5e's
    assert planner.device_peaks(_fake_device("tpu", "TPU v5"))[:2] == \
        planner.CHIP_PEAKS["v5p"]

    cpu = _fake_device("cpu", "cpu")
    assert planner.device_peaks(cpu) == (None, None, "unknown")
    assert planner.hbm_for_device(cpu) is None
