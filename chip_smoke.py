#!/usr/bin/env python3
"""chip_smoke.py - does the served path start, and answer, on the chip?

Run from the repo root on a machine with a TPU:

    python3 chip_smoke.py

It drives the main path once through the entry points a user would call,
at the full published width of llama-3.2-1b (random weights from a seed):

* leg ``kernels``: every Pallas kernel the package exports, COMPILED at the
  served model's geometry and page layout, against the XLA formulation in
  ``kafka_tpu/ops/attention.py``;
* leg ``serve``: ``python -m kafka_tpu.server`` with the default
  ServingConfig, then over HTTP two streamed turns on one thread, a turn on
  a second thread, one non-streamed completion and one forced tool call
  through ``/v1/agent/run``; the facts it asserts are read back from the
  server (``/health``, ``/metrics``, ``/debug/compiles``), then SIGTERM;
* with four or more chips visible, leg ``serve`` again as ``--tp-size 4``
  and as ``--dp-size 4``.

One process per chip: this parent never imports JAX, and each leg is a child
that owns the chip alone and has exited before the next one starts.

Output: one short line per fact, then a verdict line, then - as the last
line of stdout - ``{"ok": true, "device": {...}}``.  Exit code 0 only when
every leg passed on a TPU.  Without an accelerator it exits non-zero within
seconds and prints no result.  ``--rehearse`` runs the same legs on CPU with
``--tiny-model`` and interpreted kernels so the command can be debugged
without chip time; its verdict is REHEARSAL, never PASS, and it prints no
result line either.

Timings are printed as information under the device's name.  None of them
is a benchmark metric.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

EXIT_FAIL = 1
EXIT_NO_CHIP = 3
# The one-chip run (kernels + serve) must end inside the driver's 1200 s,
# compilation included; each four-chip leg then gets a budget of its own.
ONE_CHIP_BUDGET_S = 1150.0
FOUR_CHIP_LEG_BUDGET_S = 1500.0
KERNELS_TIMEOUT_S = 420.0
DRAIN_TIMEOUT_S = 60.0
REQUEST_TIMEOUT_S = 180.0

SMOKE_MODEL = "llama-3.2-1b"
SMOKE_VOCAB = 128256
# a builtin tool that needs no network and takes one short string
AGENT_TOOL = "saveThoughtCheckpoint"
# tp=4 must reproduce one chip's greedy output on a short prefix: ONE token.
# Every prompt ends in the same assistant-header token, and a random-weight
# model attends ~uniformly over 7.9k positions, so the context barely moves
# the logits: the first greedy token is one decision with one fixed margin
# whatever the prompt says (five different prompts gave five identical first
# tokens on the chip), and each further token multiplies the chance that a
# bf16 near-tie flips when a reduction is split four ways (model-configs
# guide: "the largest logit changes on rounding").  A sharding bug moves the
# logits by O(1) and agrees on one token in 62 by chance (filler ids decode
# to 62 letters).  The longer strings are printed for the reader.
GREEDY_PROMPT = "Name three primary colours."
GREEDY_TOKENS = 8
LEGS = ("serve", "tp4", "dp4")


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# child: leg `kernels` (the only code in this file that imports JAX)
# ---------------------------------------------------------------------------


def leg_kernels(rehearse: bool, out_dir: str) -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: no accelerator: jax found platform "
              f"{dev.platform!r}", flush=True)
        return EXIT_NO_CHIP
    tag = f"[{dev.device_kind} x{len(jax.devices())}]"

    from kafka_tpu.models import get_config
    from kafka_tpu.models.quant import quantize_array
    from kafka_tpu.ops.attention import causal_attention
    from kafka_tpu.ops.pallas import (
        paged_decode_attention,
        paged_decode_attention_int8,
        paged_prefill_attention,
        paged_verify_attention,
    )
    from kafka_tpu.runtime import compile_log
    from kafka_tpu.runtime.planner import device_peaks
    from kafka_tpu.server.config import ServingConfig

    if compile_log.compile_cache_enabled():
        compile_log.enable_compile_cache()
    # the same rule models/llama.py applies at trace time
    interpret = jax.default_backend() != "tpu"

    scfg = ServingConfig()
    if rehearse:
        # tiny geometry: interpret mode walks every DMA in Python
        cfg = get_config("tiny")
        B, ps, P, num_pages = 4, scfg.page_size, 16, 48
        buckets = (64, 128)
        seq_lens = [P * ps - 6, 100, 17, 0]
    else:
        cfg = get_config(scfg.model_name)
        B, ps = scfg.max_batch, scfg.page_size
        P, num_pages = scfg.max_pages_per_seq, scfg.num_pages
        buckets = tuple(b for b in scfg.prefill_buckets if b >= 64)
        # ragged: one lane fills the window (less the K+1 verify writes),
        # one is empty, the rest sit between
        seq_lens = [P * ps - 6, 5000, 3001, 1234, 517, 100, 15, 0][:B]
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    HD, C, S = Hkv * D, P * ps, 5  # S = K+1 verify queries
    print(f"fact kernels.geometry = Hq{Hq}/Hkv{Hkv}/D{D} page{ps} "
          f"window{C} pool{num_pages}p batch{B} {tag}", flush=True)

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16
    k_pool = jnp.asarray(rng.randn(num_pages * ps, HD), dt)
    v_pool = jnp.asarray(rng.randn(num_pages * ps, HD), dt)
    seq_lens = np.asarray(seq_lens, np.int32)
    # non-trivial page tables: every lane owns a shuffled set of physical
    # pages (page 0 is the trash page, as in runtime/kv_cache.py)
    free = list(range(1, num_pages))
    rng.shuffle(free)
    table = np.zeros((B, P), np.int32)
    for b in range(B):
        for i in range(-(-(int(seq_lens[b]) + S) // ps)):
            table[b, i] = free.pop()

    def window(pool, rows):
        """[slots, HD] pool -> [len(rows), C, Hkv, D] f32 logical windows."""
        idx = (rows[:, :, None] * ps + np.arange(ps)[None, None, :])
        return pool.astype(jnp.float32)[jnp.asarray(idx.reshape(len(rows), C))
                                        ].reshape(len(rows), C, Hkv, D)

    def reference(q, kp, vp, rows, q_pos, n_valid):
        """ops/attention.py on the gathered window, in f32 at full matmul
        precision.  q [N, Sq, Hq, D]; q_pos [N, Sq]; n_valid [N]."""
        kv_pos = np.broadcast_to(np.arange(C)[None, :], (len(rows), C))
        with jax.default_matmul_precision("highest"):
            return np.asarray(causal_attention(
                q.astype(jnp.float32), window(kp, rows), window(vp, rows),
                q_positions=jnp.asarray(q_pos),
                kv_positions=jnp.asarray(kv_pos),
                kv_valid=jnp.asarray(kv_pos < n_valid[:, None]),
            ))

    # Tolerance.  Inputs are bf16-exact and both sides accumulate in f32, so
    # what differs is rounding: the kernel's bf16 output (2^-9 relative),
    # and the MXU, which may round the f32 probabilities to bf16 before the
    # PV product (another 2^-9 per term).  |out| stays under ~4 for unit
    # normal V, so 2e-2 absolute covers both with room; on the short lanes
    # (0, 15, 100 tokens) one wrong page or one mis-masked slot moves the
    # output by order 1.
    ATOL = RTOL = 2e-2

    def check(name, got, want):
        got = np.asarray(got, np.float32)
        if not np.all(np.isfinite(got)):
            raise SmokeFailure(f"kernel {name}: non-finite output")
        err = float(np.max(np.abs(got - want)))
        if not np.allclose(got, want, atol=ATOL, rtol=RTOL):
            raise SmokeFailure(
                f"kernel {name}: max abs err {err:.4g} vs XLA reference "
                f"(atol=rtol={ATOL})")
        print(f"fact kernel.{name} = ok max_abs_err={err:.2e} "
              f"interpret={interpret} {tag}", flush=True)

    t0 = time.monotonic()
    jt, jl = jnp.asarray(table), jnp.asarray(seq_lens)

    # decode: one query at position seq_len attends seq_len + 1 slots
    q = jnp.asarray(rng.randn(B, Hq, D), dt)
    out = paged_decode_attention(q, k_pool, v_pool, jt, jl, page_size=ps,
                                 interpret=interpret)
    check("paged_decode_attention", out,
          reference(q[:, None], k_pool, v_pool, table, seq_lens[:, None],
                    seq_lens + 1)[:, 0])

    # int8 decode: the reference attends the DEQUANTIZED pool
    kq, vq = quantize_array(k_pool, (1,)), quantize_array(v_pool, (1,))
    out = paged_decode_attention_int8(
        q, kq.q, kq.s, vq.q, vq.s, jt, jl, page_size=ps,
        interpret=interpret)
    kd = (kq.q.astype(jnp.float32) * kq.s).astype(jnp.float32)
    vd = (vq.q.astype(jnp.float32) * vq.s).astype(jnp.float32)
    check("paged_decode_attention_int8", out,
          reference(q[:, None], kd, vd, table, seq_lens[:, None],
                    seq_lens + 1)[:, 0])

    # verify: S = K+1 = 5 queries per lane, ragged q_lens
    qv = jnp.asarray(rng.randn(B, S, Hq, D), dt)
    q_lens = np.asarray([(b % S) + 1 for b in range(B)], np.int32)
    out = np.asarray(paged_verify_attention(
        qv, k_pool, v_pool, jt, jl, jnp.asarray(q_lens), page_size=ps,
        interpret=interpret), np.float32)
    want = reference(qv, k_pool, v_pool, table,
                     seq_lens[:, None] + np.arange(S)[None, :],
                     seq_lens + q_lens)
    live = np.arange(S)[None, :] < q_lens[:, None]  # rows past q_len: junk
    check("paged_verify_attention", out[live], want[live])

    # flash prefill, every default bucket >= 64: a chunk that starts
    # mid-page after earlier context and ends short of the bucket
    row = table[:1]
    for bucket in buckets:
        start = min(ps * 3 + 5, C - bucket)
        chunk_len = bucket - 3
        qp = jnp.asarray(rng.randn(bucket, Hq, D), dt)
        out = np.asarray(paged_prefill_attention(
            qp, k_pool, v_pool, jnp.asarray(row[0]), jnp.int32(start),
            jnp.int32(chunk_len), page_size=ps, interpret=interpret),
            np.float32)
        want = reference(qp[None], k_pool, v_pool, row,
                         (start + np.arange(bucket))[None, :],
                         np.asarray([start + chunk_len]))[0]
        check(f"paged_prefill_attention[{bucket}]",
              out[:chunk_len], want[:chunk_len])

    peaks = device_peaks(dev)  # raises on a TPU kind the table lacks
    facts = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "interpret": interpret,
        "peak_source": peaks[2],
        "seconds": round(time.monotonic() - t0, 1),
    }
    print(f"fact kernels.seconds = {facts['seconds']} "
          f"(compile + run + reference) {tag}", flush=True)
    with open(os.path.join(out_dir, "kernels.json"), "w") as f:
        json.dump(facts, f)
    return 0


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


class Parent:
    def __init__(self, args: argparse.Namespace):
        self.rehearse: bool = args.rehearse
        self.out: str = os.path.abspath(args.out)
        self.port: int = args.port
        self.legs = tuple(args.legs.split(","))
        self.greedy_ref: str = args.greedy_ref
        self.t_start = time.monotonic()
        self.deadline = self.t_start + ONE_CHIP_BUDGET_S
        self.children: list = []
        self.tag = "[? x?]"
        self.device: dict = {}

    # -- plumbing ---------------------------------------------------------

    def left(self) -> float:
        return self.deadline - time.monotonic()

    def fact(self, name: str, value) -> None:
        print(f"fact {name} = {value} {self.tag}", flush=True)

    def check(self, name: str, ok: bool, value) -> None:
        if not ok:
            raise SmokeFailure(f"{name}: got {value!r}")
        self.fact(name, value)

    def env(self) -> dict:
        # the children run the repository's defaults, whatever the caller
        # had exported: no inherited KAFKA_TPU_* knob survives
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("KAFKA_TPU_")}
        # the default dials https://remote.mcpservers.org at boot; the chip
        # machine has no network
        env["KAFKA_TPU_MCP_SERVERS"] = "[]"
        env["PYTHONUNBUFFERED"] = "1"
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        if self.rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            # A CPU prefills the default 7.9k-token persona in ~13 s a
            # request, against an 8k window whose XLA attention it pays on
            # every warm-up chunk: the tool schemas alone (~3.2k tokens) in
            # a 4k window keep the rehearsal inside two minutes.
            env["KAFKA_TPU_SYSTEM_PROMPT"] = "terse"
            env["KAFKA_TPU_MAX_PAGES_PER_SEQ"] = "256"
            # serializing CPU executables has crashed XLA before
            env["KAFKA_TPU_COMPILE_CACHE"] = "0"
        return env

    def spawn(self, argv: list, log_name: str) -> subprocess.Popen:
        log = open(os.path.join(self.out, log_name), "wb")
        try:
            proc = subprocess.Popen(
                argv, cwd=HERE, env=self.env(), stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        finally:
            log.close()
        self.children.append(proc)
        return proc

    def kill_all(self) -> None:
        for proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except OSError:
                    pass
                proc.wait()

    def log_tail(self, log_name: str, n: int = 25) -> str:
        try:
            with open(os.path.join(self.out, log_name), "rb") as f:
                lines = f.read().decode("utf-8", "replace").splitlines()
        except OSError:
            return ""
        return "\n".join("    | " + ln for ln in lines[-n:])

    # -- leg kernels ------------------------------------------------------

    def run_kernels(self) -> None:
        argv = [sys.executable, os.path.abspath(__file__), "--leg", "kernels",
                "--out", self.out]
        if self.rehearse:
            argv.append("--rehearse")
        proc = self.spawn(argv, "kernels.log")
        try:
            rc = proc.wait(timeout=min(KERNELS_TIMEOUT_S, self.left()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("leg kernels timed out") from None
        with open(os.path.join(self.out, "kernels.log"), "rb") as f:
            text = f.read().decode("utf-8", "replace")
        for line in text.splitlines():
            if line.startswith(("fact ", "chip_smoke:")):
                print(line, flush=True)
        if rc == EXIT_NO_CHIP:
            raise SystemExit(EXIT_NO_CHIP)
        if rc != 0:
            raise SmokeFailure(
                f"leg kernels exited {rc}\n" + self.log_tail("kernels.log"))
        with open(os.path.join(self.out, "kernels.json")) as f:
            k = json.load(f)
        self.device = {"platform": k["platform"], "kind": k["kind"],
                       "count": k["count"]}
        self.tag = f"[{k['kind']} x{k['count']}]"
        if not self.rehearse:
            self.check("kernels.interpret", k["interpret"] is False,
                       k["interpret"])
            self.check("kernels.peak_source",
                       k["peak_source"] == "datasheet", k["peak_source"])

    # -- leg serve --------------------------------------------------------

    def http(self, method: str, path: str, body=None, timeout=None):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", method=method,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(
            req, timeout=timeout or REQUEST_TIMEOUT_S)

    def get_json(self, path: str) -> dict:
        with self.http("GET", path, timeout=30) as r:
            return json.load(r)

    def sse(self, name: str, path: str, body: dict) -> list:
        """POST a streamed request; every stream must end in `data: [DONE]`
        with no `error` event.  Returns the decoded events in order."""
        events, done = [], False
        with self.http("POST", path, body) as r:
            for raw in r:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    done = True
                    break
                events.append(json.loads(data))
        errors = [e for e in events if e.get("type") == "error"
                  or "error" in e]
        if errors:
            raise SmokeFailure(f"{name}: error event {errors[0]!r}")
        if not done:
            raise SmokeFailure(f"{name}: stream ended without [DONE]")
        return events

    @staticmethod
    def usage_of(events: list) -> dict:
        for e in reversed(events):
            if e.get("usage") and e.get("object") == "chat.completion.chunk":
                return e["usage"]
        return {}

    def turn(self, name: str, thread: str, text: str) -> dict:
        events = self.sse(name, f"/v1/threads/{thread}/chat/completions", {
            "model": SMOKE_MODEL, "stream": True, "max_tokens": 16,
            "temperature": 0,
            "messages": [{"role": "user", "content": text}],
        })
        usage = self.usage_of(events)
        tokens = sum(
            1 for e in events
            if e.get("choices") and e["choices"][0]["delta"].get("content"))
        cached = (usage.get("prompt_tokens_details") or {}).get(
            "cached_tokens", 0)
        self.check(f"{name}.stream", tokens > 0 and bool(usage),
                   f"[DONE] chunks={tokens} prompt_tokens="
                   f"{usage.get('prompt_tokens')} cached_tokens={cached}")
        return {"cached": cached, "usage": usage}

    def complete(self, prompt: str, max_tokens: int) -> dict:
        """One stateless non-streamed greedy completion."""
        with self.http("POST", "/v1/chat/completions", {
            "model": SMOKE_MODEL, "stream": False, "max_tokens": max_tokens,
            "temperature": 0,
            "messages": [{"role": "user", "content": prompt}],
        }) as r:
            body = json.load(r)
        return {
            "content": body["choices"][0]["message"].get("content") or "",
            "tokens": body["usage"]["completion_tokens"],
        }

    def serve_leg(self, leg: str, extra_argv: list) -> None:
        """Boot the server as a child, drive it, assert, SIGTERM."""
        argv = [sys.executable, "-m", "kafka_tpu.server",
                "--host", "127.0.0.1", "--port", str(self.port),
                "--db-path", os.path.join(self.out, f"{leg}.threads.db")]
        argv += ["--tiny-model"] if self.rehearse else ["--model",
                                                         SMOKE_MODEL]
        argv += extra_argv
        log_name = f"{leg}.log"
        t_boot = time.monotonic()
        proc = self.spawn(argv, log_name)
        try:
            self.wait_healthy(proc, leg)
            boot_s = time.monotonic() - t_boot
            self.drive(leg, boot_s)
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=DRAIN_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                raise SmokeFailure(
                    f"{leg}: no exit {DRAIN_TIMEOUT_S:.0f}s after SIGTERM"
                ) from None
            self.check(f"{leg}.sigterm_exit_code", rc == 0, rc)
        except SmokeFailure as e:
            raise SmokeFailure(f"{e}\n{self.log_tail(log_name)}") from None
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()

    def wait_healthy(self, proc, leg: str) -> None:
        while True:
            if proc.poll() is not None:
                raise SmokeFailure(
                    f"{leg}: server exited {proc.returncode} during boot")
            if self.left() <= 0:
                raise SmokeFailure(f"{leg}: boot exceeded the time budget")
            try:
                if self.get_json("/health").get("status") == "ok":
                    return
            except (urllib.error.URLError, OSError, ValueError):
                pass
            time.sleep(1.0)

    def drive(self, leg: str, boot_s: float) -> None:
        rehearse = self.rehearse
        health = self.get_json("/health")
        dev = health.get("device") or {}
        model = dev.get("model") or {}
        self.tag = f"[{dev.get('device_kind')} x{dev.get('visible')}]"
        tp = 4 if leg == "tp4" else 1
        dp = 4 if leg == "dp4" else 1

        # -- what is being served, as the server tells it ----------------
        self.check(f"{leg}.platform",
                   dev.get("platform") == ("cpu" if rehearse else "tpu"),
                   dev.get("platform"))
        self.check(f"{leg}.device_kind", bool(dev.get("device_kind")),
                   dev.get("device_kind"))
        self.check(f"{leg}.engine_devices", dev.get("count") == tp * dp,
                   dev.get("count"))
        self.fact(f"{leg}.model",
                  " ".join(f"{k}={model.get(k)}" for k in (
                      "name", "num_layers", "hidden_size", "num_heads",
                      "num_kv_heads", "head_dim", "intermediate_size",
                      "vocab_size", "dtype")))
        if not rehearse:
            self.check(f"{leg}.vocab_size",
                       model.get("vocab_size") == SMOKE_VOCAB,
                       model.get("vocab_size"))
            self.check(f"{leg}.dtype", model.get("dtype") == "bfloat16",
                       model.get("dtype"))
            # the `auto` rule must pick the kernel for this geometry on a
            # real chip, and the kernel must be compiled, not interpreted
            self.check(f"{leg}.attention_backend",
                       dev.get("attention_backend") == "pallas",
                       dev.get("attention_backend"))
            self.check(f"{leg}.interpret", dev.get("interpret") is False,
                       dev.get("interpret"))
            plan = health.get("memory_plan") or {}
            self.check(f"{leg}.memory_plan", plan.get("fits") is True,
                       f"fits={plan.get('fits')} total_gib="
                       f"{plan.get('total_gib')} of usable_gib="
                       f"{plan.get('usable_gib')}")
        else:
            self.fact(f"{leg}.attention_backend",
                      f"{dev.get('attention_backend')} "
                      f"interpret={dev.get('interpret')}")

        # -- the requests -------------------------------------------------
        self.turn(f"{leg}.thread_a.turn1", f"{leg}-a", "hi")
        t2 = self.turn(f"{leg}.thread_a.turn2", f"{leg}-a", "again")
        self.check(f"{leg}.thread_a.turn2.cached_tokens", t2["cached"] > 0,
                   t2["cached"])
        tb = self.turn(f"{leg}.thread_b.turn1", f"{leg}-b", "hello")
        self.check(f"{leg}.thread_b.cross_thread_cached_tokens",
                   tb["cached"] > 0 or dp > 1, tb["cached"])

        greedy = self.complete(GREEDY_PROMPT, max_tokens=GREEDY_TOKENS)
        self.check(f"{leg}.nonstream.completion",
                   greedy["tokens"] > 0 and bool(greedy["content"]),
                   f"{greedy['tokens']} tokens at temperature=0: "
                   f"{greedy['content']!r}")
        ref_path = os.path.join(self.out, "serve.greedy.json")
        if leg == "serve":
            with open(ref_path, "w") as f:
                json.dump(greedy["content"], f)
        elif leg == "tp4" and not rehearse:
            try:
                with open(self.greedy_ref or ref_path) as f:
                    ref = json.load(f)
            except OSError:
                print(f"skip tp4.first_greedy_token_equals_one_chip: no "
                      f"one-chip reference (run leg serve, or pass "
                      f"--greedy-ref) {self.tag}", flush=True)
            else:
                self.check("tp4.first_greedy_token_equals_one_chip",
                           greedy["content"][:1] == ref[:1],
                           f"{greedy['content']!r} vs one chip {ref!r}")

        events = self.sse(f"{leg}.agent", "/v1/agent/run", {
            # room for the forced call's JSON (the grammar lets a random
            # model pad it with whitespace until the budget forces wrap-up)
            "model": SMOKE_MODEL, "max_tokens": 160, "temperature": 0,
            "messages": [{"role": "user", "content": "save a checkpoint"}],
            "tool_choice": {"type": "function",
                            "function": {"name": AGENT_TOOL}},
        })
        calls = [c["function"]["name"] for e in events
                 for c in ((e.get("choices") or [{}])[0].get("delta") or {}
                           ).get("tool_calls") or []]
        kinds = [e.get("type") for e in events if e.get("type")]
        self.check(f"{leg}.agent.tool_calls", calls == [AGENT_TOOL], calls)
        self.check(f"{leg}.agent.events",
                   "tool_result" in kinds and kinds[-1] == "agent_done",
                   ",".join(kinds))

        if dp > 1:
            self.spread_over_replicas(leg, dp)

        # -- what the server says happened ---------------------------------
        m = self.get_json("/metrics")
        con = m.get("constrained") or {}
        self.check(f"{leg}.constrained_ondevice_tokens",
                   con.get("constrained_ondevice_tokens", 0) > 0,
                   con.get("constrained_ondevice_tokens"))
        self.check(f"{leg}.constrained_roundtrips",
                   con.get("constrained_roundtrips") == 0,
                   con.get("constrained_roundtrips"))
        self.check(f"{leg}.requests.failed",
                   m["requests"]["failed"] == 0,
                   f"{m['requests']['failed']} of "
                   f"{m['requests']['submitted']} submitted")
        util = m.get("utilization") or {}
        mem = m.get("memory") or {}
        if not rehearse:
            self.check(f"{leg}.peak_source",
                       util.get("peak_source") == "datasheet",
                       f"{util.get('peak_source')} "
                       f"{util.get('peak_tflops')} TFLOP/s "
                       f"{util.get('peak_hbm_gbps')} GB/s")
            self.check(f"{leg}.hbm_bytes_in_use",
                       mem.get("source") == "device"
                       and mem.get("hbm_bytes_in_use", 0) > 0,
                       f"{mem.get('hbm_bytes_in_use')} of "
                       f"{mem.get('hbm_bytes_limit')} "
                       f"(source {mem.get('source')})")
            if tp * dp > 1:
                self.memory_spread(leg, mem, tp * dp)
        # (the dp aggregate has no engine section of its own)
        engine = m.get("engine") or (m.get("replicas") or [{}])[0].get(
            "engine") or {}
        self.fact(f"{leg}.rtt_est_ms", engine.get("rtt_est_ms"))

        c = self.get_json("/debug/compiles")
        totals = c["totals"]
        unexpected = [r for r in c["records"]
                      if r["phase"] == "first_traffic"]
        self.check(f"{leg}.compiles.first_traffic", not unexpected,
                   [f"{r['label']}:{r.get('fn', '')}" for r in unexpected]
                   or 0)
        self.fact(f"{leg}.compiles",
                  f"{totals['compiles']} programs {totals['seconds']:.1f}s "
                  f"cache hit={totals['by_cache']['hit']} "
                  f"miss={totals['by_cache']['miss']} "
                  f"off={totals['by_cache']['off']} "
                  f"store={totals['by_cache'].get('store', 0)} "
                  f"dir={c.get('cache_dir')}")
        # (a step program the program store loaded was not compiled at all)
        hit = totals["by_cache"]["hit"] + totals["by_cache"].get("store", 0)
        miss = totals["by_cache"]["miss"]
        self.fact(f"{leg}.boot_seconds",
                  f"{boot_s:.1f} (" + ("cold compile" if hit == 0 else
                  f"cache hit on {hit} of {hit + miss} programs") + ")")

    def spread_over_replicas(self, leg: str, dp: int) -> None:
        """dp: unkeyed requests route by load alone, so a burst of 2*dp
        concurrent streams must land on every replica."""
        errors: list = []

        def one(i: int) -> None:
            try:
                self.sse(f"{leg}.burst{i}", "/v1/chat/completions", {
                    "model": SMOKE_MODEL, "stream": True, "max_tokens": 24,
                    "messages": [{"role": "user", "content": f"burst {i}"}],
                })
            except Exception as e:  # surfaced below, on the main thread
                errors.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(2 * dp)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=REQUEST_TIMEOUT_S)
        if errors or any(t.is_alive() for t in threads):
            raise SmokeFailure(f"{leg}.burst: {errors[:1] or 'timed out'}")
        per = [r["requests"]["finished"]
               for r in self.get_json("/metrics")["replicas"]]
        self.check(f"{leg}.every_replica_served",
                   len(per) == dp and min(per) >= 1, per)

    def memory_spread(self, leg: str, mem: dict, n: int) -> None:
        """Weights and KV pool must be spread: a device holding more than
        ~1.3x the mean is where un-placed params or a pool built on the
        default device piled up (device 0, if anywhere)."""
        used = {d["device"]: d["bytes_in_use"] for d in mem.get("devices", [])}
        mean = sum(used.values()) / max(1, len(used))
        worst = max(used.values(), default=0) / mean if mean else 0.0
        self.check(f"{leg}.memory_spread", len(used) == n and worst <= 1.3,
                   f"max/mean={worst:.2f} bytes_in_use={used}")

    # -- orchestration ------------------------------------------------------

    def run(self) -> None:
        os.makedirs(self.out, exist_ok=True)
        self.fact("chip_smoke.mode",
                  "rehearsal (CPU, --tiny-model, interpreted kernels)"
                  if self.rehearse else "chip")
        self.run_kernels()
        if "serve" in self.legs:
            self.serve_leg("serve", [])
        for leg, flag in (("tp4", "--tp-size"), ("dp4", "--dp-size")):
            if leg not in self.legs:
                continue
            if self.device["count"] < 4:
                print(f"skip {leg}: {self.device['count']} device(s) "
                      f"visible, the four-chip legs need 4 {self.tag}",
                      flush=True)
                continue
            self.deadline = time.monotonic() + FOUR_CHIP_LEG_BUDGET_S
            self.serve_leg(leg, [flag, "4"])
        self.fact("chip_smoke.seconds",
                  round(time.monotonic() - self.t_start, 1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, --tiny-model, interpreted kernels; the "
                         "verdict is REHEARSAL, never PASS")
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="logs, DB and leg results land here")
    ap.add_argument("--port", type=int, default=8471)
    ap.add_argument("--legs", default=",".join(LEGS),
                    help="serve legs to run (the kernels leg always runs); "
                         "tp4 and dp4 also need 4 visible devices")
    ap.add_argument("--greedy-ref", default="",
                    help="a one-chip run's serve.greedy.json, for leg tp4 "
                         "when leg serve does not run in this invocation")
    ap.add_argument("--leg", choices=["kernels"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    if not set(args.legs.split(",")) <= set(LEGS):
        ap.error(f"--legs takes a comma list of {LEGS}")

    if not os.path.isdir(os.path.join(HERE, "kafka_tpu")):
        print("chip_smoke: no kafka_tpu/ next to this script - nothing to "
              "smoke", file=sys.stderr)
        return EXIT_FAIL
    if args.leg == "kernels":
        sys.path.insert(0, HERE)
        try:
            return leg_kernels(args.rehearse, args.out)
        except SmokeFailure as e:
            print(f"FAIL {e}", flush=True)
            return EXIT_FAIL

    parent = Parent(args)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(EXIT_FAIL))
    try:
        parent.run()
    except Exception as e:  # the boundary: every failure becomes a verdict
        if not isinstance(e, SmokeFailure):
            traceback.print_exc()
        print(f"FAIL {type(e).__name__}: {e}", flush=True)
        print("verdict: FAIL", flush=True)
        return EXIT_FAIL
    finally:
        parent.kill_all()
    # the rule the whole file exists to keep: the chip is the children's
    if "jax" in sys.modules:
        print("FAIL the parent imported jax\nverdict: FAIL", flush=True)
        return EXIT_FAIL
    print("fact chip_smoke.parent_imported_jax = False "
          f"(\"jax\" not in sys.modules) {parent.tag}", flush=True)
    if args.rehearse:
        print("verdict: REHEARSAL (nothing here ran on a chip)", flush=True)
        return 0
    print("verdict: PASS", flush=True)
    print(json.dumps({"ok": True, "device": parent.device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
