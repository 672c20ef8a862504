"""Run one benchmark cell once.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent (this process) never imports JAX while the child lives: it spawns
`serve.py` (the one process that holds the chips), waits for `/health`, fills
what the traffic needs, drives the traffic from one asyncio loop, reads the
server's counters as window deltas, SIGTERMs the child and prints one JSON
object as its last line.  Everything that belongs to one configuration, one
traffic mix, one cell or one per-layer metric is a file found by the name in
BENCHMARK.json (benchmarks/README.md).

Exit codes: 0 a result line was printed; 3 no TPU or too few devices (no
result); 4 the server did not come up or died (no result).

Extra modes, not used by the driver: `--rehearse` (CPU, tiny configs under
`--root`, prints a line whose device says cpu and whose `correct` is false),
`--sweep r1,r2,...` (one boot, one window per offered rate, prints attainment
per rate: how the knee of an open-loop cell is found).
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import random
import shutil
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import aiohttp  # noqa: E402

import e2e  # noqa: E402
import loadgen  # noqa: E402
import named  # noqa: E402
import readers  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_NO_SERVER = 4
BOOT_TIMEOUT_S = 1100.0
PROFILE_SECONDS = 5.0
WARM_TURN_SHARE = 0.95
# The generator ran on time if at most this share of its sends left more
# than e2e.LATE_MS after they were due.  A lone stall of the shared host (one
# run of 33 had two sends 110 ms late, PERF.md section 6) is charged to those
# requests' TTFT, which runs from the due time, and does not flatter the
# server; a starved generator is late on many sends and fails.  The traced
# run reports `loadgen_late_p99_ms` beside it.
LATE_SHARE_LIMIT = 0.05


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of `workloads` with the files its names point at."""

    def __init__(self, root: str, bench_file: str, name: str):
        self.bench = load_json(bench_file)
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"run.py: no workload {name!r} in {bench_file}")
        self.name, self.chips = name, int(entry["chips"])
        self.config_name, self.traffic_name = entry["config"], entry["traffic"]
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == self.config_name)
        self.config_file = os.path.join(ROOT, cfg["file"])
        self.config = load_json(self.config_file)
        self.cell = load_json(os.path.join(root, "workloads", name + ".json"))
        self.params = load_json(
            os.path.join(root, "traffic", self.traffic_name + ".json"))
        self.params.update(self.cell.get("params", {}))
        self.limits = self.cell["limits"]

    def metrics(self, group: str) -> List[Dict[str, Any]]:
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]


class Server:
    """The child that holds the chips."""

    def __init__(self, cell: Cell, root: str, out: str, trace: bool,
                 rehearse: bool):
        self.out, self.cell = out, cell
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("KAFKA_TPU_")}
        env["KAFKA_TPU_MCP_SERVERS"] = "[]"  # no network to time out on
        env["PYTHONUNBUFFERED"] = "1"
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        if trace:
            env["KAFKA_TPU_PROFILING"] = "1"
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
            env["KAFKA_TPU_COMPILE_CACHE"] = "0"
            n = int(cell.config["serving"].get("dp_size", 1))
            if n > 1:
                env["XLA_FLAGS"] = (
                    f"--xla_force_host_platform_device_count={n}")
        argv = [sys.executable, os.path.join(HERE, "serve.py"),
                "--config", cell.config_file, "--name", cell.config_name,
                "--port", str(self.port), "--out", out,
                "--chips", str(cell.chips), "--root", root]
        if rehearse:
            argv.append("--rehearse")
        self.log_path = os.path.join(out, "serve.log")
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                argv, cwd=ROOT, env=env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def get(self, path: str, timeout: float = 30.0) -> Any:
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return json.load(r)

    def log_tail(self, n: int = 40) -> str:
        try:
            with open(self.log_path, "rb") as f:
                lines = f.read().decode("utf-8", "replace").splitlines()
        except OSError:
            return ""
        return "\n".join("    | " + ln for ln in lines[-n:])

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + BOOT_TIMEOUT_S
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                print(f"run.py: serve.py exited {rc}\n" + self.log_tail(),
                      file=sys.stderr, flush=True)
                raise SystemExit(
                    EXIT_NO_CHIP if rc == EXIT_NO_CHIP else EXIT_NO_SERVER)
            try:
                if self.get("/health", timeout=5).get("status") == "ok":
                    return
            except (OSError, ValueError):
                pass
            time.sleep(0.5)
        print("run.py: server not healthy in time\n" + self.log_tail(),
              file=sys.stderr, flush=True)
        raise SystemExit(EXIT_NO_SERVER)

    def stop(self) -> Optional[int]:
        """SIGTERM (the program drains), then the whole group, and wait."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=45)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except OSError:
            pass
        return self.proc.wait()


# --------------------------------------------------------------------------
# set-up traffic
# --------------------------------------------------------------------------

async def fill(http, server: Server, cell: Cell, seed: int) -> None:
    """Set-up traffic before the lead-in.  One request caches the shared
    system prompt; then a burst runs the fused multi-step decode program with
    several lanes busy, which the program's warm-up leaves to first traffic
    (PERF.md section 6).  Under dp the router's prefix-aware pick sends a
    cold thread to a replica that already holds the prefix, so the first
    burst, as wide as all the replicas' lanes together, lands mostly on one
    replica and leaves the others the prefix and a lane or two (my chip run
    1, PR 26: the other three compiled their fused program in the lead-in
    and inside the window).  The burst is therefore repeated until one that
    began with every replica warm, and so was spread by load, set off no
    compile: then every replica has run every program the traffic uses."""
    serving = cell.config["serving"]
    dp = int(serving.get("dp_size", 1))
    width = 4 if dp == 1 else dp * int(serving["max_batch"])
    rng = random.Random(f"{seed}:fill")

    async def burst(tag: str, n: int, max_tokens: int) -> None:
        now = time.monotonic()
        recs = await asyncio.gather(*[loadgen.one_request(
            http, server.base,
            f"/v1/threads/fill{seed}-{tag}-{i}/chat/completions",
            {"model": cell.config_name, "stream": True, "temperature": 0,
             "max_tokens": max_tokens,
             "messages": [{"role": "user", "content": loadgen.text(rng, 64)}]},
            {"due": now}, (0.0, 0.0)) for i in range(n)])
        bad = [r for r in recs if r["error"]]
        if bad:
            raise RuntimeError(f"fill request failed: {bad[0]['error']}")

    async def compiles() -> int:
        snap = await get_json(http, server.base + "/debug/compiles")
        return int(snap["totals"]["compiles"])  # `records` is a ring

    await burst("p", 1, 8)
    warm = False  # every replica held the prefix when the burst began
    for attempt in range(4 if dp == 1 else 8):
        seen = 0 if dp == 1 else await compiles()
        await burst(f"b{attempt}", width, 40)
        snap = await get_json(http, server.base + "/metrics")
        cached = all(
            (rep.get("prefix_cache") or {}).get("cached_pages", 0) > 0
            for rep in snap.get("replicas") or [snap])
        if cached and (dp == 1 or (warm and await compiles() == seen)):
            return
        warm = cached
    print("run.py: fill did not settle: a replica without the system prompt, "
          "or a compile in every burst", file=sys.stderr, flush=True)


async def get_json(http, url: str) -> Any:
    async with http.get(url) as r:
        return await r.json()


async def capture_trace(http, server: Server, at: float, ctx: Dict[str, Any]):
    """Once the window is a third through: POST /debug/profile."""
    await loadgen.sleep_until(at)
    async with http.post(server.base + "/debug/profile",
                         json={"seconds": PROFILE_SECONDS}) as r:
        body = await r.json()
        if r.status != 200:
            raise RuntimeError(f"/debug/profile: {r.status} {body}")
    ctx["profile"] = body


# --------------------------------------------------------------------------
# one window
# --------------------------------------------------------------------------

async def drive(server: Server, cell: Cell, seed: int, seconds: float,
                trace: bool, t_proc: float, sweep: Optional[List[float]]):
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        await fill(http, server, cell, seed)
        if sweep:
            for i, rate in enumerate(sweep):
                params = dict(cell.params, request_rate=rate)
                res = await window(http, server, cell, params,
                                   seed + 1000 * i, seconds, False, None)
                s = res["summary"]
                print("sweep " + json.dumps({
                    "rate": rate, "attempted": s["attempted"],
                    "failed": s["failed"],
                    "limits_met_share": round(s["limits_met_share"], 4),
                    "ttft_p50_ms": s["ttft_p50_ms"],
                    "ttft_p90_ms": s["ttft_p90_ms"],
                    "tpot_p50_ms": s["tpot_p50_ms"],
                    "out_tok_s": s["out_tok_s"],
                    "queue_depth_end": res["after"]["queue"]["depth"],
                    "alive_peak": res["alive_peak"],
                    "late_p99_ms": s["late_p99_ms"]}), flush=True)
                await asyncio.sleep(8.0)  # let the backlog drain
            return None
        return await window(http, server, cell, cell.params, seed, seconds,
                            trace, t_proc)


async def window(http, server, cell, params, seed, seconds, trace, t_proc):
    plan = loadgen.schedule(seed, params, seconds, cell.config_name)
    t_open = time.monotonic() + plan["lead_s"] + 0.2
    wall_open = time.time() + (t_open - time.monotonic())
    drv = loadgen.Driver(server.base, plan, params, t_open, seconds)
    ctx: Dict[str, Any] = {}

    async def snapshots() -> None:
        await loadgen.sleep_until(t_open)
        ctx["before"] = await get_json(http, server.base + "/metrics")
        await loadgen.sleep_until(t_open + seconds)
        ctx["after"] = await get_json(http, server.base + "/metrics")

    side = [snapshots()]
    if trace:
        side.append(capture_trace(http, server, t_open + seconds / 3.0, ctx))
    await drv.run(http, side)
    if "after" not in ctx:
        ctx["after"] = await get_json(http, server.base + "/metrics")
    ctx.update(
        log=drv.log, alive_peak=drv.alive_peak, kind=plan["kind"],
        t_open=t_open, t_close=t_open + seconds,
        wall_open=wall_open, wall_close=wall_open + seconds,
        setup_s=None if t_proc is None else t_open - t_proc,
        summary=e2e.summarize(drv.log, plan["kind"], t_open, t_open + seconds,
                              cell.limits),
        compiles=await get_json(http, server.base + "/debug/compiles"),
        health=await get_json(http, server.base + "/health"),
        info=await get_json(http, server.base + "/bench/info"),
    )
    return ctx


# --------------------------------------------------------------------------
# verdict and output
# --------------------------------------------------------------------------

def verdict(cell: Cell, ctx: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    s, dev = ctx["summary"], ctx["health"].get("device", {})
    expect = cell.config.get("expect", {})
    inside = [r for r in ctx["compiles"].get("records", [])
              if r.get("phase") == "first_traffic"
              and ctx["wall_open"] <= r.get("t", 0) <= ctx["wall_close"]]
    checks = {
        "platform_tpu": dev.get("platform") == "tpu",
        "device_count": dev.get("count") == cell.chips,
        "attention_backend": (dev.get("attention_backend")
                              == expect.get("attention_backend")),
        "not_interpreted": dev.get("interpret") is False,
        "no_compile_in_window": not inside,
        "lengths_from_seed": s["at_length_share"] >= 0.95,
        "loadgen_on_time": (s["late_share"] is not None
                            and s["late_share"] <= LATE_SHARE_LIMIT),
        "logit_check": bool(ctx["info"]["logit_check"].get("ok")),
    }
    if ctx["kind"] == "open_sessions":
        share = e2e.warm_turns(ctx["log"], cell.config["serving"]["page_size"])
        checks["turns_find_their_thread"] = (
            share is not None and share >= WARM_TURN_SHARE)
    names = {m["name"] for m in cell.metrics("end_to_end")}
    if "ttft_p90_ms" in names:
        checks["tail_has_samples"] = s["ttft_p90_ms"] is not None
    if "out_tok_s" in names:
        ratio = s["token_char_ratio"]
        checks["one_char_per_token"] = (
            ratio is not None and abs(ratio - 1.0) <= 0.02)
    return {"correct": all(checks.values()) and not rehearse,
            "checks": checks, "compiles_in_window": inside}


def read_layer_metric(root: str, name: str, ctx: Dict[str, Any]):
    """`<root>/layer_metrics/<name>.py` holds `read(ctx) -> value | None`;
    the benchmark's own readers serve any data root that has none."""
    return named.load((root, HERE), "layer_metrics", name).read(ctx)


def main() -> None:
    t_proc = time.monotonic()
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0, choices=(0, 1))
    p.add_argument("--root", default=HERE,
                   help="data root (traffic/, workloads/); BENCHMARK.json is "
                        "read from it when it is not benchmarks/ itself")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--sweep", default=None)
    args = p.parse_args()
    root = os.path.abspath(args.root)
    bench_file = os.path.join(ROOT if root == HERE else root, "BENCHMARK.json")
    cell = Cell(root, bench_file, args.workload)
    seconds = args.seconds or float(cell.bench["run_seconds"])
    sweep = [float(x) for x in args.sweep.split(",")] if args.sweep else None
    out = os.path.join(ROOT, ".bench_out", cell.name)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    server = Server(cell, root, out, bool(args.trace), args.rehearse)
    ctx = None
    gc.disable()  # no collector pause between a due time and its send
    try:
        server.wait_healthy()
        print(f"run.py: healthy after {time.monotonic() - t_proc:.1f} s",
              flush=True)
        ctx = asyncio.run(drive(server, cell, args.seed, seconds,
                                bool(args.trace), t_proc, sweep))
    finally:
        rc = server.stop()
        print(f"run.py: serve.py exited {rc}", flush=True)
    if ctx is None:
        return

    dev = ctx["health"].get("device", {})
    if not args.rehearse and (
            ctx["info"]["platform"] != "tpu"
            or ctx["info"]["visible"] < cell.chips):
        print("run.py: not on the chips the cell asks for", file=sys.stderr)
        raise SystemExit(EXIT_NO_CHIP)
    v = verdict(cell, ctx, args.rehearse)
    s = ctx["summary"]
    device = {"platform": ctx["info"]["platform"], "kind": ctx["info"]["kind"],
              "count": dev.get("count"),
              "memory_peak_bytes": readers.memory_peak_bytes(ctx["info"])}
    line: Dict[str, Any] = {
        "correct": v["correct"], "attempted": s["attempted"],
        "failed": s["failed"], "metrics": {}, "device": device}
    ctx.update(cell=cell, trace=None)
    if args.trace:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the child is gone; parse only
        import trace_reduce

        ctx["trace"] = trace_reduce.reduce_dir(os.path.join(out, "trace"))
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            line["breakdown"] = ctx["trace"]["breakdown"]
        for m in cell.metrics("per_layer"):
            value = read_layer_metric(root, m["name"], ctx)
            if value is not None:
                line["metrics"][m["name"]] = {
                    "value": float(value), "unit": m["unit"]}
    else:
        values = dict(s, setup_s=ctx["setup_s"])
        for m in cell.metrics("end_to_end"):
            if values.get(m["name"]) is not None:
                line["metrics"][m["name"]] = {
                    "value": float(values[m["name"]]), "unit": m["unit"]}
    print("run.py: checks " + json.dumps(v["checks"]), flush=True)
    if v["compiles_in_window"]:
        print("run.py: compiles inside the window "
              + json.dumps(v["compiles_in_window"])[:2000], flush=True)
    print("run.py: summary " + json.dumps(s), flush=True)
    print("run.py: logit check " + json.dumps(ctx["info"]["logit_check"]),
          flush=True)
    print("run.py: boot " + json.dumps(ctx["info"]["boot_s"])
          + " compiles " + json.dumps(ctx["compiles"].get("totals")),
          flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
