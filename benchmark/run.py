"""One run of one cell: `python3 -m benchmark.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>`.

Starts the real server as one child on a fresh data root inside the
checkout, drives the cell's traffic mix at it from worker processes,
and prints as the last line of stdout one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` for a traced
run), then `compared`, each number the verdict rests on beside its
limit.  `--trace 0` reports the end-to-end metrics, `--trace 1` the
per-layer ones.  No chip, a dead server or a failed set-up is exit code
1 and no result line.  This process never imports JAX.

`--rehearse-cpu` debugs a command without a chip: tiny sizes, backend
host, output stamped as a rehearsal, `correct` false, no device metric.
"""

from __future__ import annotations

import argparse
import csv
import json
import os
import shutil
import subprocess
import sys
import threading
import time

from benchmark import check, manifest, stats
from benchmark.loadgen import BUCKET, LoadGenerator, body_of
from benchmark.server import CHECKOUT, RunFailure, Server

TRACE_SECONDS = 10.0   # the traced part of a traced run's window


def wait_for(path: str, timeout: float, server: Server) -> None:
    t0 = time.perf_counter()
    while not os.path.exists(path):
        server.check_alive(f"while the harness waited for {path}")
        if time.perf_counter() - t0 > timeout:
            raise RunFailure(f"{path} did not appear in {timeout:.0f} s")
        time.sleep(0.02)


def settle(server: Server, timeout: float = 20.0) -> None:
    """Until the codec's dispatch counters stand still: whatever set-up
    queued in the background (a heal of a preloaded object) is done."""
    t0 = time.perf_counter()
    last = server.counters()["dispatches"]
    while time.perf_counter() - t0 < timeout:
        time.sleep(0.3)
        now = server.counters()["dispatches"]
        if now == last:
            return
        last = now
    raise RunFailure("background codec work did not settle in set-up")


def part_files(drive: str) -> int:
    n = 0
    for _, _, files in os.walk(drive):
        n += sum(f.startswith("part.") for f in files)
    return n


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, launcher: list[str] | None = None,
             extra_env: dict[str, str] | None = None,
             log_dir: str | None = None) -> dict:
    """The whole of a run but the printing.  `launcher` and `extra_env`
    exist for the tests under benchmark/tests, which put a broken server
    in the program's place to see `correct` come out false."""
    t_start = time.perf_counter()
    bench = manifest.benchmark()
    cell = manifest.cell(bench, workload)
    cfg = manifest.config(bench, cell["config"])
    mix = manifest.traffic(cell["traffic"], rehearsal)
    k, m, n = cfg["data_shards"], cfg["parity_shards"], cfg["drives"]
    root = os.path.join(CHECKOUT, ".bench_run", workload)
    shutil.rmtree(root, ignore_errors=True)
    ctl = os.path.join(root, "ctl")
    os.makedirs(ctl)
    ctx: dict = {"config": cfg, "mix": mix}
    env = dict(extra_env or {})
    if trace:
        launcher = launcher or [sys.executable, "-m", "benchmark.serve"]
        env.update({"BENCHMARK_TRACE_DIR": ctl, "JAX_LOG_COMPILES": "1"})
    if not cfg["fsync"]:
        raise RunFailure("no cell turns fsync off")
    server = Server(root, n, "host" if rehearsal else cfg["backend"],
                    launcher=launcher, extra_env=env)
    gen = None
    stopper = None
    try:
        gen = LoadGenerator(seed, mix, server.port)
        server.wait_live(timeout=900)
        ctx["boot_s"] = time.perf_counter() - server.t_spawn
        info = server.erasure_info()
        if not rehearsal:
            if info.get("platform") != "tpu":
                raise RunFailure(f"the server's JAX reports platform "
                                 f"{info.get('platform')!r}, not a TPU")
            where = info.get("boot", {}).get("geometry", {}).get(f"{k}+{m}")
            if where != "device":
                raise RunFailure(f"EC {k}+{m} resolves to {where!r}, not "
                                 f"to the device codec")
            if info["deviceCount"] < cell["chips"]:
                raise RunFailure(f"{info['deviceCount']} chips found, the "
                                 f"cell needs {cell['chips']}")
        status, data = server.conn.request("PUT", f"/{BUCKET}")
        if status != 200:
            raise RunFailure(f"PUT /{BUCKET} -> {status}: {data[:300]!r}")

        # ------------------------------------------------------- set-up
        if mix["preload_per_client"]:
            failed = sum(gen.call("preload", mix["preload_per_client"]))
            if failed:
                raise RunFailure(f"{failed} preload PUTs failed")
            settle(server)
        if trace:
            open(os.path.join(ctl, "trace.start"), "w").close()
            wait_for(os.path.join(ctl, "trace.started"), 120, server)
            with open(os.path.join(ctl, "trace.started")) as f:
                mark_at = json.load(f)["mark_at"]
            # The driver refuses a traced run in which no operation ran on
            # the device, and some cells' traffic does no codec work by
            # design.  So every traced run, whatever its cell, begins with
            # one PUT of one full dispatch: in the trace, seconds before
            # the window, which alone the per-layer metrics read.
            probe = cfg["dispatch_blocks"] * cfg["block_bytes"]
            coded0 = server.counters()["bytes"]["device"]
            status, _ = server.conn.request(
                "PUT", f"/{BUCKET}/probe", body=body_of(seed, "probe", probe))
            coded = server.counters()["bytes"]["device"] - coded0
            if status != 200 or (coded < probe and not rehearsal):
                raise RunFailure(
                    f"probe PUT -> {status}, device coded {coded} B")
        away = {}
        for d in mix["drives_away"]:
            away[d] = os.path.join(root, "away", f"d{d}")
            os.makedirs(os.path.dirname(away[d]), exist_ok=True)
            os.rename(os.path.join(root, f"d{d}"), away[d])
        os.sync()

        # --------------------------------------------- warm-up, window
        t_begin = time.perf_counter() + 0.25
        t0 = t_begin + float(mix["warmup_s"])
        setup_s = t0 - t_start
        gen.send("stream", t_begin, t0, seconds)
        time.sleep(max(0.0, t0 - time.perf_counter()))
        before = server.counters()
        cpu0, log0, ts0 = (server.cpu_seconds(), server.stderr_size(),
                           time.perf_counter())
        if trace:
            traced = min(seconds, TRACE_SECONDS)
            stopper = threading.Timer(
                t0 + traced + 0.25 - time.perf_counter(),
                lambda: open(os.path.join(ctl, "trace.stop"), "w").close())
            stopper.start()
        time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        after = server.counters()
        ctx["server_cpu_s"] = server.cpu_seconds() - cpu0
        ts1 = time.perf_counter()
        with open(server.stderr_path, "rb") as f:
            f.seek(log0)
            ctx["compile_log_lines"] = (
                f.read().count(b"Compiling ") if trace else None)
        replies = gen.recv()
        every = sorted((r for rep in replies for r in rep["rows"]),
                       key=lambda r: r[3])
        server.check_alive("during the window")
        rows = stats.counted(every)
        if trace:
            stopper.join()
            wait_for(os.path.join(ctl, "trace.done"), 180, server)
            with open(os.path.join(ctl, "trace.done")) as f:
                ctx["trace_window_s"] = json.load(f)["window_s"]
        info = server.erasure_info()
        ctx.update({
            "rows": rows, "counters": {"before": before, "after": after},
            "worker_cpu_shares": [rep["cpu_share"] for rep in replies],
            # what the counters' two scrapes, a few ms after the window's
            # two ends, saw served between them
            "scraped": [r for r in every if ts0 <= r[4] <= ts1],
        })
        if not rows:
            raise RunFailure("the window held no request")

        # ---------------------------------------- what the window says
        compared = {"failed_requests": sum(1 for r in every if not r[6]),
                    "wrong_answers": sum(1 for r in every if not r[7])}
        put_keys = {r[2] for r in every if r[1] == "PUT" and r[6]}
        for rep in gen.call("verify", put_keys):
            for res in rep:
                for name, v in res.items():
                    compared[name] = compared.get(name, 0) + v
        sizes = {}
        for rep in gen.call("sizes"):
            sizes.update(rep)
        if away:
            compared["lost_shards_back"] = sum(
                part_files(os.path.join(root, f"d{d}")) for d in away)
        stderr = server.stderr_text()
        if "Traceback (most recent call last)" in stderr:
            raise RunFailure(f"the server's stderr holds a traceback:\n"
                             f"{stderr[-4000:]}")
    finally:
        if stopper is not None:
            stopper.cancel()
        server.stop()
        if gen is not None:
            gen.close()

    try:
        # the drives as they stand once the server is gone, against the
        # reference: the objects the timed path wrote (or, for a cell
        # that only reads, the preloaded objects it read from)
        touched = {r[2] for r in every if r[6] and r[1] in ("PUT", "GET")}
        drive_dirs = [away.get(d, os.path.join(root, f"d{d}"))
                      for d in range(1, n + 1)]
        compared.update(check.check_sample(
            drive_dirs, {key: sizes[key] for key in touched}, seed, k, m,
            cfg["block_bytes"], cfg["inline_below_bytes"],
            mix["on_disk_sample"]))
        if trace:
            child = subprocess.run(
                [sys.executable, "-m", "benchmark.trace",
                 os.path.join(ctl, "trace"), str(t0 - mark_at),
                 str(t0 + traced - mark_at)], cwd=CHECKOUT,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, text=True, timeout=200)
            if child.returncode != 0:
                raise RunFailure(f"the trace's reduction failed:\n"
                                 f"{child.stdout[-2000:]}\n"
                                 f"{child.stderr[-2000:]}")
            ctx["trace"] = json.loads(child.stdout.splitlines()[-1])
            if log_dir:
                os.makedirs(log_dir, exist_ok=True)
                shutil.copytree(os.path.join(ctl, "trace"), os.path.join(
                    log_dir, f"trace-{workload}-{seed}"), dirs_exist_ok=True)
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
            name = f"requests-{workload}-{seed}-t{int(trace)}.csv"
            with open(os.path.join(log_dir, name), "w", newline="") as f:
                w = csv.writer(f)
                w.writerow(["client", "op", "key", "start", "ack", "bytes",
                            "ok", "identical", "phase"])
                w.writerows((r[0], r[1], r[2], f"{r[3] - t0:.6f}",
                             f"{r[4] - t0:.6f}", *r[5:]) for r in every)
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # ------------------------------------------------------------ result
    counts = ("readback_compared", "deleted_checked",
              "objects_on_disk_compared")
    in_window = sum(1 for r in every if r[6] and r[1] in ("GET", "STAT"))
    limits = {name: [v, 0] for name, v in compared.items()
              if name not in counts}
    looked = in_window + sum(compared.get(c, 0) for c in counts)
    limits["answers_compared"] = [looked, ">=1"]
    correct = looked >= 1 and all(v <= lim for v, lim in limits.values()
                                  if lim != ">=1")
    ctx["device"] = device = {
        "platform": info.get("platform", "none"),
        "kind": info.get("deviceKind", "none (host codec rehearsal)"),
        "count": info.get("deviceCount", 0),
        "memory_peak_bytes": info.get("peakBytesInUse") or 0,
    }
    metrics: dict[str, dict] = {}
    e2e = manifest.metrics_of(bench, "end_to_end", workload)
    if not trace:
        for spec in e2e:
            value = setup_s if spec["name"] == "setup_s" \
                else stats.END_TO_END[spec["name"]](rows, seconds)
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for spec in manifest.metrics_of(bench, "per_layer", workload,
                                        {s["name"] for s in e2e}):
            if rehearsal and spec["source"] == "device_trace":
                continue
            read, args = manifest.reader(spec["name"])
            value = read(ctx, **args)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        # of the whole trace, the probe in it; the readers' window apart
        device["busy_s"] = ctx["trace"]["traced_busy_s"]
        device["window_s"] = ctx["trace_window_s"]
    result = {
        "correct": bool(correct),
        "attempted": len(rows),
        "failed": sum(1 for r in rows if not r[6]),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": ctx["trace"]["device_ops"],
            "idle_gaps": ctx["trace"]["idle_gaps"]}
    result["info"] = {
        "seed": seed, "seconds": seconds, "setup_s": setup_s,
        "boot_s": ctx["boot_s"], "requests": len(every),
        "p50_ms": stats.percentile_ms(rows, 50),
        "p99_ms": stats.percentile_ms(rows, 99),
        "loadgen_busiest_pct": 100.0 * max(ctx["worker_cpu_shares"]),
        "notes": ctx.get("notes", {}),
    }
    if rehearsal:
        result["rehearsal"] = True
    result["compared"] = limits
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--log-dir", default=None,
                    help="write the window's request log there as CSV")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearsal=args.rehearse_cpu,
                          log_dir=args.log_dir)
    except (RunFailure, KeyError) as e:
        print(f"benchmark.run: no result: {e}", file=sys.stderr)
        return 1
    if "jax" in sys.modules:
        print("benchmark.run: the harness's own process imported JAX",
              file=sys.stderr)
        return 1
    if args.rehearse_cpu:
        # a rehearsal proves the command, never the system
        result["correct"] = False
    print(json.dumps(result["info"]), file=sys.stderr)
    print("compared (value, limit): " + ", ".join(
        f"{name}={v} (limit {lim})"
        for name, (v, lim) in result["compared"].items()), file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
