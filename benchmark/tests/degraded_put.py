"""The degraded PUT's on-disk control, at the cell's own size on the chip:
`python3 -m benchmark.tests.degraded_put --seed N`.

`ec12p4-16d-warp-mixed.mixed-degraded` cannot sample the drives itself
(its window deletes keys, and `check_sample` takes one geometry for
every object), so this does, beside `control.py`: the real server on
sixteen drives, drives 1 and 7 renamed away, then PUTs of the
configuration's objects.  With the drives away a PUT is written at the
upgraded parity (`benchmark/reference/parity_upgrade.py`: 10+6), a
geometry no boot compiles: the first PUTs are coded while the server
warms its device programs in the background, the last after admin info
says `device`.  Every object is read back through the server, and once
the server has stopped its fourteen shard files are compared with the
plain reference at (10, 6): placement, data shards, parity, every
frame's hash; `shards_missing` is exactly the two drives away.

Prints one JSON object; exit code 0 when every comparison holds, the
first PUT after the drives went was answered inside `--first-put-s`,
no compilation ran on a request's thread (`compile_wait` 0) and, on the
chip, the geometry became `device` and took the later PUTs.
`--rehearse-cpu`: tiny sizes, host codec, nothing to wait for.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

from benchmark import check, manifest
from benchmark.loadgen import BUCKET, body_of
from benchmark.reference import parity_upgrade
from benchmark.server import CHECKOUT, RunFailure, Server

CONFIG = "ec12p4-16d-warp-mixed"
AWAY = (1, 7)


def run(seed: int, *, rehearsal: bool = False, before: int = 3,
        after: int = 3, ready_timeout: float = 600.0) -> dict:
    bench = manifest.benchmark()
    cfg = manifest.config(bench, CONFIG)
    n, parity = cfg["drives"], cfg["parity_shards"]
    k, m = parity_upgrade.upgraded(n, parity, len(AWAY))
    size = 3 << 20 if rehearsal else cfg["objects"]["bytes"]
    root = os.path.join(CHECKOUT, ".bench_run", "degraded_put")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    server = Server(root, n, "host" if rehearsal else cfg["backend"],
                    extra_env={"JAX_LOG_COMPILES": "1"})
    out: dict = {"seed": seed, "geometry": f"{k}+{m}", "object_bytes": size}
    keys: list[str] = []

    def put(key: str) -> float:
        t0 = time.perf_counter()
        status, data = server.conn.request(
            "PUT", f"/{BUCKET}/{key}", body=body_of(seed, key, size))
        if status != 200:
            raise RunFailure(f"PUT {key} -> {status}: {data[:300]!r}")
        keys.append(key)
        return time.perf_counter() - t0

    def state() -> str | None:
        return server.erasure_info().get("boot", {}).get(
            "geometry", {}).get(f"{k}+{m}")

    try:
        server.wait_live(timeout=900)
        status, data = server.conn.request("PUT", f"/{BUCKET}")
        if status != 200:
            raise RunFailure(f"PUT /{BUCKET} -> {status}: {data[:300]!r}")
        put("healthy/000001")  # at the configured 12+4, all drives there
        healthy = keys.pop()
        away = {}
        for d in AWAY:
            away[d] = os.path.join(root, "away", f"d{d}")
            os.makedirs(os.path.dirname(away[d]), exist_ok=True)
            os.rename(os.path.join(root, f"d{d}"), away[d])
        log0 = server.stderr_size()
        c0 = server.counters()
        out["first_put_s"] = put("degraded/000001")
        out["state_after_first_put"] = state()
        out["put_s_while_warming"] = [put(f"degraded/{i:06d}")
                                      for i in range(2, before + 1)]
        t0 = time.perf_counter()
        while not rehearsal and state() != "device":
            server.check_alive("while the geometry warmed")
            if state() == "failed" or \
                    time.perf_counter() - t0 > ready_timeout:
                break
            time.sleep(0.25)
        out["ready_after_s"] = time.perf_counter() - t0
        out["state_then"] = state()
        c1 = server.counters()
        out["put_s_once_ready"] = [put(f"degraded/{i:06d}")
                                   for i in range(before + 1,
                                                  before + after + 1)]
        c2 = server.counters()
        wrong = 0
        for key in [healthy] + keys:
            buf = bytearray(size)
            status, got = server.conn.request(
                "GET", f"/{BUCKET}/{key}", into=buf)
            wrong += not (status == 200 and got == size
                          and buf == body_of(seed, key, size))
        out["readback_mismatch"] = wrong

        def coded(a: dict, b: dict, backend: str) -> int:
            return int(b["bytes"][backend] - a["bytes"][backend])

        def stage(a: dict, b: dict, family: str, name: str):
            if name not in b[family]:
                return None
            return b[family][name] - a[family].get(name, 0.0)

        out.update({
            "device_bytes_while_warming": coded(c0, c1, "device"),
            "host_bytes_while_warming": coded(c0, c1, "host"),
            "device_bytes_once_ready": coded(c1, c2, "device"),
            "host_bytes_once_ready": coded(c1, c2, "host"),
            "warming_bytes": stage(c0, c2, "stage_bytes", "warming"),
            "compile_s": stage(c0, c2, "stage_seconds", "compile"),
            "compile_wait_s": stage(c0, c2, "stage_seconds", "compile_wait"),
        })
        with open(server.stderr_path, "rb") as f:
            f.seek(log0)
            out["compiling_lines_since_the_drives_went"] = \
                f.read().count(b"Compiling ")
        out["lost_shards_back"] = sum(
            os.path.exists(os.path.join(root, f"d{d}")) for d in AWAY)
        out["device"] = server.erasure_info().get("deviceKind", "none")
    finally:
        server.stop()
    try:
        drive_dirs = [away.get(d, os.path.join(root, f"d{d}"))
                      for d in range(1, n + 1)]
        found = {"shards_missing": 0, "shard_mismatch": 0,
                 "frame_hash_mismatch": 0, "shard_files": 0}
        for key in keys:
            res = check.check_object(drive_dirs, key, body_of(seed, key, size),
                                     k, m, cfg["block_bytes"])
            for name, v in res.items():
                found[name] += v
            found["shard_files"] += n - res["shards_missing"]
        out["on_disk"] = found
        out["objects_on_disk_compared"] = len(keys)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def verdict(out: dict, *, rehearsal: bool, first_put_s: float) -> list[str]:
    """What does not hold, in words; empty when all does."""
    objects = out["objects_on_disk_compared"]
    bad = []
    want = {"shards_missing": len(AWAY) * objects, "shard_mismatch": 0,
            "frame_hash_mismatch": 0,
            "shard_files": (16 - len(AWAY)) * objects}
    if out["on_disk"] != want:
        bad.append(f"on disk {out['on_disk']}, the reference says {want}")
    if out["readback_mismatch"] or out["lost_shards_back"]:
        bad.append("a read-back differs, or a root that went came back")
    if out["first_put_s"] > first_put_s:
        bad.append(f"the first PUT after the drives went took "
                   f"{out['first_put_s']:.2f} s")
    if out["compile_wait_s"]:
        bad.append(f"a request's thread compiled: compile_wait "
                   f"{out['compile_wait_s']} s")
    if not rehearsal:
        if out["state_then"] != "device":
            bad.append(f"{out['geometry']} is {out['state_then']!r}, "
                       f"not device")
        if out["host_bytes_once_ready"] or not out["device_bytes_once_ready"]:
            bad.append("a PUT after the warm-up was coded on the host")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--first-put-s", type=float, default=1.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    try:
        out = run(args.seed, rehearsal=args.rehearse_cpu)
    except RunFailure as e:
        print(json.dumps({"crashed": str(e)[-2000:]}))
        return 1
    out["does_not_hold"] = verdict(out, rehearsal=args.rehearse_cpu,
                                   first_put_s=args.first_put_s)
    print(json.dumps(out))
    return 1 if out["does_not_hold"] else 0


if __name__ == "__main__":
    sys.exit(main())
