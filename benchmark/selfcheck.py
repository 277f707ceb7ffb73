"""The benchmark's check of itself, CPU only: `python3 -m benchmark.selfcheck`.

- the plain references against vectors of their sources (HighwayHash-256
  chained-sum vector of MinIO's bitrot self-test; the 2+2 parity rows;
  hashOrder by hand);
- the trace reduction on the small recorded trace beside it, against
  hand-worked values;
- the roofline counts for an 8+4 encode and a 2+2 one-row reconstruct of
  one 32 MiB dispatch, against hand-worked values;
- BENCHMARK.json: names, units, lengths, files, every traffic, metric and
  reader file loads, every cell reports setup_s, another end-to-end
  metric and a per-layer metric, and every per-layer metric's `moves`
  is reported by every cell that reports the metric.
Exit code 0 and "selfcheck ok" when all hold.
"""

from __future__ import annotations

import json
import os
import re
import sys

import numpy as np

from benchmark import (check, loadgen, manifest, roofline, serve, stats,
                       trace)
from benchmark.reference import gf256, highwayhash

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
HH_GOLDEN = "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313"


def need(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def check_references() -> None:
    msg = digest = b""
    for _ in range(32):
        digest = highwayhash.hh256_rows(
            np.frombuffer(msg, dtype=np.uint8)[None, :]).tobytes()
        msg += digest
    need(digest.hex() == HH_GOLDEN, "HighwayHash-256 chained-sum vector")
    need(gf256.coding_matrix(2, 2)[2:].tolist() == [[3, 2], [2, 3]],
         "2+2 parity rows")
    need(gf256.coding_matrix(8, 4)[8].tolist()
         == [26, 132, 186, 51, 231, 16, 198, 39], "8+4 first parity row")
    data = np.arange(16, dtype=np.uint8).reshape(2, 8)
    parity = gf256.encode(data, 2)
    need(parity[0].tolist() == [int(gf256.MUL[3, a] ^ gf256.MUL[2, b])
                                for a, b in zip(data[0], data[1])],
         "2+2 encode by hand")
    # crc32("a") = 0xE8B7BE43 = 3904355907; % 4 = 3: drives hold 1,2,3,4
    need(check.hash_order("a", 4) == [1, 2, 3, 4], "hashOrder by hand")
    need(len({tuple(check.hash_order(f"k{i}", 4)[j] for j in (1, 3))
              for i in range(64)} - {(1, 3), (3, 1), (2, 4), (4, 2)}) == 0,
         "drives 2 and 4 always hold one data and one parity shard of 2+2")


def check_roofline() -> None:
    ops, hbm = roofline.rs_work(8, 4, 32 << 20)
    need((ops, hbm) == (17179869184.0, 50331648.0), "8+4 encode work")
    pct, bound = roofline.roofline_pct("TPU v5 lite", ops, hbm, 61.455e-6 * 4)
    need(bound == "memory" and abs(pct - 25.0) < 0.01, "8+4 encode roofline")
    ops, hbm = roofline.rs_work(2, 1, 32 << 20)
    need((ops, hbm) == (4294967296.0, 50331648.0), "2+2 reconstruct work")
    need(roofline.roofline_pct("TPU v5 lite", ops, hbm, 1e-3)[1] == "memory",
         "2+2 reconstruct bound")
    try:
        roofline.peaks("TPU v99")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")


def check_trace() -> None:
    here = os.path.join(manifest.HERE, "tests", "data")
    with open(os.path.join(here, "recorded_trace.json")) as f:
        events = json.load(f)
    with open(os.path.join(here, "recorded_trace.expected.json")) as f:
        want = json.load(f)
    got = trace.reduce_events(events)
    need(got["devices"] == want["devices"], "trace: device planes")
    need(abs(got["busy_s"] - want["busy_s"]) < 1e-9, "trace: busy seconds")
    for name, prog in want["programs"].items():
        need(got["programs"][name]["count"] == prog["count"]
             and abs(got["programs"][name]["seconds"] - prog["seconds"])
             < 1e-9, f"trace: program {name}")
    need(got["device_ops"][0][0] == want["top_op"], "trace: largest op")
    need(abs(got["idle_gaps"][0][1] - want["longest_gap_s"]) < 1e-9,
         "trace: longest idle gap")
    # by hand: two ops that overlap and one apart; a module spanning them
    tiny = {"devices": {"/device:TPU:0": {
        "XLA Ops": [["%a = f32[] add()", 1.0, 0.5], ["%b", 1.25, 0.5],
                    ["%a = f32[] add()", 3.0, 0.25]],
        "XLA Modules": [["jit_f(123)", 1.0, 0.75], ["jit_f(124)", 3.0, 0.25]],
    }}, "host": [["waiting", 1.8, 1.1]]}
    got = trace.reduce_events(tiny)
    need(got["busy_s"] == 1.0, "trace by hand: union of intervals")
    need(got["programs"] == {"jit_f": {"count": 2, "seconds": 1.0}},
         "trace by hand: program")
    need(got["device_ops"] == [["a", 0.75], ["b", 0.5]],
         "trace by hand: ops")
    need(got["idle_gaps"] == [["waiting", 1.25]], "trace by hand: gap")
    # the window cut out of it: from 1.5 s to 4 s after a mark at 0.5
    tiny["host"].append([serve.MARK, 0.5, 0.002])
    inside, window = trace.cut(tiny, (1.5, 4.0))
    need(window == (2.0, 4.5), "trace by hand: window by the mark")
    got = trace.reduce_events(inside, window=window)
    need(got["busy_s"] == 0.25 and got["programs"] == {
        "jit_f": {"count": 1, "seconds": 0.25}}, "trace by hand: window")
    need(got["idle_gaps"] == [["unattributed", 1.25], ["unattributed", 1.0]],
         "trace by hand: a window's gaps reach to its ends")
    inside, window = trace.cut(tiny, (4.0, 6.0))
    got = trace.reduce_events(inside, window=window)
    need(got["busy_s"] == 0.0 and got["idle_gaps"] == [["unattributed", 2.0]],
         "trace by hand: a window with no device operation is one gap")


def check_stats() -> None:
    rows = [(0, "GET", "k", -1.0, 2.0, 4 << 20, True, True, 1),
            (1, "GET", "l", 1.0, 3.5, 4 << 20, True, True, 1),
            (1, "GET", "l", 3.5, 4.5, 4 << 20, True, True, 2),
            (1, "GET", "l", 1.0, 1.5, 0, False, True, 1)]
    rows = stats.counted(rows)
    need(stats.rate_MiBps(rows, 4.0, "GET") == 2.0,
         "rate: what was acknowledged in the window over its length")
    need(stats.ops_per_s(rows, 4.0) == 0.5, "ops per second")
    need(stats.percentile_ms(rows, 50) == 3000.0
         and stats.percentile_ms(rows, 95) == 1e6,
         "percentile, a failure beyond every latency")


def check_manifest() -> None:
    bench = manifest.benchmark()
    need(set(bench) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"},
         "BENCHMARK.json keys")
    need(1 <= bench["run_seconds"] <= 51, "run_seconds")
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for c in bench["configs"]:
        need(NAME.match(c["name"]) and len(c["source"]) <= 200
             and len(c["why"]) <= 200, f"config {c['name']}")
        need(c["file"].startswith("benchmark/")
             and os.path.exists(os.path.join(manifest.CHECKOUT, c["file"])),
             f"config file {c['file']}")
        cfg = manifest.config(bench, c["name"])
        need(cfg["fsync"] is True and cfg["guarantees"], "guarantees stated")
        need(set(c["reduced"]) == set(cfg["reduced"]),
             f"{c['name']}: reduced keys as the file states them")
    need(len({(w["config"], w["traffic"]) for w in bench["workloads"]})
         == len(cells), "a pair of configuration and traffic appears once")
    need(sum(w["chips"] == 4 for w in bench["workloads"])
         <= max(1, len(cells) // 2), "four-chip cells")
    for w in bench["workloads"]:
        need(NAME.match(w["name"]) and w["config"] in configs
             and w["chips"] in (1, 4) and len(w["why"]) <= 200,
             f"cell {w['name']}")
        mix = manifest.traffic(w["traffic"])
        loadgen.op_cycle(mix["shares"])
        loadgen.size_grid(mix["sizes"])
        need(mix["clients"] % mix["processes"] == 0, "clients per process")
        own = {m["name"] for m in manifest.metrics_of(
            bench, "end_to_end", w["name"])}
        need("setup_s" in own and len(own) >= 2,
             f"{w['name']}: setup_s and another end-to-end metric")
        layer = manifest.metrics_of(bench, "per_layer", w["name"], own)
        need(layer, f"{w['name']}: a per-layer metric")
        for m in layer:
            need(m["moves"] in own,
                 f"{w['name']} reports {m['name']} but not {m['moves']}")
    for m in bench["end_to_end"] + bench["per_layer"]:
        need(NAME.match(m["name"]) and UNIT.match(m["unit"])
             and m["better"] in ("lower", "higher")
             and m["source"] in SOURCES, f"metric {m['name']}")
        need(set(m.get("workloads", [])) <= cells, f"{m['name']}: cells")
    for m in bench["end_to_end"]:
        need(m["source"] in ("host_clock", "device_trace")
             and 0.01 <= m["bound"] <= 0.25, f"{m['name']}: bound")
        need(m["name"] == "setup_s" or m["name"] in stats.END_TO_END,
             f"{m['name']}: arithmetic")
    for m in bench["per_layer"]:
        need(m["moves"] in e2e and len(m["layer"]) <= 200,
             f"{m['name']}: moves")
        manifest.reader(m["name"])
    runs = 2 + 14 * 24
    need(runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200,
         "a full check of 24 cells fits its time")
    need(os.path.getsize(os.path.join(manifest.CHECKOUT, "BENCHMARK.json"))
         <= 64 << 10, "BENCHMARK.json size")


def main() -> int:
    for fn in (check_references, check_roofline, check_trace, check_stats,
               check_manifest):
        fn()
        print(f"{fn.__name__}: ok")
    print("selfcheck ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
