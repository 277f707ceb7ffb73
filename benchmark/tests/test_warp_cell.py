"""The cell of warp's default objects (ISSUE 32): its command ends with
a result line, the two codec faults come out not correct on it, and its
metric of the bytes a short dispatch carries names its reader.  CPU
only: rehearsal sizes (3 MiB objects: 3-block dispatches), host codec.

Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; the
runs share `.bench_run/`, so one process at a time."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest, run
from benchmark.readers import stage_bytes_per_byte

CELL, CONTROL = "ec12p4-16d-warp.get-degraded", "ec12p4-16d.get-degraded"
FAULTY = [sys.executable, "-m", "benchmark.tests.faulty_serve"]


def test_rehearsal_ends_with_a_result_line():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2**31 + 32), "--seconds", "2", "--trace", "0",
         "--rehearse-cpu"], cwd=manifest.CHECKOUT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["rehearsal"] and result["failed"] == 0
    assert set(result["metrics"]) == {"get_MiBps", "setup_s"}
    assert all(v <= lim for v, lim in result["compared"].values()
               if lim != ">=1"), result["compared"]
    assert result["compared"]["answers_compared"][0] >= 1


@pytest.mark.parametrize("fault,caught_by", [
    ("parity_byte", "shard_mismatch"),
    ("rebuilt_byte", "wrong_answers"),
])
def test_codec_fault_is_not_correct(fault, caught_by):
    result = run.run_cell(CELL, 2**31 + 33, 2.0, False, rehearsal=True,
                          launcher=FAULTY,
                          extra_env={"BENCHMARK_FAULT": fault})
    assert not result["correct"]
    assert result["compared"][caught_by][0] > 0, result["compared"]


def test_configuration_is_warps_defaults_on_the_control_s_node():
    bench = manifest.benchmark()
    cfg = manifest.config(bench, "ec12p4-16d-warp")
    node = manifest.config(bench, "ec12p4-16d")
    for key in ("drives", "data_shards", "parity_shards", "block_bytes",
                "shard_bytes", "dispatch_blocks", "inline_below_bytes",
                "bitrot", "fsync", "backend", "chips", "guarantees"):
        assert cfg[key] == node[key], key
    assert cfg["objects"] == {"bytes": 10 << 20, "count": 160,
                              "concurrent": 20}
    mix = manifest.traffic(manifest.cell(bench, CELL)["traffic"])
    assert mix["sizes"] == {"fixed": cfg["objects"]["bytes"]}
    assert mix["clients"] == cfg["objects"]["concurrent"]
    assert mix["clients"] * mix["preload_per_client"] \
        == cfg["objects"]["count"]
    control = manifest.traffic(manifest.cell(bench, CONTROL)["traffic"])
    assert mix["drives_away"] == control["drives_away"] == [1, 7]
    # every object is whole blocks and fewer than one compiled dispatch
    blocks, tail = divmod(cfg["objects"]["bytes"], cfg["block_bytes"])
    assert tail == 0 and 0 < blocks < cfg["dispatch_blocks"]


def test_batch_fill_metric_names_its_reader():
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"]
               if m["name"] == "batch_fill_bytes_per_byte.get"]
    assert spec["moves"] == "get_MiBps" and spec["better"] == "lower"
    assert spec["layer"] == "streaming erasure engine"
    assert spec["source"] == "program_counter" and spec["unit"] == "B/B"
    assert spec["workloads"] == [CELL, CONTROL]
    read, args = manifest.reader(spec["name"])
    assert read is stage_bytes_per_byte.read
    assert args == {"stage": "batch_fill", "per": "respond"}


@pytest.mark.parametrize("before,after,want", [
    # 10-block objects at 12+4 carried at 16: 6 blocks of 12 shards of
    # 87,382 bytes beyond every 10 MiB served (at 32 it would be 2.2)
    ({"respond": 0, "batch_fill": 0},
     {"respond": 30 << 20, "batch_fill": 3 * 6 * 12 * 87382}, 0.6000046),
    # a cell whose every dispatch is a compiled size: there, and 0
    ({"respond": 1 << 20, "batch_fill": 0},
     {"respond": 65 << 20, "batch_fill": 0}, 0.0),
    # the parent of the PR that brings the stage: nothing, no error
    ({"respond": 1 << 20}, {"respond": 65 << 20}, None),
])
def test_batch_fill_per_served_byte(before, after, want):
    ctx = {"counters": {"before": {"stage_bytes": before},
                        "after": {"stage_bytes": after}}}
    got = stage_bytes_per_byte.read(ctx, "batch_fill", "respond")
    assert got == (want if want is None else pytest.approx(want, rel=1e-6))
