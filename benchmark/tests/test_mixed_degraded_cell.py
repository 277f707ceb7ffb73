"""The cell of warp's mixed defaults on the degraded sixteen-drive node
(ISSUE 35): its command ends with a result line, the two codec faults
come out not correct on it though it samples no drive, its configuration
is the control's node and its degraded write the reference's, a degraded
PUT through the real server is on disk what the reference says at 10+6,
and each metric the cell brings names its reader.  CPU only: rehearsal
sizes (3 MiB objects), host codec.

Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; the
runs share `.bench_run/`, so one process at a time."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest, run
from benchmark.readers import (latency_percentile_ms, stage_bytes_per_byte,
                               stage_ms_per_op, stage_seconds_in_window)
from benchmark.reference import parity_upgrade
from benchmark.tests import degraded_put

CELL = "ec12p4-16d-warp-mixed.mixed-degraded"
NODE = "ec12p4-16d-warp"
FAULTY = [sys.executable, "-m", "benchmark.tests.faulty_serve"]
ZERO = ("failed_requests", "wrong_answers", "readback_mismatch",
        "deleted_still_there", "lost_shards_back", "shards_missing",
        "shard_mismatch", "frame_hash_mismatch")


def test_rehearsal_ends_with_a_result_line():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2**31 + 35), "--seconds", "3", "--trace", "0",
         "--rehearse-cpu"], cwd=manifest.CHECKOUT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["rehearsal"] and result["failed"] == 0
    assert set(result["metrics"]) == {"ops_per_s", "setup_s"}
    assert {name: result["compared"][name] for name in ZERO} \
        == {name: [0, 0] for name in ZERO}
    assert result["compared"]["answers_compared"][0] >= 1


@pytest.mark.parametrize("fault", ["parity_byte", "rebuilt_byte"])
def test_codec_fault_is_not_correct(fault):
    """No drive is sampled here (`on_disk_sample` 0): a wrong parity byte
    shows when a degraded GET rebuilds from it, a wrong rebuilt byte in
    the body, as `wrong_answers` or as a PUT that does not read back."""
    result = run.run_cell(CELL, 2**31 + 36, 3.0, False, rehearsal=True,
                          launcher=FAULTY,
                          extra_env={"BENCHMARK_FAULT": fault})
    assert not result["correct"]
    compared = result["compared"]
    assert compared["wrong_answers"][0] + compared["readback_mismatch"][0] \
        > 0, compared


def test_configuration_is_the_controls_node_with_warps_mixed_defaults():
    bench = manifest.benchmark()
    cfg = manifest.config(bench, "ec12p4-16d-warp-mixed")
    node = manifest.config(bench, NODE)
    for key in ("drives", "data_shards", "parity_shards", "block_bytes",
                "shard_bytes", "dispatch_blocks", "inline_below_bytes",
                "bitrot", "fsync", "backend", "chips"):
        assert cfg[key] == node[key], key
    assert cfg["objects"] == {"bytes": 10 << 20, "count": 240,
                              "concurrent": 20}
    mix = manifest.traffic(manifest.cell(bench, CELL)["traffic"])
    assert mix["shares"] == {"GET": 45, "STAT": 30, "PUT": 15, "DELETE": 10}
    assert mix["shares"] == manifest.traffic("small-mixed")["shares"]
    assert mix["sizes"] == {"fixed": cfg["objects"]["bytes"]}
    assert mix["clients"] == cfg["objects"]["concurrent"]
    assert mix["clients"] * mix["preload_per_client"] \
        == cfg["objects"]["count"]
    control = manifest.traffic(
        manifest.cell(bench, "ec12p4-16d-warp.get-degraded")["traffic"])
    assert mix["drives_away"] == control["drives_away"] == [1, 7]
    assert mix["on_disk_sample"] == 0 and mix["keep_bodies"] is False
    # what a PUT is written at with those drives away, by the reference
    write = cfg["degraded_write"]
    assert write["offline"] == len(mix["drives_away"])
    k, m = parity_upgrade.upgraded(
        cfg["drives"], cfg["parity_shards"], write["offline"])
    assert (write["data_shards"], write["parity_shards"]) == (k, m) == (10, 6)
    assert write["shard_bytes"] == -(-cfg["block_bytes"] // k) == 104858
    assert parity_upgrade.write_quorum(k, m) == 10
    assert cfg["drives"] - write["offline"] >= parity_upgrade.write_quorum(
        k, m)


@pytest.mark.parametrize("n,parity,offline,want,quorum", [
    (16, 4, 0, (12, 4), 12), (16, 4, 1, (11, 5), 11), (16, 4, 2, (10, 6), 10),
    (16, 4, 3, (9, 7), 9), (16, 4, 4, (8, 8), 9), (16, 4, 9, (8, 8), 9),
    (16, 2, 2, (12, 4), 12), (12, 4, 1, (7, 5), 7), (4, 2, 1, (2, 2), 3),
])
def test_parity_upgrade_by_hand(n, parity, offline, want, quorum):
    """cmd/erasure-object.go:770-805: one more parity shard for every
    drive away, up to half the set; the write quorum is the data drives,
    one more where data and parity are equal."""
    assert parity_upgrade.upgraded(n, parity, offline) == want
    assert parity_upgrade.write_quorum(*want) == quorum


def test_degraded_put_is_on_disk_what_the_reference_says_at_10_6():
    """Through the real server, drives 1 and 7 away: every object leaves
    14 shard files that `check.check_object` at (10, 6) finds identical
    (`shards_missing` the two drives away, the rest 0), reads back, and
    no root that went comes back."""
    out = degraded_put.run(2**31 + 37, rehearsal=True, before=2, after=1)
    assert out["geometry"] == "10+6"
    assert out["objects_on_disk_compared"] == 3
    assert out["on_disk"] == {"shards_missing": 6, "shard_mismatch": 0,
                              "frame_hash_mismatch": 0, "shard_files": 42}
    assert degraded_put.verdict(out, rehearsal=True, first_put_s=30.0) == []


@pytest.mark.parametrize("name,reader,args,layer,better", [
    ("compile_wait_s_in_window.ops", stage_seconds_in_window,
     {"stage": "compile_wait"}, "device", "lower"),
    ("warming_bytes_per_byte.ops", stage_bytes_per_byte,
     {"stage": "warming", "per": "read"}, "streaming erasure engine",
     "lower"),
    ("encode_ms_per_op", stage_ms_per_op, {"stage": "encode"},
     "streaming erasure engine", "lower"),
    ("write_ms_per_op", stage_ms_per_op, {"stage": "write"}, "object layer",
     "lower"),
    ("hash_ms_per_op", stage_ms_per_op, {"stage": "hash"}, "object layer",
     "lower"),
    ("decode_ms_per_op", stage_ms_per_op, {"stage": "decode"},
     "object layer", "lower"),
    ("pad_ms_per_op", stage_ms_per_op, {"stage": "pad"},
     "streaming erasure engine", "lower"),
    ("mixed_p50_ms", latency_percentile_ms, {"q": 50},
     "HTTP, SigV4, admission", "lower"),
    ("mixed_p95_ms", latency_percentile_ms, {"q": 95},
     "HTTP, SigV4, admission", "lower"),
])
def test_each_new_metric_names_its_reader(name, reader, args, layer, better):
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert spec["moves"] == "ops_per_s" and spec["workloads"] == [CELL]
    assert spec["layer"] == layer and spec["better"] == better
    read, got = manifest.reader(name)
    assert read is reader.read and got == args


def test_the_cell_reports_the_shared_ops_metrics_and_no_roofline():
    bench = manifest.benchmark()
    own = {m["name"] for m in manifest.metrics_of(bench, "per_layer", CELL,
                                                  {"ops_per_s", "setup_s"})}
    assert {"server_cpu_ms_per_op", "device_byte_share.ops",
            "device_idle_pct.ops", "compiles_in_window.ops",
            "compile_s_in_window.ops", "loadgen_busiest_pct.ops",
            "auth_ms_per_op", "admit_ms_per_op", "meta_read_ms_per_op",
            "commit_ms_per_op", "boot_s"} <= own
    # its window encodes and holds two geometries: the reconstruct
    # roofline's reader takes one geometry and counts every dispatch as
    # a reconstruct
    assert not any("roofline" in name for name in own)


@pytest.mark.parametrize("before,after,want", [
    # a window that began ready: the stage is there, and 0
    ({"read": 0, "warming": 0}, {"read": 50 << 20, "warming": 0}, 0.0),
    # five 10 MiB PUTs coded on the host at 10+6 (10 shards of 104,858
    # bytes a block) and two reads of them rebuilt there too
    ({"read": 1 << 20, "warming": 0},
     {"read": 51 << 20, "warming": 7 * 10 * 10 * 104858}, 1.4000053),
    # the parent: no such stage, nothing and no error
    ({"read": 0}, {"read": 50 << 20}, None),
])
def test_warming_bytes_per_body_byte(before, after, want):
    ctx = {"counters": {"before": {"stage_bytes": before},
                        "after": {"stage_bytes": after}}}
    got = stage_bytes_per_byte.read(ctx, "warming", "read")
    assert got == (want if want is None else pytest.approx(want, rel=1e-6))
