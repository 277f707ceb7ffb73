"""The ingest cell on the twelve-drive node: 64 MiB PUTs from eight
streams.  Its configuration is the node of `ec8p4-12d`, its traffic the
put-large row's parameters, each metric it brings names its reader, the
encode roofline's reader gives the hand-worked share and nothing where a
reconstruct could run under the same program name, and its command ends
correct on the CPU rehearsal and not correct with a parity shard wrong
or missing.  CPU only: rehearsal sizes (3 MiB objects), host codec.

Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; the
runs share `.bench_run/`, so one process at a time."""

import sys

import pytest

from benchmark import check, manifest, run
from benchmark.readers import (compiles_in_window, device_byte_share,
                               device_idle_pct, encode_roofline,
                               loadgen_busiest_pct, stage_ms_per_op,
                               stage_rest_ms_per_op, stage_s_per_GiB)

CELL = "ec8p4-12d-ingest.put-large"
FAULTY = [sys.executable, "-m", "benchmark.tests.faulty_serve"]
ZERO = ("failed_requests", "wrong_answers", "readback_mismatch",
        "shards_missing", "shard_mismatch", "frame_hash_mismatch")
HTTP, OBJECT, ENGINE = ("HTTP, SigV4, admission", "object layer",
                        "streaming erasure engine")
# what a PUT's handler thread books, one stage after another
HANDLER = ["admit", "auth", "exec_wait", "loop_wait", "ns_lock", "meta_read",
           "open", "read", "assemble", "h2d", "launch", "fetch", "write_wait",
           "close", "commit"]


def test_traffic_is_the_put_large_row():
    mix = manifest.traffic("put-large")
    assert {key: mix[key] for key in (
        "loop", "clients", "processes", "shares", "sizes",
        "preload_per_client", "keep_bodies", "drives_away",
        "on_disk_sample", "warmup_s", "stagger_s", "timeout_s")} == {
        "loop": "closed", "clients": 8, "processes": 8,
        "shares": {"PUT": 100}, "sizes": {"fixed": 67108864},
        "preload_per_client": 0, "keep_bodies": False, "drives_away": [],
        "on_disk_sample": 3, "warmup_s": 12.0, "stagger_s": 1.5,
        "timeout_s": 120}
    small = manifest.traffic("put-large", rehearsal=True)
    assert small["sizes"]["fixed"] < mix["sizes"]["fixed"]
    assert small["shares"] == mix["shares"]


def test_configuration_is_the_twelve_drive_node():
    bench = manifest.benchmark()
    cfg = manifest.config(bench, "ec8p4-12d-ingest")
    node = manifest.config(bench, "ec8p4-12d")
    for key in ("drives", "data_shards", "parity_shards", "block_bytes",
                "shard_bytes", "dispatch_blocks", "inline_below_bytes",
                "bitrot", "fsync", "backend", "chips", "guarantees",
                "assumed"):
        assert cfg[key] == node[key], key
    cell = manifest.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config"] == "ec8p4-12d-ingest"
    mix = manifest.traffic(cell["traffic"])
    assert mix["sizes"] == {"fixed": cfg["objects"]["bytes"]}
    assert mix["clients"] == cfg["objects"]["concurrent"]
    # every object two full dispatches of the compiled 32 blocks
    assert cfg["objects"]["bytes"] == 2 * cfg["dispatch_blocks"] \
        * cfg["block_bytes"]
    assert cfg["shard_bytes"] * cfg["data_shards"] == cfg["block_bytes"]


def test_the_cell_reports_ops_per_s_and_setup_s():
    bench = manifest.benchmark()
    own = {m["name"] for m in manifest.metrics_of(bench, "end_to_end", CELL)}
    assert own == {"ops_per_s", "setup_s"}
    layer = {m["name"] for m in manifest.metrics_of(bench, "per_layer",
                                                    CELL, own)}
    assert "boot_s" in layer and "rs_encode_roofline" in layer
    assert "rs_reconstruct_roofline" not in layer


def _gib(stage):
    return stage_s_per_GiB, {"stage": stage, "per": "read"}


@pytest.mark.parametrize("name,reader,layer,source,unit,better", [
    ("put_read_s_per_GiB", _gib("read"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("put_body_wait_s_per_GiB", _gib("body_wait"), HTTP, "program_counter",
     "s/GiB", "lower"),
    ("put_etag_s_per_GiB", _gib("etag"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("put_hash_s_per_GiB", _gib("hash"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("put_encode_s_per_GiB", _gib("encode"), ENGINE, "program_counter",
     "s/GiB", "lower"),
    ("put_write_s_per_GiB", _gib("write"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("put_write_wait_s_per_GiB", _gib("write_wait"), ENGINE,
     "program_counter", "s/GiB", "lower"),
    ("put_commit_s_per_GiB", _gib("commit"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("put_open_s_per_GiB", _gib("open"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("put_close_s_per_GiB", _gib("close"), OBJECT, "program_counter",
     "s/GiB", "lower"),
    ("device_byte_share.put", (device_byte_share, {}), ENGINE,
     "program_counter", "%", "higher"),
    ("device_idle_pct.put", (device_idle_pct, {}), "device", "device_trace",
     "%", "lower"),
    ("compiles_in_window.put", (compiles_in_window, {}), "device",
     "program_span", "count", "lower"),
    ("loadgen_busiest_pct.put", (loadgen_busiest_pct, {}), "load generator",
     "host_clock", "%", "lower"),
    ("put_request_ms_per_op", (stage_ms_per_op, {"stage": "request"}), HTTP,
     "program_counter", "ms/op", "lower"),
    ("put_unstaged_ms_per_op",
     (stage_rest_ms_per_op, {"of": "request", "minus": HANDLER}), HTTP,
     "program_counter", "ms/op", "lower"),
    ("rs_encode_roofline",
     (encode_roofline, {"program": "jit__coding_call_bytes"}),
     "device codec", "device_trace", "%", "higher"),
])
def test_each_new_metric_names_its_reader_and_its_cell(
        name, reader, layer, source, unit, better):
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert spec["workloads"] == [CELL] and spec["moves"] == "ops_per_s"
    assert spec["layer"] == layer and spec["source"] == source
    assert spec["unit"] == unit and spec["better"] == better
    read, args = manifest.reader(name)
    assert read is reader[0].read and args == reader[1]


def _roofline_ctx(mix=None, seconds=3.64e-3, count=8, dispatches=8):
    """A window of `dispatches` encode dispatches of 32 blocks at 8+4
    (32 MiB in each), the program's `count` executions `seconds` each."""
    per = 32 * 8 * 131072
    return {
        "config": {"data_shards": 8, "parity_shards": 4},
        "mix": mix or {"drives_away": [], "shares": {"PUT": 100}},
        "device": {"kind": "TPU v5 lite"},
        "counters": {
            "before": {"bytes": {"device": 1 << 30},
                       "dispatches": {"device": 100}},
            "after": {"bytes": {"device": (1 << 30) + dispatches * per},
                      "dispatches": {"device": 100 + dispatches}}},
        "trace": {"programs": {"jit__coding_call_bytes": {
            "count": count, "seconds": count * seconds}}},
    }


def test_encode_roofline_by_hand():
    # 32 MiB in and 16 MiB of parity out: 50,331,648 bytes at 819 GB/s
    # is 61.45 us, of 3.64 ms; 128 x 4 x 32 MiB operations at 393 TOP/s
    # is 43.7 us, so the bytes bound it
    ctx = _roofline_ctx()
    got = encode_roofline.read(ctx, "jit__coding_call_bytes")
    assert got == pytest.approx(100 * 50331648 / 819e9 / 3.64e-3, rel=1e-9)
    assert got == pytest.approx(1.6883, abs=1e-4)
    assert ctx["notes"]["encode_bound"] == "memory"


@pytest.mark.parametrize("mix", [
    {"drives_away": [1, 7], "shares": {"PUT": 100}},
    {"drives_away": [], "shares": {"GET": 45, "STAT": 30, "PUT": 15,
                                   "DELETE": 10}},
    {"drives_away": [], "shares": {"GET": 100}},
])
def test_encode_roofline_reads_nothing_where_a_reconstruct_could_run(mix):
    assert encode_roofline.read(_roofline_ctx(mix),
                                "jit__coding_call_bytes") is None


def test_encode_roofline_reads_nothing_without_the_program():
    no_trace = _roofline_ctx()
    del no_trace["trace"]
    assert encode_roofline.read(no_trace, "jit__coding_call_bytes") is None
    assert encode_roofline.read(_roofline_ctx(dispatches=0),
                                "jit__coding_call_bytes") is None
    assert encode_roofline.read(_roofline_ctx(), "jit_other") is None


def test_rehearsal_is_correct(monkeypatch):
    """Every compared number 0, every acknowledged PUT read back, three
    objects compared on disk."""
    sampled = []
    sample = check.check_sample

    def check_sample(*args):
        sampled.append(sample(*args))
        return sampled[-1]

    monkeypatch.setattr(check, "check_sample", check_sample)
    result = run.run_cell(CELL, 2**31 + 44, 2.0, False, rehearsal=True)
    assert result["correct"] and result["failed"] == 0, result["compared"]
    assert {name: result["compared"][name] for name in ZERO} \
        == {name: [0, 0] for name in ZERO}
    ((counts),) = sampled
    assert counts["objects_on_disk_compared"] == 3
    # every PUT of the run, warm-up and after the window too, read back
    assert result["compared"]["answers_compared"][0] \
        == result["info"]["requests"] + 3
    assert set(result["metrics"]) == {"ops_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["parity_fewer", "parity_byte"])
def test_parity_fault_is_not_correct(fault):
    result = run.run_cell(CELL, 2**31 + 45, 2.0, False, rehearsal=True,
                          launcher=FAULTY,
                          extra_env={"BENCHMARK_FAULT": fault})
    assert not result["correct"]
    assert result["compared"]["shard_mismatch"][0] > 0, result["compared"]
