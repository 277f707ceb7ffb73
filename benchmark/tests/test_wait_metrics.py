"""ISSUE 37's additions: the reader of what a parent stage's listed
stages leave unnamed, on two scrapes made by hand, and the 26 metrics
over the program's new rows, each with its reader, its arguments, its
layer and exactly the cells it is read in (CPU only, no server)."""

import pytest

from benchmark import manifest
from benchmark.readers import (stage_ms_per_op, stage_rest_ms_per_op,
                               stage_s_per_GiB, stage_seconds_in_window)
from benchmark.tests.test_stage_readers import ctx_of

SMALL = "ec8p4-12d.small-mixed"
MIXED = "ec12p4-16d-warp-mixed.mixed-degraded"
GETS = ["ec2p2-4d.get-degraded", "ec2p2-4d.get-healthy",
        "ec12p4-16d.get-degraded", "ec8p4-12d.get-degraded",
        "ec12p4-16d-warp.get-degraded"]
HTTP, OBJECT, ENGINE = ("HTTP, SigV4, admission", "object layer",
                        "streaming erasure engine")
UNSTAGED = {"of": "request",
            "minus": ["admit", "auth", "exec_wait", "loop_wait",
                      "meta_read", "commit", "ns_lock"]}


def test_stage_rest_ms_per_op():
    # three acknowledged operations between the scrapes
    ctx = ctx_of({"request": 10.0, "auth": 1.0, "meta_read": 2.0},
                 {"request": 13.0, "auth": 1.3, "meta_read": 3.2,
                  "ns_lock": 0.6})
    # 3.0 s of request less 0.3 + 1.2 + 0.6 (a stage the first scrape
    # did not have yet counts from 0), over three operations
    assert stage_rest_ms_per_op.read(
        ctx, "request", ["auth", "meta_read", "ns_lock"]) \
        == pytest.approx(300.0)
    # a stage of `minus` that the program does not export counts as 0
    assert stage_rest_ms_per_op.read(
        ctx, "request", ["auth", "exec_wait"]) == pytest.approx(900.0)
    assert stage_rest_ms_per_op.read(ctx, "request", []) \
        == pytest.approx(1000.0)


def test_stage_rest_ms_per_op_finds_nothing_to_read():
    """The parent tree exports no `request`: nothing, and no error; so
    with a window that acknowledged no operation."""
    ctx = ctx_of({"auth": 1.0}, {"auth": 2.0})
    assert stage_rest_ms_per_op.read(ctx, **UNSTAGED) is None
    none_done = ctx_of({"request": 1.0}, {"request": 2.0},
                       acknowledged=(False,))
    assert stage_rest_ms_per_op.read(none_done, **UNSTAGED) is None


def _ms(stage):
    return stage_ms_per_op, {"stage": stage}


def _gib(stage):
    return stage_s_per_GiB, {"stage": stage, "per": "respond"}


def _window(stage):
    return stage_seconds_in_window, {"stage": stage}


@pytest.mark.parametrize("name,reader,layer,moves,cells", [
    ("request_ms_per_op", _ms("request"), HTTP, "ops_per_s", [SMALL, MIXED]),
    ("exec_wait_ms_per_op", _ms("exec_wait"), HTTP, "ops_per_s",
     [SMALL, MIXED]),
    ("loop_wait_ms_per_op", _ms("loop_wait"), HTTP, "ops_per_s",
     [SMALL, MIXED]),
    ("pool_wait_ms_per_op", _ms("pool_wait"), ENGINE, "ops_per_s",
     [SMALL, MIXED]),
    ("ns_lock_ms_per_op", _ms("ns_lock"), OBJECT, "ops_per_s",
     [SMALL, MIXED]),
    ("meta_read_cpu_ms_per_op", _ms("meta_read_cpu"), OBJECT, "ops_per_s",
     [SMALL, MIXED]),
    ("commit_cpu_ms_per_op", _ms("commit_cpu"), OBJECT, "ops_per_s",
     [SMALL, MIXED]),
    # inline objects alone: one thread after another, so the remainder
    # is a remainder; a 10 MiB body's pipeline overlaps
    ("unstaged_ms_per_op", (stage_rest_ms_per_op, UNSTAGED), HTTP,
     "ops_per_s", [SMALL]),
    ("read_ms_per_op", _ms("read"), OBJECT, "ops_per_s", [MIXED]),
    ("body_wait_ms_per_op", _ms("body_wait"), HTTP, "ops_per_s", [MIXED]),
    ("etag_ms_per_op", _ms("etag"), OBJECT, "ops_per_s", [MIXED]),
    ("write_wait_ms_per_op", _ms("write_wait"), ENGINE, "ops_per_s",
     [MIXED]),
    ("write_cpu_ms_per_op", _ms("write_cpu"), OBJECT, "ops_per_s", [MIXED]),
    ("loop_cpu_s_in_window.ops", _window("loop_cpu"), HTTP, "ops_per_s",
     [SMALL, MIXED]),
    ("loop_cpu_s_in_window.get", _window("loop_cpu"), HTTP, "get_MiBps",
     GETS),
    ("get_request_ms_per_op", _ms("request"), HTTP, "get_MiBps", GETS),
    ("get_exec_wait_ms_per_op", _ms("exec_wait"), HTTP, "get_MiBps", GETS),
    ("get_loop_wait_ms_per_op", _ms("loop_wait"), HTTP, "get_MiBps", GETS),
    ("get_ns_lock_ms_per_op", _ms("ns_lock"), OBJECT, "get_MiBps", GETS),
    ("get_open_ms_per_op", _ms("open"), OBJECT, "get_MiBps", GETS),
    ("get_pool_wait_s_per_GiB", _gib("pool_wait"), ENGINE, "get_MiBps",
     GETS),
    ("get_decode_cpu_s_per_GiB", _gib("decode_cpu"), OBJECT, "get_MiBps",
     GETS),
    ("get_shard_read_cpu_s_per_GiB", _gib("shard_read_cpu"), OBJECT,
     "get_MiBps", GETS),
    ("get_verify_cpu_s_per_GiB", _gib("verify_cpu"), OBJECT, "get_MiBps",
     GETS),
    ("get_assemble_cpu_s_per_GiB", _gib("assemble_cpu"), ENGINE,
     "get_MiBps", GETS),
    ("get_send_cpu_s_per_GiB", _gib("send_cpu"), HTTP, "get_MiBps", GETS),
])
def test_each_metric_names_its_reader_and_its_cells(name, reader, layer,
                                                    moves, cells):
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert spec["workloads"] == cells
    assert spec["layer"] == layer and spec["moves"] == moves
    assert spec["better"] == "lower"
    assert spec["source"] == "program_counter"
    assert spec["unit"] == ("s" if name.startswith("loop_cpu") else
                            "s/GiB" if name.endswith("_s_per_GiB")
                            else "ms/op")
    read, args = manifest.reader(name)
    assert read is reader[0].read and args == reader[1]


def test_the_metrics_read_rows_the_program_exports():
    """Every stage a new metric names is a row of the seconds family
    (the scrape reads that family and the bytes' alone)."""
    from minio_tpu.erasure import stagestats

    rows = set(stagestats.seconds_rows()) | {"loop_cpu"}
    bench = manifest.benchmark()
    seen = 0
    for m in bench["per_layer"]:
        args = manifest.reader(m["name"])[1]
        named = [args[k] for k in ("stage", "of") if k in args] \
            + list(args.get("minus", ()))
        if m["source"] == "program_counter" and named:
            assert set(named) <= rows, m["name"]
            seen += 1
    assert seen >= 26 + 20
