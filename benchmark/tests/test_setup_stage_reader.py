"""The reader of a preload's stages: the first scrape alone, on contexts
made by hand (CPU only, no server)."""

import pytest

from benchmark import manifest
from benchmark.readers import setup_stage_s_per_GiB

GIB = 1 << 30


def ctx_of(seconds: dict, nbytes: dict) -> dict:
    """The window's first scrape as given; the second holds more of
    everything, which the reader must not look at."""
    after = {"stage_seconds": {s: v + 100.0 for s, v in seconds.items()},
             "stage_bytes": {s: v + GIB for s, v in nbytes.items()}}
    return {"counters": {
        "before": {"stage_seconds": seconds, "stage_bytes": nbytes},
        "after": after}}


@pytest.mark.parametrize("seconds,nbytes,stage,want", [
    # 1 GiB preloaded, 30 thread-seconds waiting for its bodies
    ({"read": 30.0}, {"read": GIB}, "read", 30.0),
    # the pipe's own work, per GiB of body the object layer took
    ({"read": 30.0, "body_copy": 0.5}, {"read": 2 * GIB, "body_copy": 2 * GIB},
     "body_copy", 0.25),
    # a stage that worked no second yet reads 0, not nothing
    ({"read": 30.0, "body_copy": 0.0}, {"read": GIB}, "body_copy", 0.0),
    # a program without the stage (the parent of the PR that brings it)
    ({"read": 30.0}, {"read": GIB}, "body_copy", None),
    # a cell that preloads nothing
    ({"read": 0.0, "body_copy": 0.0}, {"read": 0.0}, "read", None),
    ({"read": 0.0}, {}, "read", None),
])
def test_setup_stage_s_per_GiB(seconds, nbytes, stage, want):
    got = setup_stage_s_per_GiB.read(ctx_of(seconds, nbytes), stage, "read")
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name,stage", [
    ("preload_read_s_per_GiB", "read"),
    ("preload_body_copy_s_per_GiB", "body_copy")])
def test_preload_metric_names_its_reader(name, stage):
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert spec["moves"] == "setup_s"
    assert spec["workloads"] == ["ec2p2-4d.get-degraded",
                                 "ec2p2-4d.get-healthy"]
    read, args = manifest.reader(name)
    assert read is setup_stage_s_per_GiB.read
    assert args == {"stage": stage, "per": "read"}
