"""The reader of a stage's bytes per byte served, on contexts made by
hand (CPU only, no server)."""

import pytest

from benchmark import manifest
from benchmark.readers import stage_bytes_per_byte, stage_s_per_GiB

MIB = 1 << 20
NEW_CELLS = ["ec12p4-16d.get-degraded", "ec8p4-12d.get-degraded"]


def ctx_of(before: dict, after: dict) -> dict:
    return {"counters": {"before": {"stage_bytes": before},
                         "after": {"stage_bytes": after}}}


@pytest.mark.parametrize("before,after,want", [
    # EC 12+4, 32 blocks served, one row of each rebuilt: the widened
    # batch, the row cut back and the blocks' fill dropped, per byte
    ({"respond": 5 * MIB, "pad": 7},
     {"respond": 37 * MIB,
      "pad": 7 + 32 * (12 * 90112 + 87382 + MIB)}, 2.114585876),
    # a stage the first scrape did not have yet counts from 0
    ({"respond": 0}, {"respond": 4 * MIB, "pad": 2 * MIB}, 0.5),
    # tile-aligned shards (EC 8+4): the stage is there and met nothing
    ({"respond": MIB, "pad": 0}, {"respond": 9 * MIB, "pad": 0}, 0.0),
    # a program without the stage (the parent of the PR that brings it)
    ({"respond": MIB}, {"respond": 9 * MIB}, None),
    # a window that served nothing
    ({"respond": MIB, "pad": 1}, {"respond": MIB, "pad": 1}, None),
    ({"pad": 1}, {"pad": 9}, None),
])
def test_stage_bytes_per_byte(before, after, want):
    got = stage_bytes_per_byte.read(ctx_of(before, after), "pad", "respond")
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("name,reader", [
    ("pad_bytes_per_byte.get", stage_bytes_per_byte),
    ("get_pad_s_per_GiB", stage_s_per_GiB)])
def test_pad_metric_names_its_reader(name, reader):
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert spec["moves"] == "get_MiBps"
    assert spec["layer"] == "streaming erasure engine"
    assert spec["workloads"] == NEW_CELLS
    read, args = manifest.reader(name)
    assert read is reader.read
    assert args == {"stage": "pad", "per": "respond"}
