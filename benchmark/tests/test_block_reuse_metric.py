"""`block_reuse_bytes_per_byte.get` (PR 34): the bytes of a GET's
response blocks that came from the program's arena pool, their pages
there already, per byte served; on contexts made by hand (CPU only, no
server).  A data file on the reader `stage_bytes_per_byte`."""

import pytest

from benchmark import manifest
from benchmark.readers import stage_bytes_per_byte

MIB = 1 << 20
GET_CELLS = ["ec2p2-4d.get-degraded", "ec2p2-4d.get-healthy",
             "ec12p4-16d.get-degraded", "ec8p4-12d.get-degraded",
             "ec12p4-16d-warp.get-degraded"]


def test_block_reuse_metric_names_its_reader():
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"]
               if m["name"] == "block_reuse_bytes_per_byte.get"]
    assert spec["moves"] == "get_MiBps" and spec["better"] == "higher"
    assert spec["layer"] == "streaming erasure engine"
    assert spec["source"] == "program_counter" and spec["unit"] == "B/B"
    assert spec["workloads"] == GET_CELLS
    read, args = manifest.reader(spec["name"])
    assert read is stage_bytes_per_byte.read
    assert args == {"stage": "block_reuse", "per": "respond"}


@pytest.mark.parametrize("before,after,want", [
    # a window of 64 MiB GETs in which every block was a pooled one
    ({"respond": 8 * MIB, "block_reuse": 0},
     {"respond": 72 * MIB, "block_reuse": 64 * MIB}, 1.0),
    # the pool too small for the streams: every other block was fresh
    ({"respond": 8 * MIB, "block_reuse": 8 * MIB},
     {"respond": 72 * MIB, "block_reuse": 40 * MIB}, 0.5),
    # objects under one block (a tail is a fresh array): there, and 0
    ({"respond": MIB, "block_reuse": 0},
     {"respond": 3 * MIB, "block_reuse": 0}, 0.0),
    # the parent of the PR that brings the stage: nothing, no error
    ({"respond": 8 * MIB, "assemble": 8 * MIB},
     {"respond": 72 * MIB, "assemble": 72 * MIB}, None),
    # a window that served nothing
    ({"respond": 8 * MIB, "block_reuse": 8 * MIB},
     {"respond": 8 * MIB, "block_reuse": 8 * MIB}, None),
])
def test_block_reuse_per_served_byte(before, after, want):
    ctx = {"counters": {"before": {"stage_bytes": before},
                        "after": {"stage_bytes": after}}}
    got = stage_bytes_per_byte.read(ctx, "block_reuse", "respond")
    assert got == (want if want is None else pytest.approx(want))
