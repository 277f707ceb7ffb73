"""The two metrics of a degraded read's host copies (PR 29), on contexts
made by hand (CPU only, no server): `assemble_bytes_per_byte.get`, the
bytes the stream's thread copied per byte served, and
`staged_bytes_per_byte.get`, the bytes a drive's read put straight into
a dispatch's arena per byte served.  Both are data files on the reader
`stage_bytes_per_byte`."""

import pytest

from benchmark import manifest
from benchmark.readers import stage_bytes_per_byte

MIB = 1 << 20
GET_CELLS = ["ec2p2-4d.get-degraded", "ec2p2-4d.get-healthy",
             "ec12p4-16d.get-degraded", "ec8p4-12d.get-degraded"]
# a window that served 64 blocks of EC 8+4 after a warm-up of 8
SERVED = 64 * MIB
BEFORE = {"respond": 8 * MIB, "assemble": 16 * MIB, "staged": 8 * MIB}


def ctx_of(before: dict, after: dict) -> dict:
    return {"counters": {"before": {"stage_bytes": before},
                         "after": {"stage_bytes": after}}}


def after(**moved: float) -> dict:
    out = {"respond": BEFORE["respond"] + SERVED}
    for stage, per_byte in moved.items():
        out[stage] = BEFORE[stage] + per_byte * SERVED
    return out


@pytest.mark.parametrize("name,stage,better,contexts", [
    ("assemble_bytes_per_byte.get", "assemble", "lower", [
        # the parent, every group degraded: the survivors' data shards
        # to the block, all k survivors stacked, the rebuilt rows placed
        (BEFORE, after(assemble=2.0), 2.0),
        # the staged read, and any healthy group: each byte copied once
        (BEFORE, after(assemble=1.0, staged=1.0), 1.0),
        # EC 12+4 books twelve shards of 87,382 bytes a block of 1 MiB
        (BEFORE, after(assemble=12 * 87382 / MIB), 1.0000076),
        # the parent has the stage: it reads there too
        ({"respond": 0}, {"respond": SERVED, "assemble": 2 * SERVED}, 2.0),
    ]),
    ("staged_bytes_per_byte.get", "staged", "higher", [
        (BEFORE, after(assemble=1.0, staged=1.0), 1.0),
        # EC 12+4: twelve shards of 87,382 bytes a block of 1 MiB
        (BEFORE, after(staged=12 * 87382 / MIB), 1.0000076),
        # a healthy cell: the stage is there and met nothing
        (BEFORE, after(assemble=1.0, staged=0.0), 0.0),
        # a parent without the stage reads nothing, and does not raise
        ({"respond": 8 * MIB, "assemble": 16 * MIB},
         {"respond": 72 * MIB, "assemble": 144 * MIB}, None),
        # a window that served nothing
        (BEFORE, dict(BEFORE), None),
    ]),
])
def test_stage_copy_metric(name, stage, better, contexts):
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert spec["moves"] == "get_MiBps"
    assert spec["layer"] == "streaming erasure engine"
    assert spec["source"] == "program_counter"
    assert spec["unit"] == "B/B" and spec["better"] == better
    assert spec["workloads"] == GET_CELLS
    read, args = manifest.reader(name)
    assert read is stage_bytes_per_byte.read
    assert args == {"stage": stage, "per": "respond"}
    for before, now, want in contexts:
        got = read(ctx_of(before, now), **args)
        assert got == (want if want is None else pytest.approx(want)), \
            (before, now)
