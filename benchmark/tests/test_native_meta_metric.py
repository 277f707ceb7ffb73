"""`native_meta_bytes_per_byte.get`: the bytes of the `xl.meta` documents
that a drive read in one native call (stage `meta_native`) over the bytes
of the documents the quorum reads' answers carried (stage `meta_read`),
between the window's two scrapes.  About 1.0 where every local drive took
the call; nothing from a program without the stage.

Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`."""

import pytest

from benchmark import manifest
from benchmark.readers import stage_bytes_per_byte

NAME = "native_meta_bytes_per_byte.get"


def test_names_its_reader_and_every_get_cell():
    bench = manifest.benchmark()
    (spec,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert spec["moves"] == "get_MiBps" and spec["better"] == "higher"
    assert spec["layer"] == "object layer"
    assert spec["source"] == "program_counter" and spec["unit"] == "B/B"
    reporting = {w["name"] for w in bench["workloads"]
                 if any(m["name"] == "get_MiBps" for m in manifest.metrics_of(
                     bench, "end_to_end", w["name"]))}
    # containment: a later get cell only appends itself
    assert len(reporting) >= 6 and reporting <= set(spec["workloads"])
    read, args = manifest.reader(NAME)
    assert read is stage_bytes_per_byte.read
    assert args == {"stage": "meta_native", "per": "meta_read"}


@pytest.mark.parametrize("before,after,want", [
    # 14 of 16 drives answered a fan-out, each a 297-byte document read
    # in one native call: 1000 fan-outs
    ({"meta_read": 0, "meta_native": 0},
     {"meta_read": 1000 * 14 * 297, "meta_native": 1000 * 14 * 297}, 1.0),
    # a straggler abandoned at quorum: read, booked, not among the answers
    ({"meta_read": 0, "meta_native": 0},
     {"meta_read": 13 * 297, "meta_native": 14 * 297}, 14 / 13),
    # a process without the library: the stage books nothing
    ({"meta_read": 0, "meta_native": 0},
     {"meta_read": 14 * 297, "meta_native": 0}, 0.0),
    # the parent: no `meta_native` stage and no bytes on `meta_read`
    ({"meta_read": 0}, {"meta_read": 0}, None),
])
def test_per_document_byte(before, after, want):
    ctx = {"counters": {"before": {"stage_bytes": before},
                        "after": {"stage_bytes": after}}}
    got = stage_bytes_per_byte.read(ctx, "meta_native", "meta_read")
    assert got == (want if want is None else pytest.approx(want, rel=1e-9))
