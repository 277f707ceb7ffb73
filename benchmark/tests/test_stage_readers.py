"""The readers of the stage counters and of the idle gaps' names, each on
a context made by hand (CPU only, no server)."""

import pytest

from benchmark import manifest
from benchmark.readers import (idle_gaps_named_pct, stage_ms_per_op,
                               stage_seconds_in_window)


def ctx_of(before: dict, after: dict, acknowledged=(True, True, False, True)):
    """Two scrapes' stage seconds and four requests between them, one
    of them failed."""
    row = (0, "GET", "k", 0.0, 1.0, 10)
    return {"counters": {"before": {"stage_seconds": before},
                         "after": {"stage_seconds": after}},
            "scraped": [row + (ok, True, 1) for ok in acknowledged]}


def test_stage_ms_per_op():
    ctx = ctx_of({"auth": 1.0}, {"auth": 1.75, "commit": 0.3})
    assert stage_ms_per_op.read(ctx, "auth") == pytest.approx(250.0)
    # a stage the first scrape did not have yet counts from 0
    assert stage_ms_per_op.read(ctx, "commit") == pytest.approx(100.0)
    # a program without the stage, or a window without an acknowledged
    # operation, gives nothing and does not raise
    assert stage_ms_per_op.read(ctx, "admit") is None
    none_done = ctx_of({"auth": 1.0}, {"auth": 2.0}, acknowledged=(False,))
    assert stage_ms_per_op.read(none_done, "auth") is None


def test_stage_seconds_in_window():
    ctx = ctx_of({"compile": 40.5}, {"compile": 40.5, "read": 3.0})
    assert stage_seconds_in_window.read(ctx, "compile") == 0.0
    assert stage_seconds_in_window.read(ctx, "read") == 3.0
    assert stage_seconds_in_window.read(ctx, "fetch") is None


@pytest.mark.parametrize("gaps,want", [
    ([["dp.fetch", 0.2], ["unattributed", 0.1], ["drive.read_version", 0.1]],
     75.0),
    ([["unattributed", 10.0]], 0.0),
    ([["shard_args", 0.15]] + [["unattributed", 0.15]] * 9, 10.0),
    ([], None),
])
def test_idle_gaps_named_pct(gaps, want):
    got = idle_gaps_named_pct.read({"trace": {"idle_gaps": gaps}})
    assert got == (want if want is None else pytest.approx(want))


def test_idle_gaps_named_pct_without_a_trace():
    assert idle_gaps_named_pct.read({}) is None
    assert idle_gaps_named_pct.read({"trace": {}}) is None


def test_every_new_metric_names_its_reader():
    bench = manifest.benchmark()
    names = {m["name"] for m in bench["per_layer"]}
    for name, reader in [("auth_ms_per_op", stage_ms_per_op),
                         ("compile_s_in_window.get", stage_seconds_in_window),
                         ("idle_gaps_named_pct.get", idle_gaps_named_pct)]:
        assert name in names
        assert manifest.reader(name)[0] is reader.read
