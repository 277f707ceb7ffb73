"""`correct` comes out true on the sound program and false with the timed
path broken underneath: each fault a cell can have, once, driven through
the whole of a run but the look for a chip (rehearsal sizes, host codec).

Run with `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; the
runs share `.bench_run/`, so one process at a time."""

import sys

import pytest

from benchmark import run

DEG, SMALL = "ec2p2-4d.get-degraded", "ec8p4-12d.small-mixed"
HEALTHY = "ec2p2-4d.get-healthy"
FAULTY = [sys.executable, "-m", "benchmark.tests.faulty_serve"]


@pytest.mark.parametrize("workload", [DEG, SMALL, HEALTHY])
def test_sound_run_is_correct(workload):
    result = run.run_cell(workload, 2**31 + 11, 2.0, False, rehearsal=True)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0


@pytest.mark.parametrize("workload,fault,caught_by", [
    (HEALTHY, "parity_fewer", "shard_mismatch"),
    (DEG, "parity_byte", "shard_mismatch"),
    (DEG, "rebuilt_byte", "wrong_answers"),
    (DEG, "body_byte", "wrong_answers"),
    (SMALL, "body_byte", "wrong_answers"),
    (HEALTHY, "body_byte", "wrong_answers"),
    (DEG, "get_refused", "failed_requests"),
])
def test_fault_is_not_correct(workload, fault, caught_by):
    result = run.run_cell(workload, 2**31 + 12, 2.0, False, rehearsal=True,
                          launcher=FAULTY,
                          extra_env={"BENCHMARK_FAULT": fault})
    assert not result["correct"]
    assert result["compared"][caught_by][0] > 0, result["compared"]


def test_recorded_trace_loads_as_recorded():
    """The step from `.xplane.pb` to events, on the recorded trace."""
    import json
    import os

    from benchmark import trace

    here = os.path.join(os.path.dirname(__file__), "data")
    with open(os.path.join(here, "recorded_trace.json")) as f:
        want = json.load(f)
    got = trace.load_events(os.path.join(here, "recorded.xplane.pb"))
    assert json.loads(json.dumps(got)) == want


def test_selfcheck_passes():
    from benchmark import selfcheck

    assert selfcheck.main() == 0
