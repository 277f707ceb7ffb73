"""BENCHMARK.json and the files its names lead to.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name: a later PR
adds `configs/<config>.json`, `traffic/<mix>.json`,
`metrics/<metric>.json` (and a reader module where no existing reader
fits) plus entries in BENCHMARK.json, and edits no file that exists.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(CHECKOUT, "BENCHMARK.json"))


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _load(os.path.join(CHECKOUT, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, rehearsal: bool = False) -> dict:
    mix = _load(os.path.join(HERE, "traffic", f"{name}.json"))
    small = mix.pop("rehearsal", {})
    if rehearsal:
        mix.update(small)
    return mix


def metrics_of(bench: dict, group: str, workload: str,
               reported: set[str] | None = None) -> list[dict]:
    """The metrics of `group` that this cell reports: those that list
    it, and those without a `workloads` key (every cell that reports the
    end-to-end metric they move)."""
    out = []
    for m in bench[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or reported is None \
                or m["moves"] in reported:
            out.append(m)
    return out


def reader(metric: str):
    """-> (read function, args) of a per-layer metric."""
    spec = _load(os.path.join(HERE, "metrics", f"{metric}.json"))
    mod = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return mod.read, spec.get("args", {})
