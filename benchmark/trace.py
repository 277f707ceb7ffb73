"""The reduction from a profiler trace (`.xplane.pb`) to numbers.

Run as a child (`python -m benchmark.trace <trace dir> [from to]`) once
the server has stopped and the chip is free: reading a trace needs
`import jax` (for `jax.profiler.ProfileData`), which the harness's own
process never does.  Pinned to the CPU platform, it touches no device.

A traced run's trace begins in set-up and holds the warm-up; `from` and
`to`, seconds after the launcher's mark (benchmark/serve.py `MARK`), cut
the measured window out of it.  Every number below is of that window
alone; `traced_busy_s` beside them is `busy_s` of the whole trace.

What it gives, per device plane and averaged over the devices:
- `busy_s`: the union of the intervals in which an operation ran on the
  device (line "XLA Ops"; "XLA Modules" where a trace has no such line);
- `programs`: for every XLA module (one jitted program, by its stable
  name without the run id), executions and total device seconds, span
  of the module's event: everything the program runs between its inputs
  arriving and its outputs leaving, relayout copies and fusions with the
  custom call, not the custom call alone;
- `device_ops`: operations by total seconds, largest first;
- `idle_gaps`: the longest gaps between busy intervals (and, of a cut
  window, before the first and after the last), each named by the
  host-side span that covers most of it, or `unattributed`;
- `compile_events`: host spans of a compilation (none expected inside a
  window).
`selfcheck` runs `reduce_events` on a small recorded trace kept beside
this file (tests/data) against hand-worked values.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
COMPILE_SPAN = re.compile(r"(?i)(^|[^a-z])(xla|tpu|backend|pjrt)?_?compile")
RUN_ID = re.compile(r"\(\d+\)$")


def load_events(path: str) -> dict:
    """`.xplane.pb` -> {"devices": {plane: {line: [[name, start_s,
    dur_s], ...]}}, "host": [[name, start_s, dur_s], ...]}."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = out["devices"].setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                    for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out["host"] += [
                    [e.name, e.start_ns / 1e9, e.duration_ns / 1e9]
                    for e in line.events if e.duration_ns > 0]
    return out


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _plain(name: str) -> str:
    """An operation's name without its operands: `%copy.7 = u32[..]
    copy(...)` and `copy.7` both give a short stable label."""
    name = name.split(" = ")[0].strip().lstrip("%")
    return name[:80]


def cut(events: dict, after_mark: tuple[float, float]) -> tuple[dict, tuple]:
    """The events that lie wholly inside the window, which is given in
    seconds after the launcher's mark -> (events, window in trace time)."""
    from benchmark.serve import MARK

    marks = [s for name, s, _ in events["host"] if name == MARK]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} host spans named {MARK}")
    a, b = (marks[0] + t for t in after_mark)

    def inside(evs: list) -> list:
        return [e for e in evs if a <= e[1] and e[1] + e[2] <= b]

    return {"devices": {plane: {ln: inside(evs) for ln, evs in lines.items()}
                        for plane, lines in events["devices"].items()},
            "host": inside(events["host"])}, (a, b)


def reduce_events(events: dict, top: int = 10,
                  window: tuple[float, float] | None = None) -> dict:
    devices = events["devices"]
    n = len(devices)
    out: dict = {"devices": n, "busy_s": 0.0, "programs": {},
                 "device_ops": [], "idle_gaps": [], "compile_events": 0,
                 "lines": sorted({ln for d in devices.values() for ln in d})}
    if not n:
        return out
    ops: dict[str, float] = {}
    gaps: list[tuple[float, float]] = []
    for lines in devices.values():
        op_events = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy = _union([(s, s + d) for _, s, d in op_events if d > 0])
        out["busy_s"] += sum(b - a for a, b in busy) / n
        edges = [window[0]] + [t for ab in busy for t in ab] + [window[1]] \
            if window else [t for ab in busy for t in ab][1:-1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, _, d in op_events:
            ops[_plain(name)] = ops.get(_plain(name), 0.0) + d / n
        for name, _, d in lines.get("XLA Modules", []):
            prog = out["programs"].setdefault(
                RUN_ID.sub("", name), {"count": 0, "seconds": 0.0})
            prog["count"] += 1
            prog["seconds"] += d
    out["device_ops"] = [[k, v] for k, v in sorted(
        ops.items(), key=lambda kv: -kv[1])[:top]]
    host = events["host"]
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, cover = "unattributed", 0.5 * (b - a)
        for name, s, d in host:
            overlap = min(b, s + d) - max(a, s)
            if overlap > cover:
                best, cover = _plain(name), overlap
        out["idle_gaps"].append([best, b - a])
    out["compile_events"] = sum(
        1 for name, _, _ in host if COMPILE_SPAN.search(name))
    return out


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def main(argv: list[str]) -> int:
    path = argv[0] if argv[0].endswith(".pb") else find_xplane(argv[0])
    if path is None:
        print(json.dumps({"error": f"no .xplane.pb under {argv[0]}"}))
        return 1
    events = load_events(path)
    whole = reduce_events(events)
    if len(argv) == 3:
        inside, window = cut(events, (float(argv[1]), float(argv[2])))
        out = reduce_events(inside, window=window)
        out["window_s"] = window[1] - window[0]
    else:
        out = whole
    out["traced_busy_s"] = whole["busy_s"]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
