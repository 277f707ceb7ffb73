"""Launcher for a traced run: the server's own `main()` in this process,
with `jax.profiler` around the traced window.

Only the process that holds the chip can trace it, and the program has
no profiler hook, so a `--trace 1` run starts this in place of
`python -m minio_tpu.server` (same arguments, same environment).  A
thread watches the data root for two side files the harness writes:
`trace.start` starts the profiler, `trace.stop` stops it and leaves
`trace.done` holding the traced span's length by this process's
clock.  Right after the start the thread writes one host span named
`MARK` into the trace and hands its moment by the machine-wide
monotonic clock back in `trace.started`: with it the reduction finds
the measured window inside the trace.  A `--trace 0` run never loads
this file.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

MARK = "benchmark_clock_mark"


def _watch(control_dir: str) -> None:
    start = os.path.join(control_dir, "trace.start")
    stop = os.path.join(control_dir, "trace.stop")
    while not os.path.exists(start):
        time.sleep(0.02)
    import jax  # the server has long imported it: boot is the CLI's own

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(os.path.join(control_dir, "trace"),
                             profiler_options=options)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(MARK):
        time.sleep(0.002)
    tmp = os.path.join(control_dir, "trace.started.tmp")
    with open(tmp, "w") as f:
        json.dump({"mark_at": t0}, f)
    os.replace(tmp, os.path.join(control_dir, "trace.started"))
    while not os.path.exists(stop):
        time.sleep(0.02)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    tmp = os.path.join(control_dir, "trace.done.tmp")
    with open(tmp, "w") as f:
        json.dump({"window_s": window}, f)
    os.replace(tmp, os.path.join(control_dir, "trace.done"))


def main() -> int:
    from minio_tpu.server.__main__ import main as server_main

    control_dir = os.environ["BENCHMARK_TRACE_DIR"]
    threading.Thread(target=_watch, args=(control_dir,), daemon=True).start()
    return server_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
