"""Thread-seconds a data-plane stage worked between the window's two
scrapes, in milliseconds per operation acknowledged between them (the
operations `server_cpu_ms_per_op` counts), from the program's own
counter `minio_dataplane_stage_seconds_total{stage}`.  Seconds sum over
threads: work done, not wall time.  A program that does not export the
stage gives nothing.
"""


from benchmark.readers import stage_seconds_in_window


def read(ctx: dict, stage: str) -> float | None:
    seconds = stage_seconds_in_window.read(ctx, stage)
    done = sum(1 for row in ctx["scraped"] if row[6])
    if seconds is None or not done:
        return None
    return 1000.0 * seconds / done
