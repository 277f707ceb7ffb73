"""A percentile of all the window's requests' latencies, failures
counted as slower than any (benchmark/run.py `percentile_ms`)."""

from benchmark.stats import percentile_ms


def read(ctx: dict, q: float) -> float | None:
    return percentile_ms(ctx["rows"], q) if ctx["rows"] else None
