"""One small reader per kind of per-layer metric.

`benchmark/metrics/<metric>.json` names a reader module here and its
arguments; `read(ctx, **args)` takes the metric from the run's context
(counters before and after the window, the request log, /proc readings,
the trace's reduction) and returns a number, or None where it found
nothing to read: the harness then leaves the metric out of the line.
"""
