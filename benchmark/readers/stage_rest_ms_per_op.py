"""Thread-seconds of the stage `of` between the window's two scrapes
less those of the stages `minus`, in milliseconds per operation
acknowledged between them (the operations `stage_ms_per_op` counts): what
of `of` the listed stages do not name.  `of` is a stage that encloses
the others on one thread after another (`request`, the handler's whole
time), so the remainder is a remainder; where stages overlap on several
threads it may fall below zero, and is reported as it is.  A program
that does not export `of` gives nothing; a stage of `minus` that it does
not export counts as 0.
"""

from benchmark.readers import stage_ms_per_op


def read(ctx: dict, of: str, minus: list[str]) -> float | None:
    whole = stage_ms_per_op.read(ctx, of)
    if whole is None:
        return None
    return whole - sum(stage_ms_per_op.read(ctx, stage) or 0.0
                       for stage in minus)
