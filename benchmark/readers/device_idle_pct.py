"""Share (%) of the measured window's traced part in which no operation
ran on the device: of the window alone, not of the set-up and warm-up
that the trace also holds."""


def read(ctx: dict) -> float | None:
    trace = ctx.get("trace")
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
