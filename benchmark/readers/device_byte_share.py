"""Share (%) of the window's codec bytes that the device coded."""


def read(ctx: dict) -> float | None:
    before, after = (ctx["counters"][w]["bytes"] for w in ("before", "after"))
    device = after["device"] - before["device"]
    host = after["host"] - before["host"]
    if device + host == 0:
        return None
    return 100.0 * device / (device + host)
