"""Of the seconds in the window's longest idle gaps of the device
(`idle_gaps` of benchmark/trace.py's reduction: each gap beside the host
span that covers most of it), the share whose gap bears a span's name
and not `unattributed`: how much of the chip's idle time the trace can
put down to something the host was doing.  Nothing where the trace holds
no gap (a CPU rehearsal has no device plane).
"""


def read(ctx: dict) -> float | None:
    gaps = (ctx.get("trace") or {}).get("idle_gaps") or []
    total = sum(seconds for _, seconds in gaps)
    if not total:
        return None
    named = sum(seconds for name, seconds in gaps if name != "unattributed")
    return 100.0 * named / total
