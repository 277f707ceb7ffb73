"""Codec bytes the device coded per byte served in the same interval,
both from the program's counters (`respond` stage bytes are the bytes
handed to the HTTP front)."""


def read(ctx: dict) -> float | None:
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    served = (after["stage_bytes"].get("respond", 0.0)
              - before["stage_bytes"].get("respond", 0.0))
    if not served:
        return None
    return (after["bytes"]["device"] - before["bytes"]["device"]) / served
