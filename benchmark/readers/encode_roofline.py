"""The encode kernel's share (%) of its roofline: the least time the chip
could take for one dispatch's work over the device time the whole
dispatch program took, relayout and all (benchmark/trace.py `programs`,
by the program's stable name).

Work per dispatch: codec bytes per dispatch from the program's counters
over the window, rows made the configuration's m.  Encode and
reconstruct run under one program name, so the share is read only in a
window that can hold no reconstruct: no drive away and no GET in the
mix; anywhere else, nothing.
"""

from benchmark import roofline


def read(ctx: dict, program: str) -> float | None:
    mix = ctx["mix"]
    if mix["drives_away"] or mix["shares"].get("GET", 0):
        return None
    trace = ctx.get("trace")
    if not trace or program not in trace["programs"]:
        return None
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    dispatches = after["dispatches"]["device"] - before["dispatches"]["device"]
    coded = after["bytes"]["device"] - before["bytes"]["device"]
    prog = trace["programs"][program]
    if not dispatches or not prog["count"] or not prog["seconds"]:
        return None
    cfg = ctx["config"]
    ops, hbm = roofline.rs_work(cfg["data_shards"], cfg["parity_shards"],
                                coded / dispatches)
    pct, bound = roofline.roofline_pct(
        ctx["device"]["kind"], ops, hbm, prog["seconds"] / prog["count"])
    ctx.setdefault("notes", {})["encode_bound"] = bound
    return pct
