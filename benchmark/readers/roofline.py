"""The reconstruct kernel's share (%) of its roofline: the least time the
chip could take for one dispatch's work over the device time the whole
dispatch program took, relayout and all (benchmark/trace.py
`programs`, by the program's stable name).

Work per dispatch: codec bytes per dispatch from the program's counters
over the window (every dispatch a full batch, so the ratio is exact),
rows made from the layout: the data shards the drives away hold, by the
reference's placement, averaged over the bytes served.
"""

from benchmark import check, roofline


def rows_rebuilt(ctx: dict) -> float:
    cfg, away = ctx["config"], ctx["mix"]["drives_away"]
    k, n = cfg["data_shards"], cfg["drives"]
    lost = total = 0
    for row in ctx["rows"]:
        if row[1] == "GET" and row[6]:
            order = check.hash_order(row[2], n)
            lost += row[5] * sum(order[d - 1] <= k for d in away)
            total += row[5]
    return lost / total if total else 0.0


def read(ctx: dict, program: str) -> float | None:
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
    rows = rows_rebuilt(ctx)
    if not rows:
        return None
    ops, hbm = roofline.rs_work(cfg["data_shards"], rows, coded / dispatches)
    pct, bound = roofline.roofline_pct(
        ctx["device"]["kind"], ops, hbm, prog["seconds"] / prog["count"])
    ctx.setdefault("notes", {})["reconstruct_bound"] = bound
    return pct
