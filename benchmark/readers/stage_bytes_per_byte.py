"""Bytes that passed a data-plane stage per byte of user data that
passed in the same interval, both from the program's own counters:
`minio_dataplane_stage_bytes_total{stage}` over the bytes of the stage
`per` that carries user bytes (`respond` for a GET's), between the
window's two scrapes.  Where a stage exists only for some shapes of
data (`pad`: shards that are no multiple of the kernel's tile, blocks
that k does not divide), this is how much of the traffic it met, also
where it cost no time; 0 where it met none.  A program without the
stage, or a window that served no byte, gives nothing.
"""


def read(ctx: dict, stage: str, per: str) -> float | None:
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    if stage not in after["stage_bytes"] or per not in after["stage_bytes"]:
        return None
    user = after["stage_bytes"][per] - before["stage_bytes"].get(per, 0.0)
    if not user:
        return None
    moved = (after["stage_bytes"][stage]
             - before["stage_bytes"].get(stage, 0.0))
    return moved / user
