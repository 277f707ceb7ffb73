"""Thread-seconds a data-plane stage worked per GiB of user data that
passed in the same interval, both from the program's own counters:
`minio_dataplane_stage_seconds_total{stage}` over the bytes of the stage
`per` that carries user bytes (`read` for a PUT's body, `respond` for a
GET's).  Seconds sum over pool threads, so this is work done, not wall
time: stages overlap and may sum past it.
"""

GIB = float(1 << 30)


def read(ctx: dict, stage: str, per: str) -> float | None:
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    if stage not in after["stage_seconds"] or per not in after["stage_bytes"]:
        return None
    user = after["stage_bytes"][per] - before["stage_bytes"].get(per, 0.0)
    if not user:
        return None
    seconds = (after["stage_seconds"][stage]
               - before["stage_seconds"].get(stage, 0.0))
    return seconds / (user / GIB)
