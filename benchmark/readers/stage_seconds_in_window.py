"""Seconds a data-plane stage was booked between the window's two
scrapes, from the program's own counter
`minio_dataplane_stage_seconds_total{stage}`: for stage `compile`, the
seconds the server spent in XLA compilation inside the window (expected
0: set-up warms every shape), beside the count `compiles_in_window`
takes from JAX's log.  A program that does not export the stage gives
nothing.
"""


def read(ctx: dict, stage: str) -> float | None:
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    if stage not in after["stage_seconds"]:
        return None
    return (after["stage_seconds"][stage]
            - before["stage_seconds"].get(stage, 0.0))
