"""Thread-seconds a data-plane stage worked during set-up per GiB of
user data that passed then: `stage_s_per_GiB` on the first scrape
alone.  The scrape at the window's first moment is cumulative since the
server's boot, so in a cell whose window sends no PUT it is the preload
(and a traced run's one probe PUT): the only place a preload's stages
can be read, since the window's two scrapes cancel it.  A program that
does not export the stage gives nothing.
"""

from benchmark.readers import stage_s_per_GiB

BOOT = {"stage_seconds": {}, "stage_bytes": {}}  # every counter at 0


def read(ctx: dict, stage: str, per: str) -> float | None:
    since_boot = {"before": BOOT, "after": ctx["counters"]["before"]}
    return stage_s_per_GiB.read({"counters": since_boot}, stage, per)
