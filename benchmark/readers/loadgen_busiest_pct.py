"""CPU share (%) of the busiest sending process over the window, each
worker's own process time over its wall time: near 100 the generator,
not the server, is what the cell measures."""


def read(ctx: dict) -> float | None:
    shares = ctx.get("worker_cpu_shares")
    return 100.0 * max(shares) if shares else None
