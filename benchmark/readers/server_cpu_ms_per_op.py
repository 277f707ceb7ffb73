"""Server CPU (utime + stime from /proc/<pid>/stat over the window) per
operation acknowledged in the same interval, in milliseconds."""


def read(ctx: dict) -> float | None:
    done = sum(1 for row in ctx["scraped"] if row[6])
    if not done or ctx.get("server_cpu_s") is None:
        return None
    return 1000.0 * ctx["server_cpu_s"] / done
