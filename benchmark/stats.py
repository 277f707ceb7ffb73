"""The arithmetic of the end-to-end metrics, over the request log.

A request-log row is (client, op, key, start, ack, bytes, ok,
identical, phase), times by the machine-wide monotonic clock; the phase
says where the acknowledgement fell: 0 warm-up, 1 inside the window
[t0, t0 + seconds), 2 after it.  The window's requests are those
acknowledged inside it, while every client goes on sending.  A rate is
all of their work over all of the window's time: every object whole,
none in part, none left out, and a second in which nothing is
acknowledged counts as a second.
"""

from __future__ import annotations

import math

MIB = float(1 << 20)


def counted(rows: list[tuple]) -> list[tuple]:
    """The window's own requests: acknowledged in [t0, t0 + seconds)."""
    return [r for r in rows if r[8] == 1]


def rate_MiBps(rows: list[tuple], seconds: float, op: str) -> float:
    """Bytes of every request of `op` that was acknowledged in the window
    and identical, over the window's length."""
    return sum(r[5] for r in rows
               if r[1] == op and r[6] and r[7]) / MIB / seconds


def ops_per_s(rows: list[tuple], seconds: float) -> float:
    return sum(1 for r in rows if r[6] and r[7]) / seconds


def percentile_ms(rows: list[tuple], q: float,
                  failed_ms: float = 1e6) -> float:
    """Nearest-rank percentile of all requests' latencies; a request that
    failed or answered wrongly counts as slower than any that did not."""
    lat = sorted(1000.0 * (r[4] - r[3]) if r[6] and r[7] else failed_ms
                 for r in rows)
    return lat[max(0, math.ceil(q / 100.0 * len(lat)) - 1)]


# end-to-end metric name -> how it is taken from the window's rows
END_TO_END = {
    "get_MiBps": lambda rows, seconds: rate_MiBps(rows, seconds, "GET"),
    "ops_per_s": ops_per_s,
}
