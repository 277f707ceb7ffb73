"""The geometry a PUT is written at when drives of its set are away.

As MinIO writes it (cmd/erasure-object.go:770-805, putObject): the
object's parity starts at the storage class's, every drive of the set
that is nil or offline adds one, and it stops at half the set; the data
drives are the rest, and the write quorum is the data drives, one more
where data and parity are equal (cmd/erasure-object.go:810-813).  A
healthy set writes its configured geometry, sixteen drives at EC:4 with
two away write 10+6.  Imports nothing of the program.
"""

from __future__ import annotations


def upgraded(n: int, parity: int, offline: int) -> tuple[int, int]:
    """-> (k, m) of an object PUT to a set of `n` drives whose storage
    class asks for `parity`, with `offline` of the drives away."""
    m = parity + offline
    if m >= n // 2:
        m = n // 2
    return n - m, m


def write_quorum(k: int, m: int) -> int:
    return k + 1 if k == m else k
