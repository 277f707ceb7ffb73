"""Peaks of the chips, and what each kernel's work needs of them.

The counts come from the work alone -- (k, m, rows rebuilt, user bytes
coded) -- never from how the program does it: a later PR that drops the
relayout, fuses the hash or swaps the Mosaic kernel for an XLA program
is read by the same yardstick.
"""

from __future__ import annotations

# device_kind as JAX reports it -> peaks, with their source.  A kind that
# is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 393 TOP/s int8, "
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return PEAKS[device_kind]


def rs_work(k: int, rows_out: int, user_bytes: int) -> tuple[float, float]:
    """(operations, least HBM bytes) of one Reed-Solomon coding pass that
    makes `rows_out` shards from `k` over `user_bytes` of object data:
    encode makes m parity rows, a reconstruct as many rows as were lost.

    Operations: a GF(2^8) matrix-vector product carried as its GF(2) bit
    matrix, the only form a matrix unit multiplies: (8*rows_out x 8*k)
    bits against 8*k bits for every byte column, two operations (multiply,
    add) per matrix entry: 128 * rows_out * k per column of k user
    bytes, so 128 * rows_out per user byte.
    Bytes: each source shard read once and each made shard written once:
    user_bytes * (1 + rows_out / k)."""
    return 128.0 * rows_out * user_bytes, user_bytes * (1.0 + rows_out / k)


def roofline_pct(device_kind: str, ops: float, hbm_bytes: float,
                 device_seconds: float) -> tuple[float, str]:
    """Share (%) of the chip's least possible time that `device_seconds`
    is, and which of the two bounds sets that least time."""
    p = peaks(device_kind)
    t_ops = ops / p["int8_ops_per_s"]
    t_mem = hbm_bytes / p["hbm_bytes_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_ops, t_mem) / device_seconds, bound
