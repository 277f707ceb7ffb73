"""Batched HighwayHash-256 in plain numpy: the oracle of the frame hash.

The bitrot framing keys HighwayHash-256 with the pi-decimals magic key
(reference cmd/bitrot.go:55); the served path hashes with the C hasher
(``ops/host.py`` ``hh256_batch``, csrc/highwayhash.cpp).

``hh256_batch_np`` is the same hash over N equal-length rows as
vectorized numpy u64 arithmetic.  It is the reference the tests compare
the C hasher and the frames on disk against, never a target; nothing on
the served path calls it.
"""

from __future__ import annotations

import numpy as np

from .host import MAGIC_HH256_KEY

__all__ = [
    "MAGIC_HH256_KEY",
    "hh256_batch_np",
]

U64 = np.uint64
_M32 = U64(0xFFFFFFFF)

# HighwayHash init vectors (csrc/highwayhash.cpp kInit0/kInit1 —
# sqrt(2)/sqrt(3) fractional bits, same constants as minio/highwayhash)
_INIT0 = np.array(
    [0xDBE6D5D5FE4CCE2F, 0xA4093822299F31D0,
     0x13198A2E03707344, 0x243F6A8885A308D3], dtype=U64)
_INIT1 = np.array(
    [0x3BD39E10CB0EF593, 0xC0ACF169B5F18A8C,
     0xBE5466CF34E90C6C, 0x452821E638D01377], dtype=U64)


def _rot32(x):
    """Swap the 32-bit halves of each u64 (Rotate64By32)."""
    return (x >> U64(32)) | ((x & _M32) << U64(32))


def _key_lanes(key: bytes) -> np.ndarray:
    if len(key) != 32:
        raise ValueError("key must be 32 bytes")
    return np.frombuffer(key, dtype="<u8").astype(U64, copy=True)


def _init_state(n: int, key: bytes):
    """(mul0, mul1, v0, v1) each (n, 4) u64."""
    lanes = _key_lanes(key)
    mul0 = np.broadcast_to(_INIT0, (n, 4)).copy()
    mul1 = np.broadcast_to(_INIT1, (n, 4)).copy()
    v0 = mul0 ^ lanes
    v1 = mul1 ^ _rot32(lanes)
    return mul0, mul1, v0, v1


def _zipper(a, b):
    """ZipperMergeAndAdd deltas for one (v1, v0) pair of (n,) u64 columns.

    Returns (add0, add1) — csrc/highwayhash.cpp byte shuffle:
      add0 bytes = [b.3, a.4, b.2, b.5, a.6, b.1, a.7, b.0]
      add1 bytes = [a.3, b.4, a.2, a.5, a.1, b.6, a.0, b.7]
    (a = the function's v1 argument, b = its v0 argument; .N = byte N,
    byte 0 the LSB).
    """
    add0 = ((((b & U64(0xFF000000)) | (a & U64(0xFF00000000))) >> U64(24))
            | (((b & U64(0xFF0000000000))
                | (a & U64(0xFF000000000000))) >> U64(16))
            | (b & U64(0xFF0000))
            | ((b & U64(0xFF00)) << U64(32))
            | ((a & U64(0xFF00000000000000)) >> U64(8))
            | (b << U64(56)))
    add1 = ((((a & U64(0xFF000000)) | (b & U64(0xFF00000000))) >> U64(24))
            | (a & U64(0xFF0000))
            | ((a & U64(0xFF0000000000)) >> U64(16))
            | ((a & U64(0xFF00)) << U64(24))
            | ((b & U64(0xFF000000000000)) >> U64(8))
            | ((a & U64(0xFF)) << U64(48))
            | (b & U64(0xFF00000000000000)))
    return add0, add1


def _np_update(lanes, mul0, mul1, v0, v1):
    """One UpdatePacket over (n, 4) u64 lane arrays, in place."""
    v1 += mul0 + lanes
    mul0 ^= (v1 & _M32) * (v0 >> U64(32))
    v0 += mul1
    mul1 ^= (v0 & _M32) * (v1 >> U64(32))
    a0, a1 = _zipper(v1[:, 1], v1[:, 0])
    v0[:, 0] += a0
    v0[:, 1] += a1
    a0, a1 = _zipper(v1[:, 3], v1[:, 2])
    v0[:, 2] += a0
    v0[:, 3] += a1
    a0, a1 = _zipper(v0[:, 1], v0[:, 0])
    v1[:, 0] += a0
    v1[:, 1] += a1
    a0, a1 = _zipper(v0[:, 3], v0[:, 2])
    v1[:, 2] += a0
    v1[:, 3] += a1


def _remainder_packet(blocks: np.ndarray, nfull: int, rem: int) -> np.ndarray:
    """UpdateRemainder's padded 32-byte packet for every row at once."""
    n = blocks.shape[0]
    tail = rem & ~3
    mod4 = rem & 3
    base = nfull * 32
    packet = np.zeros((n, 32), dtype=np.uint8)
    packet[:, :tail] = blocks[:, base:base + tail]
    if rem & 16:
        for i in range(4):
            packet[:, 28 + i] = blocks[:, base + tail + i + mod4 - 4]
    elif mod4:
        packet[:, 16] = blocks[:, base + tail]
        packet[:, 17] = blocks[:, base + tail + (mod4 >> 1)]
        packet[:, 18] = blocks[:, base + rem - 1]
    return packet


def _rotate32_by(count: int, v: np.ndarray) -> np.ndarray:
    """Rotate each 32-bit half of each u64 left by count (count < 32)."""
    c = U64(count)
    lo = v & _M32
    hi = v >> U64(32)
    if count:
        lo = ((lo << c) & _M32) | (lo >> (U64(32) - c))
        hi = ((hi << c) & _M32) | (hi >> (U64(32) - c))
    return (hi << U64(32)) | lo


def _finalize256(mul0, mul1, v0, v1) -> np.ndarray:
    """(n, 4) states -> (n, 32) uint8 digests."""
    for _ in range(10):
        permuted = np.stack(
            [_rot32(v0[:, 2]), _rot32(v0[:, 3]),
             _rot32(v0[:, 0]), _rot32(v0[:, 1])], axis=1)
        _np_update(permuted, mul0, mul1, v0, v1)

    def modular(a3u, a2, a1, a0):
        a3 = a3u & U64(0x3FFFFFFFFFFFFFFF)
        m1 = a1 ^ ((a3 << U64(1)) | (a2 >> U64(63))) \
            ^ ((a3 << U64(2)) | (a2 >> U64(62)))
        m0 = a0 ^ (a2 << U64(1)) ^ (a2 << U64(2))
        return m1, m0

    h1, h0 = modular(v1[:, 1] + mul1[:, 1], v1[:, 0] + mul1[:, 0],
                     v0[:, 1] + mul0[:, 1], v0[:, 0] + mul0[:, 0])
    h3, h2 = modular(v1[:, 3] + mul1[:, 3], v1[:, 2] + mul1[:, 2],
                     v0[:, 3] + mul0[:, 3], v0[:, 2] + mul0[:, 2])
    out = np.stack([h0, h1, h2, h3], axis=1)
    if out.dtype.byteorder == ">":  # pragma: no cover - big-endian hosts
        out = out.byteswap()
    return out.view(np.uint8).reshape(-1, 32)


def hh256_batch_np(blocks: np.ndarray,
                   key: bytes = MAGIC_HH256_KEY) -> np.ndarray:
    """Vectorized HighwayHash-256 over N equal-length rows.

    (N, L) uint8 -> (N, 32) uint8, bit-exact with ops/host.py::hh256 on
    every row.  Pure numpy u64 — serves as the oracle for the device
    kernel's differential tests and as a library-free fallback for
    ``host.hh256_batch``.
    """
    blocks = np.ascontiguousarray(blocks, dtype=np.uint8)
    if blocks.ndim != 2:
        raise ValueError("hh256_batch_np wants (N, L)")
    n, length = blocks.shape
    if n == 0:
        return np.empty((0, 32), dtype=np.uint8)
    mul0, mul1, v0, v1 = _init_state(n, key)
    nfull, rem = divmod(length, 32)
    if nfull:
        lanes = np.ascontiguousarray(
            blocks[:, :nfull * 32]).view("<u8").reshape(n, nfull, 4)
        lanes = lanes.astype(U64, copy=False)
        for p in range(nfull):
            _np_update(lanes[:, p, :], mul0, mul1, v0, v1)
    if rem:
        v0 += (U64(rem) << U64(32)) + U64(rem)
        v1 = _rotate32_by(rem, v1)
        packet = _remainder_packet(blocks, nfull, rem)
        lanes = packet.view("<u8").reshape(n, 4).astype(U64, copy=False)
        _np_update(lanes, mul0, mul1, v0, v1)
    return _finalize256(mul0, mul1, v0, v1)
