"""Device-side batched HighwayHash-256 + fused encode/hash/etag kernels.

The PUT hot path needs two hash planes next to the Reed-Solomon encode:

- per-shard *frame* hashes for the bitrot framing (reference
  cmd/bitrot.go:55 — HighwayHash-256 keyed with the pi-decimals magic
  key), today a second full pass over payload bytes on the host;
- the whole-object MD5 *etag* (reference cmd/erasure-object.go), today
  folded by a dedicated hash-lane worker process (parallel/workers.py).

This module moves both next to the encode so one program launch makes
one pass over the payload:

- ``hh256_batch_np``: vectorized pure-numpy HighwayHash-256 over N
  equal-length rows — the bit-exact oracle the device kernel and the
  property tests check against (and a dependency-free fallback).
- ``hh256_jax``: the same hash as a jittable XLA program.  JAX runs
  without 64-bit types here, so every u64 lane is carried as a
  (lo, hi) uint32 pair: 64-bit adds ripple a carry, the 32x32->64
  multiplies split at 16 bits for the high half, and the zipper merge
  is re-derived as byte shuffles on the pair (formulas checked
  byte-for-byte against csrc/highwayhash.cpp).
- ``fused_encode_hash``: ONE jitted program ``(B, K, S) -> (parity
  (B, M, S), frame hashes (B, K+M, 32))`` — GF(2^8) bit-plane matmul
  (ops/rs_tpu.py) feeding the batched hash while shard rows are still
  live in vector memory.  This is what the batcher dispatches per tick
  when MINIO_TPU_FUSED_HASH=1.
- ``Md5Fold``: whole-object MD5 as a lax.scan over 64-byte blocks, so
  the etag folds on-device and the PR 8 hash-lane process becomes
  optional (``fused_etag_available``).

Everything here is pure XLA (no Pallas): the hash state is 16 u64
lanes per row, the update is shift/mask/multiply — XLA vectorizes it
across rows, which is the axis that matters for a tick batch.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from . import device
from .host import MAGIC_HH256_KEY

__all__ = [
    "MAGIC_HH256_KEY",
    "hh256_batch_np",
    "hh256_jax",
    "fused_encode_hash",
    "Md5Fold",
    "fused_etag_available",
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


# ---------------------------------------------------------------------------
# JAX kernel: u64 as (lo, hi) uint32 pairs (no jax_enable_x64 dependence)
# ---------------------------------------------------------------------------

def _jx():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def _add64(jnp, al, ah, bl, bh):
    rl = al + bl
    carry = (rl < al).astype(jnp.uint32)
    return rl, ah + bh + carry


def _mul32x32(jnp, a, b):
    """Full 32x32 -> 64 product as (lo, hi) uint32 (mulhi via 16-bit split)."""
    lo = a * b
    a0 = a & 0xFFFF
    a1 = a >> 16
    b0 = b & 0xFFFF
    b1 = b >> 16
    t = a0 * b1 + ((a0 * b0) >> 16)
    t2 = a1 * b0 + (t & 0xFFFF)
    hi = a1 * b1 + (t >> 16) + (t2 >> 16)
    return lo, hi


def _zipper_pair(alo, ahi, blo, bhi):
    """_zipper in the (lo, hi) uint32 representation.

    Returns ((add0_lo, add0_hi), (add1_lo, add1_hi)) with the same byte
    shuffle as the u64 formulas (a = v1 argument, b = v0 argument).
    """
    r0lo = ((blo >> 24) | ((ahi & 0xFF) << 8) | (blo & 0xFF0000)
            | (((bhi >> 8) & 0xFF) << 24))
    r0hi = (((ahi >> 16) & 0xFF) | (((blo >> 8) & 0xFF) << 8)
            | (((ahi >> 24) & 0xFF) << 16) | ((blo & 0xFF) << 24))
    r1lo = ((alo >> 24) | ((bhi & 0xFF) << 8) | (alo & 0xFF0000)
            | (((ahi >> 8) & 0xFF) << 24))
    r1hi = (((alo >> 8) & 0xFF) | (((bhi >> 16) & 0xFF) << 8)
            | ((alo & 0xFF) << 16) | (bhi & np.uint32(0xFF000000)))
    return (r0lo, r0hi), (r1lo, r1hi)


def _jax_update(jnp, state, lanes_lo, lanes_hi):
    """One UpdatePacket.  state: dict of (N, 4) uint32 arrays."""
    m0l, m0h = state["m0l"], state["m0h"]
    m1l, m1h = state["m1l"], state["m1h"]
    v0l, v0h = state["v0l"], state["v0h"]
    v1l, v1h = state["v1l"], state["v1h"]
    tl, th = _add64(jnp, m0l, m0h, lanes_lo, lanes_hi)
    v1l, v1h = _add64(jnp, v1l, v1h, tl, th)
    pl, ph = _mul32x32(jnp, v1l, v0h)
    m0l, m0h = m0l ^ pl, m0h ^ ph
    v0l, v0h = _add64(jnp, v0l, v0h, m1l, m1h)
    pl, ph = _mul32x32(jnp, v0l, v1h)
    m1l, m1h = m1l ^ pl, m1h ^ ph

    def merge(dl, dh, sl, sh):
        """Zipper-merge columns 0..3 of source s into dest d (in place on
        fresh arrays via at[] updates is slow — rebuild by stacking)."""
        (a0l, a0h), (a1l, a1h) = _zipper_pair(
            sl[:, 1], sh[:, 1], sl[:, 0], sh[:, 0])
        (b0l, b0h), (b1l, b1h) = _zipper_pair(
            sl[:, 3], sh[:, 3], sl[:, 2], sh[:, 2])
        addl = jnp.stack([a0l, a1l, b0l, b1l], axis=1)
        addh = jnp.stack([a0h, a1h, b0h, b1h], axis=1)
        return _add64(jnp, dl, dh, addl, addh)

    v0l, v0h = merge(v0l, v0h, v1l, v1h)
    v1l, v1h = merge(v1l, v1h, v0l, v0h)
    return {"m0l": m0l, "m0h": m0h, "m1l": m1l, "m1h": m1h,
            "v0l": v0l, "v0h": v0h, "v1l": v1l, "v1h": v1h}


def _bytes_to_lanes(jnp, packets):
    """(N, P, 32) uint8 -> (lo, hi) each (N, P, 4) uint32, LE lanes."""
    b = packets.astype(jnp.uint32).reshape(
        packets.shape[0], packets.shape[1], 4, 8)
    lo = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)
    hi = b[..., 4] | (b[..., 5] << 8) | (b[..., 6] << 16) | (b[..., 7] << 24)
    return lo, hi


@functools.lru_cache(maxsize=8)
def _hh256_rows_fn(key: bytes):
    """Traceable (N, L) uint8 -> (N, 32) uint8 batched HighwayHash-256
    (compose into a jit; see _hh256_rows_jit for the standalone entry)."""
    jax, jnp = _jx()
    lanes = _key_lanes(key)
    i0, i1 = _INIT0, _INIT1
    kv0, kv1 = i0 ^ lanes, i1 ^ _rot32(lanes)

    def split(u):  # (4,) u64 -> two (4,) uint32 numpy arrays
        return ((u & _M32).astype(np.uint32), (u >> U64(32)).astype(np.uint32))

    consts = {k: split(v) for k, v in
              (("m0", i0), ("m1", i1), ("v0", kv0), ("v1", kv1))}

    def run(blocks):
        n = blocks.shape[0]
        length = blocks.shape[1]  # static under jit
        state = {}
        for name, (lo, hi) in consts.items():
            state[name[0] + name[1] + "l"] = jnp.broadcast_to(
                jnp.asarray(lo), (n, 4))
            state[name[0] + name[1] + "h"] = jnp.broadcast_to(
                jnp.asarray(hi), (n, 4))
        nfull, rem = divmod(length, 32)
        if nfull:
            packets = blocks[:, :nfull * 32].reshape(n, nfull, 32)
            plo, phi = _bytes_to_lanes(jnp, packets)  # (N, P, 4)

            def body(st, lane):
                return _jax_update(jnp, st, lane[0], lane[1]), None

            state, _ = jax.lax.scan(
                body, state,
                (jnp.moveaxis(plo, 1, 0), jnp.moveaxis(phi, 1, 0)))
        if rem:
            # v0 += (rem << 32) + rem: u64 add — lo gains rem (with carry
            # into hi), hi gains rem
            state["v0l"], state["v0h"] = _add64(
                jnp, state["v0l"], state["v0h"],
                jnp.uint32(rem), jnp.uint32(rem))
            if rem % 32:
                c = rem % 32

                def rotl(x):
                    return (x << c) | (x >> (32 - c))

                state["v1l"] = rotl(state["v1l"])
                state["v1h"] = rotl(state["v1h"])
            tail = rem & ~3
            mod4 = rem & 3
            base = nfull * 32
            cols = [None] * 32
            for i in range(tail):
                cols[i] = base + i
            if rem & 16:
                for i in range(4):
                    cols[28 + i] = base + tail + i + mod4 - 4
            elif mod4:
                cols[16] = base + tail
                cols[17] = base + tail + (mod4 >> 1)
                cols[18] = base + rem - 1
            zero = jnp.zeros((n,), dtype=jnp.uint8)
            packet = jnp.stack(
                [blocks[:, c] if c is not None else zero for c in cols],
                axis=1)[:, None, :]
            plo, phi = _bytes_to_lanes(jnp, packet)
            state = _jax_update(jnp, state, plo[:, 0], phi[:, 0])
        for _ in range(10):
            pl = jnp.stack(
                [state["v0h"][:, 2], state["v0h"][:, 3],
                 state["v0h"][:, 0], state["v0h"][:, 1]], axis=1)
            ph = jnp.stack(
                [state["v0l"][:, 2], state["v0l"][:, 3],
                 state["v0l"][:, 0], state["v0l"][:, 1]], axis=1)
            state = _jax_update(jnp, state, pl, ph)

        def modular(a3, a2, a1, a0):
            a3l, a3h = a3
            a2l, a2h = a2
            a1l, a1h = a1
            a0l, a0h = a0
            a3h = a3h & 0x3FFFFFFF
            s1l = (a3l << 1) | (a2h >> 31)
            s1h = (a3h << 1) | (a3l >> 31)
            s2l = (a3l << 2) | (a2h >> 30)
            s2h = (a3h << 2) | (a3l >> 30)
            m1l = a1l ^ s1l ^ s2l
            m1h = a1h ^ s1h ^ s2h
            m0l = a0l ^ (a2l << 1) ^ (a2l << 2)
            m0h = a0h ^ ((a2h << 1) | (a2l >> 31)) \
                ^ ((a2h << 2) | (a2l >> 30))
            return (m1l, m1h), (m0l, m0h)

        def lane_sum(col):
            va = _add64(jnp, state["v1l"][:, col], state["v1h"][:, col],
                        state["m1l"][:, col], state["m1h"][:, col])
            vb = _add64(jnp, state["v0l"][:, col], state["v0h"][:, col],
                        state["m0l"][:, col], state["m0h"][:, col])
            return va, vb

        (s1a, s1b), (s0a, s0b) = lane_sum(1), lane_sum(0)
        h1, h0 = modular(s1a, s0a, s1b, s0b)
        (s3a, s3b), (s2a, s2b) = lane_sum(3), lane_sum(2)
        h3, h2 = modular(s3a, s2a, s3b, s2b)
        words = jnp.stack(
            [h0[0], h0[1], h1[0], h1[1], h2[0], h2[1], h3[0], h3[1]],
            axis=1)  # (N, 8) uint32, LE word order
        bytes_ = jnp.stack(
            [(words >> (8 * i)) & 0xFF for i in range(4)],
            axis=2).astype(jnp.uint8)
        return bytes_.reshape(n, 32)

    return run


@functools.lru_cache(maxsize=8)
def _hh256_rows_jit(key: bytes):
    jax, _ = _jx()
    return jax.jit(_hh256_rows_fn(key))


def hh256_jax(blocks, key: bytes = MAGIC_HH256_KEY):
    """Batched HighwayHash-256 as a jitted XLA program.

    (N, L) uint8 -> (N, 32) uint8, bit-exact with ops/host.py::hh256.
    Compiles per distinct (N, L) shape; callers on the PUT path only see
    the few shard widths of a tick signature.
    """
    _, jnp = _jx()
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    if blocks.ndim != 2:
        raise ValueError("hh256_jax wants (N, L)")
    if blocks.shape[0] == 0:
        return jnp.empty((0, 32), dtype=jnp.uint8)
    return _hh256_rows_jit(key)(blocks)


# ---------------------------------------------------------------------------
# Fused encode + frame-hash program
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def fused_encode_hash(k: int, m: int, key: bytes = MAGIC_HH256_KEY):
    """ONE program for a tick bucket: encode + per-shard frame hashes.

    Returns a jitted ``run(batch)``: (B, K, S) uint8 data shards ->
    ``(parity (B, M, S) uint8, hashes (B, K+M, 32) uint8)``.  The GF(2^8)
    parity rows come from the same bit-plane matmul the plain encode
    dispatch uses (ops/rs_tpu.py), and every shard row — data and parity —
    is hashed inside the same XLA program, so payload bytes cross the
    memory system once per PUT instead of once for encode plus once for
    host hashing.  hashes[:, i, :] lines up with drive i's write_frames
    rows in erasure/coding.py::encode_stream.
    """
    from . import rs_tpu
    jax, jnp = _jx()
    mat_bits = rs_tpu.encode_bits_matrix(k, m)
    rows_fn = _hh256_rows_fn(key)

    def run(batch):
        b = batch.shape[0]
        s = batch.shape[2]
        parity = rs_tpu.gf_bitmatmul(mat_bits, batch)
        rows = jnp.concatenate([batch, parity], axis=1)
        hashes = rows_fn(rows.reshape(b * (k + m), s))
        return parity, hashes.reshape(b, k + m, 32)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# MD5 etag fold (lax.scan over 64-byte blocks)
# ---------------------------------------------------------------------------

_MD5_INIT = np.array(
    [0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476], dtype=np.uint32)
_MD5_K = np.floor(
    np.abs(np.sin(np.arange(1, 65, dtype=np.float64))) * (2.0 ** 32)
).astype(np.uint64).astype(np.uint32)
_MD5_S = ([7, 12, 17, 22] * 4 + [5, 9, 14, 20] * 4
          + [4, 11, 16, 23] * 4 + [6, 10, 15, 21] * 4)


@functools.lru_cache(maxsize=1)
def _md5_scan_fn():
    jax, jnp = _jx()
    kconst = [int(x) for x in _MD5_K]

    def block_fold(state, words):
        # words: (16,) uint32 LE message words of one 64-byte block
        a, b, c, d = state[0], state[1], state[2], state[3]
        for i in range(64):
            if i < 16:
                f = (b & c) | (~b & d)
                g = i
            elif i < 32:
                f = (d & b) | (~d & c)
                g = (5 * i + 1) % 16
            elif i < 48:
                f = b ^ c ^ d
                g = (3 * i + 5) % 16
            else:
                f = c ^ (b | ~d)
                g = (7 * i) % 16
            f = f + a + jnp.uint32(kconst[i]) + words[g]
            sh = _MD5_S[i]
            a, d, c, b = d, c, b, b + ((f << sh) | (f >> (32 - sh)))
        return jnp.stack([state[0] + a, state[1] + b,
                          state[2] + c, state[3] + d]), None

    def run(state, words):  # state (4,) uint32, words (nblocks, 16) uint32
        out, _ = jax.lax.scan(block_fold, state, words)
        return out

    return jax.jit(run)


class Md5Fold:
    """Streaming MD5 with the block folds running as a jitted scan.

    hashlib-compatible result (hexdigest pinned bit-exact in tests); the
    point is the fold happens on the accelerator next to the fused
    encode+hash program instead of in a separate hash-lane process.
    Sub-block tails are buffered host-side; full 64-byte spans go to the
    device in one scan per update call.
    """

    def __init__(self):
        self._state = None  # device (4,) uint32; lazily placed
        self._state_np = _MD5_INIT.copy()
        self._tail = b""
        self._total = 0

    def _fold(self, chunk: np.ndarray) -> None:
        """chunk: (nblocks*64,) uint8 contiguous."""
        _, jnp = _jx()
        words = np.ascontiguousarray(chunk).view("<u4").reshape(-1, 16)
        if self._state is None:
            self._state = jnp.asarray(self._state_np)
        self._state = _md5_scan_fn()(self._state, jnp.asarray(words))

    def update(self, data) -> None:
        if isinstance(data, np.ndarray):
            data = np.ascontiguousarray(data, dtype=np.uint8)
            buf = data.view(np.uint8).reshape(-1)
        else:
            buf = np.frombuffer(bytes(data), dtype=np.uint8)
        self._total += buf.size
        if self._tail:
            need = 64 - len(self._tail)
            take = min(need, buf.size)
            self._tail += buf[:take].tobytes()
            buf = buf[take:]
            if len(self._tail) == 64:
                self._fold(np.frombuffer(self._tail, dtype=np.uint8))
                self._tail = b""
        nblk = buf.size // 64
        if nblk:
            self._fold(buf[:nblk * 64])
            buf = buf[nblk * 64:]
        if buf.size:
            self._tail = self._tail + buf.tobytes()

    def _final_state(self) -> np.ndarray:
        pad = self._tail + b"\x80"
        pad += b"\x00" * ((56 - len(pad)) % 64)
        pad += (self._total * 8 % (1 << 64)).to_bytes(8, "little")
        chunk = np.frombuffer(pad, dtype=np.uint8)
        if self._state is None:
            self._state = _jx()[1].asarray(self._state_np)
        final = _md5_scan_fn()(
            self._state, _jx()[1].asarray(
                np.ascontiguousarray(chunk).view("<u4").reshape(-1, 16)))
        return np.asarray(final)

    def hexdigest(self) -> str:
        return self._final_state().astype("<u4").tobytes().hex()

    def digest(self) -> bytes:
        return self._final_state().astype("<u4").tobytes()


def fused_etag_available() -> bool:
    """Should put_data skip the hash-lane process and fold MD5 inline?

    True when the fused-hash gate is on AND either a TPU is
    present (the fold rides the accelerator next to the fused tick
    program) or MINIO_TPU_FUSED_ETAG=1 forces it (tests / CPU
    validation).  MINIO_TPU_FUSED_ETAG=0 force-disables regardless.
    """
    forced = os.environ.get("MINIO_TPU_FUSED_ETAG")
    if forced == "0":
        return False
    if os.environ.get("MINIO_TPU_FUSED_HASH", "0") != "1":
        return False
    if forced == "1":
        return True
    return device.info().platform == "tpu"
