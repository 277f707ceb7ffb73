"""GF(2^8) arithmetic and Reed-Solomon coding matrices.

Field: GF(2^8) with the generator polynomial x^8 + x^4 + x^3 + x^2 + 1
(0x11D), generator element 2 — the same field used by the reference's
codec dependency (klauspost/reedsolomon, see /root/reference/go.mod:44 and
/root/reference/cmd/erasure-coding.go:63).  The coding matrix is the
"systematic Vandermonde" construction: build the (total x data) Vandermonde
matrix V[r][c] = r^c, then right-multiply by the inverse of its top
(data x data) square so the first `data` rows become the identity.  This
reproduces the reference's shard bytes exactly; correctness is pinned by
the golden xxhash64 vectors from /root/reference/cmd/erasure-coding.go:169
(see tests/test_rs_golden.py).

Everything here is host-side numpy; the TPU kernels in rs_tpu.py consume
the matrices produced here (as GF(2) bit-matrices, see `gf_matrix_to_bits`).
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1


def _build_tables():
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    # Duplicate so exp[(log a + log b)] never needs an explicit mod.
    for i in range(255, 512):
        exp[i] = exp[i - 255]
    log[0] = 0  # never consulted for zero operands; guarded by callers
    return exp, log


GF_EXP, GF_LOG = _build_tables()

# 256x256 full multiplication table: MUL_TABLE[a, b] = a*b in GF(2^8).
_a = np.arange(256)
_t = GF_EXP[(GF_LOG[_a][:, None] + GF_LOG[_a][None, :])]
_t[0, :] = 0
_t[:, 0] = 0
MUL_TABLE = _t.astype(np.uint8)
del _a, _t


def gf_mul(a, b):
    """Multiply in GF(2^8).  Accepts scalars or numpy uint8 arrays."""
    return MUL_TABLE[a, b]


def gf_div(a: int, b: int) -> int:
    if b == 0:
        raise ZeroDivisionError("GF(2^8) division by zero")
    if a == 0:
        return 0
    return int(GF_EXP[(GF_LOG[a] - GF_LOG[b]) % 255])


def gf_exp(a: int, n: int) -> int:
    """a ** n in GF(2^8) (matches klauspost galExp semantics)."""
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(GF_EXP[(GF_LOG[a] * n) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(2^8) inverse of zero")
    return int(GF_EXP[255 - GF_LOG[a]])


def gf_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Matrix product over GF(2^8) for small uint8 matrices."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        # products: (k, n) table lookups, XOR-reduced over k
        prod = MUL_TABLE[a[i][:, None], b]
        out[i] = np.bitwise_xor.reduce(prod, axis=0)
    return out


def gf_mat_inv(m: np.ndarray) -> np.ndarray:
    """Invert a square matrix over GF(2^8) by Gauss-Jordan elimination.

    Raises ValueError if singular (mirrors reedsolomon.ErrSingular).
    """
    m = np.asarray(m, dtype=np.uint8)
    n = m.shape[0]
    aug = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        # partial pivot: find a row with nonzero entry
        pivot = None
        for r in range(col, n):
            if aug[r, col] != 0:
                pivot = r
                break
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        # scale pivot row to make pivot 1
        inv_p = gf_inv(int(aug[col, col]))
        aug[col] = MUL_TABLE[aug[col], inv_p]
        # eliminate all other rows
        for r in range(n):
            if r != col and aug[r, col] != 0:
                factor = int(aug[r, col])
                aug[r] ^= MUL_TABLE[aug[col], factor]
    return aug[:, n:].copy()


@functools.lru_cache(maxsize=None)
def coding_matrix(data: int, total: int) -> np.ndarray:
    """The (total x data) systematic coding matrix.

    Top `data` rows are the identity; the bottom `total-data` rows generate
    parity.  Matches klauspost/reedsolomon's buildMatrix (Vandermonde made
    systematic), which is what the reference instantiates via
    reedsolomon.New at cmd/erasure-coding.go:63.
    """
    if not (0 < data <= total <= 256):
        raise ValueError(f"invalid RS configuration data={data} total={total}")
    vm = np.zeros((total, data), dtype=np.uint8)
    for r in range(total):
        for c in range(data):
            vm[r, c] = gf_exp(r, c)
    top = vm[:data, :]
    m = gf_matmul(vm, gf_mat_inv(top))
    m.setflags(write=False)
    return m


@functools.lru_cache(maxsize=None)
def parity_matrix(data: int, parity: int) -> np.ndarray:
    """Bottom `parity` rows of the systematic coding matrix (parity = P @ data)."""
    m = coding_matrix(data, data + parity)[data:, :].copy()
    m.setflags(write=False)
    return m


def decode_matrix(data: int, parity: int, available: tuple[int, ...]) -> np.ndarray:
    """Matrix reconstructing ALL data shards from `data` available shards.

    `available` lists >= data distinct shard indices (0..data+parity-1)
    that survive, in the order the caller stacks them (ascending as a
    rule; a degraded read whose spare took a failed read's column hands
    them in unsorted).  Returns (data x data) matrix D such that
    data_shards = D @ available_shards[:data].

    Mirrors reedsolomon.Reconstruct's subMatrix-invert step.
    """
    if len(available) < data:
        raise ValueError("not enough shards to reconstruct")
    rows = list(available)[:data]
    if len(set(rows)) != data:
        raise ValueError("available shard indices must be distinct")
    full = coding_matrix(data, data + parity)
    sub = full[list(rows), :]
    return gf_mat_inv(sub)


def reconstruct_matrix(
    data: int, parity: int, available: tuple[int, ...], wanted: tuple[int, ...]
) -> np.ndarray:
    """Matrix computing the `wanted` shards from the first `data` available shards.

    wanted_shards = R @ available_shards[:data];  works for any mix of data
    and parity targets (used by Heal to rebuild parity shards too).
    """
    dm = decode_matrix(data, parity, available)
    full = coding_matrix(data, data + parity)
    out_rows = full[list(wanted), :]  # wanted in terms of original data shards
    return gf_matmul(out_rows, dm)


def gf_matrix_to_bits(m: np.ndarray) -> np.ndarray:
    """Expand a GF(2^8) matrix (R x C) to its GF(2) bit-matrix (R*8 x C*8).

    Multiplication by a constant c is linear over GF(2); its 8x8 bit-matrix
    has column j equal to the bits of c * x^j.  A GF(2^8) matmul then
    becomes a GF(2) matmul of the expanded matrices — which the TPU executes
    as an integer matmul followed by mod 2 (see rs_tpu.py).

    Bit order: bit i is (byte >> i) & 1 (LSB-first) on both axes.
    """
    m = np.asarray(m, dtype=np.uint8)
    r8, c8 = m.shape[0] * 8, m.shape[1] * 8
    bits = np.zeros((r8, c8), dtype=np.uint8)
    for r in range(m.shape[0]):
        for c in range(m.shape[1]):
            coef = int(m[r, c])
            if coef == 0:
                continue
            for j in range(8):
                prod = int(MUL_TABLE[coef, 1 << j])
                for i in range(8):
                    bits[r * 8 + i, c * 8 + j] = (prod >> i) & 1
    return bits


# ---------------------------------------------------------------------------
# Host (numpy) shard codec — the reference semantics, vectorised.
# ---------------------------------------------------------------------------


def split(data: bytes | np.ndarray, k: int) -> np.ndarray:
    """Split a byte payload into k equal data shards, zero-padding the tail.

    Matches reedsolomon.Encoder.Split as used by EncodeData
    (cmd/erasure-coding.go:77-91): per-shard size = ceil(len/k).
    Returns a (k, shard_len) uint8 array.
    """
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray, memoryview)) else np.asarray(data, dtype=np.uint8)
    n = buf.size
    if n == 0:
        raise ValueError("cannot split empty data")
    per = -(-n // k)
    padded = np.zeros(k * per, dtype=np.uint8)
    padded[:n] = buf
    return padded.reshape(k, per)


def code_at_width(code, shards, width: int, pad=np.pad):
    """Code a (B, K, S) batch with a codec that wants another shard
    width than S: a kernel that tiles the axis, a program compiled for
    one shape.  Zero columns are appended up to `width`, the batch is
    coded, the made rows are cut back to S.  GF(2^8) coding is byte-wise,
    so zero columns give zero rows and no real column sees them.  The
    one place this is done: `pad` is numpy's on the host and jax.numpy's
    inside a jitted program, where the widening is part of the dispatch
    and costs the host no copy."""
    s = shards.shape[-1]
    if s == width:
        return code(shards)
    return code(pad(shards, ((0, 0), (0, 0), (0, width - s))))[:, :, :s]


def encode_np(shards: np.ndarray, parity: int) -> np.ndarray:
    """Compute parity shards on host: (k, n) uint8 -> (m, n) uint8."""
    k = shards.shape[0]
    pm = parity_matrix(k, parity)
    # out[m] = XOR_k mul(pm[m,k], shards[k])
    out = np.zeros((parity, shards.shape[1]), dtype=np.uint8)
    for m in range(parity):
        acc = np.zeros(shards.shape[1], dtype=np.uint8)
        for kk in range(k):
            c = int(pm[m, kk])
            if c:
                acc ^= MUL_TABLE[c, shards[kk]]
        out[m] = acc
    return out


def encode_data_np(data: bytes, k: int, m: int) -> list[np.ndarray]:
    """EncodeData equivalent: payload -> k+m shards (cmd/erasure-coding.go:77)."""
    ds = split(data, k)
    ps = encode_np(ds, m)
    return [ds[i] for i in range(k)] + [ps[j] for j in range(m)]


def reconstruct_np(
    shards: list[np.ndarray | None], k: int, m: int, data_only: bool = True
) -> list[np.ndarray]:
    """Rebuild missing shards in-place semantics of ReconstructData/Reconstruct.

    `shards` is a k+m list where missing entries are None.  Returns the full
    list with (at least) all data shards present; when data_only is False,
    parity shards are rebuilt as well (Heal path, cmd/erasure-decode.go:287).
    """
    total = k + m
    if len(shards) != total:
        raise ValueError(f"expected {total} shard slots, got {len(shards)}")
    avail = tuple(i for i, s in enumerate(shards) if s is not None)
    if len(avail) < k:
        raise ValueError("too few shards to reconstruct")
    wanted = tuple(
        i for i, s in enumerate(shards)
        if s is None and (not data_only or i < k)
    )
    if not wanted:
        return list(shards)
    n = next(s.shape[0] for s in shards if s is not None)
    rm = reconstruct_matrix(k, m, avail, wanted)
    src = np.stack([np.asarray(shards[i], dtype=np.uint8) for i in avail[:k]])
    out = list(shards)
    for row, target in enumerate(wanted):
        acc = np.zeros(n, dtype=np.uint8)
        for kk in range(k):
            c = int(rm[row, kk])
            if c:
                acc ^= MUL_TABLE[c, src[kk]]
        out[target] = acc
    return out
