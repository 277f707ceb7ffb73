"""Plain reference: Reed-Solomon over GF(2^8) as MinIO's codec writes it.

Field polynomial x^8+x^4+x^3+x^2+1 (0x11D), generator 2; the coding
matrix is klauspost/reedsolomon's buildMatrix: the (k+m) x k Vandermonde
matrix V[r][c] = r^c made systematic by right-multiplying with the
inverse of its top square.  Numpy table look-ups only: no kernel, no
batching, nothing imported from the program under test (a copy of the
arithmetic in minio_tpu/ops/gf256.py, kept here so that a later PR
cannot move the yardstick; PERF.md, Open questions).
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _tables()
_a = np.arange(256)
MUL = _EXP[_LOG[_a][:, None] + _LOG[_a][None, :]].astype(np.uint8)
MUL[0, :] = 0
MUL[:, 0] = 0
del _a


def _pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] * n) % 255])


def _inv(a: int) -> int:
    return int(_EXP[255 - _LOG[a]])


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        out[i] = np.bitwise_xor.reduce(MUL[a[i][:, None], b], axis=0)
    return out


def _mat_inv(m: np.ndarray) -> np.ndarray:
    n = m.shape[0]
    aug = np.concatenate([m.copy(), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if aug[r, col]), None)
        if pivot is None:
            raise ValueError("singular matrix over GF(2^8)")
        if pivot != col:
            aug[[col, pivot]] = aug[[pivot, col]]
        aug[col] = MUL[aug[col], _inv(int(aug[col, col]))]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[aug[col], int(aug[r, col])]
    return aug[:, n:].copy()


@functools.lru_cache(maxsize=None)
def coding_matrix(k: int, m: int) -> np.ndarray:
    """(k+m) x k systematic matrix: identity on top, parity rows below."""
    vm = np.array([[_pow(r, c) for c in range(k)] for r in range(k + m)],
                  dtype=np.uint8)
    return _matmul(vm, _mat_inv(vm[:k]))


def encode(data: np.ndarray, m: int) -> np.ndarray:
    """(k, n) uint8 data shards -> (m, n) uint8 parity shards."""
    k = data.shape[0]
    rows = coding_matrix(k, m)[k:]
    out = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for r in range(m):
        for c in range(k):
            out[r] ^= MUL[int(rows[r, c])][data[c]]
    return out
