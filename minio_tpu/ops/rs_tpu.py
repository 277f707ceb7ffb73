"""TPU Reed-Solomon codec: GF(2^8) shard coding as MXU bit-matmuls.

The reference's hot loop is a GF(2^8) matrix-vector product per byte
position (klauspost/reedsolomon AVX2 galois-multiply, used from
/root/reference/cmd/erasure-coding.go:63 and driven per 1 MiB block by
cmd/erasure-encode.go:73 / cmd/erasure-decode.go:206).  On TPU we use a
different decomposition that maps onto the systolic array instead of
table lookups:

    GF(2^8) is an 8-dimensional vector space over GF(2); multiplication
    by any constant c is GF(2)-linear.  Expanding every byte to its 8
    bits turns the (R x K) GF(2^8) coding matmul into an
    (R*8 x K*8) GF(2) matmul — i.e. an integer matmul followed by mod 2.

So: unpack uint8 shards to 0/1 int8 bits, run one int8 MXU matmul per
block batch (popcounts are at most K*8 <= 2040 and accumulate exactly in
the int32 the MXU produces), mask the low bit,
and pack back to bytes.  Encode, degraded decode ("first K of N"), and
heal all reduce to the same kernel with a different (R*8 x K*8) bit
matrix, which is a tiny host-side numpy computation (gf256.py) passed in
as a runtime operand — availability changes never trigger recompilation.

Batched over many 1 MiB blocks per dispatch, this is exactly the shape
the MXU wants: a (R8, K8) x (K8, B*S) matmul with B*S in the millions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256, residency

# ---------------------------------------------------------------------------
# Host-side matrix preparation
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def encode_bits_matrix(k: int, m: int) -> np.ndarray:
    """(m*8, k*8) GF(2) bit expansion of the parity matrix, int8."""
    return gf256.gf_matrix_to_bits(gf256.parity_matrix(k, m)).astype(np.int8)


@functools.lru_cache(maxsize=256)
def reconstruct_bits_matrix(
    k: int, m: int, available: tuple[int, ...], wanted: tuple[int, ...]
) -> np.ndarray:
    """(len(wanted)*8, k*8) bit matrix rebuilding `wanted` shards from the
    first k shards of `available`, columns in the order given.

    Bounded: the (available, wanted) signature space is combinatorial, so
    churny degraded reads with varying survivor sets would otherwise grow
    this without limit."""
    rm = gf256.reconstruct_matrix(k, m, available, wanted)
    return gf256.gf_matrix_to_bits(rm).astype(np.int8)


# (RecMatrixCache, the per-codec LRU, was folded into the shared
# signature-keyed residency — ops/residency.py, ISSUE 11.)


# ---------------------------------------------------------------------------
# Device kernel (pure XLA; the Pallas fused variant lives in rs_pallas.py)
# ---------------------------------------------------------------------------


def _unpack_bits(shards: jax.Array) -> jax.Array:
    """(..., K, S) uint8 -> (..., K*8, S) int8 of 0/1 bits (LSB-first)."""
    *lead, k, s = shards.shape
    bitpos = jnp.arange(8, dtype=jnp.uint8).reshape((1,) * len(lead) + (1, 8, 1))
    bits = jnp.right_shift(shards[..., :, None, :], bitpos) & jnp.uint8(1)
    return bits.reshape(*lead, k * 8, s).astype(jnp.int8)


def _pack_bits(bits: jax.Array) -> jax.Array:
    """(..., R*8, S) int32 0/1 -> (..., R, S) uint8 (LSB-first)."""
    *lead, r8, s = bits.shape
    r = r8 // 8
    b = bits.reshape(*lead, r, 8, s).astype(jnp.int32)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32)).reshape(
        (1,) * len(lead) + (1, 8, 1)
    )
    return jnp.sum(b * weights, axis=-2).astype(jnp.uint8)


@jax.jit
def gf_bitmatmul(mat_bits: jax.Array, shards: jax.Array) -> jax.Array:
    """out[b, r, s] = GF(2^8) matmul via bit-matmul mod 2.

    mat_bits: (R*8, K*8) int8 0/1 (from *_bits_matrix above)
    shards:   (B, K, S) uint8 — B independent blocks of K source shards
    returns:  (B, R, S) uint8
    """
    bits = _unpack_bits(shards)  # (B, K8, S)
    counts = jax.lax.dot_general(
        mat_bits,
        bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (R8, B, S)
    counts = jnp.moveaxis(counts, 1, 0)  # (B, R8, S)
    return _pack_bits(counts & 1)


class TpuRSCodec:
    """Batched Reed-Solomon codec on the default JAX device.

    Capability-equivalent to the reference's `Erasure` codec operations
    (EncodeData / DecodeDataBlocks / DecodeDataAndParityBlocks at
    cmd/erasure-coding.go:77-119) but operating on batches of blocks:
    shape (B, K, S) -> parity (B, M, S).
    """

    backend = "device"  # explicit dispatch-stats bucket (ADVICE r5)

    def __init__(self, k: int, m: int):
        if k <= 0 or m <= 0 or k + m > 256:
            raise ValueError(f"invalid RS config {k}+{m}")
        self.k = k
        self.m = m
        # matrices live in the shared signature-keyed residency
        # (ops/residency.py): one LRU, one hit/miss counter, no
        # per-instance re-transfer
        self._enc = residency.matrices.get(
            ("tpu-enc", k, m), lambda: jnp.asarray(encode_bits_matrix(k, m)))

    # -- encode -------------------------------------------------------------
    def encode(self, data_shards) -> jax.Array:
        """(B, K, S) uint8 data shards -> (B, M, S) parity shards."""
        return gf_bitmatmul(self._enc, jnp.asarray(data_shards, dtype=jnp.uint8))

    def encode_blocks(self, data_shards) -> jax.Array:
        """(B, K, S) -> (B, K+M, S) full shard set (data | parity)."""
        d = jnp.asarray(data_shards, dtype=jnp.uint8)
        return jnp.concatenate([d, gf_bitmatmul(self._enc, d)], axis=1)

    # -- decode / heal ------------------------------------------------------
    def reconstruct(
        self,
        src_shards,
        available: tuple[int, ...],
        wanted: tuple[int, ...],
    ) -> jax.Array:
        """Rebuild `wanted` shards from surviving shards.

        src_shards: (B, K, S) uint8 — the first K *available* shards,
            stacked in the order `available` names them (the caller
            reads only K of the N shard streams, mirroring
            parallelReader's first-K-of-N at cmd/erasure-decode.go:101).
        available:  tuple of surviving shard indices (>= K of them).
        wanted:     tuple of shard indices to rebuild (data and/or parity).
        returns:    (B, len(wanted), S) uint8.
        """
        sig = (tuple(available), tuple(wanted))
        mat = residency.matrices.get(
            ("tpu-rec", self.k, self.m) + sig,
            lambda: jnp.asarray(
                reconstruct_bits_matrix(self.k, self.m, *sig)))
        return gf_bitmatmul(mat, jnp.asarray(src_shards, dtype=jnp.uint8))

    def decode_data(self, src_shards, available: tuple[int, ...]) -> jax.Array:
        """All K data shards from any K survivors: (B, K, S) -> (B, K, S)."""
        return self.reconstruct(src_shards, available, tuple(range(self.k)))
