"""Boot-time codec + bitrot self-tests.

The reference refuses to start if the erasure codec or bitrot hash
produce wrong bytes (erasureSelfTest cmd/erasure-coding.go:158,
bitrotSelfTest cmd/bitrot.go:209): a silently-miscompiled SIMD path or a
corrupted multiplication table would otherwise corrupt every object
written.  Run at server start; raises SelfTestError on any mismatch.
"""

from __future__ import annotations


class SelfTestError(RuntimeError):
    """Codec/bitrot self-test mismatch — the process must not serve IO."""


# (data, parity) -> xxhash64 over `index byte || shard` of encoding
# bytes 0..255 — a subset of the reference's boot table
# (cmd/erasure-coding.go:169); the full table is pinned in
# tests/test_rs_golden.py.
_EC_GOLDEN = {
    (2, 2): 0x23FB21BE2496F5D3,
    (4, 2): 0x62B9552945504FEF,
    (5, 3): 0x7AD9161ACBB4C325,
    (8, 4): 0x03BA5E9B41BF07F0,
    (10, 4): 0x6C1CBA8631DE994A,
    (14, 1): 0x78A28BBAEC57996E,
}

# reference bitrotSelfTest chained-sum vector (cmd/bitrot.go:215)
_HH256_GOLDEN = "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313"


def erasure_self_test() -> None:
    """Encode a fixed pattern and compare shard hashes with the pinned
    reference values; then reconstruct a dropped shard.

    Runs against BOTH codecs that can serve IO: the pure-numpy table path
    (gf256) and the C++ SIMD codec (host.HostRSCodec) that Erasure
    dispatches to on the hot path — a miscompiled csrc build must refuse
    to boot, exactly like the reference's erasureSelfTest."""
    import numpy as np
    import xxhash

    from minio_tpu.ops import gf256, host

    data = bytes(range(256))
    for (k, m), want in _EC_GOLDEN.items():
        data_shards = gf256.split(data, k)
        codec = host.HostRSCodec(k, m)
        for label, parity in (
            ("numpy", gf256.encode_data_np(data, k, m)[k:]),
            ("host-simd", list(codec.encode(data_shards))),
        ):
            shards = [data_shards[i] for i in range(k)] + list(parity)
            h = xxhash.xxh64()
            for i, s in enumerate(shards):
                h.update(bytes([i]))
                h.update(np.asarray(s, dtype=np.uint8).tobytes())
            if h.intdigest() != want:
                raise SelfTestError(
                    f"erasure self-test failed for {k}+{m} ({label}): shards "
                    f"are not byte-identical with the reference codec")
        full = gf256.encode_data_np(data, k, m)
        first = full[0].copy()
        rebuilt = gf256.reconstruct_np([None] + full[1:], k, m)
        if not np.array_equal(rebuilt[0], first):
            raise SelfTestError(
                f"erasure self-test failed for {k}+{m}: reconstruction "
                f"does not round-trip")
        # SIMD reconstruct must agree as well
        avail = tuple(range(1, k + 1))
        rec = codec.reconstruct(np.stack(full[1:k + 1]), avail, (0,))
        if not np.array_equal(rec[0], first):
            raise SelfTestError(
                f"erasure self-test failed for {k}+{m} (host-simd): "
                f"reconstruction does not round-trip")


def bitrot_self_test() -> None:
    """Chained-sum HighwayHash-256 vector (cmd/bitrot.go:209)."""
    from minio_tpu.ops import host

    h = host.HH256()
    size, block = 32, 32
    msg = b""
    sum_ = b""
    for _ in range(0, size * block, size):
        h.reset()
        h.update(msg)
        sum_ = h.digest()
        msg += sum_
    if sum_.hex() != _HH256_GOLDEN:
        raise SelfTestError(
            "bitrot self-test failed: HighwayHash-256 checksum mismatch")


def device_self_test(k: int, m: int, block_size: int) -> float:
    """Encode and reconstruct through the device codec on the chip, at
    every shape a request can dispatch — one batch of full blocks at
    each of coding.DEVICE_BATCH_SIZES — and compare with the gf256
    oracle.

    The device half of the boot self-test: a kernel that fails to
    compile, or computes wrong bytes, must stop the server as a broken
    host codec does.  It is also the warm-up: at each batch size the
    encode program and the reconstruct programs for 1..m lost shards
    are compiled (or read from the persistent cache) here, and a
    dispatch of any other number of blocks is carried at the next of
    these sizes (coding._on_device), so no request compiles.  Returns
    the seconds it took.  The caller has established that this geometry
    dispatches to the device."""
    import time

    import numpy as np

    from minio_tpu.erasure import coding
    from minio_tpu.ops import gf256

    t0 = time.perf_counter()
    codec = coding._DeviceCodec.get(k, m, probe=False)
    # a full block's shard (cmd/erasure-coding.go:122): for 12+4 no
    # multiple of the kernel's tile, which is what has to be seen here.
    # One batch and one oracle parity at the largest size; a smaller
    # size takes their first blocks (coding is per block)
    sizes = coding.DEVICE_BATCH_SIZES
    s = -(-block_size // k)
    batch = np.random.default_rng(k * 256 + m).integers(
        0, 256, size=(sizes[-1], k, s), dtype=np.uint8)
    flat = np.ascontiguousarray(batch.transpose(1, 0, 2)).reshape(k, -1)
    want = gf256.encode_np(flat, m).reshape(m, -1, s).transpose(1, 0, 2)
    full = np.concatenate([batch, want], axis=1)
    for b in sizes:
        parity = np.asarray(codec.encode(batch[:b]))
        if not np.array_equal(parity, want[:b]):
            raise SelfTestError(
                f"device erasure self-test failed for {k}+{m} at {b} "
                f"blocks: parity from the device differs from the gf256 "
                f"oracle")
        for lost in range(1, m + 1):
            wanted = tuple(range(lost))
            avail = tuple(range(lost, lost + k))
            rebuilt = np.asarray(codec.reconstruct(
                np.ascontiguousarray(full[:b, lost:lost + k]), avail, wanted))
            if not np.array_equal(rebuilt, batch[:b, :lost]):
                raise SelfTestError(
                    f"device erasure self-test failed for {k}+{m} at {b} "
                    f"blocks: reconstructing {lost} lost shard(s) on the "
                    f"device does not round-trip")
    return time.perf_counter() - t0


def run_self_tests() -> None:
    erasure_self_test()
    bitrot_self_test()
