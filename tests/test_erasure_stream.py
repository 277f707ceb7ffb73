"""Streaming erasure pipeline: encode -> bitrot files -> decode/heal.

Mirrors the reference's codec-vs-tmpdir-drive tests
(cmd/erasure-decode_test.go, cmd/erasure-heal_test.go): real files, bit
flips, offline drives, quorum failures.
"""

import concurrent.futures as cf
import gc
import io
import os
import threading
import time
import weakref

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.erasure.coding import Erasure
from minio_tpu.storage import errors
from tests import device_codec


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

    def encode(self, batch, **kw):
        self.encodes += 1
        return self.inner.encode(batch, **kw)

    def reconstruct(self, batch, available, wanted, **kw):
        self.reconstructs += 1
        return self.inner.reconstruct(batch, available, wanted, **kw)


def test_device_codec_stream_roundtrip(tmp_path):
    """Full put/get/degraded-read through the Pallas kernel (interpret mode
    on CPU) — the device dispatch path encode_stream/decode_stream use on
    real TPU hardware (VERDICT r1 weak #3)."""
    from minio_tpu.erasure import coding
    from minio_tpu.ops import rs_pallas

    k, m, bs = 8, 4, 1 << 20  # shard 128 KiB: satisfies the 8192-alignment gate
    codec = _CountingCodec(rs_pallas.PallasRSCodec(k, m, interpret=True))
    device_codec.plant(k, m, codec)
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
        device_codec.unplant(k, m)


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
    device_codec.plant(k, m, codec)
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
        device_codec.unplant(k, m)


# -- the staged read of a degraded group (ISSUE 29) --------------------------
# Blocks of 64 KiB: 40 full blocks are two groups (32 + 8) and the tail a
# third; at 12+4 a shard is 5,462 bytes (k does not divide the block, no
# multiple of the kernel's tile), at 8+4 8 KiB, at 2+2 32 KiB.
_BS = 1 << 16
_SIZE = 40 * _BS + 4321


def _stored(tmp_path, k, m, size=_SIZE, backend="host"):
    """An object's shard files, written through encode_stream."""
    e = Erasure(k, m, _BS, backend=backend)
    payload = np.random.default_rng(29 + k).integers(
        0, 256, size=size, dtype=np.uint8).tobytes()
    paths = [tmp_path / f"shard{i}" for i in range(k + m)]
    writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size)
               for p in paths]
    n, failed = e.encode_stream(io.BytesIO(payload), writers, size, k + 1)
    assert n == size and not failed
    for w in writers:
        w.close()
    return e, paths, payload


def _open(e, paths, size, gone=(), wrap=None):
    till = e.shard_file_size(size)
    readers = [None if i in gone else bitrot.BitrotReader(
        open(p, "rb"), till, e.shard_size) for i, p in enumerate(paths)]
    if wrap:
        readers = [r if r is None else wrap(i, r)
                   for i, r in enumerate(readers)]
    return readers


def _flip(path, frame, frame_len):
    raw = bytearray(path.read_bytes())
    raw[frame * frame_len + 32 + 5] ^= 0x40
    path.write_bytes(bytes(raw))


def _stage_bytes():
    from minio_tpu.erasure import stagestats

    snap = stagestats.snapshot()
    return {s: snap[s]["bytes"] for s in ("staged", "assemble")}


def _shard_bytes(e, size):
    """Bytes of one shard of the object: what one column of all its
    dispatches holds."""
    full, tail = divmod(size, e.block_size)
    return full * e.shard_size + (-(-tail // e.k) if tail else 0)


class _ArenaWatch:
    """Counts the pool's arenas out and in, and keeps every one seen."""

    def __init__(self, monkeypatch):
        from minio_tpu.erasure import coding

        self.out = self.most = 0
        self.seen = []
        acquire, release = coding._arena_acquire, coding._arena_release

        def acq(nbytes):
            arr = acquire(nbytes)
            self.out += 1
            self.most = max(self.most, self.out)
            self.seen.append(arr)
            return arr

        def rel(arr):
            self.out -= 1
            release(arr)
            # the pool's other users (a PUT's slots) take flat arrays
            assert all(a.ndim == 1 for bucket in coding._arena_pool.values()
                       for a in bucket)

        monkeypatch.setattr(coding, "_arena_acquire", acq)
        monkeypatch.setattr(coding, "_arena_release", rel)


@pytest.mark.parametrize("lost", [1, 2])
@pytest.mark.parametrize("k,m", [(2, 2), (8, 4), (12, 4)])
def test_staged_degraded_read_and_heal_give_the_healthy_bytes(
        tmp_path, monkeypatch, k, m, lost):
    e, paths, payload = _stored(tmp_path, k, m)
    size = len(payload)
    originals = [p.read_bytes() for p in paths]
    gone = (0, k - 1)[:lost]  # the first and the last data shard
    col = _shard_bytes(e, size)

    healthy = io.BytesIO()
    before = _stage_bytes()
    e.decode_stream(healthy, _open(e, paths, size), 0, size, size)
    after = _stage_bytes()
    assert healthy.getvalue() == payload
    assert after["staged"] == before["staged"]  # no new line on its path
    assert after["assemble"] - before["assemble"] == k * col

    watch = _ArenaWatch(monkeypatch)
    out = io.BytesIO()
    before = _stage_bytes()
    assert e.decode_stream(out, _open(e, paths, size, gone), 0, size,
                           size) == size
    after = _stage_bytes()
    assert out.getvalue() == healthy.getvalue()
    # every survivor's rows went from its drive into the dispatch's
    # arena, and the host copied each served byte once: the survivors'
    # data shards and the rebuilt ones, to their place in the block
    assert after["staged"] - before["staged"] == k * col
    assert after["assemble"] - before["assemble"] == k * col
    assert watch.out == 0 and watch.most == 1 and len(watch.seen) == 3

    # a range that starts and ends inside a group, and one across two
    for off, ln in ((3 * _BS + 77, 5 * _BS + 1), (31 * _BS + 5, 2 * _BS)):
        out = io.BytesIO()
        assert e.decode_stream(out, _open(e, paths, size, gone), off, ln,
                               size) == ln
        assert out.getvalue() == payload[off:off + ln], (off, ln)

    # heal the lost shards, and a parity shard where m allows a third,
    # through the same read
    stale = (gone + (k + m - 1,))[:m]
    for i in stale:
        os.remove(paths[i])
    writers = [bitrot.BitrotWriter(open(paths[i], "wb"), e.shard_size)
               if i in stale else None for i in range(k + m)]
    before = _stage_bytes()
    e.heal(writers, _open(e, paths, size, stale), size)
    after = _stage_bytes()
    for w in writers:
        if w:
            w.close()
    for i in stale:
        assert paths[i].read_bytes() == originals[i], f"shard {i}"
    assert after["staged"] - before["staged"] == k * col
    assert after["assemble"] == before["assemble"]  # no host copy at all
    assert watch.out == 0 and watch.most == 1


def test_heal_of_parity_alone_takes_the_staged_read(tmp_path, monkeypatch):
    """All data shards present: a GET would read views, a heal still
    reconstructs, so its reads are staged all the same."""
    k, m = 4, 2
    e, paths, payload = _stored(tmp_path, k, m)
    want = paths[5].read_bytes()
    os.remove(paths[5])
    watch = _ArenaWatch(monkeypatch)
    writers = [None] * 5 + [bitrot.BitrotWriter(open(paths[5], "wb"),
                                                e.shard_size)]
    before = _stage_bytes()
    e.heal(writers, _open(e, paths, len(payload), (5,)), len(payload))
    writers[5].close()
    assert paths[5].read_bytes() == want
    assert _stage_bytes()["staged"] - before["staged"] == \
        k * _shard_bytes(e, len(payload))
    assert watch.out == 0 and len(watch.seen) == 3


@pytest.mark.parametrize("codec", ["host", "device"])
def test_bad_frame_in_a_staged_group_hands_its_column_to_a_spare(
        tmp_path, codec):
    """Data shard 0 is away, so the group is staged; shard 1 fails its
    hash in the middle of the second group.  The spare (shard 3) reads
    into the failed read's column, the codec takes the columns in the
    order given (3, 2: unsorted), the bytes are the healthy read's and
    the bad shard reaches `broken_out`."""
    from minio_tpu.erasure import coding
    from minio_tpu.ops import rs_pallas

    k, m = 2, 2
    if codec == "device":
        device_codec.plant(
            k, m, rs_pallas.PallasRSCodec(k, m, interpret=True))
    try:
        e, paths, payload = _stored(
            tmp_path, k, m, backend="tpu" if codec == "device" else "host")
        size = len(payload)
        _flip(paths[1], 35, 32 + e.shard_size)
        seen = []
        inner = e._reconstruct_shards_raw

        def raw(batch, available, wanted):
            assert batch.flags.c_contiguous and batch.shape[1] == k
            seen.append((available, wanted))
            return inner(batch, available, wanted)

        e._reconstruct_shards_raw = raw
        broken: set = set()
        out = io.BytesIO()
        assert e.decode_stream(out, _open(e, paths, size, (0,)), 0, size,
                               size, broken_out=broken) == size
        assert out.getvalue() == payload
        assert broken == {1}
        assert seen == [((1, 2), (0,)), ((3, 2), (0, 1)), ((2, 3), (0, 1))]
    finally:
        device_codec.unplant(k, m)


def test_group_that_turns_degraded_after_its_reads_began(tmp_path,
                                                         monkeypatch):
    """Every data shard is there when the group's reads go out, so no
    arena is taken; data shard 2 then fails its hash.  What was read
    into frame buffers is copied into an arena (booked as `assemble`),
    and the groups after it, which see the shard as broken before they
    read, are staged."""
    k, m = 4, 2
    e, paths, payload = _stored(tmp_path, k, m)
    size = len(payload)
    _flip(paths[2], 3, 32 + e.shard_size)
    watch = _ArenaWatch(monkeypatch)
    broken: set = set()
    out = io.BytesIO()
    before = _stage_bytes()
    assert e.decode_stream(out, _open(e, paths, size), 0, size, size,
                           broken_out=broken) == size
    after = _stage_bytes()
    assert out.getvalue() == payload and broken == {2}
    first = 32 * e.shard_size  # one column of the first group
    col = _shard_bytes(e, size)
    assert after["staged"] - before["staged"] == k * (col - first)
    # the first group: three data shards placed, four survivors copied
    # to the arena, one shard rebuilt and placed; the others k columns
    assert after["assemble"] - before["assemble"] == \
        (2 * k) * first + k * (col - first)
    assert watch.out == 0 and watch.most == 1 and len(watch.seen) == 3


class _ReadAtOnly:
    """A shard reader of the older protocol: `read_at` and no more."""

    def __init__(self, inner):
        self.read_at = inner.read_at
        self.close = inner.close


def test_reader_with_read_at_only_is_copied_into_its_column(tmp_path):
    k, m = 4, 2
    e, paths, payload = _stored(tmp_path, k, m)
    size = len(payload)
    readers = _open(e, paths, size, (1,),
                    wrap=lambda i, r: _ReadAtOnly(r) if i in (3, 4) else r)
    out = io.BytesIO()
    before = _stage_bytes()
    assert e.decode_stream(out, readers, 0, size, size) == size
    after = _stage_bytes()
    assert out.getvalue() == payload
    col = _shard_bytes(e, size)
    assert after["staged"] - before["staged"] == (k - 2) * col
    assert after["assemble"] - before["assemble"] == (k + 2) * col


@pytest.fixture
def empty_pool(monkeypatch):
    """The process's arena pool, emptied for one test."""
    from minio_tpu.erasure import coding

    gc.collect()  # an earlier test's dropped blocks come back first
    with coding._arena_lock:
        coding._pool_take_in()
        saved = dict(coding._arena_pool)
        coding._arena_pool.clear()
    monkeypatch.setattr(coding, "_arena_pool_bytes", 0)
    yield
    with coding._arena_lock:
        coding._arena_pool.clear()
        coding._arena_pool.update(saved)


class _AliasSink:
    """A writer that keeps what it was handed, as the HTTP front's
    queue does while the stream's thread reads the next group."""

    def __init__(self):
        self.chunks = []

    def write(self, data):
        self.chunks.append(data)
        return len(data)


class _BlockWatch:
    """Every response block the engine takes: a weak reference to the
    array whose death gives its buffer back, and where its bytes lie."""

    def __init__(self, monkeypatch):
        from minio_tpu.erasure import coding

        self.refs, self.addresses = [], []
        acquire = coding._block_acquire

        def acq(nblocks, block_len):
            data = acquire(nblocks, block_len)
            self.refs.append(weakref.ref(data.base))
            self.addresses.append(data.ctypes.data)
            return data

        monkeypatch.setattr(coding, "_block_acquire", acq)

    @property
    def out(self) -> int:
        gc.collect()
        return sum(r() is not None for r in self.refs)


def _block_reuse_bytes() -> int:
    from minio_tpu.erasure import stagestats

    return stagestats.snapshot()["block_reuse"]["bytes"]


def test_arenas_go_back_to_the_pool_and_no_response_aliases_one(
        tmp_path, monkeypatch, empty_pool):
    """No live chunk shares memory with a staging arena that was taken
    while it lived, nor with a block handed out after it (at 2+2 an
    arena and a block are one size class: a buffer serves as both, one
    after the other)."""
    from minio_tpu.erasure import coding

    k, m = 2, 2
    e, paths, payload = _stored(tmp_path, k, m, size=100 * _BS)
    size = len(payload)
    watch = _ArenaWatch(monkeypatch)
    blocks = _BlockWatch(monkeypatch)
    # a PUT's slot, an arena and a block are one size class at 2+2
    pooled = len(coding._arena_pool[32 * _BS])

    class Sink(_AliasSink):
        def __init__(self):
            super().__init__()
            self.arenas_before = []

        def write(self, data):
            self.arenas_before.append(len(watch.seen))
            return super().write(data)

    for _ in range(3):  # twelve degraded groups
        sink = Sink()
        assert e.decode_stream(sink, _open(e, paths, size, (0, 3)), 0,
                               size, size) == size
        assert watch.out == 0
        assert b"".join(bytes(c) for c in sink.chunks) == payload
        flats = [np.frombuffer(c, np.uint8) for c in sink.chunks]
        for n, flat in enumerate(flats):
            later = watch.seen[sink.arenas_before[n]:]
            assert not any(np.shares_memory(flat, a) for a in later)
            assert not any(np.shares_memory(flat, f) for f in flats[:n])
        assert blocks.out == len(sink.chunks) == 4
        del flats, flat
    del sink
    assert blocks.out == 0
    assert watch.most == 1 and len(watch.seen) == 12
    # the pool gave the same few buffers out again, as arenas and as
    # blocks: 24 takes, of which one arena and a request's four blocks
    # are out at a time
    assert len({a.ctypes.data for a in watch.seen}
               | set(blocks.addresses)) <= 5 + pooled
    assert 0 < coding._arena_pool_bytes <= coding._ARENA_POOL_MAX_BYTES

    # a group that loses its quorum while its reads are out: shard 1
    # fails its hash in the second group and no spare is left
    _flip(paths[1], 40, 32 + e.shard_size)
    with pytest.raises(errors.ErasureReadQuorum):
        e.decode_stream(_AliasSink(), _open(e, paths, size, (0, 3)), 0,
                        size, size)
    assert watch.out == 0 and watch.most == 1 and blocks.out == 0
    # and one that never had it takes no arena
    taken = len(watch.seen)
    with pytest.raises(errors.ErasureReadQuorum):
        e.decode_stream(_AliasSink(), _open(e, paths, size, (0, 2, 3)), 0,
                        size, size)
    assert len(watch.seen) == taken
    with pytest.raises(errors.ErasureReadQuorum):
        e.heal([bitrot.BitrotWriter(io.BytesIO(), e.shard_size), None,
                None, None], _open(e, paths, size, (0, 3)), size)
    assert watch.out == 0
    assert coding._arena_pool_bytes <= coding._ARENA_POOL_MAX_BYTES


# -- the response block of a group of full blocks (ISSUE 34) -----------------
def test_kept_chunk_keeps_its_block_out_of_the_pool(tmp_path, monkeypatch):
    """A consumer holds chunk 0 (and a slice, a memoryview of a slice
    and an array over it) while the same stream and later requests
    decode: its bytes stay the payload's, and the pool hands that
    memory out again only once the last of them is gone."""
    from minio_tpu.erasure import coding

    k, m = 2, 2
    e, paths, payload = _stored(tmp_path, k, m, size=100 * _BS)
    size = len(payload)
    blocks = _BlockWatch(monkeypatch)
    first = _AliasSink()
    e.decode_stream(first, _open(e, paths, size, (0,)), 0, size, size)
    kept = first.chunks[0]
    assert len(kept) == 32 * _BS
    where = np.frombuffer(kept, np.uint8).ctypes.data
    holders = {
        "chunk": kept,
        "slice": kept[5:5 + _BS],
        "array": np.frombuffer(kept, np.uint8)[7 * _BS:],
        "view of a view": np.frombuffer(kept[3:], np.uint8).reshape(-1)[9:].data,
    }
    del first, kept
    for name in list(holders):
        taken = len(blocks.addresses)
        for gone in ((), (0, 3)):  # healthy and degraded requests
            out = io.BytesIO()
            e.decode_stream(out, _open(e, paths, size, gone), 0, size, size)
            assert out.getvalue() == payload
        assert len(blocks.addresses) == taken + 8
        assert where not in blocks.addresses[taken:], name
        for held, off in (("chunk", 0), ("slice", 5), ("array", 7 * _BS),
                          ("view of a view", 12)):
            if held in holders:
                got = bytes(holders[held])
                assert got == payload[off:off + len(got)], (name, held)
        del holders[name], got
    # nothing refers to it any more: the class's most recent buffer
    gc.collect()
    raw = coding._pool_take(32 * _BS)
    assert raw.ctypes.data == where
    coding._arena_release(raw)
    assert blocks.out == 0


@pytest.mark.parametrize("gone", [(), "data", "mixed"],
                         ids=["healthy", "two-data-lost", "data-and-parity"])
@pytest.mark.parametrize("k,m", [(2, 2), (8, 4), (12, 4)])
def test_no_served_byte_is_one_the_request_did_not_write(
        tmp_path, monkeypatch, k, m, gone):
    """Every buffer the pool gives out, hit or miss, is full of a
    pattern; each served byte is the payload's all the same, whole and
    ranged."""
    from minio_tpu.erasure import coding

    e, paths, payload = _stored(tmp_path, k, m)
    size = len(payload)
    gone = {(): (), "data": (0, k - 1), "mixed": (1, k + m - 1)}[gone]
    take = coding._pool_take

    def patterned(nbytes):
        arr = take(nbytes)
        if arr is None:
            arr = np.empty(nbytes, np.uint8)
        arr[:] = 0xA5
        return arr

    monkeypatch.setattr(coding, "_pool_take", patterned)
    reused = _block_reuse_bytes()
    for off, ln in ((0, size), (3 * _BS + 77, 5 * _BS + 1),
                    (31 * _BS + 5, 2 * _BS), (_BS, 39 * _BS),
                    (40 * _BS - 1, 4322)):
        sink = _AliasSink()
        assert e.decode_stream(sink, _open(e, paths, size, gone), off, ln,
                               size) == ln
        assert b"".join(bytes(c) for c in sink.chunks) \
            == payload[off:off + ln], (off, ln)
    # every full-block group was told it came from the pool
    assert _block_reuse_bytes() - reused == (40 + 6 + 3 + 39 + 1) * _BS


def test_abandoned_and_failed_streams_leave_no_block_out(tmp_path,
                                                         monkeypatch):
    from minio_tpu.erasure.objects import ErasureObjects, _IterSink

    k, m = 2, 2
    e, paths, payload = _stored(tmp_path, k, m, size=200 * _BS)
    size = len(payload)
    blocks = _BlockWatch(monkeypatch)

    # the consumer goes away after the first chunk, as a client that
    # disconnects does (_stream_object: GeneratorExit -> abandon)
    sink = _IterSink(maxsize=2)
    worker = threading.Thread(
        target=ErasureObjects._decode_to_sink,
        args=(e, sink, _open(e, paths, size, (0, 3)), 0, size, size),
        daemon=True)
    worker.start()
    chunks = iter(sink)
    head = next(chunks)
    assert bytes(head) == payload[:32 * _BS]
    sink.abandon()
    worker.join(30)
    assert not worker.is_alive()
    assert isinstance(sink.error, BrokenPipeError)
    assert blocks.refs and blocks.out >= 1  # `head` is still held
    sink.abandon()  # what the producer put after the drain
    del head, chunks, sink, worker
    assert blocks.out == 0

    # a group loses its quorum in the middle of the stream, with the
    # chunks before it in a consumer's hands
    _flip(paths[1], 70, 32 + e.shard_size)
    kept = _AliasSink()
    with pytest.raises(errors.ErasureReadQuorum):
        e.decode_stream(kept, _open(e, paths, size, (0, 3)), 0, size, size)
    assert b"".join(bytes(c) for c in kept.chunks) == payload[:64 * _BS]
    assert blocks.out == 2
    del kept
    assert blocks.out == 0


@pytest.mark.parametrize("size", [4321, _BS - 1, 3 * _BS + 4321],
                         ids=["inline", "under-a-block", "tail"])
@pytest.mark.parametrize("gone", [(), (0,)], ids=["healthy", "degraded"])
def test_tail_blocks_and_small_objects_take_no_block(tmp_path, monkeypatch,
                                                     size, gone):
    """A tail and an object under one block are one-off sizes that
    leave as a copy: their block is a fresh array, and the healthy ones
    ask the pool for nothing at all."""
    from minio_tpu.erasure import coding

    k, m = 4, 2
    e, paths, payload = _stored(tmp_path, k, m, size=size)
    blocks = _BlockWatch(monkeypatch)
    asked = []
    take = coding._pool_take
    monkeypatch.setattr(coding, "_pool_take",
                        lambda n: asked.append(n) or take(n))
    reused = _block_reuse_bytes()
    full = size // _BS
    off = full * _BS  # the tail alone, then the whole object
    for lo, ln in ((off, size - off), (0, size)):
        out = io.BytesIO()
        assert e.decode_stream(out, _open(e, paths, size, gone), lo, ln,
                               size) == ln
        assert out.getvalue() == payload[lo:lo + ln]
    assert len(blocks.refs) == (1 if full else 0)
    tail_arena = [n for n in asked if n < _BS + k]
    assert len(tail_arena) == (2 if gone else 0)  # the staged read's
    assert len(asked) - len(tail_arena) == (len(gone) + 1 if full else 0)
    assert _block_reuse_bytes() - reused in (0, full * _BS)


def test_put_slots_stay_pooled_through_a_burst_of_gets(tmp_path, monkeypatch,
                                                       empty_pool):
    """Eight streams of two-group objects at 12+4, every chunk held to
    the end (the most a closed loop keeps alive), with the budget cut
    as the blocks are (64 KiB for 1 MiB): the slots of the PUT before
    them are still in the pool afterwards, and the next PUT takes them
    from there."""
    from minio_tpu.erasure import coding

    scale = coding.BLOCK_SIZE_V2 // _BS
    monkeypatch.setattr(coding, "_ARENA_POOL_MAX_BYTES",
                        coding._ARENA_POOL_MAX_BYTES // scale)
    k, m = 12, 4
    e, paths, payload = _stored(tmp_path, k, m, size=64 * _BS)
    size = len(payload)
    slot = 32 * _BS  # encode_stream's read slot: a full batch
    slots = {a.ctypes.data for a in coding._arena_pool[slot]}
    assert len(slots) == 2
    sinks = [_AliasSink() for _ in range(8)]
    streams = [threading.Thread(
        target=e.decode_stream,
        args=(s, _open(e, paths, size, (0, 6)), 0, size, size))
        for s in sinks]
    for t in streams:
        t.start()
    for t in streams:
        t.join(60)
    assert not any(t.is_alive() for t in streams)
    for s in sinks:
        assert b"".join(bytes(c) for c in s.chunks) == payload
    del sinks, s
    gc.collect()
    # a 12+4 arena is its own class, 32 x 12 x 5,462 bytes; a slot
    # and a block are one class, so the two slots served as blocks
    # and came back with the fourteen that the burst added
    assert coding._arena_pool_bytes <= coding._ARENA_POOL_MAX_BYTES
    assert 32 * k * e.shard_size in coding._arena_pool
    assert len(coding._arena_pool[slot]) == 16
    assert slots <= {a.ctypes.data for a in coding._arena_pool[slot]}
    # the next PUT took its two slots from there: a fresh pair would
    # have come back as a seventeenth and an eighteenth
    _stored(tmp_path, k, m, size=64 * _BS)
    assert len(coding._arena_pool[slot]) == 16


def test_pool_under_threads_hands_no_buffer_out_twice(monkeypatch,
                                                     empty_pool):
    """Sixteen threads take blocks and arenas of two size classes from
    a pool too small for them (so classes are evicted), write their own
    mark, keep a slice across a switch of threads and find the mark
    unchanged; afterwards the pool's count of bytes is the bytes in it,
    within the budget, and no buffer lies in it twice."""
    import sys

    from minio_tpu.erasure import coding

    sizes = (4096, 12288)
    monkeypatch.setattr(coding, "_ARENA_POOL_MAX_BYTES", 10 * sizes[1])
    wrong = []

    def churn(mark: int) -> None:
        for n in range(300):
            size = sizes[(n + mark) % 2]
            if n % 3:
                block = coding._block_acquire(1, size)
                kept = block.reshape(-1)[size // 2:].data
                del block
            else:
                kept = arena = coding._arena_acquire(size)
            kept_view = np.frombuffer(kept, np.uint8)
            kept_view[:] = mark
            time.sleep(0)
            if not (kept_view == mark).all():
                wrong.append((mark, n))
            del kept_view
            if n % 3 == 0:
                coding._arena_release(arena)
                del arena
            del kept

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn, args=(mark,))
                   for mark in range(1, 17)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not wrong
    gc.collect()
    with coding._arena_lock:
        coding._pool_take_in()
        pooled = [a for bucket in coding._arena_pool.values() for a in bucket]
        assert coding._arena_pool_bytes == sum(a.nbytes for a in pooled)
    assert 0 < coding._arena_pool_bytes <= coding._ARENA_POOL_MAX_BYTES
    assert len({a.ctypes.data for a in pooled}) == len(pooled)
    assert set(coding._arena_pool) <= set(sizes)


def test_slow_client_gets_its_own_bytes_through_the_handler(tmp_path):
    """The real handler (`_pump_stream`): one client takes its body a
    little at a time, so its chunks wait in the sink's queue, in the
    pump's read-ahead and in the socket's buffer, while two others
    fetch another object of the same size classes over and over, out
    of the same pool.  Every body is its own object's, byte for byte."""
    import http.client

    from minio_tpu.server import sigv4

    from .s3_harness import S3TestServer

    size = (34 << 20) + 4321  # groups of 32 and 2 blocks, and a tail
    rng = np.random.default_rng(34)
    bodies = {name: rng.integers(0, 256, size, dtype=np.uint8).tobytes()
              for name in ("slow", "fast")}
    srv = S3TestServer(str(tmp_path))
    try:
        assert srv.request("PUT", "/blocks").status == 200
        for name, body in bodies.items():
            assert srv.request("PUT", f"/blocks/{name}",
                               data=body).status == 200

        def get(name: str, pause_s: float) -> bytes:
            headers = sigv4.sign_request(
                "GET", f"/blocks/{name}", [], {"host": srv.host}, b"",
                srv.ak, srv.sk)
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=60)
            try:
                conn.request("GET", f"/blocks/{name}", headers=headers)
                resp = conn.getresponse()
                assert resp.status == 200
                got = bytearray()
                while piece := resp.read(1 << 20):
                    got += piece
                    time.sleep(pause_s)
                return bytes(got)
            finally:
                conn.close()

        reused = _block_reuse_bytes()
        with cf.ThreadPoolExecutor(3) as clients:
            slow = clients.submit(get, "slow", 0.05)
            fast = [clients.submit(
                lambda: [get("fast", 0.0) for _ in range(3)])
                for _ in range(2)]
            assert slow.result(120) == bodies["slow"]
            for f in fast:
                assert f.result(120) == [bodies["fast"]] * 3
        assert get("slow", 0.0) == bodies["slow"]
        assert _block_reuse_bytes() - reused >= 34 << 20
    finally:
        srv.close()


@pytest.mark.parametrize("available,wanted", [
    ((1, 2, 3, 4), (0,)), ((1, 5, 3, 4), (0, 2)), ((5, 4, 3, 0), (1, 2)),
    ((4, 5, 1, 0), (2, 3)),
])
def test_reconstruct_matrix_takes_columns_in_the_order_given(available,
                                                             wanted):
    """A spare that took a failed read's column leaves `available`
    unsorted: the matrix has to follow the columns, not their sort."""
    from minio_tpu.ops import gf256, host, rs_tpu

    k, m = 4, 2
    shards = np.random.default_rng(5).integers(
        0, 256, (k, 64), dtype=np.uint8)
    full = np.concatenate([shards, gf256.encode_np(shards, m)])
    src = full[list(available)]
    rm = gf256.reconstruct_matrix(k, m, available, wanted)
    rebuilt = host.HostRSCodec(k, m).matmul(rm, src)
    assert np.array_equal(rebuilt, full[list(wanted)])
    assert np.array_equal(
        rs_tpu.reconstruct_bits_matrix(k, m, available, wanted),
        gf256.gf_matrix_to_bits(rm).astype(np.int8))
    with pytest.raises(ValueError):
        gf256.decode_matrix(k, m, (1, 1, 2, 3))


def test_bitrot_file_size_math():
    e = Erasure(8, 4)
    assert bitrot.bitrot_shard_file_size(0, e.shard_size) == 0
    # 1 MiB part -> shard 128KiB, one block -> 32 + 131072
    assert bitrot.bitrot_shard_file_size(e.shard_size, e.shard_size) == 32 + e.shard_size


# ------------------------------------------ what a PUT leaves on the drives
# Through the object layer, every shard file read back raw and held to the
# oracles: where the shard lies (hashOrder), the body's split, the parity
# (ops/gf256.py, numpy) and each frame's 32-byte prefix (ops/hh_device.py
# hh256_batch_np, numpy u64).  A writer that frames a wrong digest, or an
# encode that hands over a wrong row, fails here on the file itself.
_ONDISK_BLOCK = 1 << 20


def _expected_files(body: bytes, k: int, m: int) -> list[bytes]:
    """The k+m shard files of one erasure stream, hash prefixes and all:
    the whole blocks (hashed in one call: the oracle loops over packets
    in Python), then the shorter tail block."""
    from minio_tpu.ops import gf256, hh_device

    data = np.frombuffer(body, dtype=np.uint8)
    files = [bytearray() for _ in range(k + m)]
    nfull = len(body) // _ONDISK_BLOCK
    for pieces in (data[:nfull * _ONDISK_BLOCK].reshape(nfull, _ONDISK_BLOCK),
                   data[nfull * _ONDISK_BLOCK:].reshape(1, -1)):
        nb, length = pieces.shape
        if not nb or not length:
            continue
        shard = -(-length // k)
        split = np.zeros((nb, k * shard), dtype=np.uint8)
        split[:, :length] = pieces
        rows = split.reshape(nb, k, shard)
        rows = np.concatenate(
            [rows, np.stack([gf256.encode_np(r, m) for r in rows])], axis=1)
        digests = hh_device.hh256_batch_np(
            rows.reshape(nb * (k + m), shard)).reshape(nb, k + m, 32)
        for b in range(nb):
            for i in range(k + m):
                files[i] += digests[b, i].tobytes() + rows[b, i].tobytes()
    return [bytes(f) for f in files]


def _shard_index_of_drive(key: str, n: int) -> list[int]:
    """0-based shard index that drive 0..n-1 holds (the reference's
    hashOrder, cmd/erasure-metadata-utils.go:107)."""
    import zlib

    start = (zlib.crc32(key.encode()) & 0xFFFFFFFF) % n
    return [(start + i) % n for i in range(1, n + 1)]


class TestOnDiskAgainstOracle:
    GEOMETRIES = [(2, 2), (4, 2), (12, 4)]  # 12+4: 87,382-byte shards

    @pytest.fixture()
    def drives(self, tmp_path, request):
        from minio_tpu.erasure import multipart  # noqa: F401  (binds methods)
        from minio_tpu.erasure.objects import ErasureObjects
        from minio_tpu.storage.local import LocalStorage

        k, m = request.param
        disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(k + m)]
        for d in disks:
            d.make_volume("bkt")
        return k, m, disks, ErasureObjects(disks, default_parity=m)

    @staticmethod
    def _body(size, seed):
        return np.random.default_rng(seed).integers(
            0, 256, size, dtype=np.uint8).tobytes()

    @staticmethod
    def _held(disks, key, part):
        """Raw bytes of `part` on every drive: the part file, or for an
        inline object the shard inside xl.meta."""
        import glob

        out = []
        for d in disks:
            paths = glob.glob(os.path.join(
                glob.escape(os.path.join(d.root, "bkt", key)), "*", part))
            if paths:
                assert len(paths) == 1, paths
                with open(paths[0], "rb") as f:
                    out.append(f.read())
            else:
                out.append(d.read_version("bkt", key, read_data=True).data)
        return out

    def _check(self, disks, key, part, body, k, m):
        want = _expected_files(body, k, m)
        held = self._held(disks, key, part)
        for drive, shard in enumerate(_shard_index_of_drive(key, k + m)):
            assert held[drive] is not None, f"drive {drive} holds nothing"
            assert len(held[drive]) == len(want[shard]), (drive, shard)
            assert held[drive] == want[shard], (
                f"drive {drive}, shard {shard} of {k}+{m}")

    @pytest.mark.parametrize("size", [
        100,                 # inline: shards live in xl.meta
        200_000,             # one short block
        (1 << 20) * 3 + 17,  # whole blocks and a tail frame
        (4 << 20),           # whole blocks alone
    ])
    @pytest.mark.parametrize("drives", GEOMETRIES, indirect=True,
                             ids=lambda g: f"{g[0]}+{g[1]}")
    def test_put_object(self, drives, size):
        k, m, disks, api = drives
        body = self._body(size, size)
        key = f"put/{size}"
        api.put_object("bkt", key, io.BytesIO(body), size)
        self._check(disks, key, "part.1", body, k, m)

    @pytest.mark.parametrize("drives", GEOMETRIES, indirect=True,
                             ids=lambda g: f"{g[0]}+{g[1]}")
    def test_multipart_upload(self, drives):
        k, m, disks, api = drives
        p1 = self._body(5 << 20, 13)
        p2 = self._body((1 << 20) + 313, 14)
        up = api.new_multipart_upload("bkt", "mp")
        e1 = api.put_object_part("bkt", "mp", up, 1, io.BytesIO(p1), len(p1))
        e2 = api.put_object_part("bkt", "mp", up, 2, io.BytesIO(p2), len(p2))
        api.complete_multipart_upload(
            "bkt", "mp", up, [(1, e1.etag), (2, e2.etag)])
        self._check(disks, "mp", "part.1", p1, k, m)
        self._check(disks, "mp", "part.2", p2, k, m)

    @pytest.mark.parametrize("drives", GEOMETRIES, indirect=True,
                             ids=lambda g: f"{g[0]}+{g[1]}")
    def test_heal_after_a_drive_lost_its_files(self, drives):
        import shutil

        k, m, disks, api = drives
        size = (2 << 20) + 137 * 4
        body = self._body(size, 17)
        api.put_object("bkt", "h", io.BytesIO(body), size)
        # the drive that holds data shard 0: the heal rebuilds a data row
        victim = _shard_index_of_drive("h", k + m).index(0)
        shutil.rmtree(os.path.join(disks[victim].root, "bkt", "h"))
        res = api.heal_object("bkt", "h")
        assert not res.failed and res.healed_drives == 1
        self._check(disks, "h", "part.1", body, k, m)
