"""Differential suite for the pipelined object data plane (ISSUE 5).

The pipelined PUT path (arena readinto ring, deferred etag folding,
per-drive chained shard writes, pool-dispatched host encodes) must be
BYTE-IDENTICAL to the serial reference path — shard files, xl.meta and
etags — across full/tail/inline/multipart shapes, survive hostile write
interleavings without observing a recycled arena, and leak neither
threads nor arenas.
"""

import hashlib
import io
import os
import random
import shutil
import tempfile
import threading
import time

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.erasure import coding as coding_mod
from minio_tpu.erasure import multipart  # noqa: F401  (binds methods)
from minio_tpu.erasure.coding import Erasure
from minio_tpu.erasure.objects import ErasureObjects, _HashingReader
from minio_tpu.storage.local import LocalStorage


class _KeepOpen(io.BytesIO):
    def close(self):
        pass


def _stream(e, data, pipelined, defer, nwriters=None, wrap=None):
    """encode_stream through BitrotWriters into memory; returns
    (etag, [shard bytes])."""
    n = nwriters or (e.k + e.m)
    bufs = [_KeepOpen() for _ in range(n)]
    writers = [bitrot.BitrotWriter(b, e.shard_size) for b in bufs]
    if wrap is not None:
        writers = [wrap(w) for w in writers]
    hr = _HashingReader(io.BytesIO(data), len(data), defer=defer)
    total, failed = e.encode_stream(hr, writers, len(data), e.k + 1,
                                    pipelined=pipelined)
    assert total == len(data) and not failed
    return hr.etag, [b.getvalue() for b in bufs]


SHAPES = [
    (4, 2, 1 << 18),   # aligned: bs % k == 0
    (3, 2, 1 << 18),   # unaligned: per-block shard padding path
    (8, 4, 1 << 20),   # production default geometry
]

SIZES = [1, 1000, (1 << 18) - 1, 1 << 18, (1 << 18) + 1,
         5 * (1 << 18) + 12345, 40 * (1 << 18) + 7]


class TestDifferentialEncode:
    def test_pipelined_matches_serial_across_shapes(self):
        rng = np.random.default_rng(11)
        for k, m, bs in SHAPES:
            e = Erasure(k, m, bs, backend="host")
            for size in SIZES:
                data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
                etag_p, shards_p = _stream(e, data, pipelined=True,
                                           defer=True)
                etag_s, shards_s = _stream(e, data, pipelined=False,
                                           defer=False)
                assert etag_p == etag_s == hashlib.md5(data).hexdigest(), \
                    (k, m, size)
                for i, (a, b) in enumerate(zip(shards_p, shards_s)):
                    assert a == b, (k, m, size, i)

    def test_zero_byte_stream(self):
        e = Erasure(4, 2, 1 << 18, backend="host")
        etag_p, shards_p = _stream(e, b"", pipelined=True, defer=True)
        etag_s, shards_s = _stream(e, b"", pipelined=False, defer=False)
        assert etag_p == etag_s == hashlib.md5(b"").hexdigest()
        assert shards_p == shards_s == [b""] * 6

    def test_env_knob_forces_serial(self, monkeypatch):
        """MINIO_TPU_DATAPLANE_PIPELINE=0 restores the reference path
        end to end (the escape hatch the README documents)."""
        monkeypatch.setenv("MINIO_TPU_DATAPLANE_PIPELINE", "0")
        assert not coding_mod.pipeline_enabled()
        hr = _HashingReader(io.BytesIO(b"x"), 1)
        assert hr._defer is False
        monkeypatch.setenv("MINIO_TPU_DATAPLANE_PIPELINE", "1")
        assert coding_mod.pipeline_enabled()


class _SlowJitterWriter:
    """BitrotWriter wrapper with seeded random delays and an order log:
    stresses arena recycling (slow writers hold batches while the reader
    refills slots) and proves per-drive frame order is preserved."""

    def __init__(self, inner, rng, order_log):
        self.inner = inner
        self.rng = rng
        self.order = order_log

    @property
    def shard_size(self):
        return self.inner.shard_size

    def write_frames(self, blocks):
        time.sleep(self.rng.random() * 0.01)
        self.order.append(("frames", blocks.shape[0]))
        self.inner.write_frames(blocks)

    def write(self, block):
        time.sleep(self.rng.random() * 0.01)
        self.order.append(("write", 1))
        self.inner.write(block)

    def close(self):
        self.inner.close()


class TestSlowDriveInterleaving:
    def test_slow_writers_never_observe_recycled_arena(self):
        """With per-drive jitter, batches are written in wildly
        different interleavings across drives — yet every shard file
        must still match the serial reference byte for byte (an arena
        recycled while a slow writer still reads it would corrupt the
        slow drive's later frames) and per-drive frame counts must sum
        to the stream's block count in order."""
        rng_data = np.random.default_rng(13)
        e = Erasure(4, 2, 1 << 18, backend="host")
        data = rng_data.integers(
            0, 256, 24 * (1 << 18) + 321, dtype=np.uint8).tobytes()
        etag_s, shards_s = _stream(e, data, pipelined=False, defer=False)
        logs = [[] for _ in range(6)]
        seeds = iter(range(6))

        def wrap(w, _it=iter(range(6))):
            i = next(_it)
            return _SlowJitterWriter(w, random.Random(100 + i), logs[i])

        etag_p, shards_p = _stream(e, data, pipelined=True, defer=True,
                                   wrap=wrap)
        assert etag_p == etag_s
        for i, (a, b) in enumerate(zip(shards_p, shards_s)):
            assert a == b, f"shard {i} corrupted under slow interleaving"
        nblocks = -(-len(data) // e.block_size)
        for lg in logs:
            assert sum(n for _, n in lg) == nblocks


class TestFullObjectDifferential:
    """put_object through real drives: shard files, xl.meta and etags
    byte-identical between pipelined and serial paths."""

    @pytest.fixture()
    def two_sets(self, monkeypatch):
        roots = [tempfile.mkdtemp(prefix="dp-diff-") for _ in range(2)]
        # pin every nondeterministic input so xl.meta can be compared
        # byte for byte
        monkeypatch.setattr("minio_tpu.erasure.objects.new_data_dir",
                            lambda: "d1d1d1d1-1111-4111-8111-111111111111")
        apis = []
        for root in roots:
            disks = [LocalStorage(os.path.join(root, f"d{i}"))
                     for i in range(6)]
            for d in disks:
                d.make_volume("bkt")
            apis.append(ErasureObjects(disks))
        yield roots, apis
        for root in roots:
            shutil.rmtree(root, ignore_errors=True)

    @staticmethod
    def _drive_files(root):
        out = {}
        for dirpath, _, files in sorted(os.walk(root)):
            for f in sorted(files):
                p = os.path.join(dirpath, f)
                out[os.path.relpath(p, root)] = open(p, "rb").read()
        return out

    @pytest.mark.parametrize("size", [100, 200_000, 3 * (1 << 20) + 17])
    def test_put_object_identical(self, two_sets, monkeypatch, size):
        from minio_tpu.erasure.objects import PutObjectOptions

        roots, apis = two_sets
        data = np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        opts = PutObjectOptions(mod_time=1_700_000_000.0)
        monkeypatch.setenv("MINIO_TPU_DATAPLANE_PIPELINE", "1")
        oi_p = apis[0].put_object("bkt", "o", io.BytesIO(data), size,
                                  opts)
        monkeypatch.setenv("MINIO_TPU_DATAPLANE_PIPELINE", "0")
        oi_s = apis[1].put_object("bkt", "o", io.BytesIO(data), size,
                                  opts)
        assert oi_p.etag == oi_s.etag == hashlib.md5(data).hexdigest()
        files_p = self._drive_files(roots[0])
        files_s = self._drive_files(roots[1])
        assert files_p.keys() == files_s.keys()
        for name in files_p:
            assert files_p[name] == files_s[name], name
        # and the object reads back
        oi, stream = apis[0].get_object("bkt", "o")
        assert b"".join(stream) == data

    def test_multipart_identical(self, two_sets, monkeypatch):
        roots, apis = two_sets
        rng = np.random.default_rng(99)
        p1 = rng.integers(0, 256, 6 << 20, dtype=np.uint8).tobytes()
        p2 = rng.integers(0, 256, (1 << 20) + 13, dtype=np.uint8).tobytes()
        etags = []
        for idx, mode in ((0, "1"), (1, "0")):
            monkeypatch.setenv("MINIO_TPU_DATAPLANE_PIPELINE", mode)
            api = apis[idx]
            uid = api.new_multipart_upload("bkt", "mp")
            pi1 = api.put_object_part("bkt", "mp", uid, 1,
                                      io.BytesIO(p1), len(p1))
            pi2 = api.put_object_part("bkt", "mp", uid, 2,
                                      io.BytesIO(p2), len(p2))
            oi = api.complete_multipart_upload(
                "bkt", "mp", uid, [(1, pi1.etag), (2, pi2.etag)])
            etags.append((pi1.etag, pi2.etag, oi.etag))
            _, stream = api.get_object("bkt", "mp")
            assert b"".join(stream) == p1 + p2
        assert etags[0] == etags[1]
        assert etags[0][0] == hashlib.md5(p1).hexdigest()
        # shard part files byte-identical (xl.meta differs only by
        # commit timestamps/data-dir which multipart mints per upload)
        for root_p, root_s in [roots]:
            pass
        files_p = {k: v for k, v in self._drive_files(roots[0]).items()
                   if k.endswith(("part.1", "part.2"))}
        files_s = {k: v for k, v in self._drive_files(roots[1]).items()
                   if k.endswith(("part.1", "part.2"))}
        norm_p = sorted(v for v in files_p.values())
        norm_s = sorted(v for v in files_s.values())
        assert norm_p == norm_s


class TestReadAtRegression:
    """BitrotReader.read_at: preallocated output + batched frame groups
    (the `out +=` rewrite was quadratic in frame count)."""

    def _shard_file(self, nblocks=300, shard=1024):
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, nblocks * shard,
                               dtype=np.uint8).tobytes()
        buf = _KeepOpen()
        w = bitrot.BitrotWriter(buf, shard)
        for i in range(nblocks):
            w.write(payload[i * shard:(i + 1) * shard])
        return payload, buf.getvalue(), shard

    def test_many_small_ranges_correct(self):
        payload, blob, shard = self._shard_file()
        r = bitrot.BitrotReader(io.BytesIO(blob), len(payload), shard)
        rng = random.Random(3)
        for _ in range(200):
            start_block = rng.randrange(0, 300)
            off = start_block * shard
            # frame-format contract: whole frames only (a short read is
            # legal only for a stream's final block)
            nframes = rng.randrange(1, 6)
            length = min(nframes * shard, len(payload) - off)
            assert r.read_at(off, length) == payload[off:off + length]

    def test_short_tail_block_range(self):
        """A stream whose final block is short: read_at spanning into
        the tail must return exactly the stored bytes."""
        rng = np.random.default_rng(21)
        shard = 1024
        payload = rng.integers(0, 256, 5 * shard + 123,
                               dtype=np.uint8).tobytes()
        buf = _KeepOpen()
        w = bitrot.BitrotWriter(buf, shard)
        for i in range(0, len(payload), shard):
            w.write(payload[i:i + shard])
        r = bitrot.BitrotReader(io.BytesIO(buf.getvalue()), len(payload),
                                shard)
        assert r.read_at(0, len(payload)) == payload
        assert r.read_at(4 * shard, shard + 123) == payload[4 * shard:]

    def test_large_range_uses_batched_group_reads(self):
        payload, blob, shard = self._shard_file()

        class CountingIO(io.BytesIO):
            reads = 0

            def readinto(self, b):
                CountingIO.reads += 1
                return super().readinto(b)

            def read(self, n=-1):
                CountingIO.reads += 1
                return super().read(n)

        src = CountingIO(blob)
        r = bitrot.BitrotReader(src, len(payload), shard)
        CountingIO.reads = 0
        out = r.read_at(0, len(payload))
        assert out == payload
        # 300 frames in groups of READ_AT_GROUP: a handful of reads,
        # not one per frame
        assert CountingIO.reads <= -(-300 // r.READ_AT_GROUP) + 1

    def test_rawiobase_read_only_stream(self):
        """Remote RPC shard streams subclass RawIOBase with only read():
        the inherited readinto raises NotImplementedError — the frame
        reader must fall back to read() (a silent failure here broke
        cross-node heal/GET)."""
        payload, blob, shard = self._shard_file(nblocks=8)

        class ReadOnlyStream(io.RawIOBase):
            def __init__(self, data):
                self._b = io.BytesIO(data)

            def read(self, n=-1):
                return self._b.read(n)

            def seek(self, off, whence=0):
                return self._b.seek(off, whence)

        r = bitrot.BitrotReader(ReadOnlyStream(blob), len(payload), shard)
        assert r.read_at(0, len(payload)) == payload
        got = r.read_blocks(0, 4, shard)
        assert got.tobytes() == payload[: 4 * shard]

    def test_tail_and_alignment_errors_preserved(self):
        payload, blob, shard = self._shard_file(nblocks=4)
        from minio_tpu.storage import errors as st_errors

        r = bitrot.BitrotReader(io.BytesIO(blob), len(payload), shard)
        with pytest.raises(st_errors.InvalidArgument):
            r.read_at(17, 100)  # unaligned offset
        # range past EOF -> truncated frame group
        with pytest.raises(st_errors.FileCorrupt):
            r.read_at(0, len(payload) + shard)


class _ReadOnlyStream(io.RawIOBase):
    """A remote shard stream's shape: read() and seek(), no readinto."""

    def __init__(self, data):
        self._b = io.BytesIO(data)

    def read(self, n=-1):
        return self._b.read(n)

    def seek(self, off, whence=0):
        return self._b.seek(off, whence)


class _DirectLike:
    """The O_DIRECT staging reader's shape: readinto, no descriptor."""

    def __init__(self, data):
        self._b = io.BytesIO(data)
        self.readinto = self._b.readinto
        self.read = self._b.read
        self.seek = self._b.seek
        self.close = self._b.close


class TestReadBlocksOut:
    """read_blocks(out=): the rows of a frame group read where the
    caller wants them (ISSUE 29: a column of a dispatch's batch), by
    every kind of stream, verified like any other read."""

    NBLOCKS, SHARD = 8, 1000

    def _blob(self):
        rng = np.random.default_rng(29)
        payload = rng.integers(0, 256, self.NBLOCKS * self.SHARD,
                               dtype=np.uint8).tobytes()
        buf = _KeepOpen()
        w = bitrot.BitrotWriter(buf, self.SHARD)
        for i in range(self.NBLOCKS):
            w.write(payload[i * self.SHARD:(i + 1) * self.SHARD])
        return payload, buf.getvalue()

    def _stream(self, kind, blob, tmp_path):
        if kind in ("file", "odirect"):
            (tmp_path / "part.1").write_bytes(blob)
            if kind == "file":  # a drive whose file system refuses O_DIRECT
                return open(tmp_path / "part.1", "rb")
            from minio_tpu.storage import local
            try:  # what a drive hands out where the file system allows it
                return local._DirectReader(str(tmp_path / "part.1"))
            except OSError as e:
                pytest.skip(f"no O_DIRECT under {tmp_path}: {e}")
        return {"bytesio": io.BytesIO, "readinto": _DirectLike,
                "read_only": _ReadOnlyStream}[kind](blob)

    @pytest.mark.parametrize("kind", ["file", "odirect", "bytesio",
                                      "readinto", "read_only"])
    def test_rows_land_in_out(self, kind, tmp_path):
        from minio_tpu.erasure import stagestats

        payload, blob = self._blob()
        r = bitrot.BitrotReader(self._stream(kind, blob, tmp_path),
                                len(payload), self.SHARD)
        # one shard's column of a (B, K, S) batch: rows strided
        batch = np.zeros((4, 3, self.SHARD), np.uint8)
        before = stagestats.snapshot()["staged"]["bytes"]
        for first, row in ((6, 2), (0, 0)):  # a jump back between them
            got = r.read_blocks(first * self.SHARD, 2, self.SHARD,
                                out=batch[row:row + 2, 1, :])
            assert np.shares_memory(got, batch)
        # in turn with the last one: no seek between them
        r.read_blocks(2 * self.SHARD, 2, self.SHARD,
                      out=np.empty((2, self.SHARD), np.uint8))
        want = np.frombuffer(payload, np.uint8).reshape(-1, self.SHARD)
        assert np.array_equal(batch[:2, 1, :], want[0:2])
        assert np.array_equal(batch[2:, 1, :], want[6:8])
        assert not batch[:, 0, :].any() and not batch[:, 2, :].any()
        # a read without `out` goes on where the placed ones left off
        assert r.read_blocks(4 * self.SHARD, 2, self.SHARD).tobytes() == \
            payload[4 * self.SHARD:6 * self.SHARD]
        staged = stagestats.snapshot()["staged"]["bytes"] - before
        assert staged == (0 if kind == "read_only" else 6 * self.SHARD)
        r.close()

    @pytest.mark.parametrize("kind", ["file", "bytesio", "read_only"])
    def test_flipped_byte_raises_and_leaves_the_position(self, kind,
                                                         tmp_path):
        from minio_tpu.storage import errors as st_errors

        payload, blob = self._blob()
        bad = bytearray(blob)
        bad[5 * (32 + self.SHARD) + 32 + 17] ^= 0x01  # in block 5
        r = bitrot.BitrotReader(self._stream(kind, bytes(bad), tmp_path),
                                len(payload), self.SHARD)
        out = np.empty((4, self.SHARD), np.uint8)
        assert r.read_blocks(0, 4, self.SHARD, out=out) is out
        pos = r._pos
        with pytest.raises(st_errors.FileCorrupt):
            r.read_blocks(4 * self.SHARD, 4, self.SHARD, out=out)
        # not moved on to the group's end: the next read seeks
        assert r._pos in (pos, -1)
        assert r.read_blocks(6 * self.SHARD, 2, self.SHARD,
                             out=out[:2]).tobytes() == \
            payload[6 * self.SHARD:]
        assert r.read_blocks(0, 1, self.SHARD).tobytes() == \
            payload[:self.SHARD]

    def test_fresh_reader_keeps_its_position_on_a_bad_frame(self, tmp_path):
        from minio_tpu.storage import errors as st_errors

        payload, blob = self._blob()
        bad = bytearray(blob)
        bad[40] ^= 0x80
        for kind in ("file", "bytesio"):
            r = bitrot.BitrotReader(
                self._stream(kind, bytes(bad), tmp_path),
                len(payload), self.SHARD)
            with pytest.raises(st_errors.FileCorrupt):
                r.read_blocks(0, 2, self.SHARD,
                              out=np.empty((2, self.SHARD), np.uint8))
            assert r._pos == -1

    @pytest.mark.parametrize("out", [
        np.empty((2, 2000), np.uint8)[:, ::2],      # rows not contiguous
        np.empty((1000, 2), np.uint8).T,            # column-major
        np.empty((3, 1000), np.uint8),              # another group
        np.empty((2, 1000), np.uint16),             # another type
        np.broadcast_to(np.zeros(1000, np.uint8), (2, 1000)),  # read-only
    ], ids=["strided_row", "transposed", "shape", "dtype", "read_only"])
    def test_rows_that_cannot_take_a_read_are_refused(self, out):
        payload, blob = self._blob()
        r = bitrot.BitrotReader(io.BytesIO(blob), len(payload), self.SHARD)
        with pytest.raises(ValueError):
            r.read_blocks(0, 2, self.SHARD, out=out)
        assert r._pos == -1  # nothing was read

    def test_short_final_block_and_truncation(self, tmp_path):
        from minio_tpu.storage import errors as st_errors

        rng = np.random.default_rng(30)
        payload = rng.integers(0, 256, 2 * self.SHARD + 123,
                               dtype=np.uint8).tobytes()
        buf = _KeepOpen()
        w = bitrot.BitrotWriter(buf, self.SHARD)
        for i in range(0, len(payload), self.SHARD):
            w.write(payload[i:i + self.SHARD])
        for kind in ("file", "bytesio"):
            r = bitrot.BitrotReader(
                self._stream(kind, buf.getvalue(), tmp_path),
                len(payload), self.SHARD)
            tail = np.empty((1, 123), np.uint8)
            r.read_blocks(2 * self.SHARD, 1, 123, out=tail)
            assert tail.tobytes() == payload[2 * self.SHARD:]
            with pytest.raises(st_errors.FileCorrupt):  # past the end
                r.read_blocks(self.SHARD, 2, self.SHARD,
                              out=np.empty((2, self.SHARD), np.uint8))
            with pytest.raises(st_errors.InvalidArgument):  # unaligned
                r.read_blocks(17, 1, self.SHARD,
                              out=np.empty((1, self.SHARD), np.uint8))


class TestHedgedMetadataFanout:
    """Satellite: read_version fan-out abandons slow-drive stragglers
    once a quorum FileInfo is electable, even without a deadline budget
    (first-byte latency on GET must not eat a slow drive's full read)."""

    def test_slow_drive_does_not_stall_get_info(self):
        tmp = tempfile.mkdtemp(prefix="dp-hedge-")
        try:
            disks = [LocalStorage(os.path.join(tmp, f"d{i}"))
                     for i in range(6)]
            for d in disks:
                d.make_volume("bkt")
            api = ErasureObjects(disks)
            api.put_object("bkt", "o", io.BytesIO(b"y" * 50_000), 50_000)

            class SlowDisk:
                def __init__(self, inner):
                    self._inner = inner

                def read_version(self, *a, **kw):
                    time.sleep(2.0)
                    return self._inner.read_version(*a, **kw)

                def __getattr__(self, name):
                    return getattr(self._inner, name)

            from minio_tpu.erasure import objects as eobj

            api.disks[0] = SlowDisk(api.disks[0])
            abandoned_before = eobj.hedge_stats["abandoned"]
            t0 = time.perf_counter()
            oi = api.get_object_info("bkt", "o")
            dt = time.perf_counter() - t0
            assert oi.size == 50_000
            assert dt < 1.0, f"slow drive stalled metadata election {dt}"
            assert eobj.hedge_stats["abandoned"] > abandoned_before
            # background paths (no hedge) still wait for every answer
            t0 = time.perf_counter()
            fi, missing = api.object_health("bkt", "o")
            assert time.perf_counter() - t0 >= 2.0
            assert missing == 0
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


class TestNoLeaks:
    def test_threads_and_arenas_stable_across_puts(self):
        """Chaos drill: pipelined PUTs (including failing writers) must
        not leak threads or grow the arena pool unboundedly."""
        e = Erasure(4, 2, 1 << 18, backend="host")
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, 6 * (1 << 18) + 99,
                            dtype=np.uint8).tobytes()
        _stream(e, data, pipelined=True, defer=True)  # warm the pool

        class Dying:
            def __init__(self):
                self.n = 0

            def write_frames(self, blocks):
                self.n += 1
                if self.n > 1:
                    raise OSError("dead")

            def write(self, block):
                self.write_frames(None)

            def close(self):
                pass

        before = threading.active_count()
        for i in range(10):
            bufs = [_KeepOpen() for _ in range(6)]
            writers = [bitrot.BitrotWriter(b, e.shard_size) for b in bufs]
            if i % 2:
                writers[2] = Dying()
            hr = _HashingReader(io.BytesIO(data), len(data), defer=True)
            total, failed = e.encode_stream(hr, writers, len(data), 5,
                                            pipelined=True)
            assert total == len(data)
            hr.etag
        after = threading.active_count()
        assert after <= before, f"thread leak: {before} -> {after}"
        with coding_mod._arena_lock:
            assert coding_mod._arena_pool_bytes <= \
                coding_mod._ARENA_POOL_MAX_BYTES


class TestReviewRegressions:
    """Regressions for data-plane review findings: bucket-check error
    laundering, stale cross-drive part merge, writer-open fd leaks, and
    arena-pool LRU eviction."""

    @pytest.fixture()
    def api(self):
        root = tempfile.mkdtemp(prefix="dp-rev-")
        disks = [LocalStorage(os.path.join(root, f"d{i}"))
                 for i in range(6)]
        for d in disks:
            d.make_volume("bkt")
        yield root, disks, ErasureObjects(disks)
        shutil.rmtree(root, ignore_errors=True)

    def test_check_bucket_propagates_drive_errors(self, api, monkeypatch):
        """Drive timeouts below quorum must surface as retryable errors,
        not be laundered into an authoritative BucketNotFound (404)."""
        from minio_tpu.storage import errors

        _, disks, eo = api

        def hung(volume):
            raise errors.DeadlineExceeded("stat hung")

        for d in disks[:4]:  # majority unreachable; bucket exists
            monkeypatch.setattr(d, "stat_volume", hung)
        with pytest.raises(errors.DeadlineExceeded):
            eo._check_bucket("bkt")
        # a genuinely absent bucket is still an authoritative 404
        monkeypatch.undo()
        with pytest.raises(errors.BucketNotFound):
            eo._check_bucket("nosuchbkt")

    def test_stale_part_on_one_drive_loses_to_newer_commit(self, api):
        """A drive that missed a part re-upload's commit still holds the
        stale file; the cross-drive merge must pick the NEWEST commit,
        not the first-scanned drive's view."""
        from minio_tpu.erasure.multipart import (_parse_part_fname,
                                                 _upload_path)
        from minio_tpu.storage.local import SYSTEM_VOL

        _, disks, eo = api
        uid = eo.new_multipart_upload("bkt", "mp")
        old = b"a" * 300_000
        new = b"b" * 300_000
        eo.put_object_part("bkt", "mp", uid, 1, io.BytesIO(old), len(old))
        time.sleep(0.005)  # distinct millisecond commit stamps
        pi = eo.put_object_part("bkt", "mp", uid, 1, io.BytesIO(new),
                                len(new))
        upath = _upload_path("bkt", "mp", uid)
        d0 = disks[0]
        cand = []
        for nm in d0.list_dir(SYSTEM_VOL, upath):
            p = _parse_part_fname(nm.rstrip("/"))
            if p is not None and p.part_number == 1:
                cand.append((nm.rstrip("/"), p))
        assert len(cand) == 2  # stale + fresh coexist until assembly
        newest = max(cand, key=lambda t: t[1].mod_time)
        d0.delete(SYSTEM_VOL, f"{upath}/{newest[0]}")  # d0 missed it
        # assembly must validate the client's NEW etag and serve new bytes
        eo.complete_multipart_upload("bkt", "mp", uid, [(1, pi.etag)])
        _, stream = eo.get_object("bkt", "mp")
        assert b"".join(stream) == new

    def test_put_object_open_failure_closes_writers(self, api,
                                                    monkeypatch):
        """A non-StorageError writer open (EACCES, ...) aborts the PUT:
        the writers that DID open must be closed (raw O_DIRECT fds,
        pooled staging buffers) and their staged tmp files swept."""
        from minio_tpu.storage.local import SYSTEM_VOL, TMP_DIR

        root, disks, eo = api
        data = os.urandom(2 * (1 << 20) + 7)  # above inline threshold

        def denied(volume, path, size_hint=-1):
            raise PermissionError("EACCES")

        def drive_fds() -> list[str]:
            # only fds into THIS test's drives: the process-global fd
            # count sees unrelated transients (reaper dir scans, pools)
            out = []
            for fd in os.listdir("/proc/self/fd"):
                try:
                    t = os.readlink(f"/proc/self/fd/{fd}")
                except OSError:
                    continue
                if root in t:
                    out.append(t)
            return out

        monkeypatch.setattr(disks[3], "open_file_writer", denied)
        for _ in range(5):
            with pytest.raises(PermissionError):
                eo.put_object("bkt", "o", io.BytesIO(data), len(data))
        deadline = time.time() + 5  # reaper scans release theirs shortly
        while drive_fds() and time.time() < deadline:
            time.sleep(0.05)
        assert not drive_fds(), f"leaked drive fds: {drive_fds()}"
        for d in disks:
            try:
                left = [nm for nm in d.list_dir(SYSTEM_VOL, TMP_DIR)]
            except Exception:
                left = []
            assert not left, f"staged tmp files not swept: {left}"
        monkeypatch.undo()
        # staging-buffer pool is not drained: a healthy PUT still works
        oi = eo.put_object("bkt", "o", io.BytesIO(data), len(data))
        assert oi.etag == hashlib.md5(data).hexdigest()

    def test_arena_pool_evicts_lru_size_classes(self, monkeypatch):
        """Odd one-off arena sizes must not permanently pin the pool
        budget: the least-recently-touched size class is evicted to
        admit new releases, and oversized arenas are refused outright."""
        with coding_mod._arena_lock:
            saved = dict(coding_mod._arena_pool)
            coding_mod._arena_pool.clear()
        monkeypatch.setattr(coding_mod, "_arena_pool_bytes", 0)
        monkeypatch.setattr(coding_mod, "_ARENA_POOL_MAX_BYTES", 4000)
        try:
            for size in (800, 900, 1000, 1100):  # 3800/4000 used
                coding_mod._arena_release(np.empty(size, dtype=np.uint8))
            hot = np.empty(1024, dtype=np.uint8)
            coding_mod._arena_release(hot)
            with coding_mod._arena_lock:
                # LRU classes evicted to make room; the new one admitted
                assert 800 not in coding_mod._arena_pool
                assert 900 not in coding_mod._arena_pool
                assert 1000 in coding_mod._arena_pool
                assert 1100 in coding_mod._arena_pool
            assert coding_mod._arena_acquire(1024) is hot
            coding_mod._arena_release(np.empty(5000, dtype=np.uint8))
            with coding_mod._arena_lock:
                assert 5000 not in coding_mod._arena_pool
        finally:
            with coding_mod._arena_lock:
                coding_mod._arena_pool.clear()
                coding_mod._arena_pool.update(saved)
