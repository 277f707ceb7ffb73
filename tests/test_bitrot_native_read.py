"""A staged read's group of bitrot frames in one native call.

`BitrotReader.read_blocks(out=)` on a local shard file (the O_DIRECT
reader, or the buffered one) reads the group's frames, puts each block
into its row of a dispatch arena's column and each hash into a row of its
own, and checks every block, in one call of the stream's `read_frames`
(`csrc/frame_read.cpp`).  It must give the bytes the Python path gives
(two readinto calls a frame, then one batched hash call), fail where that
path fails, leave the stream where that path leaves it, and leave every
stream without a descriptor on that path.
"""

import errno
import io
import warnings

import numpy as np
import pytest

from minio_tpu.erasure import bitrot, stagestats
from minio_tpu.erasure.coding import Erasure
from minio_tpu.ops import host
from minio_tpu.storage import errors, local

pytestmark = pytest.mark.skipif(not host.available(),
                                reason="the native library did not build")

HS = bitrot.HASH_SIZE
# one shard of a 1 MiB block at 12+4, 10+6, 8+4, 2+2
SHARDS = [87382, 104858, 131072, 524288]
NBLOCKS = [1, 10, 16, 32]
FILE_BLOCKS = 2 * max(NBLOCKS) + 1  # room for a group in the middle


def _frames(payload: np.ndarray, shard: int) -> bytes:
    buf = io.BytesIO()
    w = bitrot.BitrotWriter(buf, shard)
    for lo in range(0, payload.size, shard):
        w.write(payload[lo:lo + shard])
    return buf.getvalue()


@pytest.fixture(scope="module")
def shard_files(tmp_path_factory):
    """{shard length: (path, payload)}: FILE_BLOCKS full frames each."""
    root = tmp_path_factory.mktemp("native-read")
    made = {}
    for shard in SHARDS:
        rng = np.random.default_rng(shard)
        payload = rng.integers(0, 256, FILE_BLOCKS * shard, dtype=np.uint8)
        path = root / f"part.{shard}"
        path.write_bytes(_frames(payload, shard))
        made[shard] = (str(path), payload)
    return made


def _native(kind: str, path: str):
    """The stream a drive hands out: buffered, or O_DIRECT where the test
    file system takes it (else buffered, and a warning says so)."""
    if kind == "direct":
        try:
            return local._DirectReader(path)
        except OSError as e:
            warnings.warn(f"no O_DIRECT under {path} ({e}): the direct "
                          f"case reads the buffered stream")
    return local._ShardFile(io.FileIO(path, "rb"))


def _arena(nblocks: int, shard: int, seed: int) -> np.ndarray:
    """A (nblocks, 3, shard) batch full of stale bytes; column 1 is read."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (nblocks, 3, shard), dtype=np.uint8)


def _python_read(path, till, shard, offset, arena):
    """Today's path: a plain buffered file (no read_frames) read into the
    column with readinto, then one batched hash call.  Returns the
    frames' hashes."""
    r = bitrot.BitrotReader(open(path, "rb"), till, shard)
    try:
        hashes = r._read_rows(offset, arena[:, 1, :])
        r._verify(arena[:, 1, :], hashes)
        return hashes
    finally:
        r.close()


def _counter(stage: str) -> int:
    return stagestats.snapshot()[stage]["bytes"]


@pytest.fixture
def opened():
    """BitrotReader, the readers closed however the test ends."""
    readers = []

    def reader(*args, **kwargs):
        readers.append(bitrot.BitrotReader(*args, **kwargs))
        return readers[-1]

    yield reader
    for r in readers:
        r.close()


@pytest.mark.parametrize("kind", ["buffered", "direct"])
@pytest.mark.parametrize("where", ["first", "middle"])
@pytest.mark.parametrize("nblocks", NBLOCKS)
@pytest.mark.parametrize("shard", SHARDS)
def test_native_matches_python(shard_files, shard, nblocks, where, kind):
    path, payload = shard_files[shard]
    till = payload.size
    b0 = 0 if where == "first" else nblocks
    offset = b0 * shard
    want = _arena(nblocks, shard, seed=nblocks)
    want_hashes = _python_read(path, till, shard, offset, want)

    # the stream's own call: hashes, rows and the bytes around the column
    got = _arena(nblocks, shard, seed=nblocks)
    hashes = np.empty((nblocks, HS), np.uint8)
    stream = _native(kind, path)
    try:
        status, hash_ns = stream.read_frames(
            b0 * (HS + shard), hashes, got[:, 1, :])
    finally:
        stream.close()
    assert status == host.FRAMES_OK and hash_ns > 0
    assert np.array_equal(hashes, want_hashes)
    assert np.array_equal(got, want)
    assert np.array_equal(
        got[:, 1, :], payload[offset:offset + nblocks * shard].reshape(
            nblocks, shard))

    # the same through the reader, with the counters
    got = _arena(nblocks, shard, seed=nblocks)
    r = bitrot.BitrotReader(_native(kind, path), till, shard)
    before = {s: _counter(s) for s in ("native_read", "staged", "verify")}
    try:
        assert r.read_blocks(offset, nblocks, shard, out=got[:, 1, :]) \
            is not None
    finally:
        r.close()
    assert np.array_equal(got, want)
    for stage in before:
        assert _counter(stage) - before[stage] == nblocks * shard, stage


@pytest.mark.parametrize("kind", ["buffered", "direct"])
@pytest.mark.parametrize("tail", [1, 4095, 4096, 43691])
def test_tail_shard(tmp_path, kind, tail, opened):
    """A stream's short final block (nblocks 1) after full ones, and the
    same read again through a column of its own length."""
    shard = 87382
    rng = np.random.default_rng(tail)
    payload = rng.integers(0, 256, 3 * shard + tail, dtype=np.uint8)
    path = tmp_path / "part.1"
    path.write_bytes(_frames(payload, shard))
    want = _arena(1, tail, seed=tail)
    _python_read(str(path), payload.size, shard, 3 * shard, want)
    got = _arena(1, tail, seed=tail)
    r = opened(_native(kind, str(path)), payload.size, shard)
    before = _counter("native_read")
    r.read_blocks(3 * shard, 1, tail, out=got[:, 1, :])
    assert _counter("native_read") - before == tail
    assert np.array_equal(got, want)
    assert got[0, 1].tobytes() == payload[3 * shard:].tobytes()
    # the full frames before it, read the same way afterwards
    rows = np.empty((3, shard), np.uint8)
    r.read_blocks(0, 3, shard, out=rows)
    assert rows.tobytes() == payload[:3 * shard].tobytes()


def _corrupt_file(tmp_path, how: str):
    shard, nblocks = 131072, 8
    rng = np.random.default_rng(8)
    payload = rng.integers(0, 256, nblocks * shard, dtype=np.uint8)
    blob = bytearray(_frames(payload, shard))
    frame = HS + shard
    if how == "block":
        blob[5 * frame + HS + 4099] ^= 0x01
    elif how == "hash":
        blob[6 * frame + 7] ^= 0x80
    else:  # the file ends inside frame 7's block
        del blob[7 * frame + HS + 1000:]
    path = tmp_path / "part.1"
    path.write_bytes(bytes(blob))
    return str(path), payload, shard, nblocks


@pytest.mark.parametrize("kind", ["buffered", "direct"])
@pytest.mark.parametrize("how, message", [
    ("block", "bitrot: hash mismatch"),
    ("hash", "bitrot: hash mismatch"),
    ("truncated", "bitrot: truncated frame group"),
])
def test_corruption_is_file_corrupt(tmp_path, kind, how, message, opened):
    path, payload, shard, nblocks = _corrupt_file(tmp_path, how)
    r = opened(_native(kind, path), payload.size, shard)
    before = _counter("native_read")
    with pytest.raises(errors.FileCorrupt, match=message):
        r.read_blocks(0, nblocks, shard,
                      out=np.empty((nblocks, shard), np.uint8))
    assert r._pos == -1 and _counter("native_read") == before
    # the frames before the bad one still read, after a seek
    rows = np.empty((4, shard), np.uint8)
    r.read_blocks(0, 4, shard, out=rows)
    assert rows.tobytes() == payload[:4 * shard].tobytes()


class _ReadOnly(io.RawIOBase):
    """A remote shard stream's shape: read() and seek(), no descriptor."""

    def __init__(self, data):
        self._b = io.BytesIO(data)

    def read(self, n=-1):
        return self._b.read(n)

    def seek(self, off, whence=0):
        return self._b.seek(off, whence)


@pytest.mark.parametrize("stream", ["read_only", "bytesio"])
def test_streams_without_a_descriptor_keep_the_python_path(stream):
    shard, nblocks = 87382, 4
    rng = np.random.default_rng(4)
    payload = rng.integers(0, 256, nblocks * shard, dtype=np.uint8)
    blob = _frames(payload, shard)
    src = _ReadOnly(blob) if stream == "read_only" else io.BytesIO(blob)
    r = bitrot.BitrotReader(src, payload.size, shard)
    before = {s: _counter(s) for s in ("native_read", "staged")}
    out = np.empty((nblocks, shard), np.uint8)
    assert r.read_blocks(0, nblocks, shard, out=out) is out
    assert out.tobytes() == payload.tobytes()
    assert _counter("native_read") == before["native_read"]
    # readinto places the rows itself; read() alone is copied in
    assert _counter("staged") - before["staged"] == \
        (0 if stream == "read_only" else payload.size)


def test_no_library_keeps_the_python_path(shard_files, monkeypatch, opened):
    path, payload = shard_files[87382]
    monkeypatch.setattr(host, "available", lambda: False)
    r = opened(_native("buffered", path), payload.size, 87382)
    before = _counter("native_read")
    out = np.empty((10, 87382), np.uint8)
    r.read_blocks(87382, 10, 87382, out=out)
    assert out.tobytes() == payload[87382:11 * 87382].tobytes()
    assert _counter("native_read") == before


def test_einval_falls_back_for_that_stream(shard_files, opened):
    """A descriptor the file system refuses O_DIRECT on: this stream reads
    in Python from then on, the bytes the same."""
    path, payload = shard_files[131072]
    calls = []

    class Refusing(local._ShardFile):
        def read_frames(self, offset, hashes, out):
            calls.append(offset)
            raise OSError(errno.EINVAL, "refused")

    r = opened(Refusing(io.FileIO(path, "rb")), payload.size, 131072)
    before = _counter("native_read")
    for b0 in (0, 16):
        out = np.empty((16, 131072), np.uint8)
        r.read_blocks(b0 * 131072, 16, 131072, out=out)
        assert out.tobytes() == \
            payload[b0 * 131072:(b0 + 16) * 131072].tobytes()
    assert calls == [0] and r._no_native
    assert _counter("native_read") == before


@pytest.mark.parametrize("kind", ["buffered", "direct"])
def test_stream_position_after_the_call(shard_files, kind, opened):
    """Where one native call leaves the stream: the next group without
    `out` (the Python path, which reads from where the stream stands),
    a read_at behind it, and the raw stream's own read."""
    shard = 104858
    path, payload = shard_files[shard]
    r = opened(_native(kind, path), payload.size, shard)
    out = np.empty((16, shard), np.uint8)
    r.read_blocks(0, 16, shard, out=out)
    assert r._pos == 16 * shard
    nxt = r.read_blocks(16 * shard, 16, shard)
    assert nxt.tobytes() == payload[16 * shard:32 * shard].tobytes()
    assert r.read_at(3 * shard, 5 * shard) == \
        payload[3 * shard:8 * shard].tobytes()
    r.read_blocks(32 * shard, 10, shard, out=out[:10])
    assert out[:10].tobytes() == payload[32 * shard:42 * shard].tobytes()
    # the stream itself stands at the group's end: its next bytes are
    # frame 42's hash and block
    raw = r.r.read(HS + shard)
    assert raw[HS:] == payload[42 * shard:43 * shard].tobytes()
    assert raw[:HS] == host.hh256(payload[42 * shard:43 * shard])


def test_read_group_12p4_two_drives_away(tmp_path, opened):
    """A degraded group at 12+4 with shards 1 and 7 away, every reader a
    local shard file: the columns that the native calls filled, and the
    blocks rebuilt from them, against the host codec's."""
    e = Erasure(12, 4, backend="host")
    shard, nfull, tail = e.shard_size, 34, 5000
    total = nfull * e.block_size + tail
    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, total, dtype=np.uint8)
    files = [io.BytesIO() for _ in range(16)]
    writers = [bitrot.BitrotWriter(f, shard) for f in files]
    for lo in range(0, total, e.block_size):
        for w, s in zip(writers, e.encode_data(data[lo:lo + e.block_size])):
            w.write(s)
    paths = []
    for i, f in enumerate(files):
        paths.append(str(tmp_path / f"d{i}.part.1"))
        with open(paths[-1], "wb") as out:
            out.write(f.getvalue())
    till = e.shard_file_size(total)

    def readers():
        return [None if i in (1, 7) else opened(
            _native("direct" if i % 2 else "buffered", p), till, shard)
            for i, p in enumerate(paths)]

    # one group of 32 blocks: k columns read straight into the arena
    rs = readers()
    before = _counter("native_read")
    got, arena = e._read_group(rs, set(), 0, 32 * shard, 32, shard)
    assert arena is not None and list(got) == [0, 2, 3, 4, 5, 6, 8, 9, 10,
                                               11, 12, 13]
    assert _counter("native_read") - before == 12 * 32 * shard
    blocks = data[:32 * e.block_size].reshape(32, e.block_size)
    shards = np.stack([np.stack(e.encode_data(b)) for b in blocks])
    for j, i in enumerate(got):
        assert np.array_equal(arena[:, j, :], shards[:, i, :])
    rebuilt = host.HostRSCodec(12, 4).reconstruct(
        arena, tuple(got), (1, 7))
    assert np.array_equal(rebuilt, shards[:, [1, 7], :])
    flat = e._assemble_data(got, arena, 32, shard, e.block_size)
    assert np.array_equal(flat, blocks)

    # the whole object, the second group short and the tail block too
    rs = readers()
    sink = io.BytesIO()
    assert e.decode_stream(sink, rs, 0, total, total) == total
    assert sink.getvalue() == data.tobytes()


@pytest.mark.parametrize("bad", ["strided_row", "read_only", "empty_rows",
                                 "hash_shape", "bounce_unaligned"])
def test_unfit_buffers_are_refused_before_the_call(shard_files, bad):
    path, _ = shard_files[87382]
    out = np.empty((2, 87382), np.uint8)
    hashes = np.empty((2, HS), np.uint8)
    bounce = None
    if bad == "strided_row":
        out = np.empty((2, 2 * 87382), np.uint8)[:, ::2]
    elif bad == "read_only":
        out.flags.writeable = False
    elif bad == "empty_rows":
        out = np.empty((2, 0), np.uint8)
    elif bad == "hash_shape":
        hashes = np.empty((3, HS), np.uint8)
    else:
        bounce = np.empty(4096 + 512, np.uint8)
    with open(path, "rb") as f, pytest.raises(ValueError):
        host.read_frames(f.fileno(), 0, hashes, out, bounce)
