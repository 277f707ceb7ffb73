"""The batch axis of a device dispatch (ISSUE 32): a group of g blocks,
whatever g in 1..32, runs a program that boot compiled.

The single-chip codec's program is a `jax.jit` keyed on the batch's
shape, and boot compiles it at `coding.DEVICE_BATCH_SIZES` alone.  So
the engine carries a shorter group inside the next of those sizes and
keeps its own g blocks' rows; what the carrier held beyond them goes to
the device and into no shard file, response or hash.  Interpret mode
stands in for the chip, `ops/gf256.py` is the oracle, blocks are 64 KiB
(a shard: 32 KiB at 2+2, 8 KiB at 8+4, 5,462 bytes at 12+4, where k does
not divide the block and the shard is no multiple of the kernel's tile).
"""

import io

import numpy as np
import pytest

from minio_tpu.erasure import bitrot, coding, stagestats
from minio_tpu.erasure.coding import Erasure
from minio_tpu.ops import gf256
from tests import device_codec
from tests.device_codec import Seen as _Seen
from tests.device_codec import bytes_of as _bytes_of
from tests.device_codec import oracle_parity as _oracle_parity

BS = 1 << 16
GEOMETRIES = [(2, 2), (8, 4), (12, 4)]
PATTERN = 0xA5
CARRIER_OF_10 = coding.carrier_blocks(10)  # a warp object's one dispatch
_ids = "{0[0]}+{0[1]}".format


class _Compiles:
    """Counts XLA compilations of this process, whatever compiled: a
    `jit` function's new shape, a slice or a convert of a device array."""

    n = 0
    listening = False

    @classmethod
    def listen(cls):
        import jax.monitoring

        def fold(event: str, _seconds: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                cls.n += 1

        if not cls.listening:
            jax.monitoring.register_event_duration_secs_listener(fold)
            cls.listening = True


class _Booted:
    """What a boot leaves behind for one geometry: the device codec in
    the engine's cache, self-tested and warmed by the server's own
    `device_self_test`, and the sizes of the jit caches after it."""

    def __init__(self, k, m):
        from minio_tpu import selftest
        from minio_tpu.ops import rs_pallas

        self.k, self.m = k, m
        self.codec = _Seen(rs_pallas.PallasRSCodec(k, m, interpret=True))
        coding._DeviceCodec._cache[(k, m)] = (self.codec, None)
        _Compiles.listen()
        selftest.device_self_test(k, m, BS)
        device_codec.plant(k, m, self.codec, None)  # passed: ready
        self.jits = (rs_pallas._coding_call_bytes, rs_pallas._coding_call)
        self.cache_sizes = [f._cache_size() for f in self.jits]
        self.compiles = _Compiles.n
        self.e = Erasure(k, m, BS, backend="tpu")

    def compiled_nothing_since(self):
        assert [f._cache_size() for f in self.jits] == self.cache_sizes
        assert _Compiles.n == self.compiles

    def close(self):
        device_codec.unplant(self.k, self.m)


@pytest.fixture(scope="module", params=GEOMETRIES, ids=_ids)
def booted(request):
    b = _Booted(*request.param)
    yield b
    b.close()


@pytest.mark.parametrize("what", ["encode", "reconstruct"])
@pytest.mark.parametrize("g", range(1, coding.DEVICE_BATCH_BLOCKS + 1))
def test_any_batch_runs_a_program_that_boot_compiled(booted, g, what):
    """g blocks through the engine's own entries equal the oracle, in
    both directions and for 1..m rows; no jit cache grew and nothing
    else compiled; the codec saw a compiled batch size and nothing
    else; the counters book the g real blocks."""
    e, k, m = booted.e, booted.k, booted.m
    s = e.shard_size
    size = coding.carrier_blocks(g)
    assert size in coding.DEVICE_BATCH_SIZES and size >= g
    batch = np.random.default_rng(g * 131 + k).integers(
        0, 256, size=(g, k, s), dtype=np.uint8)
    parity = _oracle_parity(batch, m)
    del booted.codec.shapes[:]
    fill0, dev0 = _bytes_of("batch_fill"), coding.backend_stats["device"]["bytes"]
    if what == "encode":
        got = e._encode_shards(batch)
        assert got.shape == (g, m, s)
        np.testing.assert_array_equal(got, parity)
        np.testing.assert_array_equal(e._encode_shards_async(batch)(), parity)
        dispatches = 2
    else:
        full = np.concatenate([batch, parity], axis=1)
        for lost in range(1, m + 1):
            wanted = tuple(range(lost))
            avail = tuple(range(lost, lost + k))
            got = e._reconstruct_shards(
                np.ascontiguousarray(full[:, lost:lost + k]), avail, wanted)
            assert got.shape == (g, lost, s)
            np.testing.assert_array_equal(got, batch[:, :lost])
        dispatches = m
    booted.compiled_nothing_since()
    assert booted.codec.shapes == [
        (size, None if size == g else g)] * dispatches
    assert _bytes_of("batch_fill") - fill0 == dispatches * (size - g) * k * s
    assert coding.backend_stats["device"]["bytes"] - dev0 \
        == dispatches * g * k * s


def test_every_stage_is_exported_from_boot():
    """A cell without a short dispatch reads batch_fill as 0.0, not as
    nothing."""
    assert "batch_fill" in stagestats.STAGES
    assert set(stagestats.snapshot()["batch_fill"]) \
        == {"seconds", "bytes", "wall"}
    assert coding.DEVICE_BATCH_SIZES[-1] == coding.DEVICE_BATCH_BLOCKS
    assert list(coding.DEVICE_BATCH_SIZES) == sorted(
        set(coding.DEVICE_BATCH_SIZES))
    assert 1 <= len(coding.DEVICE_BATCH_SIZES) <= 4  # ISSUE 32: three rungs


# -- whole objects through the streams ---------------------------------------

def _body(size, seed):
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _put(e, tmp_path, body):
    paths = [tmp_path / f"shard{i}" for i in range(e.k + e.m)]
    writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size)
               for p in paths]
    n, failed = e.encode_stream(io.BytesIO(body), writers, len(body), e.k + 1)
    assert n == len(body) and not failed
    for w in writers:
        w.close()
    return paths


def _readers(e, paths, size, gone=()):
    till = e.shard_file_size(size)
    return [None if i in gone else bitrot.BitrotReader(
        open(p, "rb"), till, e.shard_size) for i, p in enumerate(paths)]


def _oracle_files(body, k, m):
    """The k+m shard files of one stream of BS-byte blocks, hash
    prefixes and all."""
    from minio_tpu.ops import hh_device

    data = np.frombuffer(body, dtype=np.uint8)
    files = [bytearray() for _ in range(k + m)]
    nfull = len(body) // BS
    for pieces in (data[:nfull * BS].reshape(nfull, BS),
                   data[nfull * BS:].reshape(1, -1)):
        nb, length = pieces.shape
        if not nb or not length:
            continue
        shard = -(-length // k)
        split = np.zeros((nb, k * shard), dtype=np.uint8)
        split[:, :length] = pieces
        rows = split.reshape(nb, k, shard)
        rows = np.concatenate([rows, _oracle_parity(rows, m)], axis=1)
        digests = hh_device.hh256_batch_np(
            rows.reshape(nb * (k + m), shard)).reshape(nb, k + m, 32)
        for b in range(nb):
            for i in range(k + m):
                files[i] += digests[b, i].tobytes() + rows[b, i].tobytes()
    return [bytes(f) for f in files]


@pytest.mark.parametrize("blocks", [3, 10, 21, 33])
def test_degraded_get_of_an_object_of_any_length(booted, tmp_path, blocks):
    """Full blocks and a tail, two shards away: the body comes back, the
    groups shorter than a compiled size were carried by one, and their
    stages book their own blocks (`pad` at 12+4 as a full group's)."""
    e, k, m = booted.e, booted.k, booted.m
    size = blocks * BS + 4320  # a tail that 2, 8 and 12 divide
    body = _body(size, blocks)
    paths = _put(e, tmp_path, body)
    gone = (0, k + 1)  # one data and one parity shard: one row rebuilt
    del booted.codec.shapes[:]
    fill0, pad0 = _bytes_of("batch_fill"), _bytes_of("pad")
    out = io.BytesIO()
    assert e.decode_stream(out, _readers(e, paths, size, gone), 0, size,
                           size) == size
    assert out.getvalue() == body
    booted.compiled_nothing_since()
    short = blocks % 32
    carrier = coding.carrier_blocks(short)
    want = [(32, None)] * (blocks // 32) + [
        (carrier, None if carrier == short else short)]
    assert booted.codec.shapes == want  # the tail block: host codec
    s = e.shard_size
    assert _bytes_of("batch_fill") - fill0 == (carrier - short) * k * s
    if k == 12:
        from minio_tpu.ops import rs_pallas

        # widened and cut on the device for the real blocks alone, and
        # the fill that every full block's assemble drops
        assert _bytes_of("pad") - pad0 == blocks * (
            k * rs_pallas.kernel_width(s) + s + BS)
    else:
        assert _bytes_of("pad") == pad0


def test_ten_block_object_on_disk_equals_the_oracle(booted, tmp_path):
    """A PUT whose one encode dispatch is shorter than any compiled
    size: every drive holds the reference's bytes, hashes and all."""
    e, k, m = booted.e, booted.k, booted.m
    body = _body(10 * BS, 10)
    del booted.codec.shapes[:]
    paths = _put(e, tmp_path, body)
    assert booted.codec.shapes == [(CARRIER_OF_10, 10)]
    booted.compiled_nothing_since()
    for i, want in enumerate(_oracle_files(body, k, m)):
        assert paths[i].read_bytes() == want, f"shard {i} of {k}+{m}"


def _runs_of_the_pattern(k, m):
    """32 bytes of the pattern, and of every row a codec makes of blocks
    that hold nothing else (coding is bytewise: constant rows)."""
    stale = np.full((k, 32), PATTERN, dtype=np.uint8)
    runs = {bytes([PATTERN]) * 32}
    runs |= {row.tobytes() for row in gf256.encode_np(stale, m)}
    return runs


def test_stale_bytes_of_a_carrier_reach_no_output(booted, tmp_path,
                                                  monkeypatch):
    """Every arena the pool hands out is full of a pattern.  The PUT's
    slot and the degraded GET's and the heal's staging arenas go to the
    device with it behind their 10 blocks, in place (no copy into a
    carrier is booked), and neither the pattern nor a row made of it is
    in a shard file or in the response."""
    e, k, m = booted.e, booted.k, booted.m
    acquire = coding._arena_acquire

    def patterned(nbytes):
        arr = acquire(nbytes)
        arr[:] = PATTERN
        return arr

    monkeypatch.setattr(coding, "_arena_acquire", patterned)
    size = 10 * BS
    body = _body(size, 77)
    runs = _runs_of_the_pattern(k, m)
    assert not any(r in body for r in runs)
    del booted.codec.beyond[:]
    asm0 = _bytes_of("assemble")
    paths = _put(e, tmp_path, body)
    assert _bytes_of("assemble") == asm0  # carried where it lay
    held = [p.read_bytes() for p in paths]
    assert held == _oracle_files(body, k, m)

    gone = (0, k - 1)  # two data shards: two rows rebuilt
    out = io.BytesIO()
    asm0 = _bytes_of("assemble")
    assert e.decode_stream(out, _readers(e, paths, size, gone), 0, size,
                           size) == size
    assert out.getvalue() == body
    # the host's copies are the blocks' own bytes: k - 2 shards placed,
    # two rebuilt rows placed; nothing was copied into a carrier
    assert _bytes_of("assemble") - asm0 == 10 * k * e.shard_size

    for i in gone:
        paths[i].unlink()
    writers = [bitrot.BitrotWriter(open(paths[i], "wb"), e.shard_size)
               if i in gone else None for i in range(k + m)]
    e.heal(writers, _readers(e, paths, size, gone), size)
    for w in writers:
        if w:
            w.close()
    assert [p.read_bytes() for p in paths] == held
    for raw in held + [out.getvalue()]:
        assert not any(r in raw for r in runs)
    booted.compiled_nothing_since()

    # the pattern did go to the device: the carrier's blocks of it
    # behind the GET's and the heal's 10, and behind the PUT's where its
    # slot is the carrier (at 12+4 the PUT's batch is a zero-filled copy)
    seen = booted.codec.beyond
    assert [b.shape[0] for b in seen] == [CARRIER_OF_10 - 10] * 3
    assert all((b == PATTERN).all() for b in seen[1:])
    assert (seen[0] == (0 if BS % k else PATTERN)).all()


def test_heal_of_a_ten_block_object(booted, tmp_path):
    """cmd/erasure-heal_test.go's case (12+4, three shards zeroed) at
    the warp object's length, and its like at 2+2 and 8+4: one
    reconstruct dispatch of 10 blocks makes the rows, carried at a
    compiled size."""
    e, k, m = booted.e, booted.k, booted.m
    size = 10 * BS
    body = _body(size, 1204)
    paths = _put(e, tmp_path, body)
    originals = [p.read_bytes() for p in paths]
    stale = {2: (0, 3), 8: (2, 7, 10), 12: (2, 7, 14)}[k]
    for i in stale:
        paths[i].write_bytes(b"\0" * len(originals[i]))
    writers = [bitrot.BitrotWriter(open(paths[i], "wb"), e.shard_size)
               if i in stale else None for i in range(k + m)]
    del booted.codec.shapes[:]
    e.heal(writers, _readers(e, paths, size, stale), size)
    for w in writers:
        if w:
            w.close()
    assert booted.codec.shapes == [(CARRIER_OF_10, 10)]
    booted.compiled_nothing_since()
    assert [p.read_bytes() for p in paths] == originals


def test_a_batch_that_is_no_head_is_copied_into_a_carrier(booted):
    """A caller that holds its blocks anywhere else (the sub-shard
    repair, a stream read without readinto) still reaches a compiled
    size: one host copy, booked as `assemble`, into a pooled carrier
    that goes back to the pool."""
    from minio_tpu.erasure import repair

    e, k, m = booted.e, booted.k, booted.m
    s = e.shard_size
    batch = np.random.default_rng(5).integers(
        0, 256, size=(5, k, s), dtype=np.uint8)
    full = np.concatenate([batch, _oracle_parity(batch, m)], axis=1)
    asm0 = _bytes_of("assemble")
    helpers = tuple(range(1, k + 1))
    got = repair._dispatch(e, full[:, 1:k + 1], helpers, (0,))
    np.testing.assert_array_equal(got[:, 0], batch[:, 0])
    assert _bytes_of("assemble") - asm0 == 5 * k * s
    booted.compiled_nothing_since()
    assert coding.carrier_blocks(5) * k * s in coding._arena_pool
