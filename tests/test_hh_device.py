"""The numpy HighwayHash-256 oracle against the C hasher, and the bitrot
writer's batched frame path.

`hh_device.hh256_batch_np` must agree byte-for-byte with ops/host.py
`hh256` (itself golden-pinned against the reference bitrot self-test,
cmd/bitrot.go:37): it is the reference the on-disk tests hold every
frame's 32-byte prefix to (tests/test_erasure_stream.py
TestOnDiskAgainstOracle).  `BitrotWriter.write_frames` is the one way a
PUT's shard rows reach a shard file; it always hashes them itself.
"""

import io

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.ops import hh_device, host
from minio_tpu.storage import errors

pytestmark = pytest.mark.skipif(
    not host.available(), reason="host library build unavailable"
)

# packet boundary (32), remainder classes (mod4 / &16), scan edges
LENGTHS = (0, 1, 2, 3, 4, 5, 15, 16, 17, 31, 32, 33, 63, 64, 100,
           255, 256, 1000, 4096)


def _rand(n, l, seed):
    return np.random.default_rng(seed).integers(
        0, 256, size=(n, l), dtype=np.uint8)


# ------------------------------------------------------ numpy oracle
class TestOracle:
    def test_matches_c_streaming_all_shapes(self):
        """Every length class × batch width vs the C one-shot hash."""
        for li, l in enumerate(LENGTHS):
            for n in (1, 3, 7):
                blocks = _rand(n, l, 1000 * li + n)
                got = hh_device.hh256_batch_np(blocks)
                assert got.shape == (n, 32)
                for i in range(n):
                    assert bytes(got[i]) == host.hh256(
                        blocks[i].tobytes()), (n, l, i)

    def test_matches_c_batch_entrypoint(self):
        blocks = _rand(6, 2048, 7)
        np.testing.assert_array_equal(
            hh_device.hh256_batch_np(blocks), host.hh256_batch(blocks))

    def test_reference_selftest_extends_to_batched(self):
        """The reference bitrot self-test (cmd/bitrot.go:214) driven
        through the batched oracle: build msg from successive sums with
        the magic key, expect the same golden final sum test_host.py
        pins for the streaming C implementation."""
        size, block = 32, 32
        msg = b""
        sum_ = b""
        for _ in range(0, size * block, size):
            row = np.frombuffer(msg, dtype=np.uint8).reshape(1, -1)
            sum_ = bytes(hh_device.hh256_batch_np(row)[0])
            msg += sum_
        assert sum_.hex() == (
            "39c0407ed3f01b18d22c85db4aeff11e060ca5f43131b0126731ca197cd42313")

    def test_custom_key_and_empty_batch(self):
        key = bytes(range(32))
        blocks = _rand(2, 100, 11)
        got = hh_device.hh256_batch_np(blocks, key)
        for i in range(2):
            assert bytes(got[i]) == host.hh256(blocks[i].tobytes(), key)
        assert hh_device.hh256_batch_np(
            np.empty((0, 64), dtype=np.uint8)).shape == (0, 32)


# ------------------------------------------------ the writer's frame path
class TestWriteFrames:
    def test_frames_verify_through_the_reader(self):
        """A full batch and a short last row, each frame prefixed with
        the oracle's digest, read back through the verifying reader."""
        blocks = _rand(3, 256, 64)
        last = _rand(1, 100, 65)
        buf = io.BytesIO()
        w = bitrot.BitrotWriter(buf, shard_size=256)
        w.write_frames(blocks)
        w.write_frames(last)
        raw = buf.getvalue()
        assert w.written == len(raw) == 3 * (32 + 256) + 32 + 100
        want = hh_device.hh256_batch_np(blocks)
        for i in range(3):
            frame = raw[i * 288:(i + 1) * 288]
            assert frame[:32] == bytes(want[i]), i
            assert frame[32:] == blocks[i].tobytes(), i
        assert raw[864:896] == bytes(hh_device.hh256_batch_np(last)[0])
        r = bitrot.BitrotReader(io.BytesIO(raw), till_offset=3 * 256 + 100,
                                shard_size=256)
        np.testing.assert_array_equal(r.read_blocks(0, 3, 256), blocks)
        assert bytes(r.read_at(3 * 256, 100)) == last.tobytes()

    @pytest.mark.parametrize("algo", ["sha256", "blake2b512"])
    def test_other_algorithm_takes_the_write_loop(self, algo):
        """An algorithm the batched C hasher does not serve goes row by
        row through write(): same frames, and they verify."""
        blocks = _rand(2, 128, 63)
        one, many = io.BytesIO(), io.BytesIO()
        w1 = bitrot.BitrotWriter(one, 128, algo=algo)
        for row in blocks:
            w1.write(row)
        wn = bitrot.BitrotWriter(many, 128, algo=algo)
        wn.write_frames(blocks)
        assert many.getvalue() == one.getvalue()
        assert wn.written == w1.written == len(one.getvalue())
        r = bitrot.BitrotReader(io.BytesIO(many.getvalue()),
                                till_offset=2 * 128, shard_size=128,
                                algo=algo)
        np.testing.assert_array_equal(r.read_blocks(0, 2, 128), blocks)

    def test_refuses_rows_it_cannot_place(self):
        """A row longer than the shard, a batch of several short rows
        (they would land where the reader never seeks) and a batch that
        is no (nblocks, L) matrix: refused before a byte is written."""
        buf = io.BytesIO()
        w = bitrot.BitrotWriter(buf, shard_size=128)
        with pytest.raises(errors.InvalidArgument):
            w.write_frames(_rand(1, 129, 66))
        with pytest.raises(errors.InvalidArgument):
            w.write_frames(_rand(2, 100, 67))
        with pytest.raises(errors.InvalidArgument):
            w.write_frames(_rand(2, 128, 68).reshape(2, 2, 64))
        assert buf.getvalue() == b"" and w.written == 0
