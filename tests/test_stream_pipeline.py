"""Device streaming pipeline: double-buffered encode dispatch
(BASELINE.md hard part "streaming with bounded HBM + overlap of DMA and
compute"; VERDICT r3 #4)."""

import io
import threading
import time

import numpy as np
import pytest

from minio_tpu.erasure import bitrot
from minio_tpu.erasure.coding import PIPELINE_DEPTH, Erasure

K, M = 4, 2


class _RecordingCodec:
    """Fake device codec: encode() returns a lazy handle and records the
    dispatch/resolve interleaving so tests can assert real overlap."""

    def __init__(self, k, m, delay=0.0):
        from minio_tpu.ops import host

        self._host = host.HostRSCodec(k, m)
        self.delay = delay
        self.events = []
        self.outstanding = 0
        self.max_outstanding = 0
        self._lock = threading.Lock()

    def encode(self, batch, blocks=None):
        with self._lock:
            self.outstanding += 1
            self.max_outstanding = max(self.max_outstanding,
                                       self.outstanding)
            self.events.append(("submit", len(self.events)))
        parity = self._host.encode(np.asarray(batch))
        codec = self

        class Lazy:
            def __array__(self, dtype=None, copy=None):
                if codec.delay:
                    time.sleep(codec.delay)
                with codec._lock:
                    codec.outstanding -= 1
                    codec.events.append(("resolve", len(codec.events)))
                return parity

        return Lazy()


def _patched_erasure(codec, block_size=1 << 18):
    e = Erasure(K, M, block_size, backend="host")
    e._device = lambda nbytes, shard_len, dispatch=False: codec
    return e


class _KeepOpen(io.BytesIO):
    def close(self):  # BitrotWriter.close closes its sink; keep the bytes
        pass


def _stream(e, data, nwriters=K + M):
    bufs = [_KeepOpen() for _ in range(nwriters)]
    writers = [bitrot.BitrotWriter(b, e.shard_size) for b in bufs]
    total, failed = e.encode_stream(io.BytesIO(data), writers,
                                    len(data), K + 1)
    for w in writers:
        w.close()
    return total, failed, bufs


class TestPipelineOverlap:
    def test_batches_stay_in_flight(self):
        """The encoder keeps up to PIPELINE_DEPTH batches outstanding:
        batch N+1 is submitted BEFORE batch N resolves."""
        codec = _RecordingCodec(K, M)
        e = _patched_erasure(codec)
        # enough data for several full device batches
        data = bytes(range(256)) * (4 * 32 * 1024)  # 32 MiB
        total, failed, _ = _stream(e, data)
        assert total == len(data) and not failed
        assert codec.max_outstanding == PIPELINE_DEPTH + 1, \
            codec.max_outstanding
        # at least one submit happened while an earlier dispatch was
        # still unresolved (true overlap, not lockstep)
        order = [kind for kind, _ in codec.events]
        first_resolve = order.index("resolve")
        assert order[:first_resolve].count("submit") >= 2

    def test_pipelined_output_matches_host(self):
        """Pipelining must not change a single shard byte."""
        rng = np.random.default_rng(7)
        for size in (0, 1, 1000, (1 << 18) - 1, 1 << 18, (1 << 18) + 1,
                     5 * (1 << 18) + 12345, 40 * (1 << 18)):
            data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            e_dev = _patched_erasure(_RecordingCodec(K, M))
            e_host = Erasure(K, M, 1 << 18, backend="host")
            _, _, dev_bufs = _stream(e_dev, data)
            _, _, host_bufs = _stream(e_host, data)
            for a, b in zip(dev_bufs, host_bufs):
                assert a.getvalue() == b.getvalue(), size

    def test_decode_roundtrip_through_pipeline(self):
        codec = _RecordingCodec(K, M)
        e = _patched_erasure(codec)
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, 3 * (1 << 20) + 777,
                            dtype=np.uint8).tobytes()
        _, _, bufs = _stream(e, data)
        till = e.shard_file_size(len(data))
        # drop parity-count shards: degraded read must still decode
        readers = [
            None if i in (0, 5) else
            bitrot.BitrotReader(io.BytesIO(bufs[i].getvalue()), till,
                                e.shard_size)
            for i in range(K + M)
        ]
        sink = io.BytesIO()
        e2 = Erasure(K, M, 1 << 18, backend="host")
        n = e2.decode_stream(sink, readers, 0, len(data), len(data))
        assert n == len(data) and sink.getvalue() == data

    def test_writer_failure_quorum_accounting_with_pipeline(self):
        """A writer dying mid-stream is excluded without corrupting the
        pipeline's batch ordering."""
        codec = _RecordingCodec(K, M)
        e = _patched_erasure(codec)

        class DyingWriter:
            def __init__(self):
                self.n = 0

            def write(self, b):
                self.n += 1
                if self.n > 2:
                    raise OSError("drive died")

        bufs = [io.BytesIO() for _ in range(K + M)]
        writers = [bitrot.BitrotWriter(b, e.shard_size) for b in bufs]
        writers[3] = DyingWriter()
        data = bytes(500) * (4 * 32 * 512)
        total, failed = e.encode_stream(io.BytesIO(data), writers,
                                        len(data), K + 1)
        assert total == len(data)
        assert failed == {3}

    def test_quorum_loss_aborts_cleanly(self):
        from minio_tpu.storage import errors

        codec = _RecordingCodec(K, M)
        e = _patched_erasure(codec)
        data = bytes(1 << 20) * 8

        class Dead:
            def write(self, b):
                raise OSError("nope")

        writers = [Dead() for _ in range(K + M)]
        with pytest.raises(errors.ErasureWriteQuorum):
            e.encode_stream(io.BytesIO(data), writers, len(data), K + 1)
