"""The server with its timed path broken underneath: the controls and
faults that `correct` has to fail (PERF.md, 2, "How correct is decided").

Started in the program's place by benchmark/tests (CPU, rehearsal sizes)
and by benchmark/tests/control.py (on the chip, the cell's own sizes);
never by a benchmark run.  BENCHMARK_FAULT names what is broken:

- parity_fewer  (control) one parity shard fewer than the configuration
  states: the tempting cut in durability (less to code and to write);
- parity_byte   an answer altered where it is produced: one byte of
  every encode dispatch's parity, as the erasure engine takes it from
  the codec, device or host;
- rebuilt_byte  one byte of every reconstruct dispatch's output;
- body_byte     one byte of every GET's body as the object layer hands
  it to the HTTP front;
- get_refused   every third GET is refused as if the read quorum were
  lost ("a GET with m drives away returns the same bytes": it returns
  none).
"""

from __future__ import annotations

import os
import sys

import numpy as np


def _flipped(out):
    """A copy of `out` with its first byte altered.  By index, not through
    a flat view: what the device hands back need not be C-contiguous, and
    reshaping it would alter a copy."""
    arr = np.array(out, dtype=np.uint8)
    arr[(0,) * arr.ndim] ^= 1
    return arr


def _plant_codec(name: str, coding) -> None:
    """Flip one byte of what every `name` (encode | reconstruct) dispatch
    of the erasure engine returns, whichever codec, device or host, made
    it."""
    if name == "encode":
        orig_async = coding.Erasure._encode_shards_async

        def encode_async(self, batch, pool=None):
            resolve = orig_async(self, batch, pool)
            return lambda: _flipped(resolve())

        coding.Erasure._encode_shards_async = encode_async
    else:
        orig_raw = coding.Erasure._reconstruct_shards_raw
        coding.Erasure._reconstruct_shards_raw = \
            lambda self, *args: _flipped(orig_raw(self, *args))


def plant(fault: str) -> None:
    from minio_tpu.erasure import coding, objects

    if fault == "parity_fewer":
        orig_parity = objects.ErasureObjects._parity_for
        objects.ErasureObjects._parity_for = \
            lambda self, opts: orig_parity(self, opts) - 1
    elif fault in ("parity_byte", "rebuilt_byte"):
        # broken at the first request, not at import: the program's boot
        # self-test refuses to serve with a codec that miscomputes
        stream, name = ("encode_stream", "encode") \
            if fault == "parity_byte" else ("decode_stream", "reconstruct")
        orig_stream = getattr(coding.Erasure, stream)
        planted = []

        def first(self, *args, **kw):
            if not planted:
                planted.append(True)
                _plant_codec(name, coding)
            return orig_stream(self, *args, **kw)

        setattr(coding.Erasure, stream, first)
    elif fault == "body_byte":
        orig_decode = coding.Erasure.decode_stream

        class Altered:
            def __init__(self, writer):
                self.writer, self.done = writer, False

            def write(self, data):
                if not self.done and len(data):
                    data = bytearray(data)
                    data[0] ^= 1
                    self.done = True
                return self.writer.write(data)

        def decode_stream(self, writer, *args, **kw):
            return orig_decode(self, Altered(writer), *args, **kw)

        coding.Erasure.decode_stream = decode_stream
    elif fault == "get_refused":
        from minio_tpu.storage.errors import ErasureReadQuorum

        orig_get = objects.ErasureObjects.get_object
        calls = []

        def get_object(self, *args, **kw):
            calls.append(True)
            if len(calls) % 3 == 0:
                raise ErasureReadQuorum("planted by the benchmark's test")
            return orig_get(self, *args, **kw)

        objects.ErasureObjects.get_object = get_object
    else:
        raise SystemExit(f"unknown BENCHMARK_FAULT {fault!r}")


def main() -> int:
    plant(os.environ["BENCHMARK_FAULT"])
    if os.environ.get("BENCHMARK_TRACE_DIR"):
        from benchmark import serve
        return serve.main()
    from minio_tpu.server.__main__ import main as server_main
    return server_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
