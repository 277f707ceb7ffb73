"""A stand-in for the chip's codec in the engine's place, as a boot
leaves a geometry: built and ready (its self-test passed), so that a
dispatch goes to it; and out again, with all the engine knew of the
geometry.  And what the tests of the device path share: the codec that
keeps what it was given, the oracle's parity, a stage's bytes."""

import numpy as np

from minio_tpu.erasure import coding, stagestats
from minio_tpu.ops import gf256


def plant(k: int, m: int, codec, wins=True) -> None:
    dc = coding._DeviceCodec
    dc._cache[(k, m)] = (codec, wins)
    dc._ready[(k, m)] = codec


def unplant(k: int, m: int) -> None:
    dc = coding._DeviceCodec
    for known in (dc._cache, dc._ready, dc._state):
        known.pop((k, m), None)


class Seen:
    """The device codec, keeping the shape of every batch it was given
    and what its blocks beyond the real ones held."""

    backend = "device"

    def __init__(self, inner):
        self.inner = inner
        self.shapes = []
        self.beyond = []

    def _note(self, batch, blocks):
        self.shapes.append((batch.shape[0], blocks))
        if blocks is not None:
            self.beyond.append(np.array(batch[blocks:]))

    def encode(self, batch, blocks=None):
        self._note(batch, blocks)
        return self.inner.encode(batch, blocks=blocks)

    def reconstruct(self, batch, available, wanted, blocks=None):
        self._note(batch, blocks)
        return self.inner.reconstruct(batch, available, wanted, blocks=blocks)


def oracle_parity(batch: np.ndarray, m: int) -> np.ndarray:
    return np.stack([gf256.encode_np(block, m) for block in batch])


def bytes_of(stage: str) -> int:
    return stagestats.snapshot()[stage]["bytes"]
