"""Streaming erasure engine: block pipeline + batched codec dispatch.

Equivalent of the reference's Erasure wrapper and streaming loops
(cmd/erasure-coding.go:35, cmd/erasure-encode.go:73, cmd/erasure-decode.go:206,
:287) re-shaped for TPU: instead of per-1MiB-block codec calls with
goroutine-per-drive fan-out, blocks are accumulated into batches of
(B, K, S) and dispatched to the device codec in one call; shard writes fan
out over a thread pool with write-quorum accounting.

Backend selection (reference analogue: MINIO_ERASURE_BACKEND in
BASELINE.json's north star):
- "host": C++ AVX2 PSHUFB codec (csrc/gf256_simd.cpp)
- "tpu":  Pallas fused MXU kernel (ops/rs_pallas.py); constructing an
  Erasure with it and no TPU attached raises
- "mesh": multi-device jax.sharding.Mesh codec (parallel/mesh.py
  MeshRSCodec) — (B, K, S) batches shard over (blocks, shards) axes and
  parity/heal come from ICI psum collectives; raises when fewer than 2
  devices are visible, host only when K does not divide the shards axis
- "auto": TPU when a TPU is attached AND the span is big enough to
  amortise dispatch; host otherwise (small objects are latency-bound).
Set via env MINIO_TPU_ERASURE_BACKEND.  Which device there is comes from
ops/device.info() and nowhere else; no caught exception ever turns a
device dispatch into a host one.
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import os

import threading
import time
import weakref
from typing import BinaryIO, Sequence

import numpy as np

from minio_tpu.ops import device, gf256, host
from minio_tpu.storage import errors
from minio_tpu.utils.deadline import ctx_submit, service_thread
from minio_tpu.utils.logger import log
from . import batcher as batcher_mod
from . import stagestats

BLOCK_SIZE_V2 = 1 << 20  # reference blockSizeV2, cmd/object-api-common.go:40
BACKENDS = ("auto", "host", "tpu", "mesh")

# Batch this many erasure blocks per device dispatch on the hot path.
DEVICE_BATCH_BLOCKS = 32
# The batch sizes a dispatch of the single-chip codec can have, ascending
# and ending in DEVICE_BATCH_BLOCKS.  Boot compiles and self-tests the
# device programs at these and at no other (selftest.device_self_test),
# so a group of g blocks is carried by the smallest of them that holds
# it (`carrier_blocks`): no object size compiles inside a request.  Each
# further size is (1 + m) more programs a geometry at boot (cold 4-10 s
# each, 65 s at 2+2).  16 is there because the chip said so (PERF.md
# section 6, PR 32): 20 concurrent GETs of 10 MiB objects, each a
# 10-block dispatch carried at 32, read 5-12% under their own exact
# program; a carrier's transfer, device time and read-back are serial
# among streams that run in step.
DEVICE_BATCH_SIZES = (16, DEVICE_BATCH_BLOCKS)
# Use the device only when at least this many bytes are in flight.
DEVICE_MIN_BYTES = 8 << 20
# Encoded batches kept in flight on the device pipeline (double
# buffering: transfer of N+1 overlaps compute of N and readback of N-1).
PIPELINE_DEPTH = 2
# Host-codec pipeline depth: AVX2 encodes run on the I/O pool (the C
# call releases the GIL) so encoding batch N overlaps reading batch N+1
# and writing batch N-1.  Depth 1 keeps at most one host encode in
# flight — enough to hide the encode behind the read, without the
# device path's memory profile.
HOST_PIPELINE_DEPTH = max(0, int(os.environ.get(
    "MINIO_TPU_HOST_PIPELINE_DEPTH", "1")))


def pipeline_enabled() -> bool:
    """Data-plane pipelining master switch (arena reads, deferred etag
    folding, host-encode overlap).  MINIO_TPU_DATAPLANE_PIPELINE=0
    restores the serial reference path — the differential suite compares
    the two byte-for-byte."""
    return os.environ.get(
        "MINIO_TPU_DATAPLANE_PIPELINE", "1").lower() not in (
            "0", "off", "false")


_pool_lock = threading.Lock()
_shared_pool: cf.ThreadPoolExecutor | None = None

# Reusable buffers of the streams: a PUT's read slots, a degraded read's
# staging arenas and a GET's response blocks.  A fresh 32 MiB np.empty
# per slot per PUT costs ~100 MiB of page faults per request (an mmap,
# a first touch of every page, a munmap; on the chip's machines ten
# times the copy into warm pages, PERF.md section 6, PR 29); the pool
# keeps recently-used buffers warm.  Keyed by exact size, LRU across
# size classes (dict preserves insertion order; a touch reinserts the
# key): small streams clamp slot size to the stream, so a varied-size
# workload mints many one-off classes — without eviction those would pin
# the whole budget and lock the hot full-batch arenas out of the pool.
# The budget is what may lie idle, and is sized so that the steady state
# of eight streams of 64 MiB GETs (one arena and two blocks of 32 MiB
# each, 768 MiB when all of it lies idle at once) and of twenty 10 MiB
# GETs (a 16-block arena and a 10 MiB block each, 521 MiB) is taken
# from the pool and not from the allocator.
_arena_lock = threading.Lock()
_arena_pool: dict[int, list] = {}
_ARENA_POOL_MAX_BYTES = 1 << 30
_arena_pool_bytes = 0
# buffers of response blocks that nothing refers to any more, on their
# way back to the pool.  A block's finalizer runs wherever its last
# reference goes, inside a garbage collection that an allocation under
# _arena_lock set off too, so it never waits for the lock: it appends
# here (atomic) and takes the lock only if that is free; what it leaves
# behind the pool's next caller takes in
_blocks_dropped: collections.deque = collections.deque()


def _pool_put(arr: np.ndarray) -> None:
    """Under _arena_lock: a flat buffer becomes the most recent of its
    size class, and the least recently touched classes make room."""
    # lint: allow(shared-state): per-process arena pool by design — each data-plane worker recycles its own read buffers
    global _arena_pool_bytes
    if arr.nbytes > _ARENA_POOL_MAX_BYTES:
        return
    while _arena_pool_bytes + arr.nbytes > _ARENA_POOL_MAX_BYTES:
        size, bucket = next(iter(_arena_pool.items()))
        bucket.pop()
        _arena_pool_bytes -= size
        if not bucket:
            del _arena_pool[size]
    bucket = _arena_pool.pop(arr.nbytes, [])
    bucket.append(arr)
    _arena_pool[arr.nbytes] = bucket
    _arena_pool_bytes += arr.nbytes


def _pool_take_in() -> None:
    """Under _arena_lock: the dropped blocks' buffers join the pool."""
    while _blocks_dropped:
        _pool_put(_blocks_dropped.popleft())


def _pool_take(nbytes: int) -> np.ndarray | None:
    """A pooled flat buffer of exactly `nbytes`, its pages there
    already, or None."""
    # lint: allow(shared-state): per-process arena pool by design — see _pool_put
    global _arena_pool_bytes
    with _arena_lock:
        _pool_take_in()
        bucket = _arena_pool.pop(nbytes, None)
        if not bucket:
            return None
        arr = bucket.pop()
        if bucket:
            _arena_pool[nbytes] = bucket  # reinsert: now most-recent
        _arena_pool_bytes -= nbytes
        return arr


def _arena_acquire(nbytes: int) -> np.ndarray:
    arr = _pool_take(nbytes)
    return np.empty(nbytes, dtype=np.uint8) if arr is None else arr


def _arena_release(arr: np.ndarray) -> None:
    # the head of a carrier gives back the whole of it
    arr = _carrier_of(arr, arr)
    if arr.ndim != 1:
        # the pool hands out flat arrays, whatever shape a user gave
        # its arena (contiguous: a view)
        arr = arr.reshape(-1)
    with _arena_lock:
        _pool_put(arr)


def _block_dropped(raw: np.ndarray) -> None:
    _blocks_dropped.append(raw)
    if _arena_lock.acquire(blocking=False):
        try:
            _pool_take_in()
        finally:
            _arena_lock.release()


def _block_acquire(nblocks: int, block_len: int) -> np.ndarray:
    """A (nblocks, block_len) response block on a pooled buffer, which
    goes back to the pool when nothing refers to the block's memory any
    more.  The writer keeps what it is handed for a time this module
    cannot see (the sink's queue, the pump's read-ahead, the socket's
    buffer after the write returned, a consumer's own list), so no call
    gives a block back: the pool keeps the raw buffer, every take wraps
    it in a new array, and that array's finalizer returns the buffer.
    The wrap goes over a memoryview because numpy then stops a view's
    chain of bases at the new array (a plain view of the raw array
    would hand its views the raw array as their base, and the block
    could die before them): every slice, reshape and memoryview of the
    block keeps it alive.

    The buffer holds what its last user left: the caller writes every
    byte before it hands the block on."""
    nbytes = nblocks * block_len
    raw = _pool_take(nbytes)
    if raw is None:
        raw = np.empty(nbytes, dtype=np.uint8)
    else:
        stagestats.add("block_reuse", 0.0, nbytes)
    block = np.frombuffer(memoryview(raw), dtype=np.uint8)
    weakref.finalize(block, _block_dropped, raw)
    return block.reshape(nblocks, block_len)


def carrier_blocks(g: int) -> int:
    """The compiled batch size that carries a single-chip dispatch of g
    blocks; g itself beyond the largest (the request batcher's merged
    batches, which it sizes itself)."""
    return next((b for b in DEVICE_BATCH_SIZES if b >= g), g)


def _dispatch_blocks(dev, g: int) -> int:
    """Blocks of the array that a dispatch of g blocks hands the codec
    `dev` (None: the host's): more than g where the single-chip codec
    carries them at a compiled batch size."""
    return carrier_blocks(g) if _backend_name(dev) == "device" else g


class _Head(np.ndarray):
    """The first g blocks of a (B, K, S) carrier, B one of
    DEVICE_BATCH_SIZES: a batch that a device dispatch takes as it
    lies, whole carrier and all, instead of copying it into one.  What
    the carrier holds beyond block g is whatever its buffer last held;
    the rows made of it are dropped where the output arrives
    (`_on_device`).  Views of a head are plain batches again."""

    carrier: np.ndarray | None = None


def _head(carrier: np.ndarray, g: int) -> np.ndarray:
    if g == carrier.shape[0]:
        return carrier
    head = carrier[:g].view(_Head)
    head.carrier = carrier
    return head


def _carrier_of(batch: np.ndarray, default=None):
    carrier = getattr(batch, "carrier", None)
    return default if carrier is None else carrier


def _on_device(dev, batch: np.ndarray, nrows: int, code):
    """Start one dispatch of the device codec `dev` on the g blocks of
    `batch` (g, K, S); returns resolve() -> their (g, nrows, S) rows on
    the host.  `code(shards)` is the codec's entry, `encode` or
    `reconstruct` with its matrix bound.

    The single-chip codec's program is compiled per batch size, so a
    batch of another size than DEVICE_BATCH_SIZES holds goes inside the
    next larger one: in place where the batch is the head of its carrier
    already (the staged reads' arenas, a PUT's slots), else copied into
    a pooled one.  The codec books its stages for the g blocks
    (`blocks=`), `batch_fill` takes the input bytes carried beyond them,
    and of the output only the g blocks' rows are kept: a view, nothing
    of the rest is copied anywhere."""
    g, k, s = batch.shape
    size = _dispatch_blocks(dev, g)
    own = None
    if size == g:
        out = code(batch)
    else:
        carrier = _carrier_of(batch)
        if carrier is None or carrier.shape[0] != size:
            with stagestats.timed("assemble", batch.nbytes):
                own = carrier = _arena_acquire(size * k * s).reshape(
                    size, k, s)
                carrier[:g] = batch
        stagestats.add("batch_fill", 0.0, (size - g) * k * s)
        out = code(carrier, blocks=g)

    def resolve() -> np.ndarray:
        with stagestats.timed("fetch", g * nrows * s):
            rows = np.asarray(out)[:g]
        if own is not None:
            _arena_release(own)
        return rows

    return resolve


def _io_pool() -> cf.ThreadPoolExecutor:
    # lint: allow(shared-state): per-process executor singleton by design — worker processes need their own shard-io threads
    global _shared_pool
    with _pool_lock:
        if _shared_pool is None:
            _shared_pool = cf.ThreadPoolExecutor(
                max_workers=int(os.environ.get("MINIO_TPU_IO_THREADS", "32")),
                thread_name_prefix="shard-io",
            )
        return _shared_pool


def io_submit(fn, *args) -> cf.Future:
    """`fn(*args)` on the shared I/O pool under the caller's context
    (ctx_submit: its deadline budget and its request trace ride along),
    with the task's wait for a pool thread booked as the stage
    `pool_wait`: from here until its first line on a `shard-io` thread,
    once a task.  A fan-out wider than the pool (MINIO_TPU_IO_THREADS),
    or tasks that wait inside a pool thread for their predecessor, show
    here and not in the stage of the work they queue for."""
    t0 = time.perf_counter()

    def run():
        stagestats.add("pool_wait", time.perf_counter() - t0)
        return fn(*args)

    return ctx_submit(_io_pool(), run)


# Which codec served erasure work, and how much: operators need to SEE
# whether PUT/GET/heal bytes ran on the host AVX2 path, the single-chip
# device path, or the mesh — the auto probe's verdict is useless if
# nothing surfaces it (VERDICT r4 weak #5).  Exposed via Prometheus
# (minio_erasure_*) and admin server info.
backend_stats = {
    "host": {"dispatches": 0, "bytes": 0},
    "device": {"dispatches": 0, "bytes": 0},
    "mesh": {"dispatches": 0, "bytes": 0},
}


def _backend_name(dev) -> str:
    # codecs declare their stats bucket explicitly via a `backend` class
    # attribute (_PaddedCodec delegates) — no fragile class-name matching
    # (ADVICE r5)
    if dev is None:
        return "host"
    return getattr(dev, "backend", "device")


_stats_lock = threading.Lock()


def _count(name: str, nbytes: int) -> None:
    # read-modify-write under a lock: executor threads dispatch
    # concurrently and a drifting counter is worse than none
    with _stats_lock:
        st = backend_stats[name]
        st["dispatches"] += 1
        st["bytes"] += nbytes


def probe_verdicts() -> dict:
    """{'k+m': verdict} per EC config seen so far: True = probe picked
    the device codec, False = probe rejected it (or no device codec
    exists), None = codec present but not yet probed (backend=tpu
    bypasses the probe; auto probes lazily on first use)."""
    with _DeviceCodec._lock:  # get() mutates _cache under this lock
        items = list(_DeviceCodec._cache.items())
    out = {}
    for (k, m), (codec, wins) in items:
        out[f"{k}+{m}"] = None if (codec is not None and wins is None) \
            else bool(wins) if codec is not None else False
    return out


class _DeviceCodec:
    """Lazy singleton per (k, m): the Pallas codec of the attached TPU.

    Whether there is a TPU is `device.info()`'s answer and nothing
    else: on platform "tpu" a codec that fails to build, compile or run
    raises to the caller — no exception turns a device dispatch into a
    host one.  `get(k, m)` (backend "auto") additionally runs a one-time
    calibration probe and selects the device only if a transfer-inclusive
    encode beats the host codec on this machine; the probe can lose on
    time, never on an error.  `get(k, m, probe=False)` (backend "tpu")
    bypasses the verdict and raises BackendUnavailable without a TPU.

    A dispatch takes the codec from `ready`, never from `get`: a
    geometry's programs exist (compiled, or read from the persistent
    cache, and compared with the oracle) once `self_test` has passed
    for it, and nothing compiles them on a request's thread.  Boot runs
    `self_test` for the geometries a healthy set writes; any other (the
    upgraded parity of a PUT to a set with drives away, an object
    another deployment wrote) is coded by the host codec, byte for byte
    the same, while one background thread runs its `self_test`.
    """

    _cache: dict = {}  # (k, m) -> (codec | None, device_wins: bool | None)
    _lock = threading.Lock()
    # (k, m) -> the codec whose self-test has passed: the one lookup a
    # dispatch makes
    _ready: dict = {}
    # (k, m) -> "warming" | "device" | "host" | "failed", for every
    # geometry that was asked for (admin info, the ready gauge); a
    # geometry in here is never asked for again
    _state: dict = {}
    _asked: collections.deque = collections.deque()  # (k, m, probe) waiting
    _warmer: threading.Thread | None = None  # lives while _asked holds any
    _warming = threading.local()  # .on: this thread is inside self_test

    @classmethod
    def _probe(cls, codec, k: int, m: int) -> bool:
        """True if transfer-inclusive device encode beats the host codec."""
        host_codec = host.HostRSCodec(k, m)
        shard = 128 * 1024

        def time_pair(nblocks: int) -> tuple[float, float]:
            batch = np.zeros((nblocks, k, shard), dtype=np.uint8)
            best_d = best_h = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                np.asarray(codec.encode(batch))
                best_d = min(best_d, time.perf_counter() - t0)
                t0 = time.perf_counter()
                host_codec.encode(batch)
                best_h = min(best_h, time.perf_counter() - t0)
            return best_d, best_h

        nblocks = 8
        dev_t, host_t = time_pair(nblocks)
        wins = dev_t <= 4 * host_t
        if wins:
            # close call at 8 blocks: fixed dispatch latency may dominate;
            # re-probe at the steady-state batch size before deciding.
            nblocks = DEVICE_BATCH_BLOCKS
            dev_t, host_t = time_pair(nblocks)
            wins = dev_t <= host_t
        log.info("erasure auto probe", config=f"{k}+{m}", blocks=nblocks,
                 device_seconds=dev_t, host_seconds=host_t,
                 verdict="device" if wins else "host")
        return wins

    _mesh_cache: dict = {}  # (k, m) -> MeshRSCodec | None

    @classmethod
    def get_mesh(cls, k: int, m: int):
        """Multi-device mesh codec (backend "mesh"): shards (B, K, S)
        batches over a jax.sharding.Mesh (parallel/mesh.py), replacing the
        reference's per-drive goroutine fan-out with ICI collectives.
        Raises BackendUnavailable when fewer than 2 devices are visible;
        None when K does not divide over the shards axis (a geometry the
        mesh cannot lay out — callers use the host codec for it)."""
        with cls._lock:
            key = (k, m)
            if key not in cls._mesh_cache:
                dev = device.info()
                if dev.count < 2:
                    raise device.BackendUnavailable(
                        "erasure backend 'mesh' needs at least 2 devices; "
                        f"JAX found {dev.count} x {dev.platform}")
                from minio_tpu.parallel import mesh as pmesh

                mesh = pmesh.make_mesh()
                cls._mesh_cache[key] = (
                    pmesh.MeshRSCodec(k, m, mesh)
                    if k % mesh.shape["shards"] == 0 else None)
            return cls._mesh_cache[key]

    @classmethod
    def get(cls, k: int, m: int, probe: bool = True):
        with cls._lock:
            key = (k, m)
            if key not in cls._cache:
                codec = None
                if device.info().platform == "tpu":
                    from minio_tpu.ops import rs_pallas

                    codec = rs_pallas.PallasRSCodec(k, m)
                # verdict computed lazily on the first probe=True caller;
                # backend="tpu" callers never pay for it
                cls._cache[key] = (codec, None)
            codec, wins = cls._cache[key]
            if not probe:
                if codec is None:
                    device.require_tpu("erasure backend 'tpu'")
                return codec
            if codec is None:
                return None
            if wins is None:
                # lint: allow(blocking-under-lock): one-time probe per (k, m) under the codec cache lock — the verdict is memoized, later callers never re-enter the build
                wins = cls._probe(codec, k, m)
                cls._cache[key] = (codec, wins)
            return codec if wins else None

    @classmethod
    def ready(cls, k: int, m: int, probe: bool = False, nbytes: int = 0):
        """The geometry's device codec once its self-test has passed;
        until then None, the host codec's turn, and the first such call
        asks for the self-test (`warm`).  `nbytes`: what a dispatch is
        about to code; the stage `warming` takes them where the host
        codes them only because the programs are not there."""
        codec = cls._ready.get((k, m))
        if codec is None:
            if (k, m) not in cls._state:
                cls.warm(k, m, probe)
            if nbytes and cls._state.get((k, m)) != "host":
                stagestats.add("warming", 0.0, nbytes)
        return codec

    @classmethod
    def warm(cls, k: int, m: int, probe: bool = False) -> None:
        """Ask, once, for the geometry's self-test on the one background
        thread; nothing where it is ready, asked for already, or where
        no TPU is attached (backend "auto": the host codec for good)."""
        on_tpu = device.info().platform == "tpu"
        with cls._lock:
            if (k, m) in cls._state:
                return
            if not on_tpu:
                cls._state[(k, m)] = "host"
                return
            cls._state[(k, m)] = "warming"
            cls._asked.append((k, m, probe))
            if cls._warmer is None:
                # lint: allow(shared-state): per-process by design — the warm-up thread belongs to the one process that holds the chip (data-plane workers are pinned to the host codec and never ask)
                cls._warmer = service_thread(cls._warm_loop,
                                             name="codec-warm")

    @classmethod
    def _warm_loop(cls) -> None:
        while True:
            with cls._lock:
                if not cls._asked:
                    # lint: allow(shared-state): per-process by design — see warm()
                    cls._warmer = None
                    return
                k, m, probe = cls._asked.popleft()
            geometry = f"{k}+{m}"
            try:
                if probe and cls.get(k, m) is None:
                    with cls._lock:
                        cls._state[(k, m)] = "host"
                    continue
                with stagestats.annotation("codec.warm"):
                    seconds = cls.self_test(k, m)
                log.info("erasure geometry ready on the device",
                         geometry=geometry, seconds=round(seconds, 3))
            except Exception as e:
                # a serving node goes on, this geometry on the host
                # codec; admin info and the ready gauge say so
                with cls._lock:
                    cls._state[(k, m)] = "failed"
                log.error("erasure geometry stays on the host codec: its "
                          "device self-test failed", geometry=geometry,
                          error=f"{type(e).__name__}: {e}")

    @classmethod
    def self_test(cls, k: int, m: int) -> float:
        """selftest.device_self_test for this geometry, on the calling
        thread (boot's, or the warm-up's); once it has passed, the
        geometry's dispatches go to the device.  -> its seconds."""
        from minio_tpu.selftest import device_self_test

        cls._warming.on = True
        try:
            seconds = device_self_test(k, m, BLOCK_SIZE_V2)
        finally:
            cls._warming.on = False
        with cls._lock:
            cls._ready[(k, m)] = cls._cache[(k, m)][0]
            cls._state[(k, m)] = "device"
        return seconds

    @classmethod
    def self_testing(cls) -> bool:
        """Whether the calling thread is inside `self_test`: what
        compiles there is a warm-up, what compiles anywhere else a
        request waited for (the stage `compile_wait`)."""
        return getattr(cls._warming, "on", False)


def geometry_states() -> dict:
    """{'k+m': "warming" | "device" | "host" | "failed"} of every
    geometry whose single-chip codec was asked for: `device` once its
    self-test has passed, `warming` while the background thread is at
    it (its dispatches are on the host codec), `failed` where that
    self-test did not pass, `host` where there is no device codec."""
    with _DeviceCodec._lock:
        return {f"{k}+{m}": state
                for (k, m), state in sorted(_DeviceCodec._state.items())}


def steady_state_backend(k: int, m: int,
                         block_size: int = BLOCK_SIZE_V2) -> str:
    """"device" | "mesh" | "host": where a full batch of this geometry's
    blocks is coded under the configured backend — the dispatch
    encode_stream, degraded reads and heal make in steady state, once
    the geometry's device programs are there (_DeviceCodec.self_test).
    Under "auto" on a TPU this runs the calibration probe.  The rule is
    by shard length and not by geometry: full-width shards of any length
    go to the device (12+4's 87,382 bytes too: the dispatch program
    widens them to the kernel's tile on the device, ops/rs_pallas.py),
    tail blocks and inline objects stay on the host."""
    e = Erasure(k, m, block_size)
    if m and e.backend in ("tpu", "auto"):
        return _backend_name(
            _DeviceCodec.get(k, m, probe=e.backend == "auto"))
    return _backend_name(
        e._device(block_size * DEVICE_BATCH_BLOCKS, e.shard_size))


class _PaddedCodec:
    """Codes a batch at the codec's steady-state shard width, so one
    compiled mesh program serves tail blocks too: widened on the host
    before the batch is laid over the mesh (gf256.code_at_width, the
    same widening the single-chip program does on the device for a
    shard that is no multiple of its tile); outputs are sliced back
    lazily (the JAX array stays async until resolved)."""

    def __init__(self, inner, s_full: int):
        self.inner = inner
        self.s_full = s_full

    @property
    def backend(self) -> str:
        return getattr(self.inner, "backend", "device")

    def _pad(self, batch: np.ndarray, widths) -> np.ndarray:
        b, k, _ = batch.shape
        with stagestats.timed("pad", b * k * self.s_full):
            return np.pad(batch, widths)

    def encode(self, batch: np.ndarray):
        return gf256.code_at_width(
            self.inner.encode, batch, self.s_full, self._pad)

    def reconstruct(self, batch: np.ndarray, available, wanted):
        return gf256.code_at_width(
            lambda wide: self.inner.reconstruct(wide, available, wanted),
            batch, self.s_full, self._pad)


class Erasure:
    """EC geometry + codec dispatch for one (k, m, block_size)."""

    def __init__(self, data_blocks: int, parity_blocks: int,
                 block_size: int = BLOCK_SIZE_V2, backend: str | None = None,
                 set_id: int = 0):
        if data_blocks <= 0 or parity_blocks < 0 or data_blocks + parity_blocks > 256:
            raise errors.InvalidArgument(
                f"invalid erasure config {data_blocks}+{parity_blocks}"
            )
        self.k = data_blocks
        self.m = parity_blocks
        self.block_size = block_size
        self.backend = backend or os.environ.get(
            "MINIO_TPU_ERASURE_BACKEND", "auto"
        )
        if self.backend not in BACKENDS:
            raise errors.InvalidArgument(
                f"unknown erasure backend {self.backend!r} "
                f"(one of {', '.join(BACKENDS)})")
        # an explicit device backend without its device is an error
        # here, at construction — never a host run under its name
        if self.m and self.backend == "tpu":
            _DeviceCodec.get(self.k, self.m, probe=False)
        elif self.m and self.backend == "mesh":
            _DeviceCodec.get_mesh(self.k, self.m)
        # erasure-set id of the caller: the request batcher lays tick
        # batches out set-major so the mesh shards them by erasure set
        self.set_id = set_id
        self._host = host.HostRSCodec(self.k, self.m)
        # observability: deepest device-pipeline occupancy reached by
        # encode_stream (>1 proves overlapped dispatches)
        self.max_inflight = 0

    # -- geometry (cmd/erasure-coding.go:122-150) ---------------------------
    @property
    def shard_size(self) -> int:
        return -(-self.block_size // self.k)

    def shard_file_size(self, total: int) -> int:
        if total == 0:
            return 0
        if total == -1:
            return -1
        num = total // self.block_size
        last = total % self.block_size
        last_shard = -(-last // self.k) if last else 0
        return num * self.shard_size + last_shard

    def shard_file_offset(self, start: int, length: int, total: int) -> int:
        shard_size = self.shard_size
        shard_file_size = self.shard_file_size(total)
        end_shard = (start + length) // self.block_size
        till = end_shard * shard_size + shard_size
        return min(till, shard_file_size)

    # -- single-block codec -------------------------------------------------
    def encode_data(self, data: bytes | memoryview) -> list[np.ndarray]:
        """One payload -> k+m shards (EncodeData, cmd/erasure-coding.go:77)."""
        if len(data) == 0:
            return [np.empty(0, dtype=np.uint8) for _ in range(self.k + self.m)]
        shards = gf256.split(data, self.k)
        parity = self._encode_shards(shards[None, ...])[0]
        return [shards[i] for i in range(self.k)] + list(parity)

    def _device(self, nbytes: int, shard_len: int, dispatch: bool = False):
        """The device codec to use for this dispatch, or None for host.
        `dispatch`: the caller is about to code these bytes (and does
        not only ask where they would go)."""
        if self.m == 0 or self.backend == "host":
            return None
        if self.backend == "mesh":
            codec = _DeviceCodec.get_mesh(self.k, self.m)
            if codec is None:
                return None
            if shard_len != self.shard_size:
                # streaming tail blocks (shard close to steady state):
                # pad the shard axis up to the compiled shape so the
                # SAME mesh program serves them (GF coding is byte-wise:
                # zero columns encode to zero parity, trimmed after)
                # instead of dropping to host mid-stream (VERDICT r4
                # weak #4).  SMALL dispatches (tiny objects, inline
                # blocks) stay on the host codec — padding them to full
                # width would trade a microsecond AVX2 encode for a
                # full device round trip.
                if self.shard_size // 2 <= shard_len < self.shard_size:
                    return _PaddedCodec(codec, self.shard_size)
                return None
            return codec
        # Only a geometry's full-width shards go to the single chip,
        # whatever their length (the dispatch program widens a shard
        # that is no multiple of the kernel's tile on the device): a
        # tail block or an inline object is one sub-MiB dispatch that
        # cannot amortise a round trip, and every distinct shard length
        # is another compile.  (Under "auto" DEVICE_MIN_BYTES already
        # keeps those on the host; this makes "tpu" agree.)
        # And only a geometry whose programs are there (compiled and
        # self-tested, at boot or by the warm-up thread): until then
        # the host codec, never a compile inside a request.
        if shard_len != self.shard_size:
            return None
        auto = self.backend == "auto"
        if auto and nbytes < DEVICE_MIN_BYTES:
            return None
        return _DeviceCodec.ready(self.k, self.m, probe=auto,
                                  nbytes=nbytes if dispatch else 0)

    def warm(self) -> None:
        """Ask for this geometry's device programs ahead of its first
        dispatch; nothing where a full batch does not go to the single
        chip, or where they are there or asked for."""
        self._device(self.block_size * DEVICE_BATCH_BLOCKS, self.shard_size)

    def _dispatch_codec(self, batch: np.ndarray):
        """`_device` for the one dispatch that codes `batch` now,
        counted under the backend it goes to."""
        dev = self._device(batch.nbytes, batch.shape[2], dispatch=True)
        _count(_backend_name(dev), batch.nbytes)
        return dev

    # -- batched cross-request dispatch (erasure/batcher.py, ISSUE 11) ------
    def _batcher(self):
        """The process batcher, or None (gate off / zero parity)."""
        if self.m == 0 or not batcher_mod.enabled():
            return None
        return batcher_mod.get()

    def _sig(self, kind: str, shard_len: int, extra: tuple = ()) -> tuple:
        """Geometry signature: items sharing one MUST be concatenable
        into one fused program (same codec resolution, same matrix)."""
        return (kind, self.k, self.m, self.backend, shard_len) + extra

    def _via_batcher(self, kind: str, batch: np.ndarray, raw,
                     extra: tuple = ()):
        """Route one dispatch through the request batcher: returns
        ``resolve() -> np.ndarray`` or None when not routed (gate off,
        zero parity, batcher closing).  EVERY BatcherClosed — at
        enqueue OR at resolve (fused dispatch failure, tick-thread
        death, quiesce timeout) — falls back to the per-request `raw`
        dispatch; the one definition of the fallback semantics shared
        by encode, reconstruct and repair._dispatch."""
        bt = self._batcher()
        if bt is None:
            return None
        try:
            resolve = bt.enqueue_async(
                self._sig(kind, batch.shape[2], extra), batch, raw,
                self.set_id)
        except batcher_mod.BatcherClosed:
            return None  # closing/closed: straight to the raw plane

        def resolve_or_fallback():
            # the arena slot backing `batch` stays pinned until this
            # returns, so a fallback re-dispatch reads live bytes
            try:
                return resolve()
            except batcher_mod.BatcherClosed:
                return raw(batch)

        return resolve_or_fallback

    def _encode_shards_raw(self, batch: np.ndarray) -> np.ndarray:
        """(B, K, S) -> (B, M, S) parity via the selected backend — the
        actual dispatch; the batcher feeds MERGED cross-request batches
        through here, so `_device` prices the fused size (small
        per-request dispatches coalesce their way onto the device)."""
        dev = self._dispatch_codec(batch)
        if dev is not None:
            return _on_device(dev, batch, self.m, dev.encode)()
        with stagestats.timed("host_codec", batch.nbytes):
            return self._host.encode(batch)

    def _host_encode(self, batch: np.ndarray,
                     out: np.ndarray | None = None) -> np.ndarray:
        """The host codec's encode inside the streaming pipeline: booked
        as `encode`, the stage, and as its leaf `host_codec`."""
        with stagestats.timed("encode", batch.nbytes), \
                stagestats.timed("host_codec", batch.nbytes):
            return self._host.encode(batch, out=out)

    def _encode_shards(self, batch: np.ndarray) -> np.ndarray:
        """(B, K, S) -> (B, M, S) parity, coalesced across concurrent
        requests when the batcher gate is on (per-request otherwise)."""
        routed = self._via_batcher("enc", batch, self._encode_shards_raw)
        if routed is not None:
            return routed()
        return self._encode_shards_raw(batch)

    def _encode_shards_async(self, batch: np.ndarray, pool=None):
        """Non-blocking dispatch: returns resolve() -> (B, M, S) parity.

        Device dispatches ride JAX async dispatch — device_put, the
        kernel, and the parity readback stay in flight while the caller
        reads + splits the NEXT batch from disk, so H2D DMA, MXU compute,
        D2H DMA, disk reads, and bitrot hashing all overlap (the
        double-buffered streaming BASELINE.md names as the hard part;
        reference overlaps via per-block goroutines,
        cmd/erasure-encode.go:73).  Host encodes run on the shared I/O
        pool (io_submit) when `pool` is given (the AVX2 C call releases
        the GIL, so the encode overlaps the caller's next read); without
        one they compute here and resolve immediately.

        With the request batcher gate on, the dispatch is handed to the
        batcher instead: the tick thread fuses it with concurrent
        requests' batches and the returned resolve() blocks on the
        per-item future — the pipeline depth bookkeeping upstream is
        unchanged, so the read of batch N+1 still overlaps the fused
        dispatch of batch N."""
        routed = self._via_batcher("enc", batch, self._encode_shards_raw)
        if routed is not None:
            return routed
        b, k, s = batch.shape
        dev = self._dispatch_codec(batch)
        if dev is not None:
            t0 = time.perf_counter()
            resolve = _on_device(dev, batch, self.m, dev.encode)

            def resolve_dev():
                arr = resolve()
                stagestats.add("encode", time.perf_counter() - t0,
                               batch.nbytes)
                return arr

            return resolve_dev
        if pool is not None and b > 1:
            # shard the batch across pool workers: the AVX2 matmul
            # releases the GIL, so sub-encodes run truly parallel and
            # the whole batch encodes in a fraction of the single-thread
            # time while the caller reads the next batch.  Shard count
            # follows the core count — oversubscribing a small host only
            # adds contention.
            parity = np.empty((b, self.m, s), dtype=np.uint8)
            nshards = max(1, min(4, (os.cpu_count() or 4) - 1, b))
            step = -(-b // nshards)

            def enc_range(lo: int, hi: int) -> None:
                # one batched C call per shard: parity lands in place,
                # the GIL is released for the whole span
                self._host_encode(batch[lo:hi], parity[lo:hi])

            futs = [io_submit(enc_range, lo, min(lo + step, b))
                    for lo in range(0, b, step)]

            def resolve_host():
                for f in futs:
                    f.result()
                return parity

            return resolve_host
        if pool is not None:
            return io_submit(self._host_encode, batch).result
        out = self._host_encode(batch)
        return lambda: out

    def _reconstruct_shards_raw(self, batch: np.ndarray, available: tuple,
                                wanted: tuple) -> np.ndarray:
        dev = self._dispatch_codec(batch)
        if dev is not None:
            return _on_device(
                dev, batch, len(wanted),
                lambda shards, **kw: dev.reconstruct(
                    shards, available, wanted, **kw))()
        with stagestats.timed("host_codec", batch.nbytes):
            return self._host.reconstruct(batch, available, wanted)

    def _reconstruct_shards(self, batch: np.ndarray, available: tuple,
                            wanted: tuple) -> np.ndarray:
        """Degraded-read/heal reconstruct, coalesced across concurrent
        requests when the batcher gate is on.  The signature folds the
        (available, wanted) matrix identity in, so one fused program
        serves exactly one reconstruct matrix (matrix stays
        device-resident via ops/residency.py)."""
        available = tuple(available)
        wanted = tuple(wanted)

        def dispatch(cat: np.ndarray) -> np.ndarray:
            return self._reconstruct_shards_raw(cat, available, wanted)

        routed = self._via_batcher("rec", batch, dispatch,
                                   (available, wanted))
        if routed is not None:
            return routed()
        return dispatch(batch)

    def decode_data_blocks(self, shards: list[np.ndarray | None]) -> list[np.ndarray]:
        """Rebuild missing data shards in a k+m shard list
        (DecodeDataBlocks, cmd/erasure-coding.go:96)."""
        present = [s for s in shards if s is not None]
        if len(present) == len(shards) or not present:
            return list(shards)
        return gf256.reconstruct_np(list(shards), self.k, self.m, data_only=True)

    @staticmethod
    def _readinto_full(reader, mv: memoryview) -> int:
        """Fill `mv` from the reader via readinto (short reads looped);
        returns bytes read (< len(mv) only at EOF)."""
        got = 0
        while got < len(mv):
            n = reader.readinto(mv[got:])
            if not n:
                break
            got += n
        return got

    @staticmethod
    def _read_full(reader: BinaryIO, want: int) -> bytes:
        """Read exactly `want` bytes unless EOF (raw readers may short-read)."""
        data = reader.read(want)
        if data is None:
            data = b""
        if len(data) == want or not data:
            return data
        chunks = [data]
        got = len(data)
        while got < want:
            more = reader.read(want - got)
            if not more:
                break
            chunks.append(more)
            got += len(more)
        return b"".join(chunks)

    # -- streaming encode (cmd/erasure-encode.go:73) ------------------------
    def encode_stream(self, reader: BinaryIO, writers: Sequence,
                      total_size: int, write_quorum: int,
                      pipelined: bool | None = None
                      ) -> tuple[int, set[int]]:
        """Read the payload, EC-encode per block (batched), fan shards out to
        `writers` (BitrotWriter per drive; None = offline drive).

        Pipelined mode (the default; MINIO_TPU_DATAPLANE_PIPELINE=0 or
        pipelined=False restores the serial reference path):
        - batches are read via `readinto` into a small ring of reusable
          arenas (depth + 2 slots, so an in-flight device batch or shard
          write never aliases a buffer being refilled) instead of a fresh
          per-batch allocation;
        - if the reader exposes `hash_view` (the _HashingReader etag
          protocol), each filled arena is handed to an in-order hasher
          stage on the I/O pool, taking MD5/etag folding off the read→
          encode critical path;
        - host-codec encodes dispatch to the pool (HOST_PIPELINE_DEPTH)
          so the AVX2 encode of batch N overlaps the read of batch N+1
          and the shard writes of batch N-1.

        Returns (bytes consumed, failed shard indices) so callers can
        exclude failed drives from the metadata commit and queue heal
        (reference excludes failed onlineDisks, cmd/erasure-object.go:1006).
        Raises ErasureWriteQuorum if fewer than write_quorum streams stay
        healthy.
        """
        writers = list(writers)
        n = self.k + self.m
        assert len(writers) == n
        dead: set[int] = {i for i, w in enumerate(writers) if w is None}
        if n - len(dead) < write_quorum:
            raise errors.ErasureWriteQuorum(
                f"{n - len(dead)} writers < quorum {write_quorum}"
            )
        if pipelined is None:
            pipelined = pipeline_enabled()
        pool = _io_pool()
        total = 0
        # Per-drive write CHAINS instead of a per-batch barrier: drive
        # i's write for batch N+1 is submitted chained on its batch-N
        # future (the task waits its predecessor before touching the
        # file), so per-drive write order is preserved while one slow
        # drive no longer stalls every other drive's next batch.  Chains
        # are FIFO on the pool, so a task's predecessor has always
        # already started — no worker-starvation cycle is possible.
        tails: dict[int, cf.Future] = {}

        # Pipeline depth: device batches ride JAX async dispatch up to
        # PIPELINE_DEPTH deep; host encodes go one deep on the pool
        # (HOST_PIPELINE_DEPTH) when pipelining is on, else resolve
        # inline (depth 0 — the serial reference path).
        pending: list = []  # [(slot, batch, block_len, resolve, hash_fut)]
        device_path = self._device(
            self.block_size * DEVICE_BATCH_BLOCKS, self.shard_size
        ) is not None
        if device_path:
            depth = PIPELINE_DEPTH
        elif pipelined:
            depth = HOST_PIPELINE_DEPTH
        else:
            depth = 0

        bs = self.block_size
        batch_max = DEVICE_BATCH_BLOCKS
        # bs % k == 0 (always true for the 1 MiB default with k <= 16 a
        # power of two; checked so odd geometries fall back): a full
        # block's shard split is a pure reshape, so a whole batch read is
        # viewed as (B, K, S) with zero copies.
        aligned = bs % self.k == 0

        # Arena ring: `depth + 2` reusable read buffers — one being
        # filled, up to `depth` pending on the encode pipeline, one whose
        # shard writes are still in flight.  A slot is recycled only
        # after every batch viewing it has been written AND its etag fold
        # has completed, so no in-flight consumer ever aliases a buffer
        # being refilled (the differential suite's arena-reuse drill
        # pins this).  Refcounted because a read that ends in a tail
        # block yields two batches from one arena.
        hash_view = getattr(reader, "hash_view", None) if pipelined else None
        use_arena = pipelined and hasattr(reader, "readinto")
        slot_bufs: list[np.ndarray] = []
        slot_refs: list[int] = []
        free_slots: list[int] = []
        if use_arena:
            # size the ring to the stream: a 5 MiB part must not pay
            # three 32 MiB arena allocations
            slot_bytes = bs * batch_max
            nslots = depth + 2
            if total_size >= 0:
                slot_bytes = min(slot_bytes, max(total_size, 1))
                nslots = max(1, min(
                    nslots, -(-max(total_size, 1) // slot_bytes)))
                if aligned and slot_bytes >= bs:
                    # where the device carries the slot's full blocks at
                    # a compiled batch size, the slot is that carrier
                    slot_bytes = max(slot_bytes, bs * self._carrier_blocks(
                        slot_bytes // bs, self.shard_size))
            slot_bufs = [_arena_acquire(slot_bytes) for _ in range(nslots)]
            slot_refs = [0] * nslots
            free_slots = list(range(nslots))
        # batches whose writes are in flight and whose arena/hash may
        # still be referenced: [(slot, {i: write_fut}, hash_fut)] in
        # batch order — a slot is recycled only when every write of its
        # batch AND its etag fold have completed
        holds: list = []

        def release_slot(slot: int | None) -> None:
            if slot is None:
                return
            slot_refs[slot] -= 1
            if slot_refs[slot] == 0:
                free_slots.append(slot)

        def check_quorum() -> None:
            if n - len(dead) < write_quorum:
                raise errors.ErasureWriteQuorum(
                    f"{n - len(dead)} writers < quorum {write_quorum}"
                )

        def prune_dead() -> None:
            """Fold already-completed write failures into `dead` without
            blocking (quorum loss surfaces within one batch, as the old
            per-batch barrier guaranteed)."""
            for i, f in list(tails.items()):
                if f.done() and f.exception() is not None:
                    dead.add(i)
                    tails.pop(i)
            check_quorum()

        def drain_holds(block: bool) -> None:
            """Release arena slots of fully-written batches, oldest
            first; with block=True, wait until at least the oldest batch
            has fully landed (slot pressure)."""
            while holds:
                slot, futs, hfut = holds[0]
                if not block and (
                        any(not f.done() for f in futs.values())
                        or (hfut is not None and not hfut.done())):
                    return
                holds.pop(0)
                block = False  # only the oldest is worth waiting for
                for i, f in futs.items():
                    try:
                        f.result()
                    except Exception:
                        dead.add(i)
                        if tails.get(i) is f:
                            tails.pop(i)
                if hfut is not None:
                    hfut.result()  # etag fold of this arena view is done
                release_slot(slot)

        def wait_oldest() -> None:
            """This thread's wait for the pool's shard writes (and the
            etag fold) of the oldest batch: read_wait's mirror."""
            with stagestats.timed("write_wait"):
                drain_holds(block=True)

        def emit_one() -> None:
            slot, batch, block_len, resolve, hfut = pending.pop(0)
            parity = resolve()
            prune_dead()
            shard_len = -(-block_len // self.k)

            def write_drive(i: int, prev: cf.Future | None) -> None:
                if prev is not None:
                    # chain: this drive's previous batch must be on disk
                    # first (raises if it failed -> the whole chain for
                    # the drive fails fast and the drive goes dead)
                    prev.result()
                rows = batch[:, i, :] if i < self.k else parity[:, i - self.k, :]
                wf = getattr(writers[i], "write_frames", None)
                if wf is not None:
                    wf(rows[:, :shard_len])
                else:
                    for bi in range(rows.shape[0]):
                        writers[i].write(rows[bi, :shard_len])

            # io_submit: the caller's deadline budget must ride into
            # the writer threads so the per-drive gates stay armed
            futs: dict[int, cf.Future] = {}
            for i in range(n):
                if i in dead or writers[i] is None:
                    continue
                fut = io_submit(write_drive, i, tails.get(i))
                tails[i] = fut
                futs[i] = fut
            holds.append((slot, futs, hfut))
            drain_holds(block=False)

        def acquire_slot() -> int:
            while not free_slots:
                if pending:
                    emit_one()
                elif holds:
                    wait_oldest()
                    check_quorum()
                else:  # pragma: no cover - ring accounting invariant
                    raise RuntimeError("arena ring exhausted with no "
                                       "in-flight batches")
            return free_slots.pop()

        def flush_batch(slot: int | None, batch: np.ndarray,
                        block_len: int, hfut=None) -> None:
            # batch: (B, K, S) blocks of block_len payload bytes each (a
            # short tail block always flushes alone, so one length covers
            # the whole batch).  One future per drive (goroutine-per-
            # writer analog of parallelWriter, cmd/erasure-encode.go:36);
            # a drive writes its shard of every block in order, so
            # per-file layout is stable.  Batches go out as one batched-
            # hash writev frame group per drive (write_frames); a drive's
            # rows are a strided column of the batch, no per-shard copies.
            if slot is not None:
                slot_refs[slot] += 1
            pending.append((slot, batch, block_len,
                            self._encode_shards_async(
                                batch, pool if pipelined else None), hfut))
            self.max_inflight = max(self.max_inflight, len(pending))
            while len(pending) > depth:
                emit_one()
            if slot is None:
                # no arena ring to exert slot pressure (read()-only
                # stream or the serial reference path): bound the write
                # backlog here, or a slow-but-healthy drive lets queued
                # batches pin fresh ~32 MiB buffers without limit
                while len(holds) > depth + 1:
                    wait_oldest()
                    check_quorum()

        try:
            while True:
                want = bs * batch_max if total_size < 0 else min(
                    bs * batch_max, total_size - total
                )
                if want == 0:
                    break
                if use_arena:
                    slot = acquire_slot()
                    arena = slot_bufs[slot]
                    with stagestats.timed("read", 0):
                        got = self._readinto_full(
                            reader, memoryview(arena)[:want])
                    stagestats.add("read", 0.0, got)
                    if not got:
                        free_slots.append(slot)
                        break
                    data_arr: np.ndarray = arena
                    hfut = (hash_view(memoryview(arena)[:got])
                            if hash_view is not None else None)
                else:
                    slot = None
                    with stagestats.timed("read", 0):
                        data = self._read_full(reader, want)
                    if not data:
                        break
                    got = len(data)
                    stagestats.add("read", 0.0, got)
                    data_arr = np.frombuffer(data, dtype=np.uint8)
                    hfut = None
                total += got
                nfull = got // bs
                first = True
                # the blocks the dispatch hands over: nfull, or those of
                # the carrier the full blocks are the head of
                size = self._carrier_blocks(nfull, self.shard_size) \
                    if nfull else 0
                if nfull and aligned:
                    if size * bs > data_arr.size:
                        size = nfull  # no arena: the dispatch copies
                    flush_batch(
                        slot,
                        _head(data_arr[: size * bs].reshape(
                            size, self.k, self.shard_size), nfull),
                        bs, hfut)
                    first = False
                elif nfull:
                    # k does not divide the block size: per-block shard
                    # padding, built in ONE vectorized pass (byte-equal
                    # to per-block gf256.split + stack, which cost two
                    # copies and nfull python round trips)
                    per = -(-bs // self.k)
                    with stagestats.timed("pad", nfull * bs):
                        batch = np.zeros((size, self.k * per),
                                         dtype=np.uint8)
                        batch[:nfull, :bs] = data_arr[: nfull * bs].reshape(
                            nfull, bs)
                    flush_batch(
                        slot,
                        _head(batch.reshape(size, self.k, per), nfull),
                        bs, hfut)
                    first = False
                tail = got - nfull * bs
                if tail:
                    with stagestats.timed("assemble", tail):
                        shards = gf256.split(
                            data_arr[nfull * bs:got], self.k)
                    flush_batch(slot, shards[None, ...], tail,
                                hfut if first else None)
                if got < want:
                    break
            while pending:
                emit_one()
            while holds:
                wait_oldest()
            prune_dead()  # final quorum verdict, all futures resolved
            if len(free_slots) == len(slot_bufs):
                # every batch drained and every etag fold done: no view
                # of these arenas survives, so they can be pooled.  On
                # error paths arenas are NOT pooled — escaped views
                # (async device transfers, abandoned folds) keep them
                # alive via refcounts instead.
                for buf in slot_bufs:
                    _arena_release(buf)
        except BaseException:
            # unwind: wait out in-flight shard writes so callers can safely
            # close/clean up writers the pool threads were still feeding
            pending.clear()
            for fut in list(tails.values()):
                try:
                    fut.result()
                except Exception:
                    pass
            tails.clear()
            raise
        return total, dead

    # -- streaming decode (cmd/erasure-decode.go:206) -----------------------
    def _carrier_blocks(self, nblocks: int, shard_len: int) -> int:
        """_dispatch_blocks of the codec that a dispatch of `nblocks`
        blocks of this shard length will go to."""
        return _dispatch_blocks(
            self._device(nblocks * self.k * shard_len, shard_len), nblocks)

    def _staging(self, nblocks: int, shard_len: int) -> np.ndarray:
        """A pooled (nblocks, k, shard_len) batch for one codec
        dispatch: the head of its carrier where the dispatch will have
        one, so that what is read into it is copied nowhere.
        _arena_release takes it back."""
        size = self._carrier_blocks(nblocks, shard_len)
        return _head(_arena_acquire(size * self.k * shard_len).reshape(
            size, self.k, shard_len), nblocks)

    def _read_group(self, readers: Sequence, broken: set[int],
                    shard_off: int, read_len: int, nblocks: int,
                    shard_len: int,
                    prefer: Sequence[int] | None = None,
                    rebuild: bool = False
                    ) -> tuple[dict[int, np.ndarray], np.ndarray | None]:
        """Read one group of `nblocks` consecutive shard blocks from the
        first k healthy readers, work-stealing to spare drives on failure
        (parallelReader.Read trigger channels, cmd/erasure-decode.go:101).

        `prefer` reorders the candidates (hedging: the caller puts slow
        drives last so the first k reads route around them); default is
        shard-index order.

        Returns ({shard_index: (nblocks, shard_len) uint8}, arena);
        exactly k entries.  Where the first k candidates hold every data
        shard and the caller asks for no `rebuild`, arena is None and
        the rows are views of the readers' frame buffers.  Where shards
        will have to be made (a staged read), arena is a pooled
        (nblocks, k, shard_len) batch for the codec, entry j of the
        dict is its column arena[:, j, :], read straight into it, and
        the dict's order is the `available` tuple of the dispatch.  The
        caller gives the arena back (_arena_release).
        """
        n = self.k + self.m
        got: dict[int, np.ndarray] = {}
        cand = range(n) if prefer is None else prefer
        order = [i for i in cand if readers[i] is not None and i not in broken]
        idx_iter = iter(order)
        active = []
        try:
            for _ in range(self.k):
                active.append(next(idx_iter))
        except StopIteration:
            raise errors.ErasureReadQuorum("not enough shard streams")

        arena = None
        if rebuild or any(i >= self.k for i in active):
            # a tail block's arena (under block_size + k bytes) is a
            # one-off size class like a small PUT's slot: the pool's LRU
            # evicts those before the full groups' class, and the copy
            # that a tail would take instead asks the pool for the same
            arena = self._staging(nblocks, shard_len)
            # columns ascending, as every codec's matrices have been
            # keyed; a spare takes the failed read's column
            active.sort()
        column = {i: j for j, i in enumerate(active)}

        def read_one(r, out):
            rb = getattr(r, "read_blocks", None)
            if rb is not None:
                # one file read + one batched hash verify; the rows a
                # zero-copy strided view of the frame buffer, or read
                # straight into the column they were given
                return rb(shard_off, nblocks, shard_len, out)
            rows = np.frombuffer(r.read_at(shard_off, read_len),
                                 dtype=np.uint8).reshape(nblocks, shard_len)
            if out is None:
                return rows
            with stagestats.timed("assemble", rows.size):
                out[:] = rows
            return out

        futs: dict[int, cf.Future] = {}
        try:
            while len(got) < self.k:
                futs = {
                    i: io_submit(
                        read_one, readers[i],
                        None if arena is None else arena[:, column[i], :])
                    for i in active
                }
                active = []
                # this thread's wait for the pool to bring the shards, the
                # queueing in the pool included; the drives' own time is
                # booked there as shard_read and verify (erasure/bitrot.py)
                with stagestats.timed("read_wait",
                                      len(futs) * nblocks * shard_len):
                    for i, fut in futs.items():
                        try:
                            got[i] = fut.result()
                        except Exception:
                            broken.add(i)
                            try:
                                spare = next(idx_iter)
                            except StopIteration:
                                raise errors.ErasureReadQuorum(
                                    f"shard {i} failed and no spare drives "
                                    f"remain")
                            active.append(spare)
                            column[spare] = column.pop(i)
        except BaseException:
            if arena is not None:
                # no read may still be filling a column when the arena
                # goes back to the pool
                cf.wait(list(futs.values()))
                _arena_release(arena)
            raise
        if arena is not None:
            got = {i: got[i] for i in sorted(got, key=column.__getitem__)}
        return got, arena

    def _assemble_data(self, got: dict[int, np.ndarray],
                       arena: np.ndarray | None, nblocks: int,
                       shard_len: int, block_len: int) -> np.ndarray:
        """(nblocks, block_len) object bytes from what _read_group
        brought: k read shards of blocks that hold block_len bytes each
        and, for a staged read, the arena they lie in.  Missing data
        shards are reconstructed in one batched dispatch of that arena
        as it is.  A data shard is copied once, straight to its place
        in the block; where k does not divide the block, the zeros that
        fill up the last shards stay behind in that same copy.  A
        group of full blocks lands on a pooled block, which holds
        another request's bytes until `place` has covered
        [0, block_len) for all k shards, and which goes back when the
        writer and whoever it handed views to have dropped it
        (_block_acquire).  A tail block and an inline object are
        one-off sizes that leave as a copy: a fresh array.  The arena
        goes back to the pool on every exit."""
        missing = tuple(i for i in range(self.k) if i not in got)
        shard_bytes = nblocks * shard_len
        if block_len == self.block_size:
            data = _block_acquire(nblocks, block_len)
        else:
            data = np.empty((nblocks, block_len), dtype=np.uint8)

        def place(i: int, rows: np.ndarray) -> None:
            lo = min(i * shard_len, block_len)
            hi = min(lo + shard_len, block_len)
            data[:, lo:hi] = rows[:, :hi - lo]

        try:
            # `assemble` is the host's copies alone, on both sides of the
            # dispatch and not around it: the codec books its own leaves
            with stagestats.timed(
                    "assemble", (self.k - len(missing)) * shard_bytes) as span:
                for i in range(self.k):
                    if i in got:
                        place(i, got[i])
                if missing and arena is None:
                    # the group turned degraded after its reads began (a
                    # frame failed its hash, a drive timed out): what
                    # was read into frame buffers is copied to an arena
                    arena = self._staging(nblocks, shard_len)
                    got = {i: got[i] for i in sorted(got)}
                    for j, rows in enumerate(got.values()):
                        arena[:, j, :] = rows
                    span.nbytes += self.k * shard_bytes
            if missing:
                rebuilt = self._reconstruct_shards(arena, tuple(got), missing)
                with stagestats.timed("assemble", len(missing) * shard_bytes):
                    for j, w in enumerate(missing):
                        place(w, rebuilt[:, j, :])
        finally:
            if arena is not None:
                _arena_release(arena)
        if self.k * shard_len != block_len:
            # the shards' fill never became a copy of its own: the
            # blocks' bytes, and no host time
            stagestats.add("pad", 0.0, nblocks * block_len)
        return data

    def decode_stream(self, writer, readers: Sequence, offset: int,
                      length: int, total_length: int,
                      broken_out: set | None = None,
                      prefer: Sequence[int] | None = None) -> int:
        """Read shard streams (None = unavailable), reconstruct if needed,
        write plain object bytes [offset, offset+length) to writer.

        `readers[i]` is a BitrotReader for shard i or None.  Implements the
        first-K-of-N degraded read: starts with the first k available
        shards; on a shard read/verify failure it advances to the next
        available drive (work-stealing trigger of parallelReader.Read).
        Consecutive full blocks are read and reconstructed in groups of up
        to DEVICE_BATCH_BLOCKS: one contiguous read per drive per group and
        one batched (G, K, S) reconstruct dispatch, instead of per-block
        round trips.
        """
        if length == 0:
            return 0
        n = self.k + self.m
        readers = list(readers)
        assert len(readers) == n
        if offset < 0 or length < 0 or offset + length > total_length:
            raise errors.InvalidArgument("invalid read range")

        start_block = offset // self.block_size
        end_block = (offset + length - 1) // self.block_size
        written = 0
        # shard indices that failed mid-stream (bitrot/IO): shared with
        # the caller so the read path can queue a heal — a masked
        # corruption must not stay invisible (reference parallelReader
        # feeds the read-trigger heal, cmd/erasure-object.go:316)
        broken: set[int] = broken_out if broken_out is not None else set()
        full_blocks_total = total_length // self.block_size

        block_idx = start_block
        while block_idx <= end_block:
            block_off = block_idx * self.block_size
            cur_size = min(self.block_size, total_length - block_off)
            if cur_size <= 0:
                break
            if cur_size == self.block_size:
                # group of consecutive full blocks
                g = min(
                    end_block - block_idx + 1,
                    full_blocks_total - block_idx,
                    DEVICE_BATCH_BLOCKS,
                )
                shard_len = self.shard_size
                with stagestats.timed("decode", g * self.block_size):
                    got, arena = self._read_group(
                        readers, broken, block_idx * shard_len,
                        g * shard_len, g, shard_len, prefer,
                    )
                    flat = self._assemble_data(
                        got, arena, g, shard_len, self.block_size)
                span = g * self.block_size
                lo = max(offset, block_off) - block_off
                hi = min(offset + length, block_off + span) - block_off
                if hi > lo:
                    # contiguous uint8 slice: hand the buffer to the writer
                    # without a tobytes() copy
                    with stagestats.timed("respond", hi - lo):
                        writer.write(flat.reshape(-1)[lo:hi].data)
                    written += hi - lo
                block_idx += g
            else:
                # tail block (shorter shard length)
                shard_len = -(-cur_size // self.k)
                with stagestats.timed("decode", cur_size):
                    got, arena = self._read_group(
                        readers, broken, block_idx * self.shard_size,
                        shard_len, 1, shard_len, prefer,
                    )
                    block = self._assemble_data(
                        got, arena, 1, shard_len, cur_size).reshape(-1)
                lo = max(offset, block_off) - block_off
                hi = min(offset + length, block_off + cur_size) - block_off
                if hi > lo:
                    with stagestats.timed("respond", hi - lo):
                        writer.write(block[lo:hi].tobytes())
                    written += hi - lo
                block_idx += 1
        return written

    # -- heal (cmd/erasure-decode.go:287) -----------------------------------
    def heal(self, writers: Sequence, readers: Sequence, total_length: int) -> None:
        """Rebuild the shards of drives whose writer is non-None from any k
        healthy readers, streaming in groups of full blocks with one batched
        reconstruct dispatch per group."""
        n = self.k + self.m
        writers = list(writers)
        readers = list(readers)
        wanted = tuple(i for i in range(n) if writers[i] is not None)
        if not wanted:
            return
        if sum(1 for r in readers if r is not None) < self.k:
            raise errors.ErasureReadQuorum("not enough shards to heal")
        broken: set[int] = set()
        nblocks = -(-total_length // self.block_size) if total_length else 0
        full_blocks = total_length // self.block_size

        block_idx = 0
        while block_idx < nblocks:
            if block_idx < full_blocks:
                g = min(full_blocks - block_idx, DEVICE_BATCH_BLOCKS)
                shard_len = self.shard_size
            else:
                g = 1
                cur_size = total_length - block_idx * self.block_size
                shard_len = -(-cur_size // self.k)
            try:
                # a heal always reconstructs: the staged read of a
                # degraded GET, whatever shards the first k hold
                got, arena = self._read_group(
                    readers, broken, block_idx * self.shard_size,
                    g * shard_len if shard_len == self.shard_size else shard_len,
                    g, shard_len, rebuild=True,
                )
            except errors.ErasureReadQuorum:
                raise errors.ErasureReadQuorum("healing read quorum lost")
            try:
                rebuilt = self._reconstruct_shards(arena, tuple(got), wanted)
            finally:
                _arena_release(arena)
            for j, w in enumerate(wanted):
                wf = getattr(writers[w], "write_frames", None)
                if wf is not None:
                    wf(rebuilt[:, j, :])
                else:
                    for bi in range(g):
                        writers[w].write(rebuilt[bi, j])
            block_idx += g
