"""Multi-process data plane: shared-memory arena rings + I/O worker
processes (ISSUE 8 / ROADMAP item 1 — escape the GIL).

Every PUT pipeline stage overlaps, yet on the CPU box the wall stayed
GIL-bound: read, md5 etag, erasure encode, bitrot hashing and shard
writes all share ONE interpreter, so "overlapped" stages still convoy
on bytecode glue.  This module shards the PUT data
plane across OS processes:

* ``WorkerPlane`` (front side) owns N spawned **I/O worker processes**
  plus one **hash-lane process**.  Per PUT, shard indices are
  partitioned contiguously across the workers; each worker opens,
  writes and commits its drives' files itself (the fds never leave the
  worker), so a 12-drive fan-out costs each interpreter only its slice.
  Parity shards sit at the tail of the partition, so at most the last
  worker(s) pay the GF(2^8) encode — the polynomial-RS batching of the
  in-process plane (arxiv 1312.5155) carries over unchanged: one
  batched host-codec dispatch per ring slot.

* Payload bytes travel through a ``multiprocessing.shared_memory``
  **arena ring** (`ShmRing`): the HTTP front writes each batch ONCE
  into a ring slot and publishes a seqlock-style ready counter; every
  consumer (I/O workers, hash lane) maps the same segment and reads the
  slot zero-copy (numpy views over the shared buffer), then publishes
  its per-consumer done counter.  A slot is reused only when every
  *live* consumer has consumed its previous generation — the
  cross-process lift of the PR 5 arena-ring slot lifecycle.  Plain
  aligned int64 loads/stores are the synchronization primitive
  (single writer per cell; x86-TSO ordering — the store of the payload
  precedes the store of the ready counter program-order, which the
  architecture preserves).

* The **hash lane** folds the md5 etag over ring slots in its own
  process, taking the one inherently serial PUT stage (md5 cannot be
  parallelized within one stream) off both the front's and the
  workers' interpreters.

* **Node-batched commits**: the front sends ONE commit message per
  worker per PUT; the worker renames/commits xl.meta on every drive it
  owns in-process — one coalesced round trip per "node" instead of one
  syscall dispatch per drive (the shared foundation for the ROADMAP
  item 5 metadata journal; the remote-drive analogue is
  `storage.rename_data_batch` in distributed/storage_rpc.py).

* **Codec work batches per node process** (ISSUE 11): with
  ``MINIO_TPU_BATCHER=1`` a worker's ``Erasure`` encodes submit to the
  worker PROCESS's request batcher (erasure/batcher.py) instead of
  dispatching privately — concurrent PUT jobs interleaving on one
  worker's job threads coalesce into one fused codec program per tick,
  exactly like request threads on the front.  The gate env is
  inherited by the spawned child; `_worker_main` quiesces the child's
  batcher on exit so shutdown drains or fails-retryable every queued
  item (the modelled quiesce protocol).

Everything is gated by ``MINIO_TPU_WORKERS`` (default 0 = the
in-process plane, which stays alive as the differential reference —
tests/test_mp_dataplane_diff.py pins byte identity).  Workers are
supervised: a reply-reader thread per worker detects death, fails the
worker's in-flight jobs with a retryable ``WorkerDied`` StorageError
(the PUT degrades to the surviving shards when quorum holds, and the
missing shards converge through the existing MRF/heal plane), and the
supervisor respawns the process.  Deadline budgets ride each job
message as ``deadline_ms`` — the cross-process twin of the
``x-minio-tpu-deadline-ms`` RPC header — and are reinstalled via
``deadline.scope`` in the worker.

Teardown: the plane closes via ``shutdown_plane()`` (ServiceManager /
S3Server close, conftest, atexit); segment names carry the
``mtpu-ring-`` prefix and the creating process's pid
(``segment_prefix()``) so the conftest leak check can prove that this
process left nothing in /dev/shm without reading another process's
live rings as litter, and the front's resource_tracker unlinks
segments even after a SIGKILL.  Workers UNREGISTER attached segments
from their own resource tracker — an attaching process must not unlink
a segment the creator still owns (the documented CPython multi-process
shm wart).
"""

from __future__ import annotations

import atexit
import io
import os
import threading
import time
import uuid

import numpy as np

from minio_tpu.storage import errors
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing

SHM_PREFIX = "mtpu-ring-"


def segment_prefix() -> str:
    """What every ring segment this process creates is named by."""
    return f"{SHM_PREFIX}{os.getpid()}-"


# generation sentinel lengths published in a slot's len cell
_EOF = -1    # producer finished cleanly
_ABORT = -2  # producer unwound (reader error / client disconnect)

_HDR_CELLS = 8  # magic, nslots, slot_bytes, nconsumers, pad...
_MAGIC = 0x6D74_7075  # "mtpu"

# data region starts page-aligned so numpy views over slots stay aligned
_DATA_ALIGN = 4096


def _tso_machine() -> bool:
    """The ring's plain-store seqlock relies on total-store-order: the
    payload stores precede the ready-counter store in program order
    and x86 preserves that visibility order.  Weaker architectures
    (aarch64) can make the counter visible BEFORE the payload — a
    consumer would then encode/hash stale bytes with a self-consistent
    bitrot hash, silently.  Until real barriers land, the plane only
    engages on TSO machines (override with care via
    MINIO_TPU_MP_FORCE=1, e.g. under an emulator known to be TSO)."""
    import platform

    if os.environ.get("MINIO_TPU_MP_FORCE", "") == "1":
        return True
    return platform.machine().lower() in ("x86_64", "amd64", "i686",
                                          "i386")


_warned_non_tso = False


def worker_count() -> int:
    """MINIO_TPU_WORKERS: number of I/O worker processes (0 = the
    in-process data plane; the env is re-read per call so tests can
    flip it without rebuilding layers).  Always 0 on non-TSO machines
    (see _tso_machine)."""
    try:
        n = max(0, int(os.environ.get("MINIO_TPU_WORKERS", "0") or 0))
    except ValueError:
        return 0
    if n > 0 and not _tso_machine():
        # lint: allow(shared-state): one-shot warning latch, per-process by design
        global _warned_non_tso
        if not _warned_non_tso:
            _warned_non_tso = True
            import sys

            print("minio-tpu: MINIO_TPU_WORKERS ignored — the "
                  "shared-memory ring requires a TSO (x86) machine; "
                  "set MINIO_TPU_MP_FORCE=1 only if you know the "
                  "memory model is safe", file=sys.stderr)
        return 0
    return n


def _ring_slots() -> int:
    try:
        return max(2, int(os.environ.get("MINIO_TPU_MP_RING_SLOTS", "3")))
    except ValueError:
        return 3


def _slot_bytes_cap() -> int:
    try:
        return max(1 << 20, int(os.environ.get(
            "MINIO_TPU_MP_SLOT_BYTES", str(32 << 20))))
    except ValueError:
        return 32 << 20


class WorkerDied(errors.StorageError):
    """A data-plane worker process died (or timed out) mid-operation.
    Retryable: the supervisor respawns the worker; the failed shards
    feed the MRF/heal plane like any other partial write."""


# --------------------------------------------------------------------------
# shared-memory ring
# --------------------------------------------------------------------------
def _ring_layout(nslots: int, slot_bytes: int, nconsumers: int):
    """(total_bytes, data_offset).  Control block: header cells, ready
    cells, len cells, then done cells per consumer — all int64."""
    ctrl_cells = _HDR_CELLS + nslots * (2 + nconsumers)
    data_off = -(-ctrl_cells * 8 // _DATA_ALIGN) * _DATA_ALIGN
    return data_off + nslots * slot_bytes, data_off


class _RingViews:
    """Typed views over one mapped segment (producer or consumer)."""

    def __init__(self, buf, nslots: int, slot_bytes: int, nconsumers: int):
        total, data_off = _ring_layout(nslots, slot_bytes, nconsumers)
        self.nslots = nslots
        self.slot_bytes = slot_bytes
        self.nconsumers = nconsumers
        ctrl = np.frombuffer(buf, dtype=np.int64,
                             count=(data_off // 8), offset=0)
        self.hdr = ctrl[:_HDR_CELLS]
        off = _HDR_CELLS
        self.ready = ctrl[off:off + nslots]
        off += nslots
        self.lens = ctrl[off:off + nslots]
        off += nslots
        self.done = ctrl[off:off + nslots * nconsumers].reshape(
            nconsumers, nslots)
        self.data = np.frombuffer(buf, dtype=np.uint8,
                                  count=nslots * slot_bytes,
                                  offset=data_off)

    def slot_view(self, slot: int) -> np.ndarray:
        lo = slot * self.slot_bytes
        return self.data[lo:lo + self.slot_bytes]

    def release(self) -> None:
        """Drop the numpy exports so the segment can close cleanly
        (SharedMemory.close refuses while exported pointers exist)."""
        self.hdr = self.ready = self.lens = self.done = self.data = None


class RingProducer:
    """Front side of one ring: create the segment, fill slots, publish
    generations.  Single producer; ``dead_fn(c)`` tells the wait loop a
    consumer will never advance (worker died) so its done counters are
    ignored instead of wedging the PUT."""

    def __init__(self, shm, nslots: int, slot_bytes: int, nconsumers: int):
        self.shm = shm
        self.v = _RingViews(shm.buf, nslots, slot_bytes, nconsumers)
        self.v.hdr[0] = _MAGIC
        self.v.hdr[1] = nslots
        self.v.hdr[2] = slot_bytes
        self.v.hdr[3] = nconsumers
        self.v.ready[:] = 0
        self.v.lens[:] = 0
        self.v.done[:, :] = 0
        self._gen = 0  # last published generation (1-based)

    def _wait_slot_free(self, gen: int, dead_fn, timeout: float) -> None:
        slot = (gen - 1) % self.v.nslots
        floor = gen - self.v.nslots
        if floor <= 0:
            return
        t_end = time.monotonic() + timeout
        spins = 0
        while True:
            ok = True
            for c in range(self.v.nconsumers):
                if self.v.done[c, slot] < floor and not dead_fn(c):
                    ok = False
                    break
            if ok:
                return
            spins += 1
            if spins < 50:
                time.sleep(0)
            else:
                time.sleep(0.0005)
            if time.monotonic() > t_end:
                raise WorkerDied(
                    f"ring slot {slot} not recycled within {timeout:.1f}s "
                    "(consumer stalled)")

    trace: list | None = None  # set to [] to record (gen, wait_s, t_pub)

    def next_slot(self, dead_fn, timeout: float = 60.0) -> np.ndarray:
        """Writable view of the next slot (blocks until every live
        consumer recycled its previous generation)."""
        t0 = time.perf_counter()
        self._wait_slot_free(self._gen + 1, dead_fn, timeout)
        if self.trace is not None:
            self._wait = time.perf_counter() - t0
        return self.v.slot_view((self._gen) % self.v.nslots)

    def publish(self, nbytes: int) -> None:
        self._gen += 1
        slot = (self._gen - 1) % self.v.nslots
        self.v.lens[slot] = nbytes
        self.v.ready[slot] = self._gen  # payload store precedes this store
        if self.trace is not None:
            self.trace.append((self._gen, round(self._wait, 4),
                               round(time.perf_counter(), 4)))

    def finish(self, dead_fn, abort: bool = False,
               timeout: float = 60.0) -> None:
        self._wait_slot_free(self._gen + 1, dead_fn, timeout)
        self._gen += 1
        slot = (self._gen - 1) % self.v.nslots
        self.v.lens[slot] = _ABORT if abort else _EOF
        self.v.ready[slot] = self._gen


class RingConsumer:
    """Worker side: attach by name, iterate generations zero-copy."""

    def __init__(self, shm, nslots: int, slot_bytes: int, nconsumers: int,
                 idx: int):
        self.shm = shm
        self.v = _RingViews(shm.buf, nslots, slot_bytes, nconsumers)
        self.idx = idx
        self._gen = 0

    def next(self, timeout: float = 60.0):
        """(gen, view, nbytes) for the next generation; nbytes is _EOF /
        _ABORT on the terminal generation (view is empty then).  The
        caller MUST call done(gen) once it no longer references the
        view."""
        gen = self._gen + 1
        slot = (gen - 1) % self.v.nslots
        t_end = time.monotonic() + timeout
        spins = 0
        while self.v.ready[slot] < gen:
            spins += 1
            if spins < 50:
                time.sleep(0)
            else:
                time.sleep(0.0005)
            if time.monotonic() > t_end:
                raise WorkerDied(
                    f"ring generation {gen} not published within "
                    f"{timeout:.1f}s (producer stalled)")
        self._gen = gen
        n = int(self.v.lens[slot])
        if n in (_EOF, _ABORT):
            return gen, self.v.slot_view(slot)[:0], n
        return gen, self.v.slot_view(slot)[:n], n

    def done(self, gen: int) -> None:
        self.v.done[self.idx, (gen - 1) % self.v.nslots] = gen


# --------------------------------------------------------------------------
# front-side segment registry + pool
# --------------------------------------------------------------------------
_seg_lock = threading.Lock()
_live_segments: dict[str, object] = {}  # name -> SharedMemory (created here)


def _register_segment(shm) -> None:
    with _seg_lock:
        _live_segments[shm.name] = shm


def _unlink_segment(shm) -> None:
    with _seg_lock:
        _live_segments.pop(shm.name, None)
    try:
        shm.close()
    except Exception:
        pass
    try:
        shm.unlink()
    except Exception:
        pass


def _unlink_all_segments() -> None:
    """atexit / signal-path sweep: no /dev/shm litter survives a clean
    or signalled exit (a SIGKILL is covered by the resource tracker)."""
    with _seg_lock:
        segs = list(_live_segments.values())
        _live_segments.clear()
    for shm in segs:
        try:
            shm.close()
        except Exception:
            pass
        try:
            shm.unlink()
        except Exception:
            pass


atexit.register(_unlink_all_segments)


class _RingPool:
    """Reusable ring segments keyed by exact (nslots, slot_bytes,
    nconsumers): shm_open + mmap + first-touch page faults per PUT are
    measurable, and names are never reused (uuid), so worker-side
    attachment caches can key on the name safely."""

    def __init__(self, max_bytes: int = 256 << 20):
        self._mu = threading.Lock()
        self._free: dict[tuple, list] = {}
        self._bytes = 0
        self.max_bytes = max_bytes

    def acquire(self, nslots: int, slot_bytes: int, nconsumers: int):
        from multiprocessing import shared_memory

        key = (nslots, slot_bytes, nconsumers)
        with self._mu:
            bucket = self._free.get(key)
            if bucket:
                shm = bucket.pop()
                self._bytes -= _ring_layout(*key)[0]
                return shm
        total, _ = _ring_layout(nslots, slot_bytes, nconsumers)
        shm = shared_memory.SharedMemory(
            name=f"{segment_prefix()}{uuid.uuid4().hex[:16]}", create=True,
            size=total)
        _register_segment(shm)
        return shm

    def release(self, shm, nslots: int, slot_bytes: int,
                nconsumers: int) -> None:
        key = (nslots, slot_bytes, nconsumers)
        total = _ring_layout(*key)[0]
        evict = []
        with self._mu:
            if total > self.max_bytes:
                evict.append(shm)
            else:
                while self._bytes + total > self.max_bytes and self._free:
                    k2, b2 = next(iter(self._free.items()))
                    evict.append(b2.pop())
                    self._bytes -= _ring_layout(*k2)[0]
                    if not b2:
                        del self._free[k2]
                self._free.setdefault(key, []).append(shm)
                self._bytes += total
        for s in evict:
            _unlink_segment(s)

    def drain(self) -> None:
        with self._mu:
            segs = [s for b in self._free.values() for s in b]
            self._free.clear()
            self._bytes = 0
        for s in segs:
            _unlink_segment(s)


# --------------------------------------------------------------------------
# worker process entry (runs in the spawned child)
# --------------------------------------------------------------------------
class _RingCache:
    """Worker-side segment-attachment cache: jobs run on their own
    threads, so attach/evict must be locked and an evicted segment
    must never be one a live job still reads — entries carry a
    refcount and eviction walks FIFO over idle entries only.

    CPython 3.10's attach path registers the name with the resource
    tracker too (bpo-39959); spawn children share the PARENT's tracker
    process, so that register is a set no-op and must NOT be
    "balanced" with an unregister here — doing so would strip the
    creator's entry and lose the SIGKILL-cleanup guarantee."""

    def __init__(self, cap: int = 8):
        self.cap = cap
        self.mu = threading.Lock()
        self._items: dict[str, list] = {}  # name -> [shm, refs]

    def attach(self, name: str):
        """shm for `name`, refcounted; pair with release(name)."""
        with self.mu:
            ent = self._items.get(name)
            if ent is not None:
                ent[1] += 1
                return ent[0]
        from multiprocessing import shared_memory

        shm = shared_memory.SharedMemory(name=name)
        stale = []
        with self.mu:
            ent = self._items.get(name)
            if ent is not None:  # lost a racing attach: keep theirs
                ent[1] += 1
                stale.append(shm)
                shm = ent[0]
            else:
                while len(self._items) >= self.cap:
                    idle = next((n for n, e in self._items.items()
                                 if e[1] == 0), None)
                    if idle is None:
                        break  # everything in use: grow past cap
                    stale.append(self._items.pop(idle)[0])
                self._items[name] = [shm, 1]
        for s in stale:
            try:
                s.close()
            except Exception:
                pass
        return shm

    def release(self, name: str) -> None:
        with self.mu:
            ent = self._items.get(name)
            if ent is not None and ent[1] > 0:
                ent[1] -= 1

    def close_all(self) -> None:
        with self.mu:
            items, self._items = list(self._items.values()), {}
        for shm, _refs in items:
            try:
                shm.close()
            except Exception:
                pass


def _job_budget(msg):
    # lint: allow(trace-propagation): pure converter — run_job pairs it with tracing.continuation over the same message
    return deadline_mod.from_wire_ms(msg.get("deadline_ms"))


def _exc_wire(e: BaseException) -> list:
    return [type(e).__name__, str(e)]


def _exc_unwire(pair) -> Exception:
    cls = getattr(errors, pair[0], None)
    if isinstance(cls, type) and issubclass(cls, Exception):
        return cls(pair[1])
    return errors.StorageError(f"{pair[0]}: {pair[1]}")


def _worker_drive(cache: dict, root: str):
    """Per-worker LocalStorage cache: the worker owns these drives'
    staging buffers and fds for the jobs it runs."""
    d = cache.get(root)
    if d is None:
        from minio_tpu.storage.local import LocalStorage

        d = LocalStorage(root)
        cache[root] = d
    return d


class _RingStream:
    """readinto-able view over one ring consumer: Erasure.encode_stream
    drives this exactly like a socket/file source, so the worker reuses
    the WHOLE tuned in-process pipeline (arena pool, host-encode
    overlap, per-drive write chains, bounded backlog).  A slot is
    recycled the moment its bytes are copied out — the producer is
    decoupled from this worker's write tail."""

    def __init__(self, con: RingConsumer, timeout: float):
        self.con = con
        self.timeout = timeout
        self._view: np.ndarray | None = None
        self._gen = 0
        self._pos = 0
        self.eof = False
        self.aborted = False
        self.ring_wait = 0.0

    def readinto(self, b) -> int:
        mv = memoryview(b)
        if mv.format != "B":
            mv = mv.cast("B")
        dst = np.frombuffer(mv, dtype=np.uint8)
        got = 0
        while got < len(dst):
            if self._view is None:
                if self.eof:
                    break
                t0 = time.perf_counter()
                gen, view, n = self.con.next(self.timeout)
                self.ring_wait += time.perf_counter() - t0
                if n in (_EOF, _ABORT):
                    self.aborted = n == _ABORT
                    self.eof = True
                    self.con.done(gen)
                    break
                self._gen, self._view, self._pos = gen, view, 0
            take = min(len(dst) - got, len(self._view) - self._pos)
            dst[got:got + take] = self._view[self._pos:self._pos + take]
            got += take
            self._pos += take
            if self._pos == len(self._view):
                self.con.done(self._gen)
                self._view = None
        return got


class _SubsetErasure:
    """Worker-side codec picker: a worker that owns NO parity shards
    never pays the GF(2^8) encode — its shard rows are pure slices of
    the payload (a cached zero array stands in for the parity rows
    nobody writes: the parity writers are None, so the rows are never
    read, only shape-checked)."""

    @staticmethod
    def build(k: int, m: int, bs: int, parity_owned: bool):
        from minio_tpu.erasure.coding import Erasure

        if parity_owned or m == 0:
            return Erasure(k, m, bs, backend="host")

        class _DataOnly(Erasure):
            _zeros: np.ndarray | None = None

            def _encode_shards_async(self, batch, pool=None):
                b, _k, s = batch.shape
                z = self._zeros
                if z is None or z.shape[0] < b or z.shape[2] < s:
                    z = self._zeros = np.zeros(
                        (max(b, 1), self.m, max(s, self.shard_size)),
                        dtype=np.uint8)
                out = z[:b, :, :s]
                return lambda: out

        return _DataOnly(k, m, bs, backend="host")


def _run_put_data(msg, rings: "_RingCache", drives: dict) -> dict:
    """One PUT's shard-write slice on this worker: feed the ring
    through the in-process Erasure.encode_stream against this worker's
    drives (None writers for shards other workers own), so shard bytes
    are produced by the exact same code path the workers=0 reference
    uses — byte identity by construction."""
    from minio_tpu.erasure import bitrot, stagestats
    from minio_tpu.storage import local as local_mod

    # lint: allow(shared-state): per-process by design — the worker child installs the FRONT's fsync mode for its own drives; the front's copy is the source of truth
    local_mod.FSYNC_ENABLED = bool(msg.get("fsync", True))
    k, m, bs = msg["k"], msg["m"], msg["bs"]
    n = k + m
    algo = msg["algo"]
    own = [(int(s), r) for s, r in msg["drives"]]
    own_set = {s for s, _ in own}
    parity_owned = any(s >= k for s in own_set)
    e = _SubsetErasure.build(k, m, bs, parity_owned)
    timeout = msg.get("ring_timeout", 60.0)

    shm = rings.attach(msg["ring"])
    con = RingConsumer(shm, msg["nslots"], msg["slot_bytes"],
                       msg["nconsumers"], msg["consumer"])
    stream = _RingStream(con, timeout)

    writers: list = [None] * n
    failed: dict[int, list] = {}
    for s, root in own:
        try:
            d = _worker_drive(drives, root)
            fh = d.open_file_writer(msg["tmp_vol"], msg["tmp_path"],
                                    size_hint=msg.get("shard_hint", -1))
            writers[s] = bitrot.BitrotWriter(fh, e.shard_size, algo=algo)
        except Exception as ex:
            failed[s] = _exc_wire(ex)

    total = 0
    before = stagestats.snapshot()
    try:
        # write_quorum=0: quorum is the FRONT's verdict over all
        # workers' answers; this worker reports its own failures only
        with tracing.span("mp.encode", shards=len(own),
                          parity_owned=parity_owned):
            total, dead = e.encode_stream(stream, writers,
                                          msg.get("size", -1), 0)
        for s in dead & own_set:
            failed.setdefault(s, ["FaultyDisk",
                                  f"shard {s} write failed in worker"])
    except Exception as ex:
        for s in own_set:
            failed.setdefault(s, _exc_wire(ex))
    finally:
        for s, w in enumerate(writers):
            if w is None:
                continue
            try:
                w.close()
            except Exception as ex:
                if s not in failed:
                    failed[s] = _exc_wire(ex)
        con.v.release()
        rings.release(msg["ring"])
        if stream.aborted:
            # unwind: reclaim this job's staged shard files (the abort
            # path names exactly what to sweep — a multipart part's tmp
            # FILE, not its upload dir)
            ap = msg.get("abort_path") or msg["tmp_path"]
            for s, root in own:
                try:
                    _worker_drive(drives, root).delete(
                        msg["tmp_vol"], ap,
                        recursive=bool(msg.get("abort_recursive", True)))
                except Exception:
                    pass
    delta = stagestats.delta(before, stagestats.snapshot())
    # 'read' here is the shm->arena copy the front already attributes;
    # shipping it again would double-count the stage
    stage = {st: secs for st, secs in delta.items()
             if secs and st not in ("read", "etag")}
    return {"total": total, "failed": failed, "aborted": stream.aborted,
            "stage": stage,
            "wall": {"ring_wait": round(stream.ring_wait, 4)}}


def _run_hash(msg, rings: "_RingCache") -> dict:
    """Hash-lane job: fold md5 over ring slots (the etag)."""
    import hashlib

    shm = rings.attach(msg["ring"])
    con = RingConsumer(shm, msg["nslots"], msg["slot_bytes"],
                       msg["nconsumers"], msg["consumer"])
    h = hashlib.md5()
    total = 0
    t_etag = 0.0
    timeout = msg.get("ring_timeout", 60.0)
    try:
        while True:
            gen, view, n = con.next(timeout)
            if n in (_EOF, _ABORT):
                con.done(gen)
                return {"md5": h.hexdigest() if n == _EOF else "",
                        "total": total, "stage": {"etag": t_etag}}
            t0 = time.perf_counter()
            h.update(view)
            t_etag += time.perf_counter() - t0
            total += n
            con.done(gen)
    finally:
        con.v.release()
        rings.release(msg["ring"])


def _run_commit(msg, drives: dict) -> dict:
    """Node-batched commit: rename_data / rename_file for EVERY drive
    this worker handled, in one message round trip."""
    import dataclasses

    results: dict[int, list | None] = {}
    fi_base = msg.get("fi")
    for s, root in msg["drives"]:
        s = int(s)
        try:
            d = _worker_drive(drives, root)
            if msg["kind"] == "rename_data":
                fi = dataclasses.replace(
                    fi_base,
                    erasure=dataclasses.replace(fi_base.erasure, index=s + 1))
                d.rename_data(msg["src_vol"], msg["src_path"], fi,
                              msg["bucket"], msg["obj"])
            else:
                d.rename_file(msg["src_vol"], msg["src_path"],
                              msg["dst_vol"], msg["dst_path"])
            results[s] = None
        except Exception as ex:
            results[s] = _exc_wire(ex)
    return {"results": results}


def _run_cleanup(msg, drives: dict) -> dict:
    """Sweep a job's staged tmp dirs on the worker's drives."""
    for _s, root in msg["drives"]:
        try:
            _worker_drive(drives, root).delete(
                msg["vol"], msg["path"], recursive=True)
        except Exception:
            pass
    return {}


def _worker_main(conn, kind: str, env: dict | None = None) -> None:
    """Child entry (spawn context): serve job messages until exit/EOF.
    Jobs run on their own threads so concurrent PUTs interleave; the
    reply pipe is serialized by a send lock.  `env` lands before any
    lazy storage import so per-worker shares of process-scoped budgets
    (the O_DIRECT device-write gate) take effect."""
    import signal as signal_mod

    if env:
        os.environ.update(env)
    # a terminated worker must not run atexit/network teardown of
    # inherited state; exit fast and let the supervisor respawn
    try:
        signal_mod.signal(signal_mod.SIGTERM,
                          lambda *_: os._exit(0))
    except (ValueError, OSError):
        pass

    rings = _RingCache()
    drives: dict = {}
    send_mu = threading.Lock()

    def reply(job, payload: dict) -> None:
        payload["job"] = job
        with send_mu:
            conn.send(payload)

    def run_job(msg) -> None:
        job = msg.get("job")
        op = msg.get("op", "?")
        # trace continuation (utils/tracing.py): the job message's wire
        # context opens a NON-CAPTURING fragment — the worker's spans
        # (encode, batcher ticks) and stage folds ship home in the
        # reply and are grafted under the front's job span, so one PUT
        # stays ONE tree across the process boundary
        cont = tracing.continuation(msg.get("trace"), f"mp.{op}",
                                    capture=False, pid=os.getpid())
        try:
            with deadline_mod.scope(_job_budget(msg)):
                with cont:
                    if op == "put_data":
                        out = _run_put_data(msg, rings, drives)
                    elif op == "hash":
                        out = _run_hash(msg, rings)
                    elif op == "commit":
                        out = _run_commit(msg, drives)
                    elif op == "cleanup":
                        out = _run_cleanup(msg, drives)
                    elif op == "ping":
                        out = {"pong": True, "pid": os.getpid()}
                    else:
                        out = {"err": ["InvalidArgument",
                                       f"unknown op {op}"]}
        except BaseException as ex:
            out = {"err": _exc_wire(ex)}
        exported = cont.export()
        if exported is not None and exported.get("spans"):
            # per-stage seconds already travel in the reply's "stage"
            # field (folded by the front through stagestats, which
            # attributes to the live trace) — shipping them here too
            # would double-count the worker's stage time
            exported.pop("stages", None)
            out["trace"] = exported
        reply(job, out)

    try:
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            if msg.get("op") == "exit":
                break
            deadline_mod.service_thread(run_job, msg,
                                        name=f"mp-{kind}-job")
    finally:
        try:
            # quiesce the worker-process request batcher: drain or
            # fail-retryable every queued codec item before the hard
            # exit (erasure/batcher.py shutdown protocol)
            from minio_tpu.erasure import batcher as batcher_mod

            batcher_mod.shutdown()
        except Exception:
            pass
        rings.close_all()
        os._exit(0)


# --------------------------------------------------------------------------
# front-side plane
# --------------------------------------------------------------------------
class _Pending:
    __slots__ = ("event", "reply")

    def __init__(self):
        self.event = threading.Event()
        self.reply: dict | None = None


class _WorkerHandle:
    """One supervised child process + its reply-reader thread."""

    def __init__(self, plane: "WorkerPlane", kind: str, idx: int):
        self.plane = plane
        self.kind = kind
        self.idx = idx
        self.proc = None
        self.conn = None
        self._send_mu = threading.Lock()
        self._mu = threading.Lock()
        self._pending: dict[str, _Pending] = {}
        self.alive = False
        self.restarts = -1  # first spawn is not a restart

    def spawn(self) -> None:
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        parent, child = ctx.Pipe()
        proc = ctx.Process(target=_worker_main,
                           args=(child, self.kind,
                                 self.plane.child_env(self.kind)),
                           name=f"mtpu-{self.kind}-{self.idx}", daemon=True)
        proc.start()
        child.close()
        self.proc = proc
        self.conn = parent
        self.alive = True
        self.restarts += 1
        deadline_mod.service_thread(self._read_loop, proc, parent,
                                    name=f"mp-reader-{self.kind}-{self.idx}")

    def _read_loop(self, proc, conn) -> None:
        """Reply router; detects worker death and fails its in-flight
        jobs with the retryable WorkerDied."""
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                break
            job = msg.get("job")
            with self._mu:
                p = self._pending.pop(job, None)
            if p is not None:
                p.reply = msg
                p.event.set()
        # death path (or plane close): fail whatever is still in flight
        with self._mu:
            stuck = list(self._pending.values())
            self._pending.clear()
            was_current = self.conn is conn
            if was_current:
                self.alive = False
        for p in stuck:
            p.reply = {"err": ["WorkerDied",
                               f"{self.kind} worker {self.idx} died"]}
            p.event.set()
        try:
            conn.close()  # a respawn minted a fresh pipe; drop this fd
        except Exception:
            pass
        if was_current:
            self.plane._note_worker_death(self)

    def send(self, msg: dict) -> _Pending:
        job = uuid.uuid4().hex
        msg["job"] = job
        p = _Pending()
        with self._mu:
            if not self.alive:
                raise WorkerDied(
                    f"{self.kind} worker {self.idx} is down")
            self._pending[job] = p
        try:
            with self._send_mu:
                self.conn.send(msg)
        except (OSError, ValueError, BrokenPipeError):
            with self._mu:
                self._pending.pop(job, None)
            raise WorkerDied(
                f"{self.kind} worker {self.idx} pipe broken")
        return p

    def wait(self, p: _Pending, timeout: float) -> dict:
        if not p.event.wait(timeout):
            raise WorkerDied(
                f"{self.kind} worker {self.idx} reply timed out "
                f"after {timeout:.1f}s")
        out = p.reply or {}
        if "err" in out:
            err = out["err"]
            if err[0] == "WorkerDied":
                raise WorkerDied(err[1])
            raise _exc_unwire(err)
        return out

    def close(self) -> None:
        with self._mu:
            self.alive = False
        try:
            with self._send_mu:
                self.conn.send({"op": "exit", "job": ""})
        except Exception:
            pass
        proc = self.proc
        if proc is not None:
            proc.join(timeout=2.0)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=2.0)
            if proc.is_alive():
                proc.kill()
                proc.join(timeout=2.0)
        try:
            self.conn.close()
        except Exception:
            pass


class WorkerPlane:
    """N I/O workers + 1 hash lane + ring pool + supervision."""

    def __init__(self, nworkers: int):
        self.nworkers = nworkers
        self._mu = threading.Lock()
        self._closed = False
        self.rings = _RingPool()
        self.io: list[_WorkerHandle] = []
        self.hash: _WorkerHandle | None = None
        # stats surfaced as minio_mp_* in server/metrics.py
        self.jobs = 0
        self.commits = 0
        self.failures = 0
        self.worker_deaths = 0
        for i in range(nworkers):
            h = _WorkerHandle(self, "io", i)
            h.spawn()
            self.io.append(h)
        h = _WorkerHandle(self, "hash", 0)
        h.spawn()
        self.hash = h

    def child_env(self, kind: str) -> dict:
        """Env overrides for a child: the O_DIRECT device-write gate is
        a per-PROCESS semaphore, so N writing workers would multiply
        the aggregate fan-in past the measured degradation knee —
        each worker gets an equal share of the budget instead."""
        if kind != "io":
            return {}
        try:
            from minio_tpu.storage import local as local_mod

            budget = local_mod.DEVICE_WRITE_CONCURRENCY
        except Exception:
            budget = max(2, os.cpu_count() or 2)
        per = max(1, budget // max(1, self.nworkers))
        return {"MINIO_TPU_DEVICE_WRITE_CONCURRENCY": str(per)}

    # -- supervision --------------------------------------------------------
    def _note_worker_death(self, handle: _WorkerHandle) -> None:
        with self._mu:
            if self._closed:
                return
            self.worker_deaths += 1
        # respawn off the reader thread (it is exiting)
        deadline_mod.service_thread(self._respawn, handle,
                                    name="mp-respawn")

    def _respawn(self, handle: _WorkerHandle) -> None:
        with self._mu:
            if self._closed:
                return
            try:
                handle.spawn()
            except Exception:
                pass

    def ping(self, timeout: float = 30.0) -> bool:
        """Round-trip every worker (spawn warmup / tests)."""
        try:
            ps = [(h, h.send({"op": "ping"})) for h in self.io + [self.hash]]
            for h, p in ps:
                h.wait(p, timeout)
            return True
        except (WorkerDied, errors.StorageError):
            return False

    def stats(self) -> dict:
        return {
            "workers": self.nworkers,
            "jobs": self.jobs,
            "commits": self.commits,
            "failures": self.failures,
            "workerDeaths": self.worker_deaths,
            "restarts": sum(max(0, h.restarts) for h in self.io
                            + ([self.hash] if self.hash else [])),
        }

    # -- data path ----------------------------------------------------------
    @staticmethod
    def _partition(n_shards: int, nworkers: int) -> list[list[int]]:
        """Contiguous shard ranges, parity tail concentrated in the last
        worker(s) so as few workers as possible pay the encode."""
        step = -(-n_shards // nworkers)
        return [list(range(lo, min(lo + step, n_shards)))
                for lo in range(0, n_shards, step)]

    def put_data(self, reader, roots: list[str], k: int, m: int, bs: int,
                 algo: str, size: int, tmp_vol: str, tmp_path: str,
                 shard_hint: int, fsync: bool,
                 skip: set[int] | None = None,
                 abort_path: str | None = None,
                 abort_recursive: bool = True):
        """Stream `reader` once into a shared ring; workers write the
        shard files, the hash lane folds the etag.  Returns
        (total, failed_shards, etag, groups) where groups maps each
        worker handle to its [(shard, root)] slice for the commit."""
        from minio_tpu.erasure import stagestats

        n = k + m
        assert len(roots) == n
        budget = deadline_mod.current()
        # reply/slot waits: budget-clamped when bounded, else long — a
        # worker DEATH always releases waiters via the reader thread,
        # so these timeouts only cut off a pathological live-but-hung
        # worker (the in-process analogue blocks on the hung drive too)
        timeout = 600.0
        if budget is not None and budget.t_end is not None:
            timeout = max(1.0, budget.remaining())
        # worker-side ring waits are looser still: the producer may be
        # a SLOW CLIENT trickling its body, and payload streaming is
        # budget-free by design (PR 3) — the worker must not abandon a
        # healthy slow upload.  A dead front reaps daemon children.
        ring_timeout = max(timeout, 3600.0)
        # one slot = one encode batch (the in-process DEVICE_BATCH_BLOCKS
        # shape), shrunk to the payload so small objects don't pay
        # 32 MiB segments
        slot_bytes = min(_slot_bytes_cap(), bs * 32)
        if size >= 0:
            slot_bytes = min(slot_bytes, max(
                -(-max(size, 1) // bs) * bs, bs))
        nslots = _ring_slots()
        if 0 <= size <= slot_bytes:
            nslots = 2
        parts = self._partition(n, self.nworkers)
        handles = self.io[:len(parts)]
        nconsumers = len(handles) + 1  # + hash lane
        shm = self.rings.acquire(nslots, slot_bytes, nconsumers)
        prod = RingProducer(shm, nslots, slot_bytes, nconsumers)
        if os.environ.get("MINIO_TPU_MP_TRACE"):
            prod.trace = []
        with self._mu:
            self.jobs += 1

        dead: set[int] = set()
        # spawn generation per consumer at dispatch: a worker that died
        # and was RESPAWNED is alive again but lost this job — its done
        # counters will never advance, so liveness must be sticky to
        # the generation the job was sent to
        gens: dict[int, int] = {}

        def dead_fn(c: int) -> bool:
            if c in dead:
                return True
            h = handles[c] if c < len(handles) else self.hash
            if not h.alive or h.restarts != gens.get(c, h.restarts):
                dead.add(c)
                return True
            return False

        base = {
            "k": k, "m": m, "bs": bs, "algo": algo, "fsync": fsync,
            "ring": shm.name, "nslots": nslots, "slot_bytes": slot_bytes,
            "nconsumers": nconsumers, "ring_timeout": ring_timeout,
            "tmp_vol": tmp_vol, "tmp_path": tmp_path,
            "shard_hint": shard_hint, "size": size,
            "abort_path": abort_path, "abort_recursive": abort_recursive,
        }
        wire_ms = deadline_mod.to_wire_ms()
        if wire_ms is not None:
            base["deadline_ms"] = wire_ms
        # trace context rides the job message like the deadline does;
        # the worker's exported spans come back in the reply and are
        # grafted under the per-worker job span begun at send
        trace_wire = tracing.to_wire()
        if trace_wire is not None:
            base["trace"] = trace_wire
        groups: dict[_WorkerHandle, list] = {}
        pendings: list[tuple[_WorkerHandle, _Pending, list, object]] = []
        hash_pending = None
        hash_span = None
        failed: dict[int, Exception] = {}
        pool_ring = False  # only a fully-drained ring may be pooled
        try:
            for c, (h, shard_range) in enumerate(zip(handles, parts)):
                drives = [(s, roots[s]) for s in shard_range
                          if skip is None or s not in skip]
                groups[h] = drives
                msg = dict(base)
                msg.update({"op": "put_data", "consumer": c,
                            "drives": drives})
                try:
                    gens[c] = h.restarts
                    sp = tracing.begin("mp.job", op="put_data", worker=c,
                                       shards=len(drives))
                    pendings.append((h, h.send(msg), drives, sp))
                except WorkerDied as ex:
                    dead.add(c)
                    for s, _r in drives:
                        failed[s] = ex
            hmsg = dict(base)
            hmsg.update({"op": "hash", "consumer": len(handles),
                         "drives": []})
            try:
                gens[len(handles)] = self.hash.restarts
                hash_span = tracing.begin("mp.job", op="hash")
                hash_pending = self.hash.send(hmsg)
            except WorkerDied:
                # no etag lane, no PUT: unblock the io workers (they
                # would otherwise wait out the whole ring window on a
                # generation that never comes) and surface retryable
                try:
                    prod.finish(dead_fn, abort=True, timeout=5.0)
                except WorkerDied:
                    pass
                raise

            total = 0
            t_read = 0.0
            ok = True
            t_start = time.perf_counter()
            try:
                while True:
                    want = slot_bytes if size < 0 else min(
                        slot_bytes, size - total)
                    if want == 0:
                        break
                    view = prod.next_slot(dead_fn, timeout)
                    t0 = time.perf_counter()
                    got = _fill_from(reader, view[:want])
                    t_read += time.perf_counter() - t0
                    if not got:
                        break
                    prod.publish(got)
                    total += got
                    if got < want:
                        break
            except BaseException:
                ok = False
                raise
            finally:
                try:
                    prod.finish(dead_fn, abort=not ok, timeout=timeout)
                except WorkerDied:
                    pass
            stagestats.add("read", t_read, total)
            t_fed = time.perf_counter()

            for h, p, drives, sp in pendings:
                try:
                    out = h.wait(p, timeout)
                except (WorkerDied, errors.StorageError) as ex:
                    with self._mu:
                        self.failures += 1
                    for s, _r in drives:
                        failed.setdefault(s, ex)
                    if sp is not None:
                        sp.finish(error=type(ex).__name__)
                    continue
                for s, pair in out.get("failed", {}).items():
                    failed.setdefault(int(s), _exc_unwire(pair))
                st = out.get("stage", {})
                for stage, secs in st.items():
                    stagestats.add(stage, secs, 0)
                if sp is not None:
                    tracing.graft(out.get("trace"), sp)
                    sp.finish()
                self.last_worker_wall = out.get("wall")
            hout = self.hash.wait(hash_pending, timeout)
            if hash_span is not None:
                tracing.graft(hout.get("trace"), hash_span)
                hash_span.finish()
            st = hout.get("stage", {})
            for stage, secs in st.items():
                stagestats.add(stage, secs, 0)
            etag = hout.get("md5", "")
            if not etag or hout.get("total") != total:
                raise WorkerDied(
                    "hash lane did not observe the full payload "
                    f"({hout.get('total')} != {total})")
            now = time.perf_counter()
            # per-phase wall of the last job (debugging/bench aid):
            # feed = producing into the ring (incl. slot waits),
            # drain = waiting for workers + hash lane after EOF
            self.last_job_wall = {
                "feed": round(t_fed - t_start, 4),
                "fill": round(t_read, 4),
                "drain": round(now - t_fed, 4),
            }
            if prod.trace is not None:
                self.last_job_wall["slots"] = prod.trace
            pool_ring = True
            return total, failed, etag, groups
        finally:
            prod.v.release()
            if pool_ring:
                self.rings.release(shm, nslots, slot_bytes, nconsumers)
            else:
                # an exception path may leave a LIVE consumer mid-ring;
                # pooling the segment would let the next job's zeroed
                # counters race that consumer's late done-stores —
                # unlink instead (its memory dies with the last map)
                _unlink_segment(shm)

    def commit(self, groups: dict, kind: str, src_vol: str, src_path: str,
               *, fi=None, bucket: str = "", obj: str = "",
               dst_vol: str = "", dst_path: str = "",
               skip: set[int] | None = None) -> dict[int, Exception | None]:
        """Node-batched commit: one message per worker commits every
        drive it wrote.  Returns {shard: None | Exception}."""
        budget = deadline_mod.current()
        timeout = 600.0
        if budget is not None and budget.t_end is not None:
            timeout = max(1.0, budget.remaining())
        out: dict[int, Exception | None] = {}
        sends = []
        with self._mu:
            self.commits += 1
        for h, drives in groups.items():
            drives = [(s, r) for s, r in drives
                      if skip is None or s not in skip]
            if not drives:
                continue
            msg = {"op": "commit", "kind": kind, "drives": drives,
                   "src_vol": src_vol, "src_path": src_path,
                   "fi": fi, "bucket": bucket, "obj": obj,
                   "dst_vol": dst_vol, "dst_path": dst_path}
            wire_ms = deadline_mod.to_wire_ms()
            if wire_ms is not None:
                msg["deadline_ms"] = wire_ms
            trace_wire = tracing.to_wire()
            if trace_wire is not None:
                msg["trace"] = trace_wire
            try:
                sp = tracing.begin("mp.job", op="commit",
                                   shards=len(drives))
                sends.append((h, h.send(msg), drives, sp))
            except WorkerDied as ex:
                for s, _r in drives:
                    out[s] = ex
        for h, p, drives, sp in sends:
            try:
                rep = h.wait(p, timeout)
            except (WorkerDied, errors.StorageError) as ex:
                with self._mu:
                    self.failures += 1
                for s, _r in drives:
                    out[s] = ex
                if sp is not None:
                    sp.finish(error=type(ex).__name__)
                continue
            if sp is not None:
                tracing.graft(rep.get("trace"), sp)
                sp.finish()
            results = rep.get("results", {})
            for s, _r in drives:
                pair = results.get(s, results.get(str(s)))
                out[s] = None if pair is None else _exc_unwire(pair)
        return out

    def cleanup(self, groups: dict, vol: str, path: str) -> None:
        """Best-effort sweep of a failed job's staging dirs."""
        for h, drives in groups.items():
            if not drives:
                continue
            try:
                h.send({"op": "cleanup", "drives": drives,
                        "vol": vol, "path": path})
            except WorkerDied:
                pass

    def close(self) -> None:
        with self._mu:
            if self._closed:
                return
            self._closed = True
        for h in self.io + ([self.hash] if self.hash else []):
            try:
                h.close()
            except Exception:
                pass
        self.rings.drain()


def _fill_from(reader, mv: np.ndarray) -> int:
    """Fill a shm slot view from `reader` with ONE copy: BytesIO
    sources copy straight out of their buffer, readinto sources fill
    the view directly, read()-only sources pay read + one numpy copy
    (the same traffic the in-process arena path pays)."""
    out = memoryview(mv)
    gb = getattr(reader, "getbuffer", None)
    if gb is not None:
        try:
            src = gb()
            pos = reader.tell()
            got = min(len(out), len(src) - pos)
            if got > 0:
                mv[:got] = np.frombuffer(src, dtype=np.uint8)[pos:pos + got]
                reader.seek(pos + got)
            del src
            return max(got, 0)
        except (BufferError, OSError, ValueError):
            pass
    got = 0
    use_ri = getattr(reader, "readinto", None)
    while got < len(out):
        n = 0
        if use_ri is not None:
            try:
                n = use_ri(out[got:]) or 0
            except (NotImplementedError, io.UnsupportedOperation):
                use_ri = None
                continue
        else:
            data = reader.read(len(out) - got)
            n = len(data) if data else 0
            if n:
                mv[got:got + n] = np.frombuffer(data, dtype=np.uint8)
        if not n:
            break
        got += n
    return got


# --------------------------------------------------------------------------
# process-wide singleton
# --------------------------------------------------------------------------
_plane_lock = threading.Lock()
_plane: WorkerPlane | None = None


def get_plane(create: bool = True) -> WorkerPlane | None:
    """The process-wide plane for the current MINIO_TPU_WORKERS value;
    None when disabled.  Lazily (re)built: a plane shut down by one
    server's close restarts on the next eligible PUT."""
    # lint: allow(shared-state): the plane singleton is the FRONT's handle to the workers; children never import this path
    global _plane
    n = worker_count()
    if n <= 0:
        return None
    with _plane_lock:
        if _plane is not None and not _plane._closed \
                and _plane.nworkers == n:
            return _plane
        if _plane is not None and (_plane._closed
                                   or _plane.nworkers != n):
            old, _plane = _plane, None
            try:
                old.close()
            except Exception:
                pass
        if not create:
            return None
        _plane = WorkerPlane(n)
        return _plane


def shutdown_plane() -> None:
    """Terminate workers, join them, and unlink every ring segment.
    Called by ServiceManager.close / S3Server.close / conftest /
    atexit; safe to call repeatedly."""
    # lint: allow(shared-state): front-side singleton teardown — see get_plane
    global _plane
    with _plane_lock:
        plane, _plane = _plane, None
    if plane is not None:
        plane.close()
    _unlink_all_segments()
    try:
        # the front's request batcher quiesces with the plane: the two
        # share teardown call sites (ServiceManager/S3Server close,
        # conftest, atexit) and both must leave zero threads behind
        from minio_tpu.erasure import batcher as batcher_mod

        batcher_mod.shutdown()
    except Exception:
        pass


atexit.register(shutdown_plane)


def plane_roots(disks) -> list[str] | None:
    """Drive roots when EVERY drive is an online node-local
    LocalStorage (unwrapping the instrumentation) — the mp plane's
    eligibility test.  Remote drives, chaos interposers and offline
    drives take the in-process plane (its degraded-write and
    fault-injection semantics stay authoritative there)."""
    from minio_tpu.storage.local import LocalStorage

    roots: list[str] = []
    for d in disks:
        if d is None:
            return None
        inner = d
        unwrap = getattr(inner, "unwrap", None)
        if unwrap is not None:
            inner = unwrap()
        if type(inner) is not LocalStorage:
            return None
        roots.append(inner.root)
    return roots
