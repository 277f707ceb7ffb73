"""One erasure set: quorum object operations over K+M drives.

Equivalent of the reference's erasureObjects (cmd/erasure.go:43,
cmd/erasure-object.go): PutObject encodes into per-drive bitrot shard
files staged in tmp and committed with renameData; GetObject elects a
metadata quorum, streams a degraded-tolerant decode, and triggers heal on
missing/corrupt shards; deletes are version-aware with delete markers;
small objects inline their shards into xl.meta (cmd/xl-storage.go:59).
"""

from __future__ import annotations

import concurrent.futures as cf
import hashlib
import io
import os
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import BinaryIO, Callable, Iterator, Sequence

import numpy as np

from minio_tpu.ops import host as hostops
from minio_tpu.storage import errors
from minio_tpu.storage.api import StorageAPI
from minio_tpu.storage.local import SYSTEM_VOL, TMP_DIR
from minio_tpu.storage.xlmeta import (
    ChecksumInfo, ErasureInfo, FileInfo, ObjectPartInfo,
    find_file_info_in_quorum, new_data_dir, new_version_id,
)
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing
from minio_tpu.utils.hashing import hash_order
from . import bitrot, stagestats
from . import repair as repair_mod
from .coding import BLOCK_SIZE_V2, Erasure, io_submit, pipeline_enabled

SMALL_FILE_THRESHOLD = 128 << 10  # inline shards into xl.meta below this

# --- deadline-aware read plane -------------------------------------------
# once a metadata quorum is in hand, stragglers get this much longer
# before the fan-out abandons them (reference returns at quorum and
# cancels the rest; tail-at-scale hedging literature in PAPERS.md)
STRAGGLER_GRACE = float(os.environ.get(
    "MINIO_TPU_STRAGGLER_GRACE_MS", "50")) / 1000.0
# a drive whose EWMA read latency crosses this threshold is hedged:
# deprioritized behind spare (parity) shards so quorum reads route
# around it while it stays available as a fallback
HEDGE_EWMA_S = float(os.environ.get(
    "MINIO_TPU_HEDGE_EWMA_MS", "100")) / 1000.0

# observability (read by server/metrics.py); GIL-safe counter bumps
hedge_stats = {"hedged": 0, "abandoned": 0}

# --- runtime hedge widening (ISSUE 18) -----------------------------------
# the overload controller (server/controller.py) scales BOTH hedge knobs
# down together when GET tail-latency burn dominates: a smaller straggler
# grace abandons post-quorum stragglers sooner and a lower EWMA threshold
# routes around more slow drives.  The env/default values are captured at
# import so every actuation is relative to the operator's configuration,
# and the scale is clamped so no controller bug can disable hedging
# entirely or widen it without bound.
_HEDGE_DEFAULTS = (STRAGGLER_GRACE, HEDGE_EWMA_S)
_HEDGE_SCALE_MIN = 0.25
_hedge_scale = 1.0


def hedge_scale() -> float:
    """Current widening factor: 1.0 = configured knobs untouched."""
    return _hedge_scale


def set_hedge_scale(scale: float) -> float:
    """Rescale the hedge knobs from their configured defaults; returns
    the clamped scale actually applied.  Module globals are read at
    call time by the fan-out paths, so this takes effect on the next
    read with no restart."""
    global STRAGGLER_GRACE, HEDGE_EWMA_S, _hedge_scale
    s = min(max(float(scale), _HEDGE_SCALE_MIN), 1.0)
    _hedge_scale = s
    STRAGGLER_GRACE = _HEDGE_DEFAULTS[0] * s
    HEDGE_EWMA_S = _HEDGE_DEFAULTS[1] * s
    return s

# tiering stub metadata (never surfaced to clients)
TRANSITION_STATUS_KEY = "x-minio-internal-transition-status"
TRANSITION_TIER_KEY = "x-minio-internal-transition-tier"
TRANSITION_KEY_KEY = "x-minio-internal-transition-key"
TRANSITION_COMPLETE = "complete"
MULTIPART_VOL = SYSTEM_VOL
MULTIPART_DIR = "multipart"


@dataclass
class ObjectInfo:
    bucket: str
    name: str
    version_id: str = ""
    is_latest: bool = True
    delete_marker: bool = False
    size: int = 0
    mod_time: float = 0.0
    etag: str = ""
    content_type: str = ""
    metadata: dict = field(default_factory=dict)
    parts: list = field(default_factory=list)

    @classmethod
    def from_file_info(cls, fi: FileInfo, bucket: str, name: str,
                       versioned: bool = False) -> "ObjectInfo":
        meta = dict(fi.metadata)
        return cls(
            bucket=bucket, name=name,
            version_id=fi.version_id if versioned or fi.version_id else "",
            is_latest=fi.is_latest, delete_marker=fi.deleted, size=fi.size,
            mod_time=fi.mod_time, etag=meta.pop("etag", ""),
            content_type=meta.pop("content-type", ""),
            metadata=meta, parts=list(fi.parts),
        )


# `read(offset=0, length=-1)` of an opened object (open_object)
ObjectReadFn = Callable[..., Iterator[bytes]]


def open_by_info(layer, bucket: str, obj: str, version_id: str = ""
                 ) -> tuple[ObjectInfo, ObjectReadFn]:
    """`open_object` for a layer that cannot read info and bytes in one
    go (a gateway: HEAD, then GET against the remote; the disk cache):
    the info now, the layer's `get_object` once `read`'s iterator is
    first advanced."""
    oi = layer.get_object_info(bucket, obj, version_id)

    def read(offset: int = 0, length: int = -1) -> Iterator[bytes]:
        _, stream = layer.get_object(bucket, obj, offset, length, version_id)
        yield from stream

    return oi, read


@dataclass
class PutObjectOptions:
    user_metadata: dict = field(default_factory=dict)
    content_type: str = ""
    versioned: bool = False
    version_id: str = ""
    storage_class: str = ""  # "STANDARD" | "REDUCED_REDUNDANCY"
    # nonzero pins the version's mod time (pool decommission moves
    # versions between pools without reordering history)
    mod_time: float = 0.0
    # non-empty pins the stored ETag instead of recomputing it from the
    # stream: decommission/rebalance must carry multipart composite
    # (md5-N) and SSE/compressed ETags verbatim or client caches and
    # If-Match preconditions break (reference moves versions with
    # metadata verbatim, cmd/erasure-server-pool-decom.go)
    etag: str = ""
    # called after the stream is fully consumed, just before metadata
    # commit — lets transforming wrappers (compression) contribute the
    # original size/ETag they only know at EOF
    finalize_metadata: Callable[[], dict] | None = None


@dataclass
class HealResult:
    object_size: int = 0
    drives_before: list = field(default_factory=list)
    drives_after: list = field(default_factory=list)
    healed_drives: int = 0
    failed: bool = False
    # repair-planner accounting (erasure/repair.py): which scheme
    # rebuilt the shards ("subshard" if any part took the ranged path),
    # survivor frame bytes read, and residual-scan bytes from targets
    scheme: str = "full"
    bytes_read: int = 0
    bytes_scanned: int = 0


class NamespaceLock:
    """Per-object RW locks (reference nsLockMap, cmd/namespace-lock.go:86)."""

    def __init__(self):
        self._locks: dict[str, "_RWLock"] = {}
        self._mu = threading.Lock()

    def _get(self, key: str) -> "_RWLock":
        with self._mu:
            lk = self._locks.get(key)
            if lk is None:
                lk = _RWLock()
                self._locks[key] = lk
            lk.refs += 1
            return lk

    def _put(self, key: str, lk: "_RWLock") -> None:
        with self._mu:
            lk.refs -= 1
            if lk.refs == 0 and not lk.readers and not lk.writer:
                self._locks.pop(key, None)

    def write(self, key: str):
        return _LockCtx(self, key, write=True)

    def read(self, key: str):
        return _LockCtx(self, key, write=False)


class _RWLock:
    def __init__(self):
        self.cond = threading.Condition()
        self.readers = 0
        self.writer = False
        self.refs = 0

    def acquire_read(self):
        with self.cond:
            while self.writer:
                self.cond.wait()
            self.readers += 1

    def release_read(self):
        with self.cond:
            self.readers -= 1
            self.cond.notify_all()

    def acquire_write(self):
        with self.cond:
            while self.writer or self.readers:
                self.cond.wait()
            self.writer = True

    def release_write(self):
        with self.cond:
            self.writer = False
            self.cond.notify_all()


class _LockCtx:
    def __init__(self, ns: NamespaceLock, key: str, write: bool):
        self.ns, self.key, self.write = ns, key, write

    def __enter__(self):
        self.lk = self.ns._get(self.key)
        # the lock's wait alone: what is done under it has its own stages
        with stagestats.timed("ns_lock"):
            if self.write:
                self.lk.acquire_write()
            else:
                self.lk.acquire_read()
        return self

    def __exit__(self, *exc):
        if self.write:
            self.lk.release_write()
        else:
            self.lk.release_read()
        self.ns._put(self.key, self.lk)
        return False


def _etag_of(data_hash: "hashlib._Hash") -> str:
    return data_hash.hexdigest()


class _HashingReader(io.RawIOBase):
    """Single-pass MD5 + size counter (reference internal/hash.Reader).

    Pipelined mode (the default, following coding.pipeline_enabled):
    etag folding happens on a dedicated in-order hasher stage on the
    shared I/O pool instead of inline on the reading thread — MD5 was
    ~40% of PUT wall time serial with block split + encode dispatch.
    `read()` hands each returned bytes object to the chain (immutable,
    so no lifetime coordination needed); `readinto()` + `hash_view()`
    is the arena protocol used by Erasure.encode_stream: readinto fills
    the caller's reusable buffer WITHOUT hashing, and hash_view()
    queues the fold, returning a future the arena ring waits on before
    recycling the slot.  `etag` joins the chain, so the result is
    byte-exact with the serial path (defer=False — the differential
    suite compares the two).
    """

    def __init__(self, r: BinaryIO, expected_size: int = -1,
                 defer: bool | None = None):
        self.r = r
        self.md5 = hashlib.md5()
        self.count = 0
        self.expected = expected_size
        if defer is None:
            defer = pipeline_enabled()
        self._defer = defer
        self._tail: "cf.Future | None" = None  # newest queued fold

    def _fold(self, view) -> "cf.Future":
        """Queue one in-order MD5 fold on the I/O pool.  Each task waits
        on its predecessor, and submissions are FIFO, so folds apply in
        stream order; depth is bounded by the caller's arena ring (slot
        recycling waits on the returned future)."""
        prev = self._tail

        def run() -> None:
            if prev is not None:
                prev.result()
            with stagestats.timed("etag", len(view)):
                self.md5.update(view)

        fut = io_submit(run)
        self._tail = fut
        return fut

    def read(self, n: int = -1) -> bytes:
        data = self.r.read(n)
        if data:
            self.count += len(data)
            if self._defer:
                self._fold(data)
            else:
                with stagestats.timed("etag", len(data)):
                    self.md5.update(data)
        return data

    _use_readinto = True  # cleared on the first wrapper lacking readinto

    def readinto(self, b) -> int:
        """Arena fill: bytes land in the caller's buffer UNHASHED — the
        caller pairs this with hash_view() so the fold overlaps the
        encode of the next batch (plain read() keeps hashing itself).
        Memory-resident sources (BytesIO: POST-object bodies, decom /
        replication / heal staging) copy via numpy straight out of the
        source buffer — large numpy copies release the GIL, so the fill
        overlaps the hasher and writer threads instead of convoying
        them.  Wrapped sources that only implement read() (chunked-
        signature decoders, tee hashers, SSE/compression transforms
        inherit RawIOBase's non-readinto) fall back to read + one numpy
        copy into the arena — the same byte traffic the old per-batch
        allocation paid."""
        mv = memoryview(b)
        gb = getattr(self.r, "getbuffer", None)
        if gb is not None:
            try:
                src = gb()
                pos = self.r.tell()
                got = min(len(mv), len(src) - pos)
                if got > 0:
                    np.frombuffer(mv, dtype=np.uint8)[:got] = \
                        np.frombuffer(src, dtype=np.uint8)[pos:pos + got]
                    self.r.seek(pos + got)
                else:
                    got = 0
                del src  # release the BytesIO export
                self.count += got
                return got
            except (BufferError, OSError, ValueError):
                pass
        ri = getattr(self.r, "readinto", None) if self._use_readinto else None
        if ri is not None:
            try:
                got = ri(mv) or 0
                self.count += got
                return got
            except (NotImplementedError, io.UnsupportedOperation):
                self._use_readinto = False
        data = self.r.read(len(mv))
        got = len(data) if data else 0
        if got:
            np.frombuffer(mv, dtype=np.uint8)[:got] = \
                np.frombuffer(data, dtype=np.uint8)
        self.count += got
        return got

    def hash_view(self, view):
        """Fold `view` into the etag; returns the completion future the
        arena ring must wait on before recycling the buffer (None when
        folding ran inline — nothing to wait for)."""
        if not self._defer:
            with stagestats.timed("etag", len(view)):
                self.md5.update(view)
            return None
        return self._fold(view)

    @property
    def etag(self) -> str:
        tail = self._tail
        if tail is not None:
            tail.result()  # the chain is ordered: the newest fold is last
        return self.md5.hexdigest()



def _bitrot_algo_of(fi: FileInfo) -> str:
    """Bitrot algorithm recorded for the version (reads must use the
    writer's algorithm, whatever the current default is)."""
    e = fi.erasure
    if e is not None and e.checksums:
        a = e.checksums[0].algorithm
        if a in bitrot.ALGORITHMS:
            return a
    return bitrot.DEFAULT_ALGO

class NsUpdateHooks(list):
    """Composable namespace-change callbacks: every registered
    fn(bucket, obj) fires on a mutation; one hook failing never blocks
    the others (they feed caches/trackers, not the data path)."""

    def __call__(self, bucket: str, obj: str) -> None:
        for fn in list(self):
            try:
                fn(bucket, obj)
            except Exception:
                pass


def iter_sets(object_layer):
    """Every ErasureObjects set under a pools/sets/set object."""
    if hasattr(object_layer, "pools"):
        for p in object_layer.pools:
            yield from iter_sets(p)
    elif hasattr(object_layer, "sets"):
        yield from object_layer.sets
    else:
        yield object_layer


def invalidation_plane(object_layer) -> tuple[bool, bool]:
    """(has_sets, all_local): whether `object_layer` has an erasure
    plane underneath where ns_updated choke-point hooks can be
    registered (a pure gateway has none), and whether every drive is
    node-local.  A remote drive means a PEER node's writes fire
    ns_updated on that node only — a cache keyed on this node's hook
    alone would go stale (hot tier auto-disables on that answer; the
    cross-node broadcast is the ROADMAP follow-up)."""
    sets = [es for es in iter_sets(object_layer)
            if hasattr(es, "disks")]
    all_local = all(d is None or d.is_local()
                    for es in sets for d in es.disks)
    return bool(sets), all_local


def add_ns_update_hook(object_layer, fn) -> None:
    """Register fn(bucket, obj) on every set without clobbering hooks
    other subsystems installed (scanner bloom tracker, metacache
    invalidation, peer broadcasts all share the one callback slot)."""
    for es in iter_sets(object_layer):
        cur = getattr(es, "ns_updated", None)
        if isinstance(cur, NsUpdateHooks):
            if fn not in cur:
                cur.append(fn)
        elif cur is None:
            es.ns_updated = NsUpdateHooks([fn])
        else:
            es.ns_updated = NsUpdateHooks([cur, fn])


class ErasureObjects:
    """One erasure set over `disks` (K+M drives)."""

    def __init__(self, disks: Sequence[StorageAPI],
                 default_parity: int | None = None,
                 set_index: int = 0, pool_index: int = 0,
                 ns_lock: NamespaceLock | None = None,
                 heal_queue: Callable[..., None] | None = None):
        self.disks = list(disks)
        n = len(self.disks)
        if default_parity is None:
            default_parity = default_parity_count(n)
        self.default_parity = default_parity
        self.set_index = set_index
        self.pool_index = pool_index
        self.ns = ns_lock or NamespaceLock()
        # async heal trigger (MRF analogue): (bucket, obj, version_id,
        # deep=False) — deep=True demands a bitrot-verifying heal
        self.heal_queue = heal_queue
        self.tier_delete_hook = None  # wired by the tiering subsystem
        # change-tracking hook (bucket, obj) -> None; fed to the scanner's
        # bloom filter so clean buckets skip re-walks (reference NSUpdated
        # feeding dataUpdateTracker, cmd/data-update-tracker.go:59)
        self.ns_updated = None
        # how many of the drives the set last found online (a PUT's pass
        # over them): a change asks for the device programs of the
        # geometry the next PUT writes
        self._online = n

    # ------------------------------------------------------------------ util
    @property
    def set_drive_count(self) -> int:
        return len(self.disks)

    def _online_disks(self) -> list[StorageAPI | None]:
        disks = [d if d is not None and d.is_online() else None
                 for d in self.disks]
        self._saw_online(sum(d is not None for d in disks))
        return disks

    def _saw_online(self, online: int) -> None:
        """A write's pass over the drives found `online` of them
        present.  Where that is another count than the last pass found,
        the geometry the set's PUTs are written at is another too
        (`_write_geometry`), and its device programs are asked for now,
        off any request's thread, for either storage class: by the time
        a PUT dispatches they may be there, and until they are the host
        codec does the work (coding._DeviceCodec.ready).  Reads do not
        ask: a node that only serves GETs with drives away would compile
        a geometry it never writes, and a cold compile beside twenty
        busy streams cost them 5-12% for as long as it ran (PERF.md
        section 6, PR 35)."""
        if online == self._online:
            return
        self._online = online
        n = len(self.disks)
        for sc in ("STANDARD", "REDUCED_REDUNDANCY"):
            k, m = self._write_geometry(
                self._parity_for(PutObjectOptions(storage_class=sc)),
                n - online)
            if m and online >= k:
                Erasure(k, m, BLOCK_SIZE_V2, set_id=self.set_index).warm()

    def _write_geometry(self, parity: int, offline: int) -> tuple[int, int]:
        """(k, m) of a PUT at `parity` with `offline` of the set's
        drives away: the parity upgrade of degraded writes
        (cmd/erasure-object.go:770-805)."""
        n = len(self.disks)
        if offline > 0 and parity < n // 2:
            parity = min(n // 2, parity + offline)
        return n - parity, parity

    def _shuffled_disks(self, obj: str) -> list[StorageAPI | None]:
        """Order drives by the object's hashOrder distribution
        (shuffleDisksAndPartsMetadata, cmd/erasure-metadata-utils.go:212)."""
        dist = hash_order(obj, len(self.disks))
        disks = self._online_disks()
        out: list[StorageAPI | None] = [None] * len(disks)
        for idx, pos in enumerate(dist):
            out[pos - 1] = disks[idx]
        return out, dist

    def _parity_for(self, opts: PutObjectOptions) -> int:
        if opts.storage_class == "REDUCED_REDUNDANCY":
            return max(1, self.default_parity - 2) if self.default_parity > 2 else self.default_parity
        return self.default_parity

    # -------------------------------------------------------------- metadata
    def _read_all_fileinfo(self, bucket: str, obj: str, version_id: str = "",
                           read_data: bool = False, hedge: bool = False
                           ) -> tuple[list[FileInfo | None], list[Exception | None]]:
        """`read_version` of every drive, until an answer can be elected
        (the quorum read of `xl.meta`: stage `meta_read`, whose bytes are
        the lengths of the documents its answers were parsed from)."""
        with stagestats.timed("meta_read") as span:
            disks = self.disks
            n = len(disks)
            fis: list[FileInfo | None] = [None] * n
            errs: list[Exception | None] = [None] * n

            def read(i: int):
                d = disks[i]
                if d is None or not d.is_online():
                    raise errors.DiskNotFound(str(i))
                return d.read_version(bucket, obj, version_id, read_data)

            futs = {io_submit(read, i): i for i in range(n)}
            budget = deadline_mod.current()
            bounded = budget is not None and budget.t_end is not None
            if not bounded and not hedge:
                # no deadline in play (background scans/heals): preserve the
                # complete fan-out — health accounting wants every answer
                for f, i in futs.items():
                    try:
                        fis[i] = f.result()
                    except Exception as e:
                        errs[i] = e
                span.nbytes = sum(fi.xl_bytes for fi in fis if fi is not None)
                return fis, errs
            # deadline-aware: return at quorum, abandon stragglers.  A
            # FileInfo must actually be ELECTABLE from the answers in hand
            # (modal signature at the object's own read quorum — RRS parity
            # and mixed votes during a concurrent overwrite both demand more
            # than a bare success count) before stragglers are put on the
            # STRAGGLER_GRACE clock; a +500 ms drive then costs 50 ms, not
            # the whole RPC timeout (cmd/erasure-metadata-utils.go
            # readAllFileInfo; hedged-request literature in PAPERS.md).
            # With hedge=True the same quorum+grace policy applies even
            # WITHOUT a bounded budget: the foreground read path (GET /
            # head) must not let one slow drive's read_version stall
            # first-byte latency — the metadata analogue of the shard-stream
            # hedging below (ROADMAP deadline-plane follow-up).
            def electable() -> bool:
                try:
                    rq, _ = self._quorum_from(fis)
                    find_file_info_in_quorum(fis, rq)
                    return True
                except Exception:
                    return False

            pending = set(futs)
            elected = False
            while pending:
                timeout = budget.remaining() if bounded else None
                if elected:
                    timeout = STRAGGLER_GRACE if timeout is None \
                        else min(timeout, STRAGGLER_GRACE)
                if timeout is not None and timeout <= 0:
                    break
                done, pending = cf.wait(pending, timeout=timeout,
                                        return_when=cf.FIRST_COMPLETED)
                if not done:
                    break  # grace or budget spent: abandon the rest
                got_new = False
                for f in done:
                    i = futs[f]
                    try:
                        fis[i] = f.result()
                        got_new = True
                    except Exception as e:
                        errs[i] = e
                if got_new and not elected:
                    elected = electable()
            for f in pending:
                i = futs[f]
                f.cancel()  # un-started pool items never run
                errs[i] = errors.DeadlineExceeded(
                    f"drive {i}: straggler abandoned at quorum")
                hedge_stats["abandoned"] += 1
            if pending:
                tracing.event("read.stragglers_abandoned", count=len(pending))
            span.nbytes = sum(fi.xl_bytes for fi in fis if fi is not None)
            return fis, errs

    def _quorum_info(self, bucket, obj, version_id="", read_data=False,
                     hedge=False):
        fis, errs = self._read_all_fileinfo(bucket, obj, version_id,
                                            read_data, hedge)
        not_found = sum(1 for e in errs if isinstance(e, errors.FileNotFound))
        ver_not_found = sum(
            1 for e in errs if isinstance(e, errors.FileVersionNotFound)
        )
        n = len(self.disks)
        if not_found > n // 2:
            raise errors.ObjectNotFound(f"{bucket}/{obj}")
        if ver_not_found > n // 2:
            raise errors.VersionNotFound(f"{bucket}/{obj}@{version_id}")
        read_quorum, _ = self._quorum_from(fis)
        fi = find_file_info_in_quorum(fis, read_quorum)
        return fi, fis, errs

    def _quorum_from(self, fis: list[FileInfo | None]) -> tuple[int, int]:
        parity = self.default_parity
        data = len(self.disks) - parity
        for fi in fis:
            if fi is not None and fi.erasure is not None:
                parity = fi.erasure.parity_blocks
                data = fi.erasure.data_blocks
                break
        wq = data + 1 if data == parity else data
        return data, wq

    # ------------------------------------------------------------------- PUT
    def put_object(self, bucket: str, obj: str, reader: BinaryIO,
                   size: int = -1, opts: PutObjectOptions | None = None
                   ) -> ObjectInfo:
        opts = opts or PutObjectOptions()
        disks, dist = self._shuffled_disks(obj)
        n = len(disks)
        offline = sum(1 for d in disks if d is None)
        k, parity = self._write_geometry(self._parity_for(opts), offline)
        write_quorum = k + 1 if k == parity else k
        if n - offline < write_quorum:
            raise errors.ErasureWriteQuorum(
                f"{n - offline} online drives < write quorum {write_quorum}"
            )

        erasure = Erasure(k, parity, BLOCK_SIZE_V2,
                          set_id=self.set_index)
        version_id = (
            opts.version_id or (new_version_id() if opts.versioned else "")
        )
        data_dir = new_data_dir()
        tmp_id = str(uuid.uuid4())
        tmp_prefix = f"{TMP_DIR}/{tmp_id}"

        inline = 0 <= size <= SMALL_FILE_THRESHOLD and \
            erasure.shard_file_size(size) <= SMALL_FILE_THRESHOLD

        # multi-process data plane (ISSUE 8, parallel/workers.py): when
        # MINIO_TPU_WORKERS > 0 and every drive is node-local, the
        # payload streams ONCE into a shared-memory ring; worker
        # processes erasure-encode + bitrot-write the shards and the
        # hash lane folds the etag — the whole PUT data path leaves this
        # interpreter.  Inline (small) objects, remote drives and chaos
        # interposers keep the in-process plane, which stays the
        # differential reference (tests/test_mp_dataplane_diff.py).
        mp_plane = None
        mp_roots: list[str] | None = None
        mp_groups = None
        if not inline:
            from minio_tpu.parallel import workers as workers_mod

            if workers_mod.worker_count() > 0:
                mp_roots = workers_mod.plane_roots(disks)
                if mp_roots is not None:
                    mp_plane = workers_mod.get_plane()
        hreader = None if mp_plane is not None \
            else _HashingReader(reader, size)

        shards_inline: list[bytes | None] = [None] * n
        failed_shards: set[int] = set()
        etag = ""

        if inline:
            payload = hreader.read(size) if size >= 0 else hreader.read()
            if len(payload) != size:
                raise errors.InvalidArgument(
                    f"short read: {len(payload)} != {size}"
                )
            shards = erasure.encode_data(payload)
            for i in range(n):
                # streaming-bitrot framing even inline, for uniform verify
                buf = io.BytesIO()
                w = bitrot.BitrotWriter(buf, erasure.shard_size,
                                        algo=bitrot.algo_from_env())
                if len(shards[i]):
                    w.write(shards[i])
                shards_inline[i] = buf.getvalue()
            total_size = size
        elif mp_plane is not None:
            from minio_tpu.storage import local as local_mod

            shard_hint = -1 if size < 0 else bitrot.bitrot_shard_file_size(
                erasure.shard_file_size(size), erasure.shard_size,
                bitrot.algo_from_env())
            try:
                total_size, mp_failed, etag, mp_groups = mp_plane.put_data(
                    reader, mp_roots, k, parity, BLOCK_SIZE_V2,
                    bitrot.algo_from_env(), size, SYSTEM_VOL,
                    f"{tmp_prefix}/part.1", shard_hint,
                    local_mod.FSYNC_ENABLED)
            except errors.StorageError:
                # retryable (WorkerDied and friends): the supervisor is
                # already respawning; sweep staging and surface it
                self._cleanup_tmp(tmp_prefix)
                raise
            failed_shards = set(mp_failed)
            if n - len(failed_shards) < write_quorum:
                self._cleanup_tmp(tmp_prefix)
                raise errors.ErasureWriteQuorum(
                    f"{n - len(failed_shards)} worker shard streams < "
                    f"quorum {write_quorum}")
            if size >= 0 and total_size != size:
                self._cleanup_tmp(tmp_prefix)
                raise errors.InvalidArgument(
                    f"short read: {total_size} != {size}"
                )
        else:
            shard_hint = -1 if size < 0 else bitrot.bitrot_shard_file_size(
                erasure.shard_file_size(size), erasure.shard_size,
                bitrot.algo_from_env())

            def open_writer(i: int):
                d = disks[i]
                if d is None:
                    return None
                try:
                    fh = d.open_file_writer(SYSTEM_VOL,
                                            f"{tmp_prefix}/part.1",
                                            size_hint=shard_hint)
                except errors.StorageError:
                    # faulty drive: degrade to a missing writer, the
                    # write-quorum accounting decides (reference drops
                    # failed disks before encode, cmd/erasure-encode.go)
                    return None
                return bitrot.BitrotWriter(
                    fh, erasure.shard_size, algo=bitrot.algo_from_env())

            # parallel writer opens: O_DIRECT open + staging-buffer setup
            # costs milliseconds per drive — serial, that is a full
            # drive-count round before the first byte is encoded
            open_futs = [io_submit(open_writer, i) for i in range(n)]
            writers = []
            try:
                with stagestats.timed("open"):
                    for f in open_futs:
                        writers.append(f.result())
            except BaseException:
                # a non-StorageError open (EACCES, MemoryError, ...)
                # aborts the PUT: close the writers that DID open (raw
                # O_DIRECT fds + pooled staging buffers have no
                # finalizer) and sweep their staged tmp files
                for f in open_futs:
                    try:
                        w = f.result()
                    except Exception:
                        continue
                    if w is not None:
                        try:
                            w.close()
                        except Exception:
                            pass
                self._cleanup_tmp(tmp_prefix)
                raise
            try:
                total_size, failed_shards = erasure.encode_stream(
                    hreader, writers, size, write_quorum
                )
            finally:
                # one drive after another: each shard file's last flush
                # and its fdatasync
                with stagestats.timed("close"):
                    for w in writers:
                        if w is not None:
                            try:
                                w.close()
                            except Exception:
                                pass
            if size >= 0 and total_size != size:
                self._cleanup_tmp(tmp_prefix)
                raise errors.InvalidArgument(
                    f"short read: {total_size} != {size}"
                )

        if hreader is not None:
            etag = hreader.etag
        mod_time = opts.mod_time or time.time()
        metadata = dict(opts.user_metadata)
        metadata["etag"] = etag
        if opts.content_type:
            metadata["content-type"] = opts.content_type
        if opts.finalize_metadata is not None:
            metadata.update(opts.finalize_metadata() or {})
            etag = metadata.get("etag", etag)
        if opts.etag:
            etag = opts.etag
            metadata["etag"] = etag

        part = ObjectPartInfo(1, total_size, total_size, mod_time, etag)

        def make_fi(i: int) -> FileInfo:
            return FileInfo(
                volume=bucket, name=obj, version_id=version_id,
                data_dir="" if inline else data_dir, mod_time=mod_time,
                size=total_size, metadata=metadata, parts=[part],
                erasure=ErasureInfo(
                    algorithm="rs-vandermonde", data_blocks=k,
                    parity_blocks=parity, block_size=BLOCK_SIZE_V2,
                    index=i + 1, distribution=dist,
                    checksums=[ChecksumInfo(
                        1, bitrot.algo_from_env(), b"")],
                ),
                data=shards_inline[i] if inline else None,
            )

        def commit(i: int) -> None:
            d = disks[i]
            if d is None:
                raise errors.DiskNotFound(str(i))
            if i in failed_shards:
                # this drive's shard stream failed mid-write: do not commit
                # metadata claiming a healthy shard (reference drops failed
                # onlineDisks before renameData, cmd/erasure-object.go:990)
                raise errors.DiskNotFound(f"shard write failed on {i}")
            fi = make_fi(i)
            if inline:
                d.write_metadata(bucket, obj, fi)
            else:
                d.rename_data(SYSTEM_VOL, tmp_prefix, fi, bucket, obj)

        with self.ns.write(f"{bucket}/{obj}"):
            replaced_tier_meta = None
            if self.tier_delete_hook is not None and not version_id:
                # an unversioned/null-version PUT replaces the existing
                # version in place: if that version was a tiered stub,
                # its warm-tier copy must be reclaimed or it leaks
                try:
                    prev, _, _ = self._quorum_info(bucket, obj)
                    if prev.metadata.get(TRANSITION_STATUS_KEY) == \
                            TRANSITION_COMPLETE:
                        replaced_tier_meta = dict(prev.metadata)
                except errors.StorageError:
                    pass
            if mp_groups is not None:
                # node-batched commit over the worker plane: one
                # message per worker commits every drive it wrote
                with stagestats.timed("commit"):
                    res = mp_plane.commit(
                        mp_groups, "rename_data", SYSTEM_VOL, tmp_prefix,
                        fi=make_fi(0), bucket=bucket, obj=obj,
                        skip=failed_shards)
                commit_errs = [None] * n
                for i in range(n):
                    if i in failed_shards:
                        commit_errs[i] = errors.DiskNotFound(
                            f"shard write failed on {i}")
                    elif i in res:
                        commit_errs[i] = res[i]
                    else:
                        commit_errs[i] = errors.DiskNotFound(str(i))
            else:
                with stagestats.timed("commit"):
                    commit_errs = self._commit_all(
                        commit, make_fi, disks, inline, failed_shards,
                        tmp_prefix, bucket, obj)
        if not inline:
            # a successful commit MOVED the staged dir (rename_data);
            # only drives whose commit did not land still hold staging —
            # sweeping all n was a per-PUT fixed cost of n no-op deletes
            leftover = [i for i in range(n) if commit_errs[i] is not None]
            if leftover:
                self._cleanup_tmp(tmp_prefix, leftover)
        ok = sum(1 for e in commit_errs if e is None)
        if ok < write_quorum:
            raise errors.ErasureWriteQuorum(
                f"committed on {ok} < quorum {write_quorum}"
            )
        # partial-write drives -> async heal (MRF, cmd/erasure-object.go:1006)
        if self.heal_queue and ok < n:
            self.heal_queue(bucket, obj, version_id)

        if self.ns_updated is not None:
            self.ns_updated(bucket, obj)
        if replaced_tier_meta is not None:
            self.tier_delete_hook(replaced_tier_meta)
        fi = FileInfo(
            volume=bucket, name=obj, version_id=version_id, mod_time=mod_time,
            size=total_size, metadata=metadata, parts=[part],
        )
        return ObjectInfo.from_file_info(fi, bucket, obj, opts.versioned)

    def _fan_out(self, fn: Callable[[int], None], idxs) -> list[Exception | None]:
        # io_submit carries the request's deadline budget into the pool
        # threads so remote hops clamp their retries; writes still await
        # EVERY drive (quorum accounting needs all outcomes — only the
        # read path returns early).  Budget-free all-local fan-outs are
        # grouped into at most ~2x-core-count tasks: 16 futures of 100us
        # syscall work each cost more in thread wakeups than in work on
        # a small host.  A group runs SERIALLY in one worker, so it is
        # only safe when drives cannot individually stall: under a
        # deadline budget a slow drive would charge its wall to the
        # drives queued behind it (failing their clamped ops), and a
        # hung remote drive would multiply the fan-out wall by its group
        # size — those keep one task per drive.
        idxs = list(idxs)
        out: list[Exception | None] = [None] * len(self.disks)
        group_ok = deadline_mod.current() is None and all(
            self.disks[i] is None or self.disks[i].is_local() for i in idxs)
        if not group_ok:
            futs = {i: io_submit(fn, i) for i in idxs}
            for i, f in futs.items():
                try:
                    f.result()
                except Exception as e:
                    out[i] = e
            return out
        ngroups = max(4, 2 * (os.cpu_count() or 4))
        step = max(1, -(-len(idxs) // ngroups))

        def run_group(group: list[int]) -> list[Exception | None]:
            res: list[Exception | None] = []
            for i in group:
                try:
                    fn(i)
                    res.append(None)
                except Exception as e:
                    res.append(e)
            return res

        groups = [idxs[lo: lo + step] for lo in range(0, len(idxs), step)]
        futs = [(g, io_submit(run_group, g)) for g in groups]
        for g, f in futs:
            for i, err in zip(g, f.result()):
                out[i] = err
        return out

    def _commit_meta(self, fn: Callable[[int], None]
                     ) -> list[Exception | None]:
        """A version's metadata written to every drive of the set, syncs
        included (stage `commit`, as a PUT's rename_data fan-out)."""
        with stagestats.timed("commit"):
            return self._fan_out(fn, range(len(self.disks)))

    def _commit_all(self, commit, make_fi, disks, inline, failed_shards,
                    tmp_prefix, bucket, obj) -> list[Exception | None]:
        """Commit fan-out, optionally NODE-BATCHED for remote drives:
        with MINIO_TPU_COMMIT_BATCH_RPC=1, sibling drives on one peer
        commit through a single rename_data_batch RPC (one coalesced
        round trip per node per PUT, ISSUE 8 — the wire twin of the
        worker plane's per-worker commit message; the shared
        foundation for the ROADMAP metadata-journal item).

        OFF by default: the batch handler commits its items
        sequentially, so ONE hung drive convoys every healthy sibling
        on its node behind the RPC timeout — the chaos drill's
        hung-remote-drive PUT blew its latency ceiling exactly this
        way — and a transport failure after a PARTIAL batch cannot be
        retried per-drive safely (the committed drives' staging is
        gone, so the retry reads FileNotFound and votes a spurious
        quorum loss).  The per-drive fan-out keeps hung-drive damage
        isolated; item 5's journal layer is where per-node batching
        gets per-drive isolation for free."""
        n = len(disks)
        batched: dict[int, Exception | None] = {}
        groups: list[tuple[object, list[tuple[int, str]]]] = []
        batch_enabled = os.environ.get(
            "MINIO_TPU_COMMIT_BATCH_RPC", "").lower() in ("1", "on", "true")
        if not inline and batch_enabled:
            by_client: dict[int, list[tuple[int, str]]] = {}
            leaders: dict[int, object] = {}
            for i in range(n):
                d = disks[i]
                if d is None or i in failed_shards:
                    continue
                inner = d.unwrap() if hasattr(d, "unwrap") else d
                cl = getattr(inner, "client", None)
                if cl is None or not hasattr(inner, "rename_data_batch"):
                    continue
                key = id(cl)
                leaders.setdefault(key, inner)
                by_client.setdefault(key, []).append((i, inner.drive))
            groups = [(leaders[kk], lst) for kk, lst in by_client.items()
                      if len(lst) >= 2]

        def run_batch(leader, lst):
            items = [(dr, make_fi(i)) for i, dr in lst]
            try:
                res = leader.rename_data_batch(
                    SYSTEM_VOL, tmp_prefix, items, bucket, obj)
            except Exception:
                return None  # transport trouble: per-drive path decides
            return {i: r for (i, _dr), r in zip(lst, res)}

        if groups:
            futs = [(lst, io_submit(run_batch, leader, lst))
                for leader, lst in groups]
            for lst, f in futs:
                res = f.result()
                if res is not None:
                    batched.update(res)
        rest = [i for i in range(n) if i not in batched]
        out = self._fan_out(commit, rest)
        for i, e2 in batched.items():
            out[i] = e2
        return out

    def _cleanup_tmp(self, tmp_prefix: str, idxs=None) -> None:
        def rm(i: int) -> None:
            d = self.disks[i]
            if d is not None and d.is_online():
                try:
                    d.delete(SYSTEM_VOL, tmp_prefix, recursive=True)
                except errors.FileNotFound:
                    pass

        self._fan_out(rm, range(len(self.disks)) if idxs is None else idxs)

    def contains(self, bucket: str, obj: str) -> bool:
        """Quorum-visible object record exists (ANY version, including a
        delete-marker latest) — the pool-routing probe (reference probes
        pools with a raw meta read, cmd/erasure-server-pool.go:289)."""
        try:
            with self.ns.read(f"{bucket}/{obj}"):
                self._quorum_info(bucket, obj)
            return True
        except errors.StorageError:
            return False

    # ------------------------------------------------------------------- GET
    def _open(self, bucket: str, obj: str, version_id: str,
              read_data: bool
              ) -> tuple[ObjectInfo, FileInfo, list[FileInfo | None]]:
        """The one quorum read of a read request: `xl.meta` of every
        drive under the namespace read lock, one election.  Headers and
        bytes of a GET both come from what this returns."""
        with self.ns.read(f"{bucket}/{obj}"):
            fi, fis, _ = self._quorum_info(bucket, obj, version_id,
                                           read_data=read_data, hedge=True)
        if fi.deleted:
            if not version_id:
                raise errors.ObjectNotFound(f"{bucket}/{obj}")
            oi = ObjectInfo.from_file_info(fi, bucket, obj, True)
            raise MethodNotAllowedDeleteMarker(oi)
        oi = ObjectInfo.from_file_info(fi, bucket, obj, bool(version_id))
        return oi, fi, fis

    def open_object(self, bucket: str, obj: str, version_id: str = ""
                    ) -> tuple[ObjectInfo, ObjectReadFn]:
        """-> (info, read): the elected version's ObjectInfo, and
        `read(offset=0, length=-1)`, which streams that range of that
        same election (its `fi` and `data_dir`) however often it is
        called.  `read` returns at once; no shard file is opened before
        its iterator is first advanced, so a caller that answers from
        the info alone (304, 412, a bad Range, a tier stub) drops the
        handle and touches no drive again (the reference's
        GetObjectNInfo: metadata read once, reader and info together)."""
        opened = self._open(bucket, obj, version_id, read_data=True)

        def read(offset: int = 0, length: int = -1) -> Iterator[bytes]:
            return self.get_object(bucket, obj, offset, length, version_id,
                                   opened=opened)[1]

        return opened[0], read

    def get_object_info(self, bucket: str, obj: str, version_id: str = ""
                        ) -> ObjectInfo:
        return self._open(bucket, obj, version_id, read_data=False)[0]

    def object_health(self, bucket: str, obj: str, version_id: str = ""
                      ) -> tuple[FileInfo, int]:
        """Quorum FileInfo plus the number of ONLINE drives missing this
        version — the scanner's heal-trigger signal (the reference's
        disksWithAllParts classification, cmd/erasure-healing-common.go:184)."""
        fi, fis, _ = self._quorum_info(bucket, obj, version_id)
        missing = sum(
            1 for i, f in enumerate(fis)
            if f is None and self.disks[i] is not None
            and self.disks[i].is_online()
        )
        return fi, missing

    def get_object(self, bucket: str, obj: str, offset: int = 0,
                   length: int = -1, version_id: str = "", *,
                   opened: tuple | None = None
                   ) -> tuple[ObjectInfo, Iterator[bytes]]:
        """-> (info, stream of `[offset, offset + length)`).  The one place
        where a range of an election becomes a stream: it opens the object
        itself, or streams what `open_object` elected (`opened`, which
        that handle's `read` passes)."""
        if opened is None:
            try:
                opened = self._open(bucket, obj, version_id, read_data=True)
            except MethodNotAllowedDeleteMarker:
                raise errors.ObjectNotFound(f"{bucket}/{obj}") from None
        oi, fi, fis = opened
        if length < 0:
            length = fi.size - offset
        if offset < 0 or offset + length > fi.size:
            raise errors.InvalidArgument(
                f"range [{offset}, {offset + length}) outside size {fi.size}"
            )
        return oi, self._stream_object(bucket, obj, fi, fis, offset, length)

    def _stream_object(self, bucket, obj, fi: FileInfo,
                       fis: list[FileInfo | None], offset: int, length: int
                       ) -> Iterator[bytes]:
        if length == 0 or fi.size == 0:
            return
        e = Erasure(fi.erasure.data_blocks, fi.erasure.parity_blocks,
                    fi.erasure.block_size, set_id=self.set_index)
        n = e.k + e.m
        # order drives by this object's distribution
        dist = fi.erasure.distribution
        disks_by_index: list[StorageAPI | None] = [None] * n
        inline_by_index: list[bytes | None] = [None] * n
        for disk_idx, pos in enumerate(dist):
            d = self.disks[disk_idx] if disk_idx < len(self.disks) else None
            di = fis[disk_idx] if disk_idx < len(fis) else None
            # trust each drive's own recorded shard index when present
            shard_pos = pos - 1
            if di is not None and di.erasure is not None and di.data_dir == fi.data_dir:
                shard_pos = di.erasure.index - 1
            if 0 <= shard_pos < n and disks_by_index[shard_pos] is None:
                disks_by_index[shard_pos] = (
                    d if d is not None and d.is_online() else None
                )
                if di is not None and di.data is not None:
                    inline_by_index[shard_pos] = di.data

        heal_needed = False
        heal_deep = False

        def _queue_heal():
            # runs in a finally: a client disconnect mid-stream must not
            # drop the heal for corruption already detected
            if heal_needed and self.heal_queue:
                try:
                    self.heal_queue(bucket, obj, fi.version_id,
                                    deep=heal_deep)
                except TypeError:
                    self.heal_queue(bucket, obj, fi.version_id)

        # stream every part overlapping [offset, offset+length)
        part_start = 0
        remaining = length
        try:
            for part in fi.parts:
                part_end = part_start + part.size
                if part_end <= offset or remaining <= 0:
                    part_start = part_end
                    continue
                local_off = max(offset - part_start, 0)
                local_len = min(part.size - local_off, remaining)

                till = e.shard_file_size(part.size)
                readers: list[bitrot.BitrotReader | None] = [None] * n
                # hedge: classify shard sources by EWMA read latency —
                # a drive past HEDGE_EWMA_S is deprioritized behind the
                # spare (parity) shards, and its reader is only opened
                # when the fast shards cannot cover k+1 (quorum + one
                # steal target).  Slow drives stop taxing every read;
                # they remain fallbacks if a fast shard fails
                # (tail-at-scale hedged requests; reference picks
                # readers by health, cmd/erasure-decode.go).
                fast: list[int] = []
                slow: list[int] = []
                for i in range(n):
                    if inline_by_index[i] is not None:
                        fast.append(i)
                        continue
                    d = disks_by_index[i]
                    if d is None:
                        heal_needed = True
                        continue
                    ewma_of = getattr(d, "op_ewma", None)
                    lat = (ewma_of("read_file_stream")
                           if ewma_of is not None else 0.0)
                    (slow if lat > HEDGE_EWMA_S else fast).append(i)
                # enough fast shards -> slow drives are hedged out
                # entirely (waiting on a slow spare would reintroduce
                # the tail); short of k, pull in slow ones + one spare
                # as steal margin.  A failed fast open falls back to a
                # second round over the hedged-out drives below.
                if len(fast) >= e.k:
                    want = len(fast)
                else:
                    want = min(e.k + 1, len(fast) + len(slow))
                open_set = fast + slow[:max(0, want - len(fast))]
                skipped = (len(fast) + len(slow)) - len(open_set)
                if skipped > 0:
                    hedge_stats["hedged"] += skipped
                    # trace mark: this read steered around slow drives
                    # (ISSUE 12: hedged reads are visible in the tree)
                    tracing.event("read.hedged", skipped=skipped,
                                  part=part.number)
                prefer = list(open_set)  # fast first, chosen slow last

                def open_one(i: int):
                    if inline_by_index[i] is not None:
                        return bitrot.BitrotReader(
                            io.BytesIO(inline_by_index[i]), till,
                            e.shard_size)
                    fh = disks_by_index[i].read_file_stream(
                        bucket, f"{obj}/{fi.data_dir}/part.{part.number}",
                        0, bitrot.bitrot_shard_file_size(
                            till, e.shard_size, _bitrot_algo_of(fi)),
                    )
                    return bitrot.BitrotReader(
                        fh, till, e.shard_size, algo=_bitrot_algo_of(fi))

                def open_round(idxs) -> None:
                    # parallel opens: with injected +500 ms latency the
                    # cost is one round, not one round PER drive
                    nonlocal heal_needed
                    futs = {i: io_submit(open_one, i) for i in idxs}
                    for i, f in futs.items():
                        try:
                            readers[i] = f.result()
                        except Exception:
                            heal_needed = True
                            readers[i] = None

                # what a part costs before its first group: this thread
                # waiting for the pool to open the shard readers
                with stagestats.timed("open"):
                    open_round(open_set)
                    short = sum(1 for i in open_set
                                if readers[i] is not None) < e.k
                    if short:
                        # fast opens fell short of k: the hedged-out
                        # slow drives are the remaining sources — open
                        # them now
                        rest = [i for i in fast + slow
                                if i not in open_set]
                        open_round(rest)
                        prefer = prefer + rest
                if not short:
                    # hedged-out drives stay available as LAZY steal
                    # targets: nothing is opened (no latency paid) until
                    # a fast shard fails MID-STREAM and the decode
                    # work-steals to a spare — without this, exactly-k
                    # fast readers would turn one bitrot hit into a
                    # read-quorum error while healthy slow shards sit
                    # unused
                    lazies = [i for i in slow if i not in open_set]
                    for i in lazies:
                        readers[i] = _LazyShardReader(open_one, i)
                    prefer = prefer + lazies
                sink = _IterSink()
                broken: set[int] = set()
                # copied context: the caller's context is already
                # budget-free here (whole-payload phase), but it DOES
                # carry the request trace — the decode/respond stage
                # folds must attribute to the live span (ISSUE 12)
                import contextvars

                decode_ctx = contextvars.copy_context()
                # lint: allow(budget-propagation): whole-payload decode stream is deliberately budget-free (the copied ctx has no budget — see _run_nobudget); joined in finally
                worker = threading.Thread(
                    target=decode_ctx.run,
                    args=(self._decode_to_sink, e, sink, readers,
                          local_off, local_len, part.size,
                          broken, prefer),
                    daemon=True,
                )
                worker.start()
                try:
                    yield from sink
                except GeneratorExit:
                    sink.abandon()
                    raise
                finally:
                    worker.join()
                    for r in readers:
                        if r is not None:
                            try:
                                r.close()
                            except Exception:
                                pass
                if sink.error is not None and not isinstance(sink.error, BrokenPipeError):
                    raise sink.error
                if broken:
                    # a shard failed bitrot/IO mid-stream: the client got
                    # clean data (reconstructed) but the drive needs a
                    # VERIFYING heal (the corrupt file is size-correct, so a
                    # shallow part check would see nothing wrong)
                    heal_needed = True
                    heal_deep = True
                remaining -= local_len
                part_start = part_end
        finally:
            _queue_heal()

    @staticmethod
    def _decode_to_sink(e, sink, readers, offset, length, total,
                        broken_out=None, prefer=None):
        try:
            e.decode_stream(sink, readers, offset, length, total,
                            broken_out=broken_out, prefer=prefer)
        except Exception as ex:
            sink.error = ex
        finally:
            sink.close()

    # ------------------------------------------------------------ TIERING
    def transition_version(self, bucket: str, obj: str, version_id: str,
                           meta_updates: dict,
                           expected_mod_time: float = 0.0) -> None:
        """Free the version's local shard data on every drive, leaving a
        metadata stub pointing at the warm tier (reference transition
        path, cmd/bucket-lifecycle.go + xl free-versions).

        `expected_mod_time` guards against freeing a version that was
        overwritten while its bytes were being uploaded to the tier (the
        upload happens outside this lock)."""
        with self.ns.write(f"{bucket}/{obj}"):
            if expected_mod_time:
                fi0, _, _ = self._quorum_info(bucket, obj, version_id)
                if abs(fi0.mod_time - expected_mod_time) > 1e-6:
                    raise errors.InvalidArgument(
                        "version changed during transition")

            def free(i: int) -> None:
                d = self.disks[i]
                if d is None or not d.is_online():
                    raise errors.DiskNotFound(str(i))
                d.free_version_data(bucket, obj, version_id, meta_updates)

            errs = self._fan_out(free, range(len(self.disks)))
            _, wq = self._quorum_from([None] * len(self.disks))
            if sum(1 for e2 in errs if e2 is None) < wq:
                raise errors.ErasureWriteQuorum("transition quorum not met")

    def put_delete_marker(self, bucket: str, obj: str, version_id: str,
                          mod_time: float) -> None:
        """Write a delete marker with a PINNED version id and mod time —
        pool decommission replays markers into the target pool without
        reordering version history (the reference's decom moves versions
        verbatim, cmd/erasure-server-pool-decom.go)."""
        marker = FileInfo(volume=bucket, name=obj, version_id=version_id,
                          deleted=True, mod_time=mod_time)
        with self.ns.write(f"{bucket}/{obj}"):
            def put_marker(i: int) -> None:
                d = self.disks[i]
                if d is None or not d.is_online():
                    raise errors.DiskNotFound(str(i))
                d.write_metadata(bucket, obj, marker)

            errs = self._commit_meta(put_marker)
            _, wq = self._quorum_from([None] * len(self.disks))
            if sum(1 for e2 in errs if e2 is None) < wq:
                raise errors.ErasureWriteQuorum("delete marker quorum")
        if self.ns_updated is not None:
            self.ns_updated(bucket, obj)

    # ---------------------------------------------------------------- DELETE
    def delete_object(self, bucket: str, obj: str, version_id: str = "",
                      versioned: bool = False,
                      suspended: bool = False) -> ObjectInfo:
        with self.ns.write(f"{bucket}/{obj}"):
            if suspended and not version_id:
                # versioning suspended: the delete marker takes the null id,
                # permanently replacing any existing null version while
                # leaving real versions intact (AWS suspended semantics;
                # reference null-version handling in DeleteObject)
                from minio_tpu.storage.xlmeta import NULL_VERSION_ID

                marker = FileInfo(volume=bucket, name=obj, version_id="",
                                  deleted=True, mod_time=time.time())

                def put_null_marker(i: int) -> None:
                    d = self.disks[i]
                    if d is None or not d.is_online():
                        raise errors.DiskNotFound(str(i))
                    d.delete_version(bucket, obj, marker,
                                     force_del_marker=True)

                errs = self._commit_meta(put_null_marker)
                _, wq = self._quorum_from([None] * len(self.disks))
                if sum(1 for e2 in errs if e2 is None) < wq:
                    raise errors.ErasureWriteQuorum("delete marker quorum")
                if self.ns_updated is not None:
                    self.ns_updated(bucket, obj)
                return ObjectInfo(bucket=bucket, name=obj,
                                  version_id=NULL_VERSION_ID,
                                  delete_marker=True,
                                  mod_time=marker.mod_time)
            if versioned and not version_id:
                # versioned delete without version: write a delete marker
                marker = FileInfo(
                    volume=bucket, name=obj, version_id=new_version_id(),
                    deleted=True, mod_time=time.time(),
                )

                def put_marker(i: int) -> None:
                    d = self.disks[i]
                    if d is None or not d.is_online():
                        raise errors.DiskNotFound(str(i))
                    d.write_metadata(bucket, obj, marker)

                errs = self._commit_meta(put_marker)
                _, wq = self._quorum_from([None] * len(self.disks))
                if sum(1 for e2 in errs if e2 is None) < wq:
                    raise errors.ErasureWriteQuorum("delete marker quorum")
                if self.ns_updated is not None:
                    self.ns_updated(bucket, obj)
                oi = ObjectInfo(bucket=bucket, name=obj,
                                version_id=marker.version_id,
                                delete_marker=True, mod_time=marker.mod_time)
                return oi

            tier_meta = None
            if self.tier_delete_hook is not None:
                # capture the stub's tier pointer now, enqueue the remote
                # reclaim only AFTER the local delete succeeds (a failed
                # delete must not strand a live stub pointing at deleted
                # tier data) — reference tier-journal, cmd/tier-journal.go
                try:
                    fi0, _, _ = self._quorum_info(bucket, obj, version_id)
                    if fi0.metadata.get(TRANSITION_STATUS_KEY) == \
                            TRANSITION_COMPLETE:
                        tier_meta = dict(fi0.metadata)
                except errors.StorageError:
                    pass

            fi = FileInfo(volume=bucket, name=obj, version_id=version_id,
                          deleted=False, mod_time=time.time())

            def del_version(i: int) -> None:
                d = self.disks[i]
                if d is None or not d.is_online():
                    raise errors.DiskNotFound(str(i))
                d.delete_version(bucket, obj, fi)

            errs = self._commit_meta(del_version)
            ok = sum(1 for e2 in errs
                     if e2 is None or isinstance(e2, errors.FileNotFound))
            # deletes use MAJORITY quorum regardless of the version's
            # parity (reference DeleteObject writeQuorum = n/2+1) — the
            # object's own parity is unknown without an extra read
            if ok < len(self.disks) // 2 + 1:
                raise errors.ErasureWriteQuorum("delete quorum not met")
            if tier_meta is not None:
                self.tier_delete_hook(tier_meta)
            if self.ns_updated is not None:
                self.ns_updated(bucket, obj)
            return ObjectInfo(bucket=bucket, name=obj, version_id=version_id)

    def delete_objects(self, bucket: str, dels: list[dict]) -> list:
        """Bulk delete: ONE delete_versions RPC per drive for the whole
        batch (reference DeleteObjects -> per-disk DeleteVersions,
        cmd/erasure-object.go DeleteObjects).

        dels: [{"obj":..., "version_id":..., "versioned":bool,
        "suspended":bool}]; returns per-entry ObjectInfo or Exception."""
        import contextlib

        results: list = [None] * len(dels)
        items: list[tuple[int, str, FileInfo, bool]] = []
        markers: list[tuple[int, dict]] = []
        # hold every object's write lock for the batch, in sorted order
        # (deadlock-free), so bulk deletes cannot race concurrent PUTs
        # into split sub-quorum states
        lock_keys = sorted({f"{bucket}/{d0['obj']}" for d0 in dels})
        with contextlib.ExitStack() as stack:
            for lk in lock_keys:
                stack.enter_context(self.ns.write(lk))
            for j, d0 in enumerate(dels):
                obj = d0["obj"]
                vid = d0.get("version_id", "")
                versioned = d0.get("versioned", False)
                suspended = d0.get("suspended", False)
                if not vid and (versioned or suspended):
                    # marker writes have per-object quorum/return
                    # semantics: reuse the single-object path (rare in
                    # bulk deletes compared to plain removals)
                    markers.append((j, d0))
                    continue
                fi = FileInfo(volume=bucket, name=obj, version_id=vid,
                              deleted=False, mod_time=time.time())
                items.append((j, obj, fi, False))
            if self.tier_delete_hook is not None and items:
                # prefetch tier pointers CONCURRENTLY — serial quorum
                # reads under the held locks would dwarf the single
                # batched delete round
                def fetch(j_obj):
                    j, obj, _, _ = j_obj
                    try:
                        fi0, _, _ = self._quorum_info(
                            bucket, obj, dels[j].get("version_id", ""))
                        if fi0.metadata.get(TRANSITION_STATUS_KEY) == \
                                TRANSITION_COMPLETE:
                            dels[j]["_tier_meta"] = dict(fi0.metadata)
                    except errors.StorageError:
                        pass

                with cf.ThreadPoolExecutor(
                        max_workers=min(8, len(items))) as pre:
                    list(pre.map(fetch, items))

            if items:
                batch = [(obj, fi, force) for _, obj, fi, force in items]
                per_drive: dict[int, list] = {}

                def run(i: int) -> None:
                    d = self.disks[i]
                    if d is None or not d.is_online():
                        raise errors.DiskNotFound(str(i))
                    per_drive[i] = d.delete_versions(bucket, batch)

                drive_errs = self._fan_out(run, range(len(self.disks)))
                n = len(self.disks)
                wq = n // 2 + 1  # majority, like single-object deletes
                for pos, (j, obj, fi, _) in enumerate(items):
                    # success = the delete took effect on a WRITE QUORUM
                    # of drives (already-absent counts as deleted), else
                    # a later read could resurrect the object from the
                    # surviving copies
                    ok = 0
                    for i in range(n):
                        e2 = drive_errs[i] if drive_errs[i] is not None \
                            else per_drive[i][pos]
                        if e2 is None or isinstance(e2,
                                                    errors.FileNotFound):
                            ok += 1
                    if ok < wq:
                        results[j] = errors.ErasureWriteQuorum(
                            f"delete quorum not met for {obj}")
                        continue
                    results[j] = ObjectInfo(bucket=bucket, name=obj,
                                            version_id=fi.version_id)
                    # per-item hooks must NEVER abort the batch: the
                    # drives are already modified for every other key
                    try:
                        if self.ns_updated is not None:
                            self.ns_updated(bucket, obj)
                        tm = dels[j].get("_tier_meta")
                        if tm is not None \
                                and self.tier_delete_hook is not None:
                            self.tier_delete_hook(tm)
                    except Exception:
                        pass

        for j, d0 in markers:
            try:
                results[j] = self.delete_object(
                    bucket, d0["obj"], d0.get("version_id", ""),
                    d0.get("versioned", False), d0.get("suspended", False))
            except Exception as e:
                results[j] = e
        return results

    # ------------------------------------------------------------- METADATA
    TAGS_KEY = "x-minio-tags"  # urlencoded tag set on a version

    def update_object_metadata(self, bucket: str, obj: str,
                               updates: dict, version_id: str = ""
                               ) -> ObjectInfo:
        """Set (value) / remove (None) metadata keys on one version across
        all drives under write quorum (reference PutObjectTags →
        updateObjectMeta, cmd/erasure-object.go:1530)."""
        with self.ns.write(f"{bucket}/{obj}"):
            fi, fis, _ = self._quorum_info(bucket, obj, version_id)
            if fi.deleted:
                raise errors.MethodNotAllowed(f"{bucket}/{obj}")

            def upd(i: int) -> None:
                d = self.disks[i]
                fi_i = fis[i]
                if d is None or not d.is_online() or fi_i is None:
                    raise errors.DiskNotFound(str(i))
                for k, v in updates.items():
                    if v is None:
                        fi_i.metadata.pop(k, None)
                    else:
                        fi_i.metadata[k] = v
                d.update_metadata(bucket, obj, fi_i)

            errs = self._fan_out(upd, range(len(self.disks)))
            _, wq = self._quorum_from(fis)
            if sum(1 for e in errs if e is None) < wq:
                raise errors.ErasureWriteQuorum("metadata update quorum")
            if self.ns_updated is not None:
                # tag changes alter tag-filtered lifecycle eligibility:
                # the bucket must scan dirty
                self.ns_updated(bucket, obj)
            for k, v in updates.items():
                if v is None:
                    fi.metadata.pop(k, None)
                else:
                    fi.metadata[k] = v
            return ObjectInfo.from_file_info(fi, bucket, obj)

    def put_object_tags(self, bucket: str, obj: str, tags: str,
                        version_id: str = "") -> ObjectInfo:
        return self.update_object_metadata(
            bucket, obj, {self.TAGS_KEY: tags}, version_id)

    def get_object_tags(self, bucket: str, obj: str,
                        version_id: str = "") -> str:
        oi = self.get_object_info(bucket, obj, version_id)
        return oi.metadata.get(self.TAGS_KEY, "")

    def delete_object_tags(self, bucket: str, obj: str,
                           version_id: str = "") -> ObjectInfo:
        return self.update_object_metadata(
            bucket, obj, {self.TAGS_KEY: None}, version_id)

    # ------------------------------------------------------------------ LIST
    def list_objects(self, bucket: str, prefix: str = "") -> list[str]:
        """Union of per-drive sorted walks (metacache-lite)."""
        from . import listing

        return listing.union_walk(self.disks, bucket, prefix)

    def list_entries(self, bucket: str, prefix: str = "", marker: str = "",
                     include_marker: bool = False):
        """Sorted (name, versions) entry stream for this set."""
        from . import listing

        return listing.set_list_entries(self, bucket, prefix, marker,
                                        include_marker)

    # ------------------------------------------------------------------ HEAL
    def heal_object(self, bucket: str, obj: str, version_id: str = "",
                    deep: bool = False) -> HealResult:
        """Rebuild missing/corrupt shards onto their drives
        (cmd/erasure-healing.go:257)."""
        with self.ns.write(f"{bucket}/{obj}"):
            try:
                fi, fis, errs = self._quorum_info(bucket, obj, version_id,
                                                  read_data=True)
            except (errors.ObjectNotFound, errors.VersionNotFound,
                    errors.ErasureReadQuorum):
                # dangling object: not enough shards/metadata survive to
                # ever reconstruct it (isObjectDangling,
                # cmd/erasure-healing.go:836)
                return HealResult(failed=True)
            if fi.deleted:
                return HealResult(object_size=0)
            if fi.metadata.get(TRANSITION_STATUS_KEY) == TRANSITION_COMPLETE:
                # tiered stub: no shards to rebuild, but the xl.meta stub
                # itself must exist on every drive or the tier pointer can
                # fall below quorum as drives are replaced
                result = HealResult(object_size=fi.size)
                fi.data = None
                for i, d in enumerate(self.disks):
                    result.drives_before.append(
                        "missing" if fis[i] is None else "ok")
                    if d is not None and d.is_online() and fis[i] is None:
                        try:
                            d.write_metadata(bucket, obj, fi)
                            result.healed_drives += 1
                            result.drives_after.append("healed")
                            continue
                        except errors.StorageError:
                            pass
                    result.drives_after.append(
                        "missing" if fis[i] is None else "ok")
                return result
            e = Erasure(fi.erasure.data_blocks, fi.erasure.parity_blocks,
                        fi.erasure.block_size, set_id=self.set_index)
            n = e.k + e.m
            dist = fi.erasure.distribution
            result = HealResult(object_size=fi.size)

            # classify drives (disksWithAllParts analogue)
            shard_disk: list[StorageAPI | None] = [None] * n
            shard_meta: list[FileInfo | None] = [None] * n
            for disk_idx, pos in enumerate(dist):
                if disk_idx >= len(self.disks):
                    continue
                shard_pos = pos - 1
                di = fis[disk_idx]
                if di is not None and di.erasure is not None:
                    shard_pos = di.erasure.index - 1
                if not (0 <= shard_pos < n):
                    continue
                shard_disk[shard_pos] = self.disks[disk_idx]
                shard_meta[shard_pos] = fis[disk_idx]

            healthy: list[bool] = [False] * n
            for i in range(n):
                d, di = shard_disk[i], shard_meta[i]
                if d is None or not d.is_online() or di is None:
                    continue
                if di.data_dir != fi.data_dir or di.mod_time != fi.mod_time:
                    continue
                try:
                    if di.data is not None:
                        healthy[i] = True
                    elif deep:
                        d.verify_file(bucket, obj, di)
                        healthy[i] = True
                    else:
                        d.check_parts(bucket, obj, di)
                        healthy[i] = True
                except Exception:
                    healthy[i] = False
            result.drives_before = list(healthy)

            stale = [i for i in range(n) if not healthy[i]
                     and shard_disk[i] is not None and shard_disk[i].is_online()]
            if not stale:
                result.drives_after = list(healthy)
                return result
            if sum(healthy) < e.k:
                # dangling object (cmd/erasure-healing.go:836)
                result.failed = True
                return result

            inline = fi.data is not None or (
                fi.size <= SMALL_FILE_THRESHOLD and fi.parts and
                e.shard_file_size(fi.parts[0].size) <= SMALL_FILE_THRESHOLD
                and any(m is not None and m.data is not None for m in shard_meta)
            )

            # stage rebuilt shards of every part, then commit once per drive
            tmp_ids = {i: str(uuid.uuid4()) for i in stale}
            inline_sinks: dict[int, io.BytesIO] = {}
            algo = _bitrot_algo_of(fi)
            read_acct = repair_mod.ByteCounter()
            scan_acct = repair_mod.ByteCounter()
            local_idx = {i for i in range(n)
                         if shard_disk[i] is not None
                         and shard_disk[i].is_local()}
            for part in fi.parts:
                till = e.shard_file_size(part.size)
                part_path = f"{obj}/{fi.data_dir}/part.{part.number}"
                # Survivor readers open LAZILY, after planning: a
                # sub-shard plan touches only its k helpers, and an
                # eager open would charge every remote survivor a
                # full-window stream RPC per part (the remote stream
                # issues its fetch at create) that the ranged protocol
                # then abandons.
                readers: list[bitrot.BitrotReader | None] = [None] * n
                shard_fsize = bitrot.bitrot_shard_file_size(
                    till, e.shard_size, algo)

                def open_reader(i: int, at_frame: int = 0,
                                ranged: bool = False):
                    di = shard_meta[i]
                    if di is not None and di.data is not None:
                        return bitrot.BitrotReader(
                            io.BytesIO(di.data), till, e.shard_size,
                            algo=algo)
                    fh = shard_disk[i].read_file_stream(
                        bucket, part_path, at_frame,
                        shard_fsize - at_frame)
                    if ranged and hasattr(fh, "drain_max"):
                        # ranged helper: skips re-issue the RPC instead
                        # of draining, so a remote survivor ships only
                        # the planned fraction over the wire
                        fh.drain_max = 0
                    return bitrot.BitrotReader(
                        fh, till, e.shard_size, algo=algo)

                def open_survivors(idxs, at_frame: int = 0,
                                   ranged: bool = False) -> None:
                    for i in idxs:
                        if readers[i] is not None:
                            continue
                        try:
                            readers[i] = open_reader(i, at_frame, ranged)
                        except Exception:
                            pass

                candidates = [
                    i for i in range(n)
                    if healthy[i] and (
                        (shard_meta[i] is not None
                         and shard_meta[i].data is not None)
                        or shard_disk[i] is not None)]
                if len(candidates) < e.k:
                    result.failed = True
                    return result

                # -- repair planning (erasure/repair.py): price reusing
                # the targets' surviving frames against the k-full-shard
                # decode.  Inline objects stay on the full path (their
                # shards live in xl.meta; no drive bytes to save).
                residuals: dict[int, repair_mod.ResidualMap] = {}
                nblocks_part = -(-till // e.shard_size) if till > 0 else 0
                # the operator's full-decode override skips the residual
                # scan entirely: pricing that can't change the decision
                # must not cost a full target-shard read (remote stale
                # drives would pay it as an extra RPC transfer per part)
                ov = "full" if inline else repair_mod.scheme_override()
                if not inline and till > 0 and ov != "full":
                    for i in stale:
                        rm = None
                        try:
                            tfh = shard_disk[i].read_file_stream(
                                bucket, part_path, 0, -1)
                        except Exception:
                            # wiped/rotated drive or stale version: no
                            # same-version file — every block needs the
                            # k-wide rebuild
                            rm = repair_mod.ResidualMap(
                                nblocks=nblocks_part,
                                good=np.zeros(nblocks_part, dtype=bool))
                        if rm is None:
                            try:
                                rm = repair_mod.scan_residual(
                                    tfh, till, e.shard_size, algo=algo)
                                scan_acct.add(rm.scanned_bytes)
                            finally:
                                try:
                                    tfh.close()
                                except Exception:
                                    pass
                        residuals[i] = rm
                plan = repair_mod.plan_repair(
                    e, stale, candidates, part.size,
                    residuals=residuals or None, local=local_idx,
                    algo=algo, override=ov)

                def open_writers() -> list:
                    ws: list[bitrot.BitrotWriter | None] = [None] * n
                    for i in stale:
                        # healed shards keep the recorded algorithm
                        if inline:
                            sink = inline_sinks.setdefault(i, io.BytesIO())
                            ws[i] = bitrot.BitrotWriter(
                                sink, e.shard_size, algo=algo)
                        else:
                            fh = shard_disk[i].open_file_writer(
                                SYSTEM_VOL,
                                f"{TMP_DIR}/{tmp_ids[i]}/part.{part.number}",
                                size_hint=bitrot.bitrot_shard_file_size(
                                    till, e.shard_size, algo),
                            )
                            ws[i] = bitrot.BitrotWriter(
                                fh, e.shard_size, algo=algo)
                    return ws

                def counted(scheme: str) -> list:
                    def acct(nb: int, _s=scheme) -> None:
                        read_acct.add(nb)
                        repair_mod.add_read(_s, nb)
                    return [None if r is None
                            else repair_mod.CountingReader(r, algo, acct)
                            for r in readers]

                def discard_staging() -> None:
                    # a failed heal must not leave per-uuid staged part
                    # files behind (tmp/ has no reaper; MRF retries the
                    # object, so a leak repeats per attempt)
                    if inline:
                        return
                    for i in stale:
                        try:
                            shard_disk[i].delete(
                                SYSTEM_VOL, f"{TMP_DIR}/{tmp_ids[i]}",
                                recursive=True)
                        except Exception:
                            pass

                def close_readers() -> None:
                    for r in readers:
                        if r is not None:
                            try:
                                r.close()
                            except Exception:
                                pass

                done = False
                if plan.scheme == "full":
                    # the full decode needs k readable survivor streams;
                    # prove that BEFORE staging tmp writers so a cleanly
                    # unhealable object leaves nothing behind
                    open_survivors(candidates)
                    if sum(1 for r in readers if r) < e.k:
                        result.failed = True
                        close_readers()
                        return result
                writers = open_writers()
                if plan.scheme == "subshard":
                    # open ONLY the k helpers, positioned at the first
                    # planned frame so the remote stream's create-time
                    # fetch starts on useful bytes; ranged mode makes
                    # later skips re-issue the RPC instead of draining
                    fb = 0
                    if plan.bad_blocks is not None \
                            and plan.bad_blocks.any():
                        fb = int(np.flatnonzero(plan.bad_blocks)[0])
                    _, _hs = bitrot.hasher_of(algo)
                    open_survivors(
                        plan.helpers,
                        at_frame=fb * (_hs + e.shard_size), ranged=True)
                    tstreams: dict[int, object] = {}
                    try:
                        for i in stale:
                            rm = residuals.get(i)
                            if rm is None or not rm.good.any():
                                continue
                            try:
                                tstreams[i] = shard_disk[i].read_file_stream(
                                    bucket, part_path, 0, -1)
                            except Exception:
                                pass  # rebuilt entirely from helpers
                        cr = counted("subshard")
                        repair_mod.execute_subshard(
                            e, plan,
                            {h: cr[h] for h in plan.helpers},
                            {i: writers[i] for i in stale},
                            tstreams, on_scan=scan_acct.add)
                        result.scheme = "subshard"
                        done = True
                    except repair_mod.SubshardAbort:
                        # discard the partial staging, fall back to the
                        # full-shard decode — heal always converges
                        repair_mod.note_fallback()
                        for i in stale:
                            if writers[i] is not None and not inline:
                                try:
                                    writers[i].close()
                                except Exception:
                                    pass
                        for h in plan.helpers:
                            st = getattr(readers[h], "r", None)
                            if st is not None and hasattr(st, "drain_max"):
                                st.drain_max = getattr(
                                    type(st), "_DRAIN_MAX", st.drain_max)
                        writers = open_writers()
                part_failed = False
                try:
                    if not done:
                        open_survivors(candidates)
                        if sum(1 for r in readers if r) < e.k:
                            result.failed = True
                            part_failed = True
                            return result
                        e.heal(writers, counted("full"), part.size)
                except BaseException:
                    part_failed = True
                    raise
                finally:
                    for i in stale:
                        if writers[i] is not None and not inline:
                            try:
                                writers[i].close()
                            except Exception:
                                pass
                    close_readers()
                    if part_failed:
                        # after the writer closes: a remote writer's
                        # close can flush, which would resurrect a file
                        # deleted first
                        discard_staging()
            result.bytes_read = read_acct.n
            result.bytes_scanned = scan_acct.n

            for i in stale:
                d = shard_disk[i]
                nfi = FileInfo(
                    volume=bucket, name=obj, version_id=fi.version_id,
                    data_dir="" if inline else fi.data_dir,
                    mod_time=fi.mod_time, size=fi.size,
                    metadata=dict(fi.metadata), parts=list(fi.parts),
                    erasure=ErasureInfo(
                        algorithm=fi.erasure.algorithm, data_blocks=e.k,
                        parity_blocks=e.m, block_size=fi.erasure.block_size,
                        index=i + 1, distribution=dist,
                        checksums=[ChecksumInfo(
                            p.number, _bitrot_algo_of(fi), b"")
                            for p in fi.parts],
                    ),
                    data=inline_sinks[i].getvalue() if inline else None,
                )
                try:
                    if inline:
                        d.write_metadata(bucket, obj, nfi)
                    else:
                        d.rename_data(SYSTEM_VOL, f"{TMP_DIR}/{tmp_ids[i]}",
                                      nfi, bucket, obj)
                    healthy[i] = True
                    result.healed_drives += 1
                except Exception:
                    pass
            result.drives_after = list(healthy)
            if result.healed_drives and self.ns_updated is not None:
                # heal rewrote shard files: route through the same
                # invalidation choke point as every other mutation so
                # serving-tier caches (serving/hotcache.py) and change
                # trackers observe the rewrite (ISSUE 7 invalidation
                # matrix)
                self.ns_updated(bucket, obj)
            return result


class _LazyShardReader:
    """Steal-only spare: a hedged-out slow drive's BitrotReader that is
    opened on FIRST USE, not upfront.  The happy path never touches it
    (no latency paid); the decode work-steal path resolves it only when
    a fast shard fails mid-stream, paying the slow open once for the
    recovery instead of on every read."""

    def __init__(self, open_fn, idx: int):
        self._open_fn = open_fn
        self._idx = idx
        self._inner = None
        self._mu = threading.Lock()

    def _resolve(self):
        with self._mu:
            if self._inner is None:
                self._inner = self._open_fn(self._idx)  # may raise: steal
            return self._inner                          # marks it broken

    def read_blocks(self, offset: int, nblocks: int, block_len: int,
                    out=None):
        return self._resolve().read_blocks(offset, nblocks, block_len, out)

    def read_at(self, offset: int, length: int) -> bytes:
        return self._resolve().read_at(offset, length)

    def close(self) -> None:
        with self._mu:
            inner, self._inner = self._inner, None
        if inner is not None:
            inner.close()


class MethodNotAllowedDeleteMarker(errors.MethodNotAllowed):
    def __init__(self, oi: ObjectInfo):
        super().__init__(f"{oi.bucket}/{oi.name} is a delete marker")
        self.object_info = oi


class _IterSink:
    """Writer-side of a bounded byte-chunk pipe (decode thread -> consumer).

    Abandonment-safe: if the consumer drops the generator mid-stream (HTTP
    client disconnect), abandon() unblocks the producer, whose next write
    raises BrokenPipeError so the decode thread exits instead of deadlocking
    on the full queue."""

    def __init__(self, maxsize: int = 8):
        import queue as q

        self._qmod = q
        self._q: "q.Queue" = q.Queue(maxsize=maxsize)
        self.error: Exception | None = None
        self.abandoned = False

    def write(self, data: bytes) -> int:
        while True:
            if self.abandoned:
                raise BrokenPipeError("consumer abandoned stream")
            try:
                self._q.put(data, timeout=0.05)
                return len(data)
            except self._qmod.Full:
                continue

    def abandon(self) -> None:
        self.abandoned = True
        while True:  # drain so a blocked put() returns promptly
            try:
                self._q.get_nowait()
            except self._qmod.Empty:
                return

    def close(self) -> None:
        while True:
            if self.abandoned:
                return
            try:
                self._q.put(None, timeout=0.05)
                return
            except self._qmod.Full:
                continue

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            yield item


def default_parity_count(drive_count: int) -> int:
    """Reference defaults (cmd/format-erasure.go:873-884)."""
    if drive_count == 1:
        return 0
    if drive_count <= 3:
        return 1
    if drive_count <= 5:
        return 2
    if drive_count <= 7:
        return 3
    return 4
