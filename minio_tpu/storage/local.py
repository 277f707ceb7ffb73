"""Local POSIX drive implementation.

Equivalent of the reference's xlStorage (cmd/xl-storage.go:90): one
directory per drive, objects stored as
    <drive>/<bucket>/<object>/xl.meta
    <drive>/<bucket>/<object>/<data_dir>/part.N
with a `.minio_tpu.sys` system volume for tmp staging, multipart state and
drive metadata (format.json, healing tracker).  Writes stage into tmp and
move into place with atomic renames (reference RenameData,
cmd/xl-storage.go:1964).
"""

from __future__ import annotations

import io
import json
import os
import shutil
import threading
import time
import uuid
from typing import BinaryIO, Iterator

import numpy as _np

from minio_tpu.erasure import stagestats
from minio_tpu.ops import host
from minio_tpu.utils.deadline import service_thread

from . import errors, metajournal
from .api import DiskInfo, StorageAPI, VolInfo
from .xlmeta import NULL_VERSION_ID, FileInfo, XLMeta, file_info_from_raw

SYSTEM_VOL = ".minio_tpu.sys"
TMP_DIR = "tmp"
XL_META_FILE = "xl.meta"
FORMAT_FILE = "format.json"
HEALING_FILE = ".healing.bin"

# Durability: fdatasync files before commit renames and fsync parent dirs
# after, so an ACKed write survives power loss (reference fdatasync usage,
# cmd/xl-storage.go:1667 + internal/disk/fdatasync_linux.go:40).  Tests
# disable via MINIO_TPU_FSYNC=0 for speed; production default is on.
FSYNC_ENABLED = os.environ.get("MINIO_TPU_FSYNC", "1").lower() not in (
    "0", "off", "false")

# O_DIRECT streaming for shard files: bulk data bypasses the page cache so
# a storage node's RAM stays available for caches that matter (metacache,
# usage) and write throughput is the drive's, not the flush daemon's
# (reference cmd/xl-storage.go:1667 CreateFile / :1558 ReadFileStream via
# odirectReader + internal/disk/directio_unix.go:27-50).  Filesystems
# without O_DIRECT (tmpfs) fall back to buffered IO per drive,
# automatically.
ODIRECT_ENABLED = os.environ.get("MINIO_TPU_ODIRECT", "1").lower() not in (
    "0", "off", "false") and hasattr(os, "O_DIRECT")
_ALIGN = 4096          # logical block alignment O_DIRECT demands
_DIO_BUF = 1 << 20     # aligned staging-buffer size
# files smaller than this are written buffered even when O_DIRECT is on:
# a sub-1MiB shard never fills the aligned staging buffer, so the whole
# file goes out through the drop-O_DIRECT tail path anyway — paying the
# mmap/fcntl setup for nothing (the reference gates odirect behind a
# small-file threshold the same way, cmd/xl-storage.go CreateFile)
ODIRECT_MIN_BYTES = int(os.environ.get(
    "MINIO_TPU_ODIRECT_MIN_BYTES", str(1 << 20)))
# concurrent O_DIRECT device writes allowed across ALL drives of this
# process: synchronous direct writes contend at the backing device, and
# past a small fan-in aggregate bandwidth DEGRADES (measured here:
# 2-way 1.7 GiB/s vs 12-way 0.89 GiB/s on one backing device).  Default
# scales with cores — a many-core storage server with real independent
# drives effectively disables the gate; single-device sandboxes get the
# optimal small fan-in.  0 disables.
DEVICE_WRITE_CONCURRENCY = int(os.environ.get(
    "MINIO_TPU_DEVICE_WRITE_CONCURRENCY",
    str(max(2, os.cpu_count() or 2))))
_device_write_gate = (
    threading.BoundedSemaphore(DEVICE_WRITE_CONCURRENCY)
    if DEVICE_WRITE_CONCURRENCY > 0 else None)
# longest a flush waits for a gate slot before writing ungated: slots
# held by writes to a hung drive must not fence healthy drives
_GATE_WAIT_S = float(os.environ.get(
    "MINIO_TPU_DEVICE_WRITE_GATE_WAIT_S", "2.0"))
TRASH_DIR = "trash"

# Reusable page-aligned staging buffers for _DirectWriter: every PUT
# opens one writer per drive, and a fresh mmap + munmap per writer is
# measurable syscall/page-fault churn on the hot path.
_staging_lock = threading.Lock()
_staging_pool: list = []
_STAGING_POOL_MAX = 16


def _staging_acquire():
    import mmap

    with _staging_lock:
        if _staging_pool:
            return _staging_pool.pop()
    return mmap.mmap(-1, _DIO_BUF)


def _staging_release(buf) -> None:
    with _staging_lock:
        if len(_staging_pool) < _STAGING_POOL_MAX:
            _staging_pool.append(buf)
            return
    buf.close()


def _fdatasync(fileobj) -> None:
    if not FSYNC_ENABLED:
        return
    fileobj.flush()
    if hasattr(os, "fdatasync"):
        os.fdatasync(fileobj.fileno())
    else:  # pragma: no cover - non-linux
        os.fsync(fileobj.fileno())


def _fsync_dir(path: str) -> None:
    """Persist a directory entry (the rename itself) to disk."""
    if not FSYNC_ENABLED:
        return
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class _SyncedWriter:
    """File wrapper that fdatasyncs on close, so shard bytes are durable
    before the commit rename publishes them."""

    def __init__(self, f):
        self._f = f

    def write(self, data) -> int:
        return self._f.write(data)

    def flush(self) -> None:
        self._f.flush()

    def fileno(self) -> int:
        return self._f.fileno()

    def close(self) -> None:
        if not self._f.closed:
            _fdatasync(self._f)
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False


def _disable_direct(fd: int) -> None:
    """Drop O_DIRECT from an open fd (for the unaligned tail — reference
    disableDirectIO, internal/disk/directio_unix.go:40)."""
    import fcntl

    flags = fcntl.fcntl(fd, fcntl.F_GETFL)
    fcntl.fcntl(fd, fcntl.F_SETFL, flags & ~os.O_DIRECT)


class _DirectWriter:
    """Sequential O_DIRECT writer: data accumulates in a page-aligned
    staging buffer and is written in aligned 1 MiB bursts; the unaligned
    tail is written after dropping O_DIRECT at close (the reference's
    odirectWriter tail handling, cmd/xl-storage.go:1667).  On the first
    EINVAL (filesystem without O_DIRECT) the writer downgrades itself
    and reports it via `storage`, so the drive stops trying."""

    #: bitrot write_frames hint: per-row write() calls land in the
    #: aligned staging buffer anyway, so row-wise feeding skips the
    #: interleaved-frame materialization pass (cheap calls, same bytes)
    prefers_row_writes = True

    def __init__(self, path: str, storage: "LocalStorage"):
        self._storage = storage
        self._fd = os.open(path,
                           os.O_WRONLY | os.O_CREAT | os.O_TRUNC
                           | os.O_DIRECT, 0o644)
        self._buf = _staging_acquire()
        self._view = memoryview(self._buf)
        # numpy view for staging copies: large contiguous numpy copies
        # release the GIL (memoryview slice assignment does not), so a
        # 12-drive shard fan-out's staging memcpys overlap instead of
        # convoying the interpreter
        self._np = _np.frombuffer(self._buf, dtype=_np.uint8)
        self._fill = 0
        self._direct = True
        self._closed = False

    def write(self, data) -> int:
        src = _np.frombuffer(
            data if isinstance(data, (bytes, bytearray)) else
            memoryview(data).cast("B"), dtype=_np.uint8)
        total = src.size
        pos = 0
        while pos < total:
            n = min(_DIO_BUF - self._fill, total - pos)
            self._np[self._fill:self._fill + n] = src[pos:pos + n]
            self._fill += n
            pos += n
            if self._fill == _DIO_BUF:
                self._flush_aligned(_DIO_BUF)
        return total

    def _flush_aligned(self, nbytes: int) -> None:
        done = 0
        gate = _device_write_gate
        held = False
        if gate is not None:
            # bounded wait: the gate is a throughput optimization, not a
            # correctness fence — a slot pinned by a write to a hung
            # drive (os.write to D-state storage ignores deadlines) must
            # not stall healthy drives' flushes, or one dead device
            # blocks write quorum across the whole node
            held = gate.acquire(timeout=_GATE_WAIT_S)
        try:
            while done < nbytes:
                try:
                    done += os.write(self._fd, self._view[done:nbytes])
                except OSError as e:
                    import errno

                    if self._direct and e.errno == errno.EINVAL:
                        # filesystem rejected direct IO: downgrade this
                        # fd and remember per drive
                        _disable_direct(self._fd)
                        self._direct = False
                        self._storage._odirect = False
                        continue
                    raise
        finally:
            if held:
                gate.release()
        self._fill -= nbytes
        if self._fill:
            self._view[:self._fill] = self._view[nbytes:nbytes + self._fill]

    def flush(self) -> None:
        """No-op: alignment forbids partial flushes; close() drains."""

    # no fileno(): raw-fd fast paths (the bitrot writev gather) would
    # bypass the aligned staging buffer and EINVAL on the O_DIRECT fd —
    # their AttributeError fallback routes bytes through write() instead

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            aligned = (self._fill // _ALIGN) * _ALIGN
            if aligned:
                self._flush_aligned(aligned)
            if self._fill:
                if self._direct:
                    _disable_direct(self._fd)
                done = 0
                while done < self._fill:
                    done += os.write(self._fd, self._view[done:self._fill])
                self._fill = 0
            if FSYNC_ENABLED:
                if hasattr(os, "fdatasync"):
                    os.fdatasync(self._fd)
                else:  # pragma: no cover - non-linux
                    os.fsync(self._fd)
        finally:
            os.close(self._fd)
            self._np = None  # drop the buffer export before pooling
            self._view.release()
            _staging_release(self._buf)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False


class _DirectReader:
    """Sequential O_DIRECT reader from offset 0: refills a page-aligned
    1 MiB buffer with os.readv and serves arbitrary read() sizes from it
    (reference odirectReader, cmd/xl-storage.go:1558).  The final short
    read at an unaligned EOF is legal under O_DIRECT."""

    def __init__(self, path: str):
        import stat as stat_mod

        self._fd = os.open(path, os.O_RDONLY | os.O_DIRECT)
        if stat_mod.S_ISDIR(os.fstat(self._fd).st_mode):
            os.close(self._fd)
            raise IsADirectoryError(path)
        self._buf = _staging_acquire()
        self._have = 0     # valid bytes in buffer
        self._pos = 0      # consumed bytes in buffer
        self._buf_off = 0  # file offset of the buffer's first byte
        self._next_off = 0  # file offset of the next readv
        self._eof = False
        self._final = False
        self._closed = False

    def _refill(self) -> None:
        if self._eof:
            return
        if self._final:
            # a short O_DIRECT read only happens at EOF; another readv
            # would run from an unaligned offset
            self._eof = True
            return
        self._pos = 0
        self._buf_off = self._next_off
        self._have = os.readv(self._fd, [self._buf])
        self._next_off += self._have
        if self._have == 0:
            self._eof = True
        elif self._have < _DIO_BUF:
            self._final = True

    def seek(self, target: int, whence: int = 0) -> int:
        """Absolute seeks only (the shard read path positions to frame
        boundaries); re-reads from the preceding aligned offset so the
        fd's O_DIRECT alignment is preserved."""
        if whence != 0:
            raise OSError("O_DIRECT reader supports absolute seek only")
        if self._buf_off <= target <= self._buf_off + self._have:
            self._pos = target - self._buf_off
            self._eof = False
            return target
        aligned = (target // _ALIGN) * _ALIGN
        os.lseek(self._fd, aligned, os.SEEK_SET)
        self._next_off = aligned
        self._have = self._pos = 0
        self._buf_off = aligned
        self._eof = self._final = False
        skip = target - aligned
        if skip:
            self._refill()
            self._pos = min(skip, self._have)
        return target

    def tell(self) -> int:
        return self._buf_off + self._pos

    def read(self, n: int = -1) -> bytes:
        out = []
        want = n if n >= 0 else None
        while want is None or want > 0:
            if self._pos == self._have:
                self._refill()
                if self._eof:
                    break
            take = self._have - self._pos if want is None \
                else min(want, self._have - self._pos)
            out.append(self._buf[self._pos:self._pos + take])
            self._pos += take
            if want is not None:
                want -= take
        return b"".join(out)

    def readinto(self, b) -> int:
        """Fill a caller-provided buffer straight from the aligned
        staging buffer — the bitrot frame reader preallocates its frame
        group and pulls it here in ONE copy (read() would slice + join,
        an extra pass per group)."""
        mv = memoryview(b)
        if mv.format != "B":
            mv = mv.cast("B")
        src = memoryview(self._buf)
        got = 0
        while got < len(mv):
            if self._pos == self._have:
                self._refill()
                if self._eof:
                    break
            take = min(len(mv) - got, self._have - self._pos)
            mv[got:got + take] = src[self._pos:self._pos + take]
            self._pos += take
            got += take
        return got

    def read_frames(self, offset: int, hashes: _np.ndarray,
                    out: _np.ndarray) -> tuple[int, int]:
        """The bitrot frames from file `offset` read, placed and checked
        in one native call (`ops/host.py` `read_frames`) through this
        reader's aligned buffer, which keeps the call's last read (and
        the descriptor's offset where that read ended, where `_refill`
        goes on): the stream then stands where the group ends, or, where
        the call failed, wherever the buffer says.  Returns (status,
        hash_ns).  OSError where a read failed (EINVAL: the file system
        refuses O_DIRECT)."""
        try:
            status, _, hash_ns, self._buf_off, self._have = \
                host.read_frames(self._fd, offset, hashes, out,
                                 _np.frombuffer(self._buf, dtype=_np.uint8),
                                 _ALIGN)
        except OSError:
            # nothing in the buffer holds; go on from the failed read
            self._buf_off = self._next_off = os.lseek(self._fd, 0,
                                                      os.SEEK_CUR)
            self._have = self._pos = 0
            self._eof = self._final = False
            raise
        end = offset + out.shape[0] * (32 + out.shape[1])
        self._pos = min(max(end - self._buf_off, 0), self._have)
        self._next_off = self._buf_off + self._have
        # a read that ended off the alignment ended at the file's end
        self._final = self._have % _ALIGN != 0
        self._eof = False
        return status, hash_ns

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            os.close(self._fd)
            _staging_release(self._buf)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False


class _ShardFile(io.BufferedReader):
    """A shard file opened buffered (a ranged read, or a drive whose file
    system refuses O_DIRECT): a plain buffered reader that also reads a
    group of bitrot frames in one native call."""

    def read_frames(self, offset: int, hashes: _np.ndarray,
                    out: _np.ndarray) -> tuple[int, int]:
        """The bitrot frames from file `offset` read straight into their
        rows, placed and checked in one native call (`ops/host.py`
        `read_frames`); the stream then stands where the group ends.
        Returns (status, hash_ns)."""
        status, _, hash_ns, _, _ = host.read_frames(
            self.fileno(), offset, hashes, out)
        if status == host.FRAMES_OK:
            self.seek(offset + out.shape[0] * (32 + out.shape[1]))
        return status, hash_ns


def _stored_algo(fi: FileInfo) -> str:
    """Bitrot algorithm a version's shards were written with."""
    from minio_tpu.erasure import bitrot

    e = fi.erasure
    if e is not None and e.checksums:
        a = e.checksums[0].algorithm
        if a in bitrot.ALGORITHMS:
            return a
    return bitrot.DEFAULT_ALGO


def _clean(path: str) -> str:
    path = path.strip("/")
    if ".." in path.split("/"):
        raise errors.FileAccessDenied(path)
    return path


class LocalStorage(StorageAPI):
    def __init__(self, root: str, endpoint: str = "", quota: int | None = None):
        self.root = os.path.abspath(root)
        self._endpoint = endpoint or self.root
        self._disk_id = ""
        # staged files written unsynced (append_file) pending a commit sync
        self._unsynced: set[str] = set()
        self._lock = threading.Lock()
        # optional per-drive capacity cap: disk_info reports
        # total=quota / free=quota-used so pool placement (weighted by
        # available space, cmd/erasure-server-pool.go:222) works on
        # shared filesystems where statvfs can't tell drives apart
        if quota is None:
            quota = int(os.environ.get("MINIO_TPU_DRIVE_QUOTA", "0") or 0)
        self._quota = max(quota, 0)
        self._du_cache: tuple[float, int] = (0.0, 0)
        self._odirect = ODIRECT_ENABLED
        self._reaper: threading.Thread | None = None
        os.makedirs(self.root, exist_ok=True)
        os.makedirs(os.path.join(self.root, SYSTEM_VOL, TMP_DIR), exist_ok=True)
        # xl.meta commit journal (ISSUE 17): replay a leftover journal
        # unconditionally — a crashed journal-on process followed by a
        # journal-off one must still recover its acked commits and must
        # not leave a stale journal behind to clobber newer writes
        self._journal: metajournal.MetaJournal | None = None
        self._index_stale = False  # journal-off invalidation, once
        if metajournal.JOURNAL_ENABLED:
            self._journal = metajournal.MetaJournal(
                self.root, self._apply_xl_raw, self._apply_unlink_raw,
                list_names=self._walk_names, fsync=FSYNC_ENABLED)
            self._meta_index = self._journal.index
        else:
            metajournal.startup_replay(
                self.root, self._apply_xl_raw, self._apply_unlink_raw,
                fsync=FSYNC_ENABLED)
            # read-only index view: still serves listings if this
            # process never mutates metadata (first mutation drops the
            # VALID marker)
            self._meta_index = metajournal.MetaIndex(
                self.root, fsync=FSYNC_ENABLED)
        # reap trash a previous process left behind (crash mid-reap)
        trash = os.path.join(self.root, SYSTEM_VOL, TRASH_DIR)
        if os.path.isdir(trash) and os.listdir(trash):
            self._kick_reaper()

    # -- trash (non-blocking deletes) ---------------------------------------
    def _move_to_trash(self, path: str) -> bool:
        """Rename a file/dir into the trash for background reaping — the
        request path pays one rename, not an rmtree (reference
        moveToTrash, cmd/xl-storage.go:950).  False -> caller deletes
        inline."""
        trash = self._sys_path(TRASH_DIR)
        try:
            os.makedirs(trash, exist_ok=True)
            os.replace(path, os.path.join(trash, uuid.uuid4().hex))
        except OSError:
            return False
        self._kick_reaper()
        return True

    def _kick_reaper(self) -> None:
        """Reaper thread runs until the trash is empty, then exits (no
        idle thread per drive; the next trashed item respawns it)."""
        with self._lock:
            if self._reaper is not None and self._reaper.is_alive():
                return
            t = service_thread(self._reap_loop, start=False,
                               name=f"trash-reaper:{self.root}")
            self._reaper = t
        t.start()

    def _reap_loop(self) -> None:
        trash = self._sys_path(TRASH_DIR)
        while True:
            try:
                entries = os.listdir(trash)
            except OSError:
                entries = []
            if not entries:
                # re-check under the lock so a rename that raced the
                # empty listing still gets a live reaper
                with self._lock:
                    try:
                        if not os.listdir(trash):
                            self._reaper = None
                            return
                    except OSError:
                        self._reaper = None
                        return
                continue
            for name in entries:
                p = os.path.join(trash, name)
                try:
                    if os.path.isdir(p):
                        shutil.rmtree(p, ignore_errors=True)
                    else:
                        os.remove(p)
                except OSError:
                    pass

    def _discard_dir(self, path: str) -> None:
        """Reclaim a data dir without blocking the request path."""
        if os.path.isdir(path):
            if not self._move_to_trash(path):
                shutil.rmtree(path, ignore_errors=True)

    def wait_trash_empty(self, timeout: float = 10.0) -> bool:
        """Test/maintenance hook: block until the reaper drains."""
        deadline = time.time() + timeout
        trash = self._sys_path(TRASH_DIR)
        while time.time() < deadline:
            try:
                if not os.listdir(trash):
                    return True
            except OSError:
                return True
            time.sleep(0.02)
        return False

    # -- identity -----------------------------------------------------------
    def disk_id(self) -> str:
        return self._disk_id

    def set_disk_id(self, disk_id: str) -> None:
        self._disk_id = disk_id

    def is_online(self) -> bool:
        return os.path.isdir(self.root)

    def endpoint(self) -> str:
        return self._endpoint

    def _used_bytes(self) -> int:
        """Bytes stored under this drive root (0.5 s TTL cache: the pool
        placement probe hits this on every PUT)."""
        now = time.monotonic()
        ts, used = self._du_cache
        if now - ts < 0.5:
            return used
        used = 0
        for dirpath, _, files in os.walk(self.root):
            for f in files:
                try:
                    used += os.lstat(os.path.join(dirpath, f)).st_size
                except OSError:
                    pass
        self._du_cache = (now, used)
        return used

    def invalidate_usage_cache(self) -> None:
        """Force the next disk_info() to re-measure (rebalance rounds
        steer by used bytes and must not see the 0.5 s-stale value)."""
        self._du_cache = (0.0, 0)

    def disk_info(self) -> DiskInfo:
        st = shutil.disk_usage(self.root)
        total, free, used = st.total, st.free, st.used
        if self._quota:
            du = self._used_bytes()
            total = self._quota
            used = min(du, self._quota)
            free = min(max(self._quota - du, 0), st.free)
        return DiskInfo(
            total=total, free=free, used=used,
            healing=os.path.exists(self._sys_path(HEALING_FILE)),
            endpoint=self._endpoint, mount_path=self.root, id=self._disk_id,
        )

    def _sys_path(self, *parts: str) -> str:
        return os.path.join(self.root, SYSTEM_VOL, *parts)

    # -- path helpers -------------------------------------------------------
    def _vol_path(self, volume: str) -> str:
        if not volume:
            raise errors.InvalidArgument("empty volume")
        return os.path.join(self.root, volume)

    def _file_path(self, volume: str, path: str) -> str:
        return os.path.join(self._vol_path(volume), _clean(path))

    # -- volumes ------------------------------------------------------------
    def make_volume(self, volume: str) -> None:
        p = self._vol_path(volume)
        if os.path.isdir(p):
            raise errors.VolumeExists(volume)
        os.makedirs(p, exist_ok=True)

    def list_volumes(self) -> list[VolInfo]:
        out = []
        for name in sorted(os.listdir(self.root)):
            p = os.path.join(self.root, name)
            if os.path.isdir(p) and name != SYSTEM_VOL:
                out.append(VolInfo(name=name, created=os.stat(p).st_ctime))
        return out

    def stat_volume(self, volume: str) -> VolInfo:
        p = self._vol_path(volume)
        if not os.path.isdir(p):
            raise errors.VolumeNotFound(volume)
        return VolInfo(name=volume, created=os.stat(p).st_ctime)

    def delete_volume(self, volume: str, force: bool = False) -> None:
        p = self._vol_path(volume)
        if not os.path.isdir(p):
            raise errors.VolumeNotFound(volume)
        if force and volume != SYSTEM_VOL and self._journal is not None:
            # durable tombstone BEFORE the dir goes: a crash mid-delete
            # replays the tombstone instead of resurrecting journaled
            # objects of the dead bucket (the tombstone also drops the
            # bucket's index inside the committer).  Only the force
            # path journals — a failed non-force rmdir must not leave a
            # tombstone that would rmtree a live bucket on replay.
            try:
                self._journal.bucket_delete(volume)
            except metajournal.JournalDead:
                self._mark_index_stale()
                self._meta_index.drop_bucket(volume)
        elif volume != SYSTEM_VOL:
            # the bucket's index dies with it (segments would otherwise
            # resurrect its names if the bucket is recreated)
            self._meta_index.drop_bucket(volume)
        if force:
            if not self._move_to_trash(p):
                shutil.rmtree(p, ignore_errors=True)
            return
        try:
            os.rmdir(p)
        except OSError:
            raise errors.BucketNotEmpty(volume)

    # -- flat files ---------------------------------------------------------
    def read_all(self, volume: str, path: str) -> bytes:
        p = self._file_path(volume, path)
        try:
            with open(p, "rb") as f:
                return f.read()
        except FileNotFoundError:
            raise errors.FileNotFound(f"{volume}/{path}")
        except IsADirectoryError:
            raise errors.FileNotFound(f"{volume}/{path}")

    def write_all(self, volume: str, path: str, data: bytes) -> None:
        p = self._file_path(volume, path)
        target = p + f".tmp.{uuid.uuid4().hex[:8]}"
        for attempt in (0, 1):
            try:
                # try-first: parent usually exists; makedirs after a miss
                f = open(target, "wb")
                break
            except FileNotFoundError:
                if attempt:
                    raise
                self._ensure_parent(p)
        with f:
            f.write(data)
            _fdatasync(f)
        os.replace(target, p)
        _fsync_dir(os.path.dirname(p))

    def delete(self, volume: str, path: str, recursive: bool = False) -> None:
        p = self._file_path(volume, path)
        try:
            if os.path.isdir(p):
                if recursive:
                    try:
                        # empty dir (drained multipart staging, cleaned
                        # tmp): plain rmdir — a trash rename would spin
                        # up a reaper thread for nothing
                        os.rmdir(p)
                    except OSError:
                        # one rename; the reaper does the rmtree off the
                        # request path (moveToTrash, cmd/xl-storage.go:950)
                        if not self._move_to_trash(p):
                            shutil.rmtree(p)
                else:
                    os.rmdir(p)
            else:
                os.remove(p)
        except FileNotFoundError:
            raise errors.FileNotFound(f"{volume}/{path}")
        # prune now-empty parents up to the volume root.  Structural
        # system dirs (tmp staging, trash) are never pruned: concurrent
        # writers makedirs+create under them, and a prune racing that
        # walk turns a parallel multipart commit into FileNotFoundError
        parent = os.path.dirname(p)
        vol_root = self._vol_path(volume)
        keep = {vol_root}
        if volume == SYSTEM_VOL:
            keep.add(os.path.join(vol_root, TMP_DIR))
            keep.add(os.path.join(vol_root, TRASH_DIR))
        while parent not in keep and parent.startswith(vol_root):
            try:
                os.rmdir(parent)
            except OSError:
                break
            parent = os.path.dirname(parent)

    def rename_file(self, src_volume: str, src_path: str,
                    dst_volume: str, dst_path: str) -> None:
        src = self._file_path(src_volume, src_path)
        dst = self._file_path(dst_volume, dst_path)
        try:
            # try-first: one syscall on the hot path; the pre-stat +
            # makedirs walk only runs after a miss
            os.replace(src, dst)
        except FileNotFoundError:
            if not os.path.exists(src):
                raise errors.FileNotFound(f"{src_volume}/{src_path}")
            self._ensure_parent(dst)
            try:
                os.replace(src, dst)
            except FileNotFoundError:
                raise errors.FileNotFound(f"{src_volume}/{src_path}")
        _fsync_dir(os.path.dirname(dst))

    # -- shard files --------------------------------------------------------
    def create_file(self, volume: str, path: str, size: int,
                    reader: BinaryIO) -> None:
        with self.open_file_writer(volume, path) as w:
            remaining = size if size >= 0 else None
            while True:
                chunk = reader.read(1 << 20)
                if not chunk:
                    break
                w.write(chunk)
                if remaining is not None:
                    remaining -= len(chunk)
                    if remaining <= 0:
                        break

    @staticmethod
    def _ensure_parent(p: str) -> None:
        """makedirs that tolerates a concurrent empty-parent prune: a
        delete() on a sibling can rmdir an intermediate dir between our
        walk and our mkdir — re-walk instead of failing the writer."""
        for attempt in range(3):
            try:
                os.makedirs(os.path.dirname(p), exist_ok=True)
                return
            except FileNotFoundError:
                if attempt == 2:
                    raise

    def open_file_writer(self, volume: str, path: str,
                         size_hint: int = -1) -> BinaryIO:
        """`size_hint` >= 0 is the expected file size: small files skip
        O_DIRECT (they would ride the unaligned-tail fallback anyway and
        the buffered writer keeps the writev gather fast path)."""
        p = self._file_path(volume, path)
        # try-first: the parent almost always exists (upload dirs, tmp)
        # and fs metadata ops are the multipart hot path — only walk
        # makedirs after a miss
        for attempt in (0, 1):
            try:
                if self._odirect and not 0 <= size_hint < ODIRECT_MIN_BYTES:
                    try:
                        return _DirectWriter(p, self)
                    except FileNotFoundError:
                        raise
                    except OSError:
                        self._odirect = False  # fs rejected O_DIRECT
                return _SyncedWriter(open(p, "wb"))
            except FileNotFoundError:
                if attempt:
                    raise
                self._ensure_parent(p)

    def append_file(self, volume: str, path: str, data: bytes,
                    append: bool = True) -> None:
        """Append (or truncate-then-write) a chunk; the remote shard-stream
        protocol's write primitive (reference AppendFile,
        cmd/xl-storage.go).  Not synced per-chunk: the path is recorded so
        rename_data fdatasyncs it once at commit."""
        p = self._file_path(volume, path)
        self._ensure_parent(p)
        with open(p, "ab" if append else "wb") as f:
            f.write(data)
        self._unsynced.add(p)

    def read_file_stream(self, volume: str, path: str, offset: int,
                         length: int) -> BinaryIO:
        p = self._file_path(volume, path)
        if offset == 0 and self._odirect:
            # whole-file sequential reads ride O_DIRECT (reference
            # odirectReader for offset 0, cmd/xl-storage.go:1558);
            # ranged reads stay buffered — their offsets are unaligned
            try:
                f = _DirectReader(p)
            except FileNotFoundError:
                raise errors.FileNotFound(f"{volume}/{path}")
            except IsADirectoryError:
                raise errors.FileNotFound(f"{volume}/{path}")
            except OSError:
                self._odirect = False
            else:
                if length >= 0:
                    size = os.fstat(f._fd).st_size
                    if size < length:
                        f.close()
                        raise errors.FileCorrupt(
                            f"{volume}/{path}: size {size} < {length}")
                return f
        try:
            f = _ShardFile(io.FileIO(p, "rb"))
        except FileNotFoundError:
            raise errors.FileNotFound(f"{volume}/{path}")
        except IsADirectoryError:
            raise errors.FileNotFound(f"{volume}/{path}")
        if length >= 0:
            st = os.fstat(f.fileno())
            if st.st_size < offset + length:
                f.close()
                raise errors.FileCorrupt(
                    f"{volume}/{path}: size {st.st_size} < {offset + length}"
                )
        f.seek(offset)
        return f

    def read_file(self, volume: str, path: str, offset: int,
                  buf_size: int) -> bytes:
        with self.read_file_stream(volume, path, offset, buf_size) as f:
            return f.read(buf_size)

    # -- object metadata ----------------------------------------------------
    def _meta_path(self, volume: str, path: str) -> str:
        return os.path.join(self._file_path(volume, path), XL_META_FILE)

    def _read_meta(self, volume: str, path: str
                   ) -> tuple[bytes | memoryview, int | None]:
        """The xl.meta document of volume/path: (a view of it in this
        thread's buffer, valid until the thread's next read; the native
        call's own nanoseconds), one native call with the interpreter
        lock let go once (`ops/host.py` `read_file`); or (bytes, None)
        read in Python where the process has no native library.  Either
        way ENOENT and ENOTDIR are FileNotFound, a directory is
        IsADirectoryError and any other errno an OSError with it."""
        p = self._meta_path(volume, path)
        try:
            if host.available():
                return host.read_file(p)
            with open(p, "rb") as f:
                return f.read(), None
        except (FileNotFoundError, NotADirectoryError):
            raise errors.FileNotFound(f"{volume}/{path}")

    def read_xl(self, volume: str, path: str) -> bytes:
        return bytes(self._read_meta(volume, path)[0])

    def read_version(self, volume: str, path: str, version_id: str = "",
                     read_data: bool = False) -> FileInfo:
        # parsed straight from the thread's buffer: msgpack copies every
        # value it returns
        raw, ns = self._read_meta(volume, path)
        if ns is not None:
            stagestats.add("meta_native", ns * 1e-9, len(raw))
        return file_info_from_raw(raw, volume, path, version_id, read_data)

    # -- journal plumbing (ISSUE 17) ----------------------------------------
    def _apply_xl_raw(self, bucket: str, path: str, data: bytes) -> None:
        """Buffered xl.meta apply (tmp+rename, NO sync): durability is
        the journal's group fsync; rotation/replay sync the file.

        Hot-path economies (the committer is the ONLY caller, plus the
        single-threaded startup replay, so one reusable tmp name under
        the sys dir is race-free): no per-write uuid tmp, and makedirs
        only on the ENOENT fallback — the target dir almost always
        exists.  os.replace is atomic across dirs on the same fs, the
        same .minio.sys/tmp -> bucket rename MinIO itself does."""
        p = self._meta_path(bucket, path)
        tmp = os.path.join(self.root, SYSTEM_VOL, "xl-apply.tmp")
        flags = os.O_WRONLY | os.O_CREAT | os.O_TRUNC
        try:
            fd = os.open(tmp, flags, 0o644)
        except FileNotFoundError:
            os.makedirs(os.path.dirname(tmp), exist_ok=True)
            fd = os.open(tmp, flags, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
        try:
            os.replace(tmp, p)
        except FileNotFoundError:
            os.makedirs(os.path.dirname(p), exist_ok=True)
            os.replace(tmp, p)

    def _apply_unlink_raw(self, bucket: str, path: str) -> None:
        """Idempotent object-dir removal for journal apply/replay."""
        try:
            self.delete(bucket, path, recursive=True)
        except errors.FileNotFound:
            pass  # replayed unlink already applied

    def _walk_names(self, bucket: str):
        """Name stream for background index seeding."""
        return self.walk_dir(bucket)

    def _mark_index_stale(self) -> None:
        """Journal-off metadata mutation: the on-disk index can no
        longer trust itself (one unlink, then a cached flag)."""
        if not self._index_stale:
            self._index_stale = True
            self._meta_index.invalidate()

    def index_names(self, bucket: str, prefix: str = "",
                    marker: str = "") -> list[str] | None:
        """Sorted live object names from the metadata index, or None
        when the index can't serve this bucket (caller walks)."""
        if bucket == SYSTEM_VOL:
            return None
        try:
            return self._meta_index.names(bucket, prefix, marker)
        except Exception:
            return None

    def index_available(self, bucket: str) -> bool:
        return bucket != SYSTEM_VOL and self._meta_index.is_valid() \
            and self._meta_index.bucket_seeded(bucket)

    def _write_xl(self, volume: str, path: str, xl: XLMeta) -> None:
        if self._journal is not None and volume != SYSTEM_VOL:
            try:
                # blocks until the group fsync lands AND the buffered
                # xl.meta rename is visible (read-your-writes)
                self._journal.commit(volume, _clean(path), xl.dumps())
                return
            except metajournal.JournalDead:
                pass  # committer gone: fall through to the synced path
        if volume != SYSTEM_VOL:
            self._mark_index_stale()
        p = self._meta_path(volume, path)
        tmp = p + f".tmp.{uuid.uuid4().hex[:8]}"
        flags = os.O_WRONLY | os.O_CREAT | os.O_TRUNC
        try:
            fd = os.open(tmp, flags, 0o644)
        except FileNotFoundError:
            os.makedirs(os.path.dirname(p), exist_ok=True)
            fd = os.open(tmp, flags, 0o644)
        try:
            os.write(fd, xl.dumps())
            if FSYNC_ENABLED:
                if hasattr(os, "fdatasync"):
                    os.fdatasync(fd)
                else:  # pragma: no cover - macOS fallback
                    os.fsync(fd)
        finally:
            os.close(fd)
        os.replace(tmp, p)
        _fsync_dir(os.path.dirname(p))

    def write_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        try:
            xl = XLMeta.loads(self.read_xl(volume, path))
        except errors.FileNotFound:
            xl = XLMeta()
        xl.add_version(fi)
        self._write_xl(volume, path, xl)

    def update_metadata(self, volume: str, path: str, fi: FileInfo) -> None:
        xl = XLMeta.loads(self.read_xl(volume, path))
        if xl.find_version(fi.version_id) is None:
            raise errors.FileVersionNotFound(f"{volume}/{path}@{fi.version_id}")
        xl.add_version(fi)
        self._write_xl(volume, path, xl)

    def delete_version(self, volume: str, path: str, fi: FileInfo,
                       force_del_marker: bool = False) -> None:
        if fi.version_id == NULL_VERSION_ID:
            # API sentinel for the internal empty-id null version
            import dataclasses

            fi = dataclasses.replace(fi, version_id="")
        try:
            xl = XLMeta.loads(self.read_xl(volume, path))
        except errors.FileNotFound:
            if fi.deleted and force_del_marker:
                self.write_metadata(volume, path, fi)
                return
            raise
        if fi.deleted and not fi.version_id:
            # writing a delete marker on top; under suspended versioning the
            # marker has the null id and permanently replaces any existing
            # null version (AWS suspended-bucket semantics) — reclaim its data
            replaced = xl.add_version(fi)
            if replaced is not None and replaced.get("dd"):
                self._discard_dir(
                    os.path.join(self._file_path(volume, path),
                                 replaced["dd"]))
            self._write_xl(volume, path, xl)
            return
        v = xl.delete_version(fi.version_id)
        if v is None and fi.version_id:
            raise errors.FileVersionNotFound(f"{volume}/{path}@{fi.version_id}")
        if v is not None:
            data_dir = v.get("dd", "")
            if data_dir:
                dpath = os.path.join(self._file_path(volume, path), data_dir)
                self._discard_dir(dpath)
        if xl.versions:
            self._write_xl(volume, path, xl)
        elif self._journal is not None and volume != SYSTEM_VOL:
            # journaled unlink: durable once the group fsync lands,
            # tombstoned in the index, replayed idempotently on crash
            try:
                self._journal.unlink(volume, _clean(path))
            except metajournal.JournalDead:
                self._mark_index_stale()
                self.delete(volume, path, recursive=True)
        else:
            if volume != SYSTEM_VOL:
                self._mark_index_stale()
            self.delete(volume, path, recursive=True)

    def free_version_data(self, volume: str, path: str, version_id: str,
                          meta_updates: dict) -> None:
        """Drop a version's local data (parts dir + inline bytes) while
        keeping its xl.meta entry, merging `meta_updates` into the
        version's metadata — the tiering stub left behind after a
        transition (reference DeleteVersion w/ transition free-versions,
        cmd/xl-storage-free-version.go)."""
        if version_id == NULL_VERSION_ID:
            version_id = ""
        xl = XLMeta.loads(self.read_xl(volume, path))
        v = xl.find_version(version_id or "")
        if v is None or (version_id and v.get("v", "") != version_id):
            raise errors.FileVersionNotFound(f"{volume}/{path}@{version_id}")
        dd = v.get("dd", "")
        if dd:
            self._discard_dir(
                os.path.join(self._file_path(volume, path), dd))
        v["dd"] = ""
        v.pop("data", None)
        meta = v.setdefault("meta", {})
        meta.update(meta_updates)
        self._write_xl(volume, path, xl)

    def delete_versions(self, volume: str,
                         items: list) -> list:
        """Batched version deletes: items = [(path, FileInfo,
        force_del_marker)], one result slot per item (None = ok).
        Reference DeleteVersions (cmd/storage-interface.go,
        cmd/xl-storage.go DeleteVersions) — bulk deletes hit each drive
        once instead of once per object."""
        out = []
        for path, fi, force in items:
            try:
                self.delete_version(volume, path, fi,
                                    force_del_marker=force)
                out.append(None)
            except Exception as e:
                out.append(e)
        return out

    def rename_data(self, src_volume: str, src_path: str, fi: FileInfo,
                    dst_volume: str, dst_path: str) -> None:
        """Move staged part files into place and commit xl.meta atomically."""
        dst_obj_dir = self._file_path(dst_volume, dst_path)
        os.makedirs(dst_obj_dir, exist_ok=True)
        if fi.data is None and fi.data_dir:
            src_dir = self._file_path(src_volume, src_path)
            if not os.path.isdir(src_dir):
                raise errors.FileNotFound(f"{src_volume}/{src_path}")
            if FSYNC_ENABLED:
                # shards written via append_file (remote streams) were not
                # synced per-chunk; make those durable before the rename
                # publishes the version.  Locally-streamed shards were
                # already fdatasync'd by _SyncedWriter.close — skip them.
                for name in os.listdir(src_dir):
                    fp = os.path.join(src_dir, name)
                    if fp in self._unsynced and os.path.isfile(fp):
                        with open(fp, "rb+") as f:
                            _fdatasync(f)
                        self._unsynced.discard(fp)
            dst_data_dir = os.path.join(dst_obj_dir, fi.data_dir)
            if os.path.isdir(dst_data_dir):
                self._discard_dir(dst_data_dir)
            if os.path.isdir(dst_data_dir):
                shutil.rmtree(dst_data_dir)  # trash move failed
            os.replace(src_dir, dst_data_dir)
            _fsync_dir(dst_obj_dir)
        try:
            xl = XLMeta.loads(self.read_xl(dst_volume, dst_path))
        except errors.FileNotFound:
            xl = XLMeta()
        replaced = xl.add_version(fi)
        self._write_xl(dst_volume, dst_path, xl)
        if replaced is not None and replaced.get("dd") \
                and replaced["dd"] != fi.data_dir:
            # overwrite of an unversioned / null version: reclaim the old
            # data dir (reference deletes old dataDir in RenameData,
            # cmd/xl-storage.go:1964)
            self._discard_dir(os.path.join(dst_obj_dir, replaced["dd"]))

    # -- listing ------------------------------------------------------------
    def list_dir(self, volume: str, path: str, count: int = -1) -> list[str]:
        p = self._file_path(volume, path) if path else self._vol_path(volume)
        try:
            entries = sorted(os.listdir(p))
        except FileNotFoundError:
            raise errors.FileNotFound(f"{volume}/{path}")
        out = []
        for e in entries:
            if os.path.isdir(os.path.join(p, e)):
                out.append(e + "/")
            else:
                out.append(e)
            if 0 < count <= len(out):
                break
        return out

    def walk_dir(self, volume: str, base: str = "",
                 recursive: bool = True) -> Iterator[str]:
        vol_root = self._vol_path(volume)
        if not os.path.isdir(vol_root):
            raise errors.VolumeNotFound(volume)
        start = os.path.join(vol_root, _clean(base)) if base else vol_root

        def walk(d: str, prefix: str) -> Iterator[str]:
            try:
                entries = sorted(os.listdir(d))
            except (FileNotFoundError, NotADirectoryError):
                return
            if XL_META_FILE in entries:
                yield prefix.rstrip("/")
                return
            for e in entries:
                sub = os.path.join(d, e)
                if os.path.isdir(sub):
                    if recursive:
                        yield from walk(sub, prefix + e + "/")
                    else:
                        yield prefix + e + "/"

        yield from walk(start, _clean(base) + "/" if base else "")

    # -- verification -------------------------------------------------------
    def verify_file(self, volume: str, path: str, fi: FileInfo) -> None:
        from minio_tpu.erasure import bitrot

        if fi.erasure is None:
            raise errors.InvalidArgument("no erasure info")
        if fi.data is not None:
            return  # inline data verified via xl.meta integrity
        for part in fi.parts:
            shard_size = fi.erasure.shard_size
            shard_file_size = fi.erasure.shard_file_size(part.size)
            pp = os.path.join(self._file_path(volume, path), fi.data_dir,
                              f"part.{part.number}")
            try:
                f = open(pp, "rb")
            except FileNotFoundError:
                raise errors.FileNotFound(pp)
            with f:
                bitrot.bitrot_verify_stream(
                    f, os.fstat(f.fileno()).st_size, shard_file_size,
                    shard_size, algo=_stored_algo(fi),
                )

    def check_parts(self, volume: str, path: str, fi: FileInfo) -> None:
        if fi.data is not None:
            return
        from minio_tpu.erasure import bitrot

        for part in fi.parts:
            pp = os.path.join(self._file_path(volume, path), fi.data_dir,
                              f"part.{part.number}")
            try:
                st = os.stat(pp)
            except FileNotFoundError:
                raise errors.FileNotFound(pp)
            want = bitrot.bitrot_shard_file_size(
                fi.erasure.shard_file_size(part.size), fi.erasure.shard_size,
                _stored_algo(fi),
            )
            if st.st_size != want:
                raise errors.FileCorrupt(
                    f"{pp}: size {st.st_size} != expected {want}"
                )

    # -- misc ---------------------------------------------------------------
    def set_healing(self, healing: bool) -> None:
        p = self._sys_path(HEALING_FILE)
        if healing:
            with open(p, "w") as f:
                json.dump({"started": time.time()}, f)
        elif os.path.exists(p):
            os.remove(p)
