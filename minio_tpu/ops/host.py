"""ctypes bindings for the C++ host library (csrc/).

Provides:
- HostRSCodec: AVX2 PSHUFB GF(2^8) codec: what serves objects under one
  block, inline ones among them, and the `host` backend (the reference's
  equivalent is klauspost/reedsolomon's AVX2 assembly).
- hh256 / HH256: bit-exact HighwayHash-256 for bitrot checksums
  (reference: minio/highwayhash used at cmd/bitrot.go:55).
- sock_send: a response body's piece written to its socket without the
  interpreter lock (server/app.py _BodySender).
- read_frames: a group of a shard file's bitrot frames read, placed and
  checked without the interpreter lock (storage/local.py shard streams).
- read_file: a drive's xl.meta read whole without the interpreter lock
  (storage/local.py read_xl, read_version).

The library is built from the committed sources on the machine that
loads it, on first use, into a file named by a content hash of those
sources (`lib_path`), so a stale build or one made for another CPU is
never opened.  Pure-numpy fallbacks keep tests functional without a
compiler; `available()` says which codec a process has.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

from . import gf256

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "csrc")
_lock = threading.Lock()
_lib = None
_lib_tried = False

# HighwayHash-256 of the first 100 decimals of pi (reference cmd/bitrot.go:37).
MAGIC_HH256_KEY = bytes(
    [0x4B, 0xE7, 0x34, 0xFA, 0x8E, 0x23, 0x8A, 0xCD, 0x26, 0x3E, 0x83, 0xE6,
     0xBB, 0x96, 0x85, 0x52, 0x04, 0x0F, 0x93, 0x5D, 0xA3, 0x9F, 0x44, 0x14,
     0x97, 0xE0, 0x9D, 0x13, 0x22, 0xDE, 0x36, 0xA0]
)


def _lib_name() -> str:
    """File name of the host library for the sources as they are now: a
    content hash of csrc/*.cpp, *.h and the Makefile, so a changed
    source or flag is a different file."""
    digest = hashlib.sha256()
    for src in sorted(os.listdir(_CSRC)):
        if src.endswith((".cpp", ".h")) or src == "Makefile":
            digest.update(src.encode() + b"\0")
            with open(os.path.join(_CSRC, src), "rb") as f:
                digest.update(f.read())
    return f"libminio_tpu_host-{digest.hexdigest()[:16]}.so"


def lib_path() -> str | None:
    """Path of the host library for this checkout's sources, built here
    if it is not there yet; None when it cannot be built.

    The one rule every loader shares (this module, select/native.py,
    tests/conftest.py).  MINIO_TPU_NATIVE_LIB (sanitizer harness: an
    asan/ubsan/tsan build) overrides it."""
    override = os.environ.get("MINIO_TPU_NATIVE_LIB")
    if override:
        return override
    name = _lib_name()
    path = os.path.join(_CSRC, name)
    if not os.path.exists(path):
        try:
            proc = subprocess.run(
                ["make", "-C", _CSRC, "-s", f"LIB={name}"],
                capture_output=True, text=True, timeout=600)
        except (OSError, subprocess.TimeoutExpired) as e:
            print(f"minio-tpu: native library build did not run: {e}",
                  file=sys.stderr)
            return None
        if proc.returncode != 0:
            print(f"minio-tpu: native library build failed "
                  f"(make rc={proc.returncode}):\n{proc.stderr}",
                  file=sys.stderr)
            return None
    return path


def _load():
    # lint: allow(shared-state): per-process ctypes handle by design — each worker process must dlopen the codec itself
    global _lib, _lib_tried
    if _lib is not None:  # loaded: no lock on the calls' path
        return _lib
    with _lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        # lint: allow(blocking-under-lock): one-time native build under the dedicated dlopen lock — the lock exists to serialize exactly this init
        path = lib_path()
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        lib.gf256_matmul.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.gf256_matmul_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_size_t,
        ]
        lib.hh256_state_size.restype = ctypes.c_int
        lib.hh256_init.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.hh256_update.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t]
        lib.hh256_final.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.hh256_sum.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p
        ]
        lib.hh256_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.sock_send.restype = ctypes.c_size_t
        lib.sock_send.argtypes = [
            ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.frame_read.restype = ctypes.c_int
        lib.frame_read.argtypes = [
            ctypes.c_int, ctypes.c_int64, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_void_p,
        ]
        lib.file_read.restype = ctypes.c_int
        lib.file_read.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _as_c(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_char_p)


def sock_send(fd: int, data, stall_ms: int) -> int:
    """Write `data` (bytes-like, contiguous) to the non-blocking socket
    `fd` with the interpreter lock let go for the whole of it: `send`
    until it is out, `poll(POLLOUT)` where the socket is full.  Returns
    the bytes the socket took: fewer than `len(data)` where it took
    nothing for `stall_ms`.  Raises OSError (EPIPE, ECONNRESET, ...)
    where the connection failed before anything of `data` was sent or
    after a part of it; the part is lost to the caller, who gives the
    connection up.  Only where `available()`."""
    lib = _load()
    # read-only buffers too (bytes): no copy, and `arr` keeps `data`
    # alive while native code reads it
    arr = np.frombuffer(data, dtype=np.uint8)
    err = ctypes.c_int(0)
    sent = lib.sock_send(fd, arr.ctypes.data, arr.size, stall_ms,
                         ctypes.byref(err))
    if err.value:
        raise OSError(err.value, os.strerror(err.value))
    return sent


# read_frames statuses (csrc/frame_read.cpp)
FRAMES_OK, FRAMES_SHORT, FRAMES_MISMATCH = 0, 1, 2
_FRAMES_ERRNO = 3


def read_frames(fd: int, offset: int, hashes: np.ndarray, out: np.ndarray,
                bounce: np.ndarray | None = None, align: int = 4096
                ) -> tuple[int, int, int, int, int]:
    """Read the bitrot frames [32-byte hash | block] x n that start at
    file offset `offset` of `fd` with the interpreter lock let go for the
    whole of it: each hash into its row of `hashes` ((n, 32) uint8,
    contiguous), each block into its row of `out` ((n, L) uint8, rows
    contiguous, any row stride), then every block hashed with the bitrot
    key (HighwayHash-256) and compared with its stored hash.

    `bounce` None: a buffered descriptor, read straight into the rows,
    its offset untouched.  Otherwise an O_DIRECT one: read at multiples
    of `align` through `bounce` (aligned, a multiple of `align` long),
    copied to the rows, and its offset left where the last read ended.

    Returns (status, detail, hash_ns, bounce_off, bounce_have): status
    FRAMES_OK; FRAMES_SHORT where the file ends inside the group;
    FRAMES_MISMATCH with detail the first frame whose hash differs;
    hash_ns the time the hashing took; `bounce` holds the file's bytes
    [bounce_off, bounce_off + bounce_have) (the last read's).  Raises
    OSError where a read failed.  Only where `available()`."""
    n, length = out.shape
    if (out.dtype != np.uint8 or not out.flags.writeable or length < 1
            or out.strides[1] != 1 or (n > 1 and out.strides[0] < length)
            or hashes.dtype != np.uint8 or hashes.shape != (n, 32)
            or not hashes.flags.c_contiguous or not hashes.flags.writeable
            or (bounce is not None and (
                bounce.dtype != np.uint8 or not bounce.flags.c_contiguous
                or bounce.size < align or bounce.size % align))):
        raise ValueError("read_frames: rows, hashes or bounce unfit")
    info = np.zeros(4, dtype=np.int64)
    status = _load().frame_read(
        fd, offset, n, length, hashes.ctypes.data, out.ctypes.data,
        out.strides[0], None if bounce is None else bounce.ctypes.data,
        0 if bounce is None else bounce.size, align, MAGIC_HH256_KEY,
        info.ctypes.data)
    if status == _FRAMES_ERRNO:
        err = int(info[0])
        raise OSError(err, os.strerror(err))
    return status, int(info[0]), int(info[1]), int(info[2]), int(info[3])


# a thread's first buffer for read_file; a larger file grows it
FILE_BUF_BYTES = 64 * 1024
_file_tls = threading.local()


class _FileBuf:
    """One thread's read_file buffer and the call's info words, with the
    addresses the call takes (read once, not on every call)."""

    __slots__ = ("buf", "addr", "info", "info_addr")

    def __init__(self, size: int):
        self.buf = np.empty(size, dtype=np.uint8)
        self.addr = self.buf.ctypes.data
        self.info = np.zeros(2, dtype=np.int64)
        self.info_addr = self.info.ctypes.data


def read_file(path: str) -> tuple[memoryview, int]:
    """The file at `path` read whole in one native call with the
    interpreter lock let go for the whole of it (csrc/file_read.cpp:
    open, fstat, the reads up to the size, close), into a buffer the
    calling thread keeps; where fstat reports a file larger than the
    buffer, the buffer grows and the call is made once more.

    Returns (the file's bytes, a view into that buffer that holds until
    the thread's next read_file; the call's own nanoseconds, both calls'
    where it grew).  Raises OSError with the call's errno and `path`, of
    the subclass Python's own `open` would raise (IsADirectoryError for
    a directory).  Only where `available()`."""
    fb = getattr(_file_tls, "fb", None)
    if fb is None:
        fb = _file_tls.fb = _FileBuf(FILE_BUF_BYTES)
    call = _load().file_read
    name = os.fsencode(path)
    ns = 0
    while True:
        status = call(name, fb.addr, fb.buf.size, fb.info_addr)
        got, took = fb.info.tolist()
        ns += took
        if status:
            raise OSError(got, os.strerror(got), path)
        if got <= fb.buf.size:
            return memoryview(fb.buf)[:got], ns
        fb = _file_tls.fb = _FileBuf(1 << (got - 1).bit_length())


# Column tile for the pure-numpy GF(2^8) fallback matmul: one tile of
# every source shard plus the accumulator row stays L1/L2-resident
# across all output rows (cache-aware tiling + loop reordering per
# arxiv 2108.02692 — the untiled row-major sweep streamed the whole
# source through cache once PER OUTPUT ROW).
MATMUL_TILE = max(4096, int(os.environ.get(
    "MINIO_TPU_MATMUL_TILE", str(64 * 1024))))


class HostRSCodec:
    """CPU GF(2^8) codec with the TpuRSCodec surface (single block at a time
    it operates on (K, S); batches loop on host)."""

    def __init__(self, k: int, m: int):
        self.k, self.m = k, m
        self._lib = _load()

    def _matmul(self, mat: np.ndarray, src: np.ndarray) -> np.ndarray:
        rows = mat.shape[0]
        src = np.ascontiguousarray(src, dtype=np.uint8)
        n = src.shape[-1]
        out = np.empty((rows, n), dtype=np.uint8)
        if self._lib is not None:
            self._lib.gf256_matmul(
                _as_c(np.ascontiguousarray(mat)), rows, src.shape[0],
                _as_c(src), out.ctypes.data_as(ctypes.c_char_p), n,
            )
        else:
            # tile columns, then loop rows INSIDE the tile: every source
            # shard's tile is touched once per output row while still
            # cache-hot, instead of re-streaming all of src per row; the
            # inner ^= stays a vectorized MUL_TABLE gather
            for lo in range(0, n, MATMUL_TILE):
                hi = min(lo + MATMUL_TILE, n)
                tile = src[:, lo:hi]
                for r in range(rows):
                    acc = np.zeros(hi - lo, dtype=np.uint8)
                    for j in range(src.shape[0]):
                        c = int(mat[r, j])
                        if c:
                            acc ^= gf256.MUL_TABLE[c, tile[j]]
                    out[r, lo:hi] = acc
        return out

    def _matmul_batch(self, mat: np.ndarray, src: np.ndarray,
                      out: np.ndarray | None) -> np.ndarray:
        """(B, K, S) x mat -> (B, rows, S) in ONE C call (GIL released
        once for the whole batch; `out` writes parity in place, skipping
        a per-block copy).  Falls back to the per-block path without the
        native library."""
        b, k, s = src.shape
        rows = mat.shape[0]
        if out is None:
            out = np.empty((b, rows, s), dtype=np.uint8)
        if self._lib is not None and out.flags["C_CONTIGUOUS"]:
            src = np.ascontiguousarray(src, dtype=np.uint8)
            self._lib.gf256_matmul_batch(
                _as_c(np.ascontiguousarray(mat)), rows, k, _as_c(src),
                out.ctypes.data_as(ctypes.c_char_p), s, b,
            )
            return out
        for bi in range(b):
            out[bi] = self._matmul(mat, src[bi])
        return out

    def encode(self, data_shards: np.ndarray,
               out: np.ndarray | None = None) -> np.ndarray:
        """(K, S) -> (M, S) parity (or batched (B, K, S) -> (B, M, S);
        `out` receives batched parity in place when given)."""
        data_shards = np.asarray(data_shards, dtype=np.uint8)
        mat = np.asarray(gf256.parity_matrix(self.k, self.m))
        if data_shards.ndim == 3:
            return self._matmul_batch(mat, data_shards, out)
        return self._matmul(mat, data_shards)

    def reconstruct(self, src_shards, available_idx, wanted) -> np.ndarray:
        """(K, S) first-K-available -> (len(wanted), S)."""
        mat = gf256.reconstruct_matrix(
            self.k, self.m, tuple(available_idx), tuple(wanted)
        )
        src = np.asarray(src_shards, dtype=np.uint8)
        if src.ndim == 3:
            return self._matmul_batch(np.asarray(mat), src, None)
        return self._matmul(mat, src)

    def matmul(self, mat: np.ndarray, src: np.ndarray) -> np.ndarray:
        """Apply an arbitrary (R, K) GF(2^8) matrix to (K, S) shards (or
        batched (B, K, S) -> (B, R, S)).  The repair executor hands in
        precomputed, LRU-cached dual-codeword rows (erasure/repair.py)
        so no per-dispatch matrix construction happens here."""
        mat = np.asarray(mat, dtype=np.uint8)
        src = np.asarray(src, dtype=np.uint8)
        if src.ndim == 3:
            return self._matmul_batch(mat, src, None)
        return self._matmul(mat, src)


class HH256:
    """Streaming HighwayHash-256 (Go hash.Hash semantics)."""

    SIZE = 32
    BLOCK_SIZE = 32

    def __init__(self, key: bytes = MAGIC_HH256_KEY):
        if len(key) != 32:
            raise ValueError("key must be 32 bytes")
        self._key = key
        lib = _load()
        if lib is None:
            raise RuntimeError(
                "host library unavailable; build csrc/ (make -C csrc)"
            )
        self._lib = lib
        self._state = ctypes.create_string_buffer(lib.hh256_state_size())
        self.reset()

    def reset(self):
        self._lib.hh256_init(self._state, self._key)

    def update(self, data: bytes | np.ndarray):
        if isinstance(data, np.ndarray):
            data = np.ascontiguousarray(data, dtype=np.uint8)
            self._lib.hh256_update(
                self._state, data.ctypes.data_as(ctypes.c_char_p), data.nbytes
            )
        else:
            self._lib.hh256_update(self._state, bytes(data), len(data))

    def digest(self) -> bytes:
        out = ctypes.create_string_buffer(32)
        self._lib.hh256_final(self._state, out)
        return out.raw


def hh256(data, key: bytes = MAGIC_HH256_KEY) -> bytes:
    """One-shot HighwayHash-256.

    Accepts bytes, bytearray, memoryview and uint8 ndarrays; any 1-D
    contiguous buffer is hashed IN PLACE (no bytes() materialization) —
    the bitrot write path hands shard rows and arena views straight
    through, so hashing costs zero extra memory passes."""
    lib = _load()
    if lib is None:
        raise RuntimeError("host library unavailable; build csrc/ (make -C csrc)")
    out = ctypes.create_string_buffer(32)
    if isinstance(data, np.ndarray):
        data = np.ascontiguousarray(data, dtype=np.uint8)
        lib.hh256_sum(key, data.ctypes.data_as(ctypes.c_char_p), data.nbytes, out)
    elif isinstance(data, bytes):
        lib.hh256_sum(key, data, len(data), out)
    else:
        mv = memoryview(data)
        if mv.ndim != 1 or not mv.contiguous:
            mv = memoryview(bytes(mv))
        arr = np.frombuffer(mv, dtype=np.uint8)  # zero-copy buffer view
        lib.hh256_sum(key, arr.ctypes.data_as(ctypes.c_char_p),
                      arr.nbytes, out)
    return out.raw


def hh256_batch(blocks: np.ndarray, key: bytes = MAGIC_HH256_KEY) -> np.ndarray:
    """Hash N equal-length streams: (N, L) uint8 -> (N, 32) uint8.

    Rows may be strided (e.g. one shard's column of a (B, K, S) batch, or
    the block lanes of an interleaved [hash|block] frame buffer) as long
    as each row itself is contiguous — the C call takes a row stride, so
    no defensive copy is made on the hot path."""
    lib = _load()
    if lib is None:
        raise RuntimeError("host library unavailable; build csrc/ (make -C csrc)")
    blocks = np.asarray(blocks, dtype=np.uint8)
    if (blocks.ndim != 2 or blocks.strides[1] != 1
            or blocks.strides[0] < blocks.shape[1]):
        blocks = np.ascontiguousarray(blocks)
    n, l = blocks.shape
    out = np.empty((n, 32), dtype=np.uint8)
    lib.hh256_batch(
        key, ctypes.c_char_p(blocks.ctypes.data), n, l, blocks.strides[0],
        out.ctypes.data_as(ctypes.c_char_p),
    )
    return out
