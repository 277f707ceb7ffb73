"""Streaming bitrot protection: hash-interleaved shard files.

File format matches the reference's streamingBitrotWriter
(cmd/bitrot-streaming.go:35): every shard-size block is preceded by the
32-byte HighwayHash-256 of that block, keyed with the magic pi key —
    [h0 | b0 | h1 | b1 | ... | hN | bN]
Reads must be shard-size aligned; each block is verified on read
(cmd/bitrot-streaming.go:142).  Hashing uses the C++ host library
(bit-exact with minio/highwayhash, pinned by cmd/bitrot.go:215 vectors).
"""

from __future__ import annotations

import errno
import hashlib
import io
import os
from typing import BinaryIO, Callable

import numpy as np

from minio_tpu.ops import host
from minio_tpu.storage import errors
from . import stagestats

HASH_SIZE = 32  # size for the default algorithm (HighwayHash-256)
DEFAULT_ALGO = "highwayhash256S"

# algorithm registry (reference BitrotAlgorithm set, cmd/bitrot.go:39-44:
# SHA256, BLAKE2b512, HighwayHash256, HighwayHash256S).  Each entry:
# (hash_fn(bytes)->digest, digest_size).  highwayhash256 is the same
# function as the streaming variant — the reference distinguishes them
# only by whole-file vs streaming framing.
ALGORITHMS: dict[str, tuple[Callable[[bytes], bytes], int]] = {
    "highwayhash256S": (lambda b: host.hh256(b), 32),
    "highwayhash256": (lambda b: host.hh256(b), 32),
    "sha256": (lambda b: hashlib.sha256(b).digest(), 32),
    "blake2b512": (lambda b: hashlib.blake2b(b).digest(), 64),
}


def algo_from_env() -> str:
    """Write-path algorithm (reads always honor the algo recorded in the
    version's ChecksumInfo)."""
    a = os.environ.get("MINIO_TPU_BITROT_ALGO", DEFAULT_ALGO)
    return a if a in ALGORITHMS else DEFAULT_ALGO


def hasher_of(algo: str) -> tuple[Callable[[bytes], bytes], int]:
    try:
        return ALGORITHMS[algo]
    except KeyError:
        raise errors.InvalidArgument(f"unknown bitrot algorithm {algo!r}")


def bitrot_shard_file_size(size: int, shard_size: int,
                           algo: str = DEFAULT_ALGO) -> int:
    """On-disk size of a shard file with interleaved hashes
    (cmd/bitrot.go:146)."""
    if size == 0:
        return 0
    if size < 0:
        return -1
    nblocks = -(-size // shard_size)
    return nblocks * hasher_of(algo)[1] + size


class BitrotWriter:
    """Wraps a shard-file handle; every write() must be one erasure block's
    shard (shard_size bytes, or less for the final block)."""

    def __init__(self, w: BinaryIO, shard_size: int,
                 algo: str = DEFAULT_ALGO):
        self.w = w
        self.shard_size = shard_size
        self.written = 0
        self.algo = algo
        self._hash, self._hsize = hasher_of(algo)

    def write(self, block: bytes | memoryview) -> None:
        if len(block) > self.shard_size:
            raise errors.InvalidArgument(
                f"bitrot write of {len(block)} exceeds shard size {self.shard_size}"
            )
        # hash straight from the caller's buffer (bytes, memoryview or a
        # contiguous ndarray row) — no bytes() materialization; hh256
        # reads any 1-D contiguous buffer zero-copy (ops/host.py)
        with stagestats.timed("hash", len(block)):
            h = self._hash(block)
        with stagestats.timed("write", len(block)):
            self.w.write(h)
            self.w.write(block)
        self.written += self._hsize + len(block)

    def write_frames(self, blocks: np.ndarray) -> None:
        """Write many shard blocks as [hash|block] frames in one shot.

        blocks: (nb, L) uint8, L <= shard_size, every row one erasure
        block's shard (only a stream's final block may be short, so a
        multi-row call implies L == shard_size for all rows).  Hashing is
        one batched C call over the (possibly strided) rows; the frames
        go out via one writev(2) on real files — the kernel gathers the
        hash/block segments straight from the source buffers, so the
        interleaved layout costs no extra memory pass.  Equivalent to the
        per-block write() loop (cmd/bitrot-streaming.go:43) and
        byte-identical on disk.
        """
        blocks = np.asarray(blocks, dtype=np.uint8)
        if blocks.ndim != 2:
            raise errors.InvalidArgument("write_frames wants (nblocks, L)")
        if blocks.shape[1] and blocks.strides[1] != 1:
            blocks = np.ascontiguousarray(blocks)
        nb, length = blocks.shape
        if length > self.shard_size:
            raise errors.InvalidArgument(
                f"bitrot write of {length} exceeds shard size {self.shard_size}"
            )
        if nb > 1 and length != self.shard_size:
            # short frames are only legal as a stream's final block; a
            # multi-row short batch would land at the wrong file offsets
            # for the reader's shard_size-spaced seeks
            raise errors.InvalidArgument(
                "write_frames: short blocks must be written one at a time"
            )
        if self.algo not in ("highwayhash256S", "highwayhash256"):
            for row in blocks:
                self.write(row)
            return
        try:
            with stagestats.timed("hash", blocks.nbytes):
                hashes = host.hh256_batch(blocks)
        except RuntimeError:
            for row in blocks:
                self.write(row)
            return
        fd = None
        try:
            fd = self.w.fileno()
        except (AttributeError, OSError, ValueError):
            pass
        with stagestats.timed("write", blocks.nbytes):
            if fd is not None:
                self.w.flush()
                for lo in range(0, nb, 500):  # stay under IOV_MAX segments
                    hi = min(lo + 500, nb)
                    iov: list = []
                    for bi in range(lo, hi):
                        iov.append(hashes[bi].data)
                        iov.append(blocks[bi].data)
                    total = (hi - lo) * (self._hsize + length)
                    sent = os.writev(fd, iov)
                    if sent < total:  # partial writev (signals): resume mid-frame
                        rest = bytearray()
                        off = 0
                        for seg in iov:
                            if off + len(seg) > sent:
                                rest += seg[max(0, sent - off):]
                            off += len(seg)
                        rest = bytes(rest)
                        while rest:
                            n = os.write(fd, rest)
                            rest = rest[n:]
            elif getattr(self.w, "prefers_row_writes", False):
                # local staging writer (O_DIRECT): write the frames
                # row-wise straight into its aligned buffer —
                # materializing one interleaved [hash|block] buffer
                # first would cost a full extra memory pass per batch
                for bi in range(nb):
                    self.w.write(hashes[bi].data)
                    self.w.write(blocks[bi].data)
            else:
                # unknown sink (remote RPC writer, BytesIO): one
                # interleaved buffer, ONE write — a row-wise loop would
                # turn a batch into 2*nb round trips on wire-backed
                # writers
                buf = np.empty((nb, self._hsize + length), dtype=np.uint8)
                buf[:, : self._hsize] = hashes
                buf[:, self._hsize:] = blocks
                self.w.write(buf.reshape(-1).data)
        self.written += nb * (self._hsize + length)

    def close(self) -> None:
        self.w.close()


class BitrotReader:
    """Verified reader over a hash-interleaved shard file.

    read_at(offset, length): offset/length are in *logical* shard bytes and
    offset must be shard_size aligned (cmd/bitrot-streaming.go:142-189).
    """

    def __init__(self, r: BinaryIO, till_offset: int, shard_size: int,
                 algo: str = DEFAULT_ALGO):
        self.r = r
        self.shard_size = shard_size
        self.till_offset = till_offset  # logical shard bytes available
        self._pos = -1  # current logical offset (-1: not positioned)
        self.algo = algo
        self._hash, self._hsize = hasher_of(algo)

    def _file_offset(self, offset: int) -> int:
        """Where logical `offset`'s frame starts in the file."""
        if offset % self.shard_size != 0:
            raise errors.InvalidArgument(
                f"bitrot read offset {offset} not aligned to {self.shard_size}"
            )
        return offset // self.shard_size * (self._hsize + self.shard_size)

    def _seek_to(self, offset: int) -> None:
        file_off = self._file_offset(offset)
        if self._pos != offset:
            self.r.seek(file_off)
            self._pos = offset

    def _read_frames(self, offset: int, want: int) -> bytearray | bytes:
        """`want` bytes of [hash|block] frames from logical `offset`."""
        self._seek_to(offset)
        # fill a preallocated frame buffer via readinto when the source
        # supports it (one copy straight off the O_DIRECT staging buffer
        # or socket); read()-only streams (remote RPC shards) wrap the
        # returned bytes zero-copy instead of paying an extra buffer and
        # a second memory pass
        raw: bytearray | bytes = b""
        got = 0
        ri = getattr(self.r, "readinto", None) \
            if not getattr(self, "_no_readinto", False) else None
        if ri is not None:
            raw = bytearray(want)
            mv = memoryview(raw)
            try:
                while got < want:
                    n = ri(mv[got:])
                    if not n:
                        break
                    got += n
            except (NotImplementedError, io.UnsupportedOperation):
                # RawIOBase subclasses that only implement read()
                # (remote RPC shard streams) inherit a non-functional
                # readinto — remember and fall back for this stream.
                # The default raises before consuming anything, but
                # reposition defensively in case a partial read landed.
                self._no_readinto = True
                ri = None
                if got:
                    self._pos = -1
                    self._seek_to(offset)
                got = 0
        if ri is None:
            raw = self.r.read(want)
            got = len(raw)
        if got != want:
            raise errors.FileCorrupt("bitrot: truncated frame group")
        return raw

    def _read_rows(self, offset: int, out: np.ndarray
                   ) -> np.ndarray | None:
        """The frames from logical `offset` read apart, two readinto
        calls a frame: each hash into a row of a small array, each block
        straight into its row of `out`.  Returns the hashes, or None
        where the stream has no readinto (remote RPC shard streams)."""
        ri = None if getattr(self, "_no_readinto", False) \
            else getattr(self.r, "readinto", None)
        if ri is None:
            return None
        nblocks, block_len = out.shape
        hashes = np.empty((nblocks, self._hsize), dtype=np.uint8)
        self._seek_to(offset)
        # the stream moves from here on: until the rows are verified it
        # stands nowhere the next read may rely on
        self._pos = -1
        with stagestats.timed("shard_read",
                              nblocks * (self._hsize + block_len)):
            try:
                for i in range(nblocks):
                    for seg in (hashes[i].data, out[i].data):
                        got = 0
                        while got < len(seg):
                            n = ri(seg[got:])
                            if not n:
                                raise errors.FileCorrupt(
                                    "bitrot: truncated frame group")
                            got += n
            except (NotImplementedError, io.UnsupportedOperation):
                # RawIOBase subclasses that only implement read(): see
                # _read_frames
                self._no_readinto = True
                return None
        return hashes

    def _read_native(self, offset: int, out: np.ndarray) -> bool:
        """The frames from logical `offset` read, placed in `out` and
        checked in one native call of the stream (`read_frames`: a local
        shard file's descriptor, the interpreter lock let go once for the
        group).  False, having read nothing, where the stream has no
        such call, the native library is not there, the frames are not
        HighwayHash-256's, or the file system refused O_DIRECT (then for
        this stream from now on): the caller reads in Python."""
        rf = None if getattr(self, "_no_native", False) \
            or self.algo not in ("highwayhash256S", "highwayhash256") \
            or not out.size else getattr(self.r, "read_frames", None)
        if rf is None or not host.available():
            return False
        nblocks, block_len = out.shape
        hashes = np.empty((nblocks, self._hsize), dtype=np.uint8)
        file_off = self._file_offset(offset)
        # until the rows are checked the stream stands nowhere the next
        # read may rely on
        self._pos = -1
        with stagestats.timed("shard_read",
                              nblocks * (self._hsize + block_len)):
            try:
                status, hash_ns = rf(file_off, hashes, out)
            except OSError as e:
                if e.errno != errno.EINVAL:
                    raise
                self._no_native = True
                return False
        if status == host.FRAMES_SHORT:
            raise errors.FileCorrupt("bitrot: truncated frame group")
        # the hash's own time, inside shard_read's interval
        stagestats.add("verify", hash_ns * 1e-9, out.size)
        if status == host.FRAMES_MISMATCH:
            raise errors.FileCorrupt("bitrot: hash mismatch")
        stagestats.add("native_read", 0.0, out.size)
        return True

    def _verify(self, blocks: np.ndarray, hashes: np.ndarray) -> None:
        """One batched hash call over the (possibly strided) rows
        against their frames' hashes; FileCorrupt where any differs."""
        nblocks, block_len = blocks.shape
        with stagestats.timed("verify", nblocks * block_len):
            try:
                batched = (
                    host.hh256_batch(blocks)
                    if self.algo in ("highwayhash256S", "highwayhash256")
                    else None
                )
            except RuntimeError:
                batched = None
            if batched is not None:
                ok = np.array_equal(batched, hashes)
            else:
                ok = all(
                    self._hash(blocks[i].data) == hashes[i].tobytes()
                    for i in range(nblocks)
                )
        if not ok:
            raise errors.FileCorrupt("bitrot: hash mismatch")

    def read_blocks(self, offset: int, nblocks: int, block_len: int,
                    out: np.ndarray | None = None) -> np.ndarray:
        """Read + verify `nblocks` frames of `block_len` logical bytes each
        starting at logical `offset` in ONE file read and ONE batched hash
        call, returning a (nblocks, block_len) uint8 view into the frame
        buffer (rows strided past the interleaved hashes — zero extra
        copies).  block_len == shard_size except for a stream's final
        short block (then nblocks must be 1).

        With `out`, a (nblocks, block_len) uint8 array whose rows are
        contiguous (any row stride: one shard's column of a dispatch's
        (B, K, S) batch), the rows are read where the caller wants them
        and `out` is returned: no frame buffer, no copy: in one native
        call where the stream has one (_read_native), else two readinto
        calls a frame (_read_rows).  Verified before the call returns
        like any other read; a stream that can place no rows is read as
        without `out` and copied."""
        if out is not None:
            if (out.dtype != np.uint8 or out.shape != (nblocks, block_len)
                    or not out.flags.writeable
                    or (block_len and out.strides[1] != 1)
                    or (nblocks > 1 and out.strides[0] < block_len)):
                raise ValueError(
                    f"read_blocks: out must be a writable ({nblocks}, "
                    f"{block_len}) uint8 array of contiguous rows")
            placed = self._read_native(offset, out)
            if not placed:
                hashes = self._read_rows(offset, out)
                if hashes is not None:
                    self._verify(out, hashes)
                    placed = True
            if placed:
                self._pos = offset + nblocks * block_len
                stagestats.add("staged", 0.0, out.size)
                return out
        frame = self._hsize + block_len
        want = nblocks * frame
        with stagestats.timed("shard_read", want):
            raw = self._read_frames(offset, want)
        arr = np.frombuffer(raw, dtype=np.uint8).reshape(nblocks, frame)
        blocks = arr[:, self._hsize:]
        self._verify(blocks, arr[:, : self._hsize])
        self._pos = offset + nblocks * block_len
        if out is None:
            return blocks
        with stagestats.timed("assemble", blocks.size):
            out[:] = blocks
        return out

    def read_at_ranges(self, runs, block_len: int | None = None
                       ) -> dict[int, np.ndarray]:
        """Ranged sub-shard read mode (the repair executor's survivor
        protocol): ``runs`` is [(block_idx, nblocks)] ascending; each
        run is one seek + one frame-group read + one batched hash
        verify, so a survivor ships ONLY the requested frames — remote
        shard streams re-issue their ranged RPC at the new offset
        instead of draining skipped bytes when their ``drain_max`` is 0
        (distributed/storage_rpc.py).  Returns {block_idx: (nblocks,
        block_len) uint8 rows}.  ``block_len`` defaults to shard_size;
        a short final block must be its own single-block run."""
        if block_len is None:
            block_len = self.shard_size
        return {b0: self.read_blocks(b0 * self.shard_size, nb, block_len)
                for b0, nb in runs}

    # frames per read_at group: bounds the transient frame buffer while
    # keeping the one-read/one-hash batching for large ranges
    READ_AT_GROUP = 256

    def read_at(self, offset: int, length: int) -> bytes:
        """Verified logical-byte range read.  Preallocates the output and
        reads full-shard frames in batched groups (one file read + one
        batched hash verify per group) instead of growing a bytes
        accumulator one frame at a time — many-small-frame ranges used to
        go quadratic in the `out +=` rewrite."""
        if length <= 0:
            return b""
        out = bytearray(length)
        out_arr = np.frombuffer(out, dtype=np.uint8)
        pos = 0
        off = offset
        nfull = length // self.shard_size
        while nfull > 0:
            g = min(nfull, self.READ_AT_GROUP)
            blocks = self.read_blocks(off, g, self.shard_size)
            span = g * self.shard_size
            # one vectorized gather from the strided frame rows
            out_arr[pos: pos + span].reshape(g, self.shard_size)[:] = blocks
            pos += span
            off += span
            nfull -= g
        rem = length - pos
        if rem:
            out_arr[pos:] = self.read_blocks(off, 1, rem)[0]
        return bytes(out)

    def close(self) -> None:
        self.r.close()


def bitrot_verify_stream(f: BinaryIO, file_size: int, shard_file_size: int,
                         shard_size: int, algo: str = DEFAULT_ALGO) -> None:
    """Verify a whole shard file (reference bitrotVerify, cmd/bitrot.go:154)."""
    hash_fn, hsize = hasher_of(algo)
    want_size = bitrot_shard_file_size(shard_file_size, shard_size, algo)
    if file_size != want_size:
        raise errors.FileCorrupt(
            f"bitrot: file size {file_size} != expected {want_size}"
        )
    left = shard_file_size
    while left > 0:
        h = f.read(hsize)
        if len(h) != hsize:
            raise errors.FileCorrupt("bitrot: truncated hash")
        want = min(shard_size, left)
        block = f.read(want)
        if len(block) != want:
            raise errors.FileCorrupt("bitrot: truncated block")
        if hash_fn(block) != h:
            raise errors.FileCorrupt("bitrot: hash mismatch")
        left -= want
