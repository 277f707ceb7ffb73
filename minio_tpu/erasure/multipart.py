"""Multipart uploads for ErasureObjects.

Reference: cmd/erasure-multipart.go — uploads stage under
`.minio_tpu.sys/multipart/<sha256(bucket/object)>/<uploadID>/` on every
drive of the set; each part is EC-encoded with the same engine as
PutObject; CompleteMultipartUpload validates the client's part list
against stored part metadata, then commits the staged directory as the
object's data dir with a single rename per drive (cmd/erasure-multipart.go:771).
"""

from __future__ import annotations

import binascii
import hashlib
import io
import os
import time
import uuid
from dataclasses import dataclass, field

from minio_tpu.storage import errors
from minio_tpu.storage.local import SYSTEM_VOL
from minio_tpu.storage.xlmeta import (
    ChecksumInfo, ErasureInfo, FileInfo, ObjectPartInfo,
    find_file_info_in_quorum, new_version_id,
)
from . import bitrot
from .coding import BLOCK_SIZE_V2, Erasure, io_submit
from .objects import (
    ErasureObjects, ObjectInfo, PutObjectOptions, _HashingReader,
)

MULTIPART_DIR = "multipart"
MIN_PART_SIZE = 5 << 20  # S3 minimum for all but the last part

# upload-metadata cache TTL: the upload's FileInfo (EC geometry, bitrot
# algo, distribution) is immutable after new_multipart_upload, yet every
# put_object_part paid a full drive fan-out to re-read it — for a 5 MiB
# part that was ~10% of the wall.  Local abort/complete invalidate
# immediately; a remote abort is seen after at most this many seconds
# (the stale-upload cleanup reclaims anything a racing part re-creates).
MP_META_TTL_S = float(os.environ.get("MINIO_TPU_MP_META_TTL_S", "2.0"))


@dataclass
class PartInfo:
    part_number: int
    etag: str
    size: int
    mod_time: float = 0.0
    #: on-disk name of the committed part file (metadata-in-name
    #: format, or legacy "part.N" when read from a sidecar)
    fname: str = ""


def _part_fname(n: int, size: int, etag: str, mt: float) -> str:
    """Committed part filename with the metadata IN the name:
    `part.<n>.c.<size>.<md5hex>.<mt_ms>`.  One same-dir rename commits a
    part — the sidecar file cost 3 extra fs metadata ops per drive per
    part and a read per drive per part at assembly, which dominated
    multipart wall time on high-syscall-latency hosts.  A re-uploaded
    part lands under a new name; listings resolve duplicates by the
    newest mt and CompleteMultipartUpload's one-sweep upload-dir delete
    reclaims the rest."""
    return f"part.{n}.c.{size}.{etag}.{int(mt * 1000)}"


def _parse_part_fname(name: str) -> PartInfo | None:
    t = name.split(".")
    if len(t) != 6 or t[0] != "part" or t[2] != "c":
        return None
    try:
        return PartInfo(int(t[1]), t[4], int(t[3]), int(t[5]) / 1000.0,
                        fname=name)
    except ValueError:
        return None


@dataclass
class MultipartInfo:
    bucket: str
    object: str
    upload_id: str
    initiated: float = 0.0
    metadata: dict = field(default_factory=dict)


def _upload_root(bucket: str, obj: str) -> str:
    h = hashlib.sha256(f"{bucket}/{obj}".encode()).hexdigest()
    return f"{MULTIPART_DIR}/{h}"


def _upload_path(bucket: str, obj: str, upload_id: str) -> str:
    return f"{_upload_root(bucket, obj)}/{upload_id}"


class MultipartMixin:
    """Mixed into ErasureObjects (see bottom of module)."""

    def new_multipart_upload(self: ErasureObjects, bucket: str, obj: str,
                             opts: PutObjectOptions | None = None) -> str:
        opts = opts or PutObjectOptions()
        # ensure object bucket exists on quorum of drives
        self._check_bucket(bucket)
        upload_id = uuid.uuid4().hex
        upath = _upload_path(bucket, obj, upload_id)
        _, dist = self._shuffled_disks(obj)
        n = len(self.disks)
        parity = self._parity_for(opts)
        k = n - parity
        metadata = dict(opts.user_metadata)
        if opts.content_type:
            metadata["content-type"] = opts.content_type
        # pin the bitrot algorithm for the whole upload: parts and the
        # final checksums must agree even if the env changes (or another
        # node completes the upload)
        metadata["x-minio-internal-bitrot-algo"] = bitrot.algo_from_env()
        # the directory layout hashes bucket/object away: record them so
        # bucket-wide upload enumeration can recover the logical key
        metadata["x-minio-internal-upload-bucket"] = bucket
        metadata["x-minio-internal-upload-object"] = obj
        now = time.time()

        def write(i: int) -> None:
            d = self.disks[i]
            if d is None or not d.is_online():
                raise errors.DiskNotFound(str(i))
            fi = FileInfo(
                volume=bucket, name=obj, version_id="", mod_time=now,
                metadata=metadata,
                erasure=ErasureInfo(
                    algorithm="rs-vandermonde", data_blocks=k,
                    parity_blocks=parity, block_size=BLOCK_SIZE_V2,
                    index=i + 1, distribution=dist,
                ),
            )
            d.write_metadata(SYSTEM_VOL, upath, fi)

        errs = self._fan_out(write, range(n))
        wq = k + 1 if k == parity else k
        if sum(1 for e in errs if e is None) < wq:
            raise errors.ErasureWriteQuorum("multipart init quorum")
        return upload_id

    def _check_bucket(self: ErasureObjects, bucket: str) -> None:
        # parallel stat fan-out: serial, a drive-count of stat round
        # trips gates EVERY multipart call (ISSUE 5 sequential-loop kill)
        def stat(i: int) -> None:
            d = self.disks[i]
            if d is None or not d.is_online():
                raise errors.DiskNotFound(str(i))
            d.stat_volume(bucket)

        errs = self._fan_out(stat, range(len(self.disks)))
        ok = sum(1 for e in errs if e is None)
        if ok >= len(self.disks) // 2 + 1:
            return
        # below quorum: only VolumeNotFound (or an offline drive, which
        # the old serial loop also skipped) votes "missing" — any other
        # drive error (timeout, RPC failure) propagates as a retryable
        # 5xx instead of being laundered into an authoritative 404 that
        # SDKs treat as terminal
        other = next((e for e in errs if e is not None and not isinstance(
            e, (errors.VolumeNotFound, errors.DiskNotFound))), None)
        if other is not None:
            raise other
        raise errors.BucketNotFound(bucket)

    def _mp_cache(self: ErasureObjects) -> dict:
        cache = getattr(self, "_mp_meta_cache", None)
        if cache is None:
            cache = self._mp_meta_cache = {}
        return cache

    def _upload_meta(self: ErasureObjects, bucket: str, obj: str,
                     upload_id: str) -> tuple[FileInfo, list]:
        cache = self._mp_cache()
        key = (bucket, obj, upload_id)
        hit = cache.get(key)
        if hit is not None and time.monotonic() - hit[2] < MP_META_TTL_S:
            return hit[0], hit[1]
        upath = _upload_path(bucket, obj, upload_id)
        fis, errs = self._read_all_fileinfo(SYSTEM_VOL, upath)
        nf = sum(1 for e in errs if isinstance(e, errors.FileNotFound))
        if nf > len(self.disks) // 2:
            cache.pop(key, None)
            raise errors.InvalidArgument(f"upload id {upload_id} not found")
        read_q, _ = self._quorum_from(fis)
        fi = find_file_info_in_quorum(fis, read_q)
        if len(cache) > 256:  # bound: stale entries expire by TTL anyway
            cache.clear()
        cache[key] = (fi, fis, time.monotonic())
        return fi, fis

    def put_object_part(self: ErasureObjects, bucket: str, obj: str,
                        upload_id: str, part_number: int, reader,
                        size: int = -1) -> PartInfo:
        if part_number < 1 or part_number > 10000:
            raise errors.InvalidArgument(f"part number {part_number}")
        ufi, _ = self._upload_meta(bucket, obj, upload_id)
        upload_algo = ufi.metadata.get("x-minio-internal-bitrot-algo",
                                       bitrot.DEFAULT_ALGO)
        e = Erasure(ufi.erasure.data_blocks, ufi.erasure.parity_blocks,
                    ufi.erasure.block_size, set_id=self.set_index)
        n = e.k + e.m
        wq = e.k + 1 if e.k == e.m else e.k
        upath = _upload_path(bucket, obj, upload_id)
        dist = ufi.erasure.distribution
        # shard-order drives per upload distribution
        disks_by_index = [None] * n
        for disk_idx, pos in enumerate(dist):
            if disk_idx < len(self.disks):
                d = self.disks[disk_idx]
                disks_by_index[pos - 1] = d if d is not None and d.is_online() else None

        # stage INSIDE the upload dir under a tmp suffix: the dir already
        # exists on every drive (created at upload init), so staging
        # costs one open + one same-dir rename per drive instead of a
        # mkdir + cross-dir rename + rmdir round trip — fs metadata op
        # latency, not bytes, dominated small parts on the sampler
        tmp_name = f"part.{part_number}.tmp-{uuid.uuid4().hex[:12]}"

        # multi-process data plane (ISSUE 8): parts ride the worker
        # plane exactly like single-PUT payloads — encode + shard
        # writes in the I/O workers, etag in the hash lane, one commit
        # message per worker for the same-dir rename
        mp_plane = None
        mp_roots = mp_groups = None
        from minio_tpu.parallel import workers as workers_mod

        if workers_mod.worker_count() > 0:
            mp_roots = workers_mod.plane_roots(disks_by_index)
            if mp_roots is not None:
                mp_plane = workers_mod.get_plane()
        hreader = None if mp_plane is not None \
            else _HashingReader(reader, size)

        def cleanup_tmp() -> None:
            def rm(i: int) -> None:
                d = disks_by_index[i]
                if d is not None:
                    try:
                        d.delete(SYSTEM_VOL, f"{upath}/{tmp_name}")
                    except errors.StorageError:
                        pass

            self._fan_out(rm, range(n))

        shard_hint = -1 if size < 0 else bitrot.bitrot_shard_file_size(
            e.shard_file_size(size), e.shard_size, upload_algo)

        if mp_plane is not None:
            from minio_tpu.storage import local as local_mod

            try:
                total, mp_failed, etag, mp_groups = mp_plane.put_data(
                    reader, mp_roots, e.k, e.m, ufi.erasure.block_size,
                    upload_algo, size, SYSTEM_VOL, f"{upath}/{tmp_name}",
                    shard_hint, local_mod.FSYNC_ENABLED,
                    abort_path=f"{upath}/{tmp_name}",
                    abort_recursive=False)
            except errors.StorageError:
                cleanup_tmp()
                raise
            failed_shards = set(mp_failed)
            if n - len(failed_shards) < wq:
                cleanup_tmp()
                raise errors.ErasureWriteQuorum(
                    f"{n - len(failed_shards)} worker part streams < "
                    f"quorum {wq}")
            if size >= 0 and total != size:
                cleanup_tmp()
                raise errors.InvalidArgument(
                    f"short read {total} != {size}")
            now = time.time()
            final_name = _part_fname(part_number, total, etag, now)
            res = mp_plane.commit(
                mp_groups, "rename_file", SYSTEM_VOL,
                f"{upath}/{tmp_name}", dst_vol=SYSTEM_VOL,
                dst_path=f"{upath}/{final_name}", skip=failed_shards)
            ok = sum(1 for i in range(n)
                     if i not in failed_shards and res.get(i, 1) is None)
            if failed_shards:
                # reclaim the failed shards' staged files (the commit
                # path of the in-process plane does the same sweep)
                def rm_failed(i: int) -> None:
                    d = disks_by_index[i]
                    if d is not None and i in failed_shards:
                        try:
                            d.delete(SYSTEM_VOL, f"{upath}/{tmp_name}")
                        except errors.StorageError:
                            pass

                self._fan_out(rm_failed, sorted(failed_shards))
            if ok < wq:
                raise errors.ErasureWriteQuorum("part commit quorum")
            return PartInfo(part_number, etag, total, now)

        def open_writer(i: int):
            d = disks_by_index[i]
            if d is None:
                return None
            fh = d.open_file_writer(SYSTEM_VOL, f"{upath}/{tmp_name}",
                                    size_hint=shard_hint)
            return bitrot.BitrotWriter(fh, e.shard_size, algo=upload_algo)

        # parallel writer opens (serial was one O_DIRECT open + staging
        # setup per drive before the first encoded byte)
        open_futs = [io_submit(open_writer, i) for i in range(n)]
        open_errs: list[Exception | None] = [None] * n
        writers = []
        for i, f in enumerate(open_futs):
            try:
                writers.append(f.result())
            except Exception as ex:
                writers.append(None)
                open_errs[i] = ex
        if any(open_errs):
            # preserve the serial path's contract: a failed open aborts
            # the part (no silent degrade) — but close what DID open
            for w in writers:
                if w is not None:
                    try:
                        w.close()
                    except Exception:
                        pass
            cleanup_tmp()
            raise next(ex for ex in open_errs if ex is not None)
        def close_all() -> None:
            def close_one(i: int) -> None:
                if writers[i] is not None:
                    try:
                        writers[i].close()
                    except Exception:
                        pass

            self._fan_out(close_one, range(n))

        try:
            total, failed_shards = e.encode_stream(hreader, writers, size, wq)
        except Exception:
            close_all()
            cleanup_tmp()
            raise
        close_all()
        if size >= 0 and total != size:
            cleanup_tmp()
            raise errors.InvalidArgument(f"short read {total} != {size}")

        etag = hreader.etag
        now = time.time()
        final_name = _part_fname(part_number, total, etag, now)

        def commit(i_pos: int) -> None:
            d = disks_by_index[i_pos]
            if d is None or writers[i_pos] is None \
                    or i_pos in failed_shards:
                if d is not None:
                    try:  # reclaim the staged file of a failed shard
                        d.delete(SYSTEM_VOL, f"{upath}/{tmp_name}")
                    except errors.StorageError:
                        pass
                raise errors.DiskNotFound(str(i_pos))
            # metadata rides the filename: ONE same-dir rename commits
            # the part — no sidecar write, no sidecar read at assembly
            d.rename_file(SYSTEM_VOL, f"{upath}/{tmp_name}",
                          SYSTEM_VOL, f"{upath}/{final_name}")

        # commit-rename fan-out with quorum accounting (the serial loop
        # was one rename + sidecar write round trip PER drive)
        errs = self._commit_meta(commit)
        if sum(1 for x in errs if x is None) < wq:
            raise errors.ErasureWriteQuorum("part commit quorum")
        return PartInfo(part_number, etag, total, now)

    def list_object_parts(self: ErasureObjects, bucket: str, obj: str,
                          upload_id: str,
                          want: set[int] | None = None) -> list[PartInfo]:
        """Stored parts of an upload: part metadata is parsed straight
        from the committed filenames (one list_dir per drive, no
        per-part reads); legacy sidecar entries (.meta) are still read
        for uploads staged before the metadata-in-name format.  With
        `want` (internal: the part numbers a CompleteMultipartUpload
        names), drives are scanned in small parallel waves and the walk
        stops once every wanted part was seen — every drive normally
        holds every part, so a full-union walk is pure overhead on the
        assembly path."""
        self._upload_meta(bucket, obj, upload_id)
        upath = _upload_path(bucket, obj, upload_id)

        def scan(d) -> dict[int, PartInfo]:
            found: dict[int, PartInfo] = {}
            if d is None or not d.is_online():
                return found
            try:
                names = d.list_dir(SYSTEM_VOL, upath)
            except Exception:
                return found
            legacy = []
            for nm in names:
                nm = nm.rstrip("/")
                pi = _parse_part_fname(nm)
                if pi is not None:
                    # a re-uploaded part lands under a fresh name: the
                    # newest commit wins
                    cur = found.get(pi.part_number)
                    if cur is None or pi.mod_time > cur.mod_time:
                        found[pi.part_number] = pi
                elif nm.endswith(".meta") and nm.startswith("part."):
                    legacy.append(nm)
            for nm in legacy:
                import msgpack

                try:
                    doc = msgpack.unpackb(
                        d.read_all(SYSTEM_VOL, f"{upath}/{nm}"))
                    found.setdefault(
                        doc["n"],
                        PartInfo(doc["n"], doc["e"], doc["s"], doc["mt"],
                                 fname=f"part.{doc['n']}"),
                    )
                except Exception:
                    continue
            return found

        # parallel waves; the newest commit wins ACROSS drives too — a
        # drive whose commit-rename failed may still hold only the stale
        # copy of a re-uploaded part, and first-drive-wins would validate
        # the client's new etag against it and reject a quorate upload
        parts: dict[int, PartInfo] = {}
        disks = list(self.disks)
        majority = len(disks) // 2 + 1
        scanned = 0
        for lo in range(0, len(disks), 4):
            futs = [io_submit(scan, d) for d in disks[lo: lo + 4]]
            scanned += len(futs)
            for f in futs:
                for num, pi in f.result().items():
                    cur = parts.get(num)
                    if cur is None or pi.mod_time > cur.mod_time:
                        parts[num] = pi
            # stop only once a MAJORITY of drives was scanned: a part
            # commit lands on a write quorum (always a strict majority),
            # so any majority scan intersects it and sees the newest
            # copy — an earlier break could return a stale re-upload
            # from the few drives whose commit-rename failed
            if want is not None and scanned >= majority \
                    and want <= parts.keys():
                break
        return [parts[k] for k in sorted(parts)]

    def enumerate_multipart_uploads(
            self: ErasureObjects) -> list[MultipartInfo]:
        """Every in-progress upload on this set, across ALL buckets, in
        ONE walk (reference ListMultipartUploads backing + the
        stale-upload cleanup, cmd/erasure-sets.go:489).  Object names
        come from the upload's own metadata — the directory layout
        hashes them away.  Entries whose metadata is unreadable on every
        drive (or predates the recorded keys) surface with bucket="" and
        their raw directory in metadata["__dir"], so the cleanup can
        still reclaim them."""
        resolved: dict[tuple[str, str], MultipartInfo] = {}
        pending: dict[tuple[str, str], float] = {}
        for d in self.disks:
            if d is None or not d.is_online():
                continue
            try:
                roots = d.list_dir(SYSTEM_VOL, MULTIPART_DIR)
            except Exception:
                continue
            for h in roots:
                h = h.rstrip("/")
                try:
                    uids = d.list_dir(SYSTEM_VOL, f"{MULTIPART_DIR}/{h}")
                except Exception:
                    continue
                for uid in uids:
                    uid = uid.rstrip("/")
                    key = (h, uid)
                    if key in resolved:
                        continue
                    try:
                        fi = d.read_version(
                            SYSTEM_VOL, f"{MULTIPART_DIR}/{h}/{uid}")
                    except Exception:
                        pending.setdefault(key, 0.0)
                        continue
                    up_bucket = fi.metadata.get(
                        "x-minio-internal-upload-bucket", "")
                    up_obj = fi.metadata.get(
                        "x-minio-internal-upload-object", "")
                    if not up_bucket or not up_obj:
                        # legacy/orphan entry: readable but unmapped
                        pending[key] = max(pending.get(key, 0.0),
                                           fi.mod_time)
                        continue
                    pending.pop(key, None)
                    resolved[key] = MultipartInfo(
                        up_bucket, up_obj, uid, initiated=fi.mod_time,
                        metadata=dict(fi.metadata))
        out = list(resolved.values())
        for (h, uid), mt in pending.items():
            out.append(MultipartInfo(
                "", "", uid, initiated=mt,
                metadata={"__dir": f"{MULTIPART_DIR}/{h}/{uid}"}))
        out.sort(key=lambda u: (u.bucket, u.object, u.upload_id))
        return out

    def list_all_multipart_uploads(self: ErasureObjects, bucket: str,
                                   prefix: str = "") -> list[MultipartInfo]:
        """Bucket view over enumerate_multipart_uploads."""
        return [u for u in self.enumerate_multipart_uploads()
                if u.bucket == bucket
                and (not prefix or u.object.startswith(prefix))]

    def list_multipart_uploads(self: ErasureObjects, bucket: str,
                               obj: str) -> list[MultipartInfo]:
        root = _upload_root(bucket, obj)
        ids: set[str] = set()
        for d in self.disks:
            if d is None or not d.is_online():
                continue
            try:
                for nm in d.list_dir(SYSTEM_VOL, root):
                    ids.add(nm.rstrip("/"))
            except Exception:
                continue
        return [MultipartInfo(bucket, obj, i) for i in sorted(ids)]

    def abort_multipart_upload(self: ErasureObjects, bucket: str, obj: str,
                               upload_id: str) -> None:
        self._upload_meta(bucket, obj, upload_id)
        self._mp_cache().pop((bucket, obj, upload_id), None)
        upath = _upload_path(bucket, obj, upload_id)

        def rm(i: int) -> None:
            d = self.disks[i]
            if d is not None and d.is_online():
                try:
                    d.delete(SYSTEM_VOL, upath, recursive=True)
                except errors.FileNotFound:
                    pass

        self._fan_out(rm, range(len(self.disks)))

    def complete_multipart_upload(self: ErasureObjects, bucket: str, obj: str,
                                  upload_id: str,
                                  parts: list[tuple[int, str]]) -> ObjectInfo:
        """parts: [(part_number, etag), ...] in client order."""
        ufi, _ = self._upload_meta(bucket, obj, upload_id)
        upload_algo = ufi.metadata.get("x-minio-internal-bitrot-algo",
                                       bitrot.DEFAULT_ALGO)
        stored = {p.part_number: p for p in
                  self.list_object_parts(bucket, obj, upload_id,
                                         want={n for n, _ in parts})}
        if not parts:
            raise errors.InvalidArgument("no parts")
        prev = 0
        total = 0
        chosen: list[PartInfo] = []
        md5cat = b""
        for idx, (num, etag) in enumerate(parts):
            if num <= prev:
                raise errors.InvalidArgument("parts out of order")
            prev = num
            sp = stored.get(num)
            if sp is None or sp.etag.strip('"') != etag.strip('"'):
                raise errors.InvalidArgument(f"part {num} invalid or missing")
            if idx != len(parts) - 1 and sp.size < MIN_PART_SIZE:
                raise EntityTooSmall(f"part {num} is {sp.size} bytes")
            chosen.append(sp)
            total += sp.size
            md5cat += binascii.unhexlify(sp.etag.strip('"'))
        final_etag = hashlib.md5(md5cat).hexdigest() + f"-{len(parts)}"

        e = Erasure(ufi.erasure.data_blocks, ufi.erasure.parity_blocks,
                    ufi.erasure.block_size, set_id=self.set_index)
        n = e.k + e.m
        wq = e.k + 1 if e.k == e.m else e.k
        dist = ufi.erasure.distribution
        upath = _upload_path(bucket, obj, upload_id)
        from minio_tpu.storage.xlmeta import new_data_dir

        data_dir = new_data_dir()
        now = time.time()
        metadata = dict(ufi.metadata)
        metadata.pop("x-minio-internal-bitrot-algo", None)
        metadata.pop("x-minio-internal-upload-bucket", None)
        metadata.pop("x-minio-internal-upload-object", None)
        metadata["etag"] = final_etag
        version_id = ""

        part_infos = [
            ObjectPartInfo(p.part_number, p.size, p.size, p.mod_time, p.etag)
            for p in chosen
        ]

        disks_by_index = [None] * n
        for disk_idx, pos in enumerate(dist):
            if disk_idx < len(self.disks):
                d = self.disks[disk_idx]
                disks_by_index[pos - 1] = d if d is not None and d.is_online() else None

        stage_id = uuid.uuid4().hex

        def commit(i_pos: int) -> None:
            d = disks_by_index[i_pos]
            if d is None:
                raise errors.DiskNotFound(str(i_pos))
            # move the CHOSEN part files into a fresh staging dir and
            # commit that as the data dir; the upload dir (xl.meta,
            # sidecars, unreferenced parts) is then reclaimed in ONE
            # recursive delete — the old prune walked and deleted every
            # sidecar individually, which scaled with total parts, not
            # chosen parts, and dominated assembly wall time.  A drive
            # missing a chosen part file fails its rename and drops out
            # of the commit quorum (heal rebuilds it later) instead of
            # committing metadata that claims a shard it lacks.
            stage = f"tmp/mpc-{stage_id}"
            for p in chosen:
                src = p.fname or f"part.{p.part_number}"
                d.rename_file(SYSTEM_VOL, f"{upath}/{src}",
                              SYSTEM_VOL, f"{stage}/part.{p.part_number}")
            fi = FileInfo(
                volume=bucket, name=obj, version_id=version_id,
                data_dir=data_dir, mod_time=now, size=total,
                metadata=metadata, parts=part_infos,
                erasure=ErasureInfo(
                    algorithm="rs-vandermonde", data_blocks=e.k,
                    parity_blocks=e.m, block_size=ufi.erasure.block_size,
                    index=i_pos + 1, distribution=dist,
                    checksums=[
                        ChecksumInfo(p.part_number, upload_algo, b"")
                        for p in chosen
                    ],
                ),
            )
            d.rename_data(SYSTEM_VOL, stage, fi, bucket, obj)
            try:
                d.delete(SYSTEM_VOL, upath, recursive=True)
            except errors.StorageError:
                pass  # leftover upload dir: the stale-upload sweep reclaims

        with self.ns.write(f"{bucket}/{obj}"):
            # commit fan-out: list/prune + rename_data per drive ride the
            # shared I/O pool with quorum accounting, the same shape as
            # put_object's commit (serial, assembly latency grew with
            # drive count even though every disk was idle 15/16ths of it)
            errs = self._commit_meta(commit)
        self._mp_cache().pop((bucket, obj, upload_id), None)
        if sum(1 for x in errs if x is None) < wq:
            raise errors.ErasureWriteQuorum("complete multipart quorum")

        if self.ns_updated is not None:
            self.ns_updated(bucket, obj)
        fi = FileInfo(volume=bucket, name=obj, version_id=version_id,
                      mod_time=now, size=total, metadata=metadata,
                      parts=part_infos)
        return ObjectInfo.from_file_info(fi, bucket, obj)


class EntityTooSmall(errors.InvalidArgument):
    pass


# Bind multipart capabilities onto ErasureObjects.
for _name in (
    "new_multipart_upload", "_check_bucket", "_upload_meta", "_mp_cache",
    "put_object_part", "list_object_parts", "list_multipart_uploads",
    "list_all_multipart_uploads", "enumerate_multipart_uploads",
    "abort_multipart_upload", "complete_multipart_upload",
):
    setattr(ErasureObjects, _name, getattr(MultipartMixin, _name))
