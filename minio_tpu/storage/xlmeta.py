"""Object metadata model and the on-drive `xl.meta` document.

Capability-equivalent to the reference's FileInfo/ErasureInfo
(cmd/storage-datatypes.go:117, cmd/erasure-metadata.go) and the xl.meta v2
multi-version file (cmd/xl-storage-format-v2.go): every shard file is
accompanied by a self-describing msgpack document carrying the EC
parameters, the per-part bitrot checksums, the drive distribution, and all
object versions (incl. delete markers and optional inlined small-object
data) — so any surviving read quorum can reconstruct the object without
external state.

Format here is our own msgpack schema (versioned, field-named) rather than
a byte-clone of minio's msgp structs; self-description and quorum
semantics match.
"""

from __future__ import annotations

import secrets
import time
import uuid
from dataclasses import dataclass, field

import msgpack

XL_META_FORMAT = 1
ERASURE_ALGO = "rs-vandermonde"  # reference: "rs-vandermonde" ReedSolomon
NULL_VERSION_ID = "null"


@dataclass
class ChecksumInfo:
    """Bitrot checksum for one part on one drive
    (reference ChecksumInfo, cmd/erasure-metadata.go:37)."""

    part_number: int
    algorithm: str  # "highwayhash256S" (streaming) etc.
    hash: bytes     # empty for streaming bitrot (hashes interleaved in file)


@dataclass
class ErasureInfo:
    """EC geometry for one object version on one drive
    (reference ErasureInfo, cmd/erasure-metadata.go:60)."""

    algorithm: str
    data_blocks: int
    parity_blocks: int
    block_size: int
    index: int                 # 1-based shard index this drive holds
    distribution: list[int]    # hashOrder drive shuffle
    checksums: list[ChecksumInfo] = field(default_factory=list)

    @property
    def shard_size(self) -> int:
        return -(-self.block_size // self.data_blocks)

    def shard_file_size(self, total: int) -> int:
        if total == 0:
            return 0
        if total == -1:
            return -1
        num = total // self.block_size
        last = total % self.block_size
        last_shard = -(-last // self.data_blocks) if last else 0
        return num * self.shard_size + last_shard


@dataclass
class ObjectPartInfo:
    number: int
    size: int            # plaintext part size
    actual_size: int     # pre-compression size
    mod_time: float = 0.0
    etag: str = ""


@dataclass
class FileInfo:
    """One object version as stored on one drive (reference FileInfo)."""

    volume: str = ""
    name: str = ""
    version_id: str = ""
    is_latest: bool = True
    deleted: bool = False          # delete marker
    data_dir: str = ""
    mod_time: float = 0.0
    size: int = 0
    metadata: dict = field(default_factory=dict)
    parts: list[ObjectPartInfo] = field(default_factory=list)
    erasure: ErasureInfo | None = None
    # small objects: shard bytes inlined into xl.meta (cmd/xl-storage.go:59)
    data: bytes | None = None
    fresh: bool = False
    idx: int = 0
    # length of the drive's xl.meta this was parsed from (0 where it came
    # another way, over the wire among them): the bytes of the quorum
    # read's stage `meta_read`
    xl_bytes: int = field(default=0, compare=False)

    def shard_file_size(self, part_size: int) -> int:
        assert self.erasure is not None
        return self.erasure.shard_file_size(part_size)

    def to_obj(self) -> dict:
        d = {
            "v": self.version_id,
            "del": self.deleted,
            "dd": self.data_dir,
            "mt": self.mod_time,
            "sz": self.size,
            "meta": self.metadata,
            "parts": [
                {"n": p.number, "s": p.size, "as": p.actual_size,
                 "mt": p.mod_time, "e": p.etag}
                for p in self.parts
            ],
        }
        if self.erasure is not None:
            e = self.erasure
            d["ec"] = {
                "algo": e.algorithm, "k": e.data_blocks, "m": e.parity_blocks,
                "bs": e.block_size, "ix": e.index, "dist": e.distribution,
                "cs": [
                    {"p": c.part_number, "a": c.algorithm, "h": c.hash}
                    for c in e.checksums
                ],
            }
        if self.data is not None:
            d["data"] = self.data
        return d

    @classmethod
    def from_obj(cls, volume: str, name: str, d: dict) -> "FileInfo":
        ec = None
        if "ec" in d:
            e = d["ec"]
            ec = ErasureInfo(
                algorithm=e["algo"], data_blocks=e["k"], parity_blocks=e["m"],
                block_size=e["bs"], index=e["ix"], distribution=list(e["dist"]),
                checksums=[
                    ChecksumInfo(c["p"], c["a"], c["h"]) for c in e.get("cs", [])
                ],
            )
        return cls(
            volume=volume, name=name, version_id=d.get("v", ""),
            deleted=d.get("del", False), data_dir=d.get("dd", ""),
            mod_time=d.get("mt", 0.0), size=d.get("sz", 0),
            metadata=dict(d.get("meta", {})),
            parts=[
                ObjectPartInfo(p["n"], p["s"], p["as"], p.get("mt", 0.0),
                               p.get("e", ""))
                for p in d.get("parts", [])
            ],
            erasure=ec,
            data=d.get("data"),
        )


def new_version_id() -> str:
    return str(uuid.uuid4())


def new_data_dir() -> str:
    return str(uuid.UUID(bytes=secrets.token_bytes(16)))


class XLMeta:
    """Multi-version xl.meta document for one object on one drive."""

    def __init__(self, versions: list[dict] | None = None):
        # newest first, like the reference's sorted version headers
        self.versions: list[dict] = versions or []

    # -- serialization ------------------------------------------------------
    def dumps(self) -> bytes:
        return msgpack.packb(
            {"fmt": XL_META_FORMAT, "vers": self.versions}, use_bin_type=True
        )

    @classmethod
    def loads(cls, raw: bytes | memoryview) -> "XLMeta":
        doc = msgpack.unpackb(raw, raw=False, strict_map_key=False)
        if doc.get("fmt") != XL_META_FORMAT:
            raise ValueError(f"unsupported xl.meta format {doc.get('fmt')}")
        return cls(doc.get("vers", []))

    # -- version operations -------------------------------------------------
    def add_version(self, fi: FileInfo) -> dict | None:
        """Insert a version, replacing any same-id entry.  Returns the
        replaced entry (if any) so the caller can reclaim its data dir."""
        obj = fi.to_obj()
        vid = obj.get("v", "")
        replaced = None
        kept = []
        for v in self.versions:
            if v.get("v", "") == vid:
                replaced = v
            else:
                kept.append(v)
        self.versions = kept
        self.versions.insert(0, obj)
        self.versions.sort(key=lambda v: v.get("mt", 0.0), reverse=True)
        return replaced

    def delete_version(self, version_id: str) -> dict | None:
        # the API-level sentinel "null" addresses the internal empty-id
        # version (the "null version" written while versioning is off or
        # suspended — reference nullVersionID, cmd/xl-storage-format-v2.go)
        if version_id == NULL_VERSION_ID:
            version_id = ""
        for i, v in enumerate(self.versions):
            if v.get("v", "") == version_id:
                return self.versions.pop(i)
        return None

    def find_version(self, version_id: str) -> dict | None:
        if version_id == NULL_VERSION_ID:
            for v in self.versions:
                if v.get("v", "") == "":
                    return v
            return None
        if not version_id:
            return self.versions[0] if self.versions else None
        for v in self.versions:
            if v.get("v", "") == version_id:
                return v
        return None

    @property
    def latest(self) -> dict | None:
        return self.versions[0] if self.versions else None


def file_info_from_raw(raw: bytes | memoryview, volume: str, name: str,
                       version_id: str = "", read_data: bool = False) -> FileInfo:
    xl = XLMeta.loads(raw)
    v = xl.find_version(version_id)
    if v is None:
        from . import errors
        raise errors.FileVersionNotFound(f"{volume}/{name}@{version_id}")
    fi = FileInfo.from_obj(volume, name, v)
    fi.is_latest = xl.versions and xl.versions[0].get("v", "") == fi.version_id
    fi.xl_bytes = len(raw)
    if not read_data:
        fi.data = None
    return fi


def find_file_info_in_quorum(parts_metadata: list[FileInfo | None],
                             quorum: int) -> FileInfo:
    """Pick the FileInfo agreed by >= quorum drives.

    Mirrors findFileInfoInQuorum (cmd/erasure-metadata.go:285): drives vote
    with a hash over (mod_time, data_dir, EC geometry, distribution); the
    modal variant wins if it meets quorum.
    """
    from . import errors

    counts: dict = {}
    for fi in parts_metadata:
        if fi is None:
            continue
        e = fi.erasure
        sig = (
            round(fi.mod_time, 6), fi.data_dir, fi.deleted, fi.version_id,
            None if e is None else (
                e.data_blocks, e.parity_blocks, e.block_size,
                tuple(e.distribution),
            ),
        )
        counts[sig] = counts.get(sig, 0) + 1
    if not counts:
        raise errors.ErasureReadQuorum("no metadata read")
    best = max(counts, key=lambda s: counts[s])
    if counts[best] < quorum:
        raise errors.ErasureReadQuorum(
            f"metadata quorum not met: {counts[best]} < {quorum}"
        )
    for fi in parts_metadata:
        if fi is None:
            continue
        e = fi.erasure
        sig = (
            round(fi.mod_time, 6), fi.data_dir, fi.deleted, fi.version_id,
            None if e is None else (
                e.data_blocks, e.parity_blocks, e.block_size,
                tuple(e.distribution),
            ),
        )
        if sig == best:
            return fi
    raise errors.ErasureReadQuorum("unreachable")


def object_quorum_from_meta(parts_metadata: list[FileInfo | None],
                            default_parity: int) -> tuple[int, int]:
    """(read_quorum, write_quorum) from stored EC geometry
    (cmd/erasure-metadata.go:391)."""
    parity = default_parity
    for fi in parts_metadata:
        if fi is not None and fi.erasure is not None:
            parity = fi.erasure.parity_blocks
            data = fi.erasure.data_blocks
            break
    else:
        data = None
    if data is None:
        n = len(parts_metadata)
        data = n - parity
    write_q = data + 1 if data == parity else data
    return data, write_q
