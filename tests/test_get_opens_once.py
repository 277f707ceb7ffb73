"""A GET opens its object once (ISSUE 31).

Through the served handler, on drives that count: every GET, whatever
branch it takes (plain, Range, conditional, versioned, delete marker,
SSE-C, compressed, inline), costs exactly one `read_version` a drive,
answers that need no byte (304, 412, 416, 404, 405) open no shard file,
HEAD keeps its one read without data, and what the client gets equals
what `get_object_info` + `get_object` of the object layer give.  And the
stricter guarantee the single open buys: headers and bytes are of one
election, also when an overwrite lands between the open and the read.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os

import pytest

from minio_tpu.erasure import objects as objects_mod
from minio_tpu.erasure.sets import ErasureServerPools, ErasureSets
from minio_tpu.server.app import _http_date
from minio_tpu.storage import errors
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import compress

from .s3_harness import S3TestServer

N_DRIVES = 4
ADMIN = "/minio/admin/v3"
XMLNS = "http://s3.amazonaws.com/doc/2006-03-01/"
BKT = "onceb"      # unversioned
VBKT = "oncev"     # versioned

BIG = os.urandom(300 << 10)           # past the inline limit: shard files
BIG_V1 = os.urandom(200 << 10)
SMALL = b"inline object " * 100       # 1.4 KB: shards inside xl.meta
TEXT = (b"compress me please -- " * 8192) + b"tail"

SSE_KEY = b"\x31" * 32
SSEC = {
    "x-amz-server-side-encryption-customer-algorithm": "AES256",
    "x-amz-server-side-encryption-customer-key":
        base64.b64encode(SSE_KEY).decode(),
    "x-amz-server-side-encryption-customer-key-md5":
        base64.b64encode(hashlib.md5(SSE_KEY).digest()).decode(),
}


class _CountingDisk:
    """A drive that counts what a read request may cost it."""

    def __init__(self, inner):
        self._inner = inner
        self.reset()

    def reset(self) -> None:
        self.versions: list[bool] = []   # read_data of each read_version
        self.streams = 0                 # read_file_stream calls

    def read_version(self, volume, path, version_id="", read_data=False):
        self.versions.append(read_data)
        return self._inner.read_version(volume, path, version_id, read_data)

    def read_file_stream(self, *a, **kw):
        self.streams += 1
        return self._inner.read_file_stream(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    root = tmp_path_factory.mktemp("once")
    # an answer that is late on a busy box must not be abandoned by the
    # hedge: the test counts every drive
    mp = pytest.MonkeyPatch()
    mp.setattr(objects_mod, "STRAGGLER_GRACE", 30.0)
    disks = [_CountingDisk(LocalStorage(str(root / f"d{i}")))
             for i in range(N_DRIVES)]
    pools = ErasureServerPools([ErasureSets(disks)])
    srv = S3TestServer(str(root / "unused"), pools=pools)
    ids = {}
    try:
        for b in (BKT, VBKT):
            assert srv.request("PUT", f"/{b}").status == 200
        vcfg = (f'<VersioningConfiguration xmlns="{XMLNS}"><Status>Enabled'
                f"</Status></VersioningConfiguration>").encode()
        assert srv.request("PUT", f"/{VBKT}", query=[("versioning", "")],
                           data=vcfg).status == 200
        assert srv.request("PUT", f"/{BKT}/big", data=BIG).status == 200
        assert srv.request("PUT", f"/{BKT}/small", data=SMALL).status == 200
        assert srv.request("PUT", f"/{BKT}/secret", data=BIG,
                           headers=SSEC).status == 200
        ids["v1"] = srv.request("PUT", f"/{VBKT}/doc", data=BIG_V1
                                ).headers["x-amz-version-id"]
        ids["v2"] = srv.request("PUT", f"/{VBKT}/doc", data=BIG
                                ).headers["x-amz-version-id"]
        srv.request("PUT", f"/{VBKT}/gone", data=BIG)
        ids["marker"] = srv.request("DELETE", f"/{VBKT}/gone"
                                    ).headers["x-amz-version-id"]
        # compression comes on last, so that only doc.txt is compressed
        assert srv.request("PUT", f"{ADMIN}/set-config-kv", data=json.dumps(
            {"subsys": "compression", "kv": {"enable": "on"}}
        ).encode()).status == 200
        assert srv.request("PUT", f"/{BKT}/doc.txt", data=TEXT).status == 200
        assert pools.get_object_info(BKT, "doc.txt").metadata[
            compress.META_COMPRESSION] == compress.SCHEME
        yield srv, pools, disks, ids
    finally:
        srv.close()
        mp.undo()


def _etag(pools, bucket, key, vid=""):
    return pools.get_object_info(bucket, key, vid).etag


# name, bucket, key, the version asked for (a key of `ids`, or ""),
# request headers, expected status, the client's bytes (None: no body is
# compared), whether shard files may be opened
CASES = [
    ("plain", BKT, "big", "", {}, 200, BIG, True),
    ("range", BKT, "big", "", {"Range": "bytes=70000-200000"}, 206,
     BIG[70000:200001], True),
    ("suffix-range", BKT, "big", "", {"Range": "bytes=-4097"}, 206,
     BIG[-4097:], True),
    ("if-none-match-304", BKT, "big", "", {"If-None-Match": "<etag>"}, 304,
     None, False),
    ("if-match-412", BKT, "big", "", {"If-Match": '"no-such-etag"'}, 412,
     None, False),
    ("bad-range-416", BKT, "big", "", {"Range": "bytes=900000-"}, 416,
     None, False),
    ("version-id-old", VBKT, "doc", "v1", {}, 200, BIG_V1, True),
    ("version-id-latest", VBKT, "doc", "v2", {}, 200, BIG, True),
    ("delete-marker-404", VBKT, "gone", "", {}, 404, None, False),
    ("delete-marker-version-405", VBKT, "gone", "marker", {}, 405, None,
     False),
    ("sse-c", BKT, "secret", "", SSEC, 200, BIG, True),
    ("sse-c-range", BKT, "secret", "", {**SSEC, "Range": "bytes=65530-65600"},
     206, BIG[65530:65601], True),
    ("compressed", BKT, "doc.txt", "", {}, 200, TEXT, True),
    ("compressed-range", BKT, "doc.txt", "",
     {"Range": "bytes=100000-100099"}, 206, TEXT[100000:100100], True),
    ("inline", BKT, "small", "", {}, 200, SMALL, False),
    ("inline-range", BKT, "small", "", {"Range": "bytes=5-20"}, 206,
     SMALL[5:21], False),
]


@pytest.mark.parametrize(
    "bucket,key,version,headers,status,body,opens_shards",
    [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_get_reads_xl_meta_once(world, bucket, key, version, headers,
                                status, body, opens_shards):
    srv, pools, disks, ids = world
    vid = ids.get(version, "")
    headers = {k: (f'"{_etag(pools, bucket, key, vid)}"' if v == "<etag>"
                   else v) for k, v in headers.items()}
    query = [("versionId", vid)] if vid else []
    for d in disks:
        d.reset()
    r = srv.request("GET", f"/{bucket}/{key}", headers=headers, query=query)
    counted = [(list(d.versions), d.streams) for d in disks]
    assert r.status == status, r.text()

    # one quorum read, with the data: one read_version a drive
    assert [v for v, _ in counted] == [[True]] * N_DRIVES
    streams = sum(n for _, n in counted)
    if opens_shards:
        # every drive's shard file at most once (one part), at least k
        assert all(n <= 1 for _, n in counted) and streams >= N_DRIVES // 2
    else:
        assert streams == 0

    if status >= 400 or status == 304:
        assert status != 304 or r.body == b""
        return
    # what get_object_info + get_object give
    oi = pools.get_object_info(bucket, key, vid)
    assert r.headers["ETag"] == f'"{oi.etag}"'
    assert r.headers["Last-Modified"] == _http_date(oi.mod_time)
    if bucket == VBKT:
        assert r.headers["x-amz-version-id"] == oi.version_id == vid
    assert r.body == body
    assert int(r.headers["Content-Length"]) == len(body)
    stored = oi.metadata.get(compress.META_COMPRESSION) or "secret" in key
    if not stored:
        # a plain object's bytes are the object layer's bytes
        rng = r.headers.get("Content-Range")
        off = int(rng.split()[1].split("-")[0]) if rng else 0
        _, stream = pools.get_object(bucket, key, off, len(body), vid)
        assert b"".join(stream) == r.body


@pytest.mark.parametrize("key,size", [("big", len(BIG)),
                                      ("small", len(SMALL)),
                                      ("doc.txt", len(TEXT))])
def test_head_reads_once_without_data(world, key, size):
    srv, _, disks, _ = world
    for d in disks:
        d.reset()
    r = srv.request("HEAD", f"/{BKT}/{key}")
    assert r.status == 200
    assert int(r.headers["Content-Length"]) == size
    assert [d.versions for d in disks] == [[False]] * N_DRIVES
    assert sum(d.streams for d in disks) == 0


def test_wrappers_keep_their_answers(world):
    """`get_object` hides a delete marker as not found whatever version
    was asked for; `get_object_info` and `open_object` name it."""
    _, pools, _, ids = world
    with pytest.raises(errors.ObjectNotFound):
        pools.get_object(VBKT, "gone")
    with pytest.raises(errors.ObjectNotFound):
        pools.get_object(VBKT, "gone", version_id=ids["marker"])
    for fn in (pools.get_object_info, pools.open_object):
        with pytest.raises(errors.ObjectNotFound):
            fn(VBKT, "gone")
        with pytest.raises(objects_mod.MethodNotAllowedDeleteMarker):
            fn(VBKT, "gone", ids["marker"])
    with pytest.raises(errors.InvalidArgument):
        pools.open_object(BKT, "big")[1](len(BIG) - 10, 11)


def test_read_is_lazy_and_repeatable(world):
    """`read` touches no drive until its iterator is advanced, and every
    call streams the same election again."""
    _, pools, disks, _ = world
    for d in disks:
        d.reset()
    oi, read = pools.open_object(BKT, "big")
    first = read(10, 1000)
    assert [d.versions for d in disks] == [[True]] * N_DRIVES
    assert sum(d.streams for d in disks) == 0
    assert b"".join(first) == BIG[10:1010]
    assert b"".join(read()) == BIG
    assert oi.size == len(BIG)
    assert [d.versions for d in disks] == [[True]] * N_DRIVES


@pytest.mark.parametrize("size", [300 << 10, 1400], ids=["shards", "inline"])
def test_overwrite_between_open_and_read_serves_one_version(world, size):
    """The stricter guarantee: the bytes are of the elected `fi` and its
    `data_dir`, as the headers are, also when an overwrite lands between
    the open and the first read: never headers of one version and bytes
    of another."""
    srv, pools, _, _ = world
    old, new = os.urandom(size), os.urandom(size + 17)
    key = f"racing-{size}"
    for bucket in (BKT, VBKT):
        assert srv.request("PUT", f"/{bucket}/{key}", data=old).status == 200
        oi, read = pools.open_object(bucket, key)
        assert srv.request("PUT", f"/{bucket}/{key}", data=new).status == 200
        assert oi.etag == hashlib.md5(old).hexdigest() and oi.size == size
        try:
            got = b"".join(read())
        except Exception:
            # unversioned: the overwrite took the old data_dir away; an
            # error is an honest answer, the new bytes would not be
            assert bucket == BKT and size > 128 << 10
            continue
        assert got == old
        # and the next open sees the new version whole
        oi2, read2 = pools.open_object(bucket, key)
        assert oi2.etag == hashlib.md5(new).hexdigest()
        assert b"".join(read2()) == new
