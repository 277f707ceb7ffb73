"""A drive's xl.meta read in one native call.

`LocalStorage.read_xl` and `read_version` read the document through
`ops/host.py` `read_file` (`csrc/file_read.cpp`: open, fstat, the reads up
to the size, close, the interpreter lock let go once) into a buffer the
thread keeps.  They must return the bytes and the FileInfo the Python path
(`open` + `read`) returns, fail where it fails with the exceptions it
raises, book the stage `meta_native` once a `read_version` and nowhere
else, and leave a process without the library on the Python path.  The
quorum read books the documents' lengths as `meta_read`'s bytes, so that
over a fan-out of local drives the two stages' bytes agree.
"""

import errno
import io
import os
import threading

import pytest

from minio_tpu.distributed.rpc import RpcRouter
from minio_tpu.distributed.storage_rpc import register_storage_rpc
from minio_tpu.erasure import objects, stagestats
from minio_tpu.erasure.objects import SMALL_FILE_THRESHOLD, ErasureObjects
from minio_tpu.ops import host
from minio_tpu.storage import errors
from minio_tpu.storage.local import LocalStorage
from minio_tpu.storage.xlmeta import (ChecksumInfo, ErasureInfo, FileInfo,
                                      ObjectPartInfo)

pytestmark = pytest.mark.skipif(not host.available(),
                                reason="the native library did not build")


def _fi(version_id: str, mod_time: float, data: bytes | None = None
        ) -> FileInfo:
    return FileInfo(
        volume="bkt", name="obj", version_id=version_id,
        data_dir="" if data is not None else f"dd-{version_id}",
        mod_time=mod_time, size=64 << 20,
        metadata={"etag": f"e-{version_id}", "content-type": "x/y"},
        parts=[ObjectPartInfo(1, 64 << 20, 64 << 20, mod_time, "p1")],
        erasure=ErasureInfo("rs-vandermonde", 12, 4, 1 << 20, 3,
                            list(range(1, 17)),
                            [ChecksumInfo(1, "highwayhash256S", b"")]),
        data=data)


def _python(monkeypatch):
    monkeypatch.setattr(host, "available", lambda: False)


def _both(drive, monkeypatch, fn):
    """fn(drive) on the native path in a thread with a fresh buffer,
    then on the Python path: (native result or exception, python's)."""
    def call():
        try:
            return fn(drive)
        except Exception as e:  # compared by the caller
            return e

    monkeypatch.setattr(host, "_file_tls", threading.local())
    got = call()
    with monkeypatch.context() as m:
        _python(m)
        want = call()
    return got, want


def _one_version(d):
    d.write_metadata("bkt", "obj", _fi("v1", 1.0))


def _several_versions(d):
    for i in range(5):
        d.write_metadata("bkt", "obj", _fi(f"v{i}", 1.0 + i))


def _inline_shard(d):
    # an inline shard of the largest inline size: more than the thread's
    # first buffer, so the call grows it and reads once more
    data = os.urandom(SMALL_FILE_THRESHOLD)
    assert len(data) > host.FILE_BUF_BYTES
    d.write_metadata("bkt", "obj", _fi("v1", 1.0, data=data))


def _empty(d):
    os.makedirs(os.path.join(d.root, "bkt", "obj"))
    open(os.path.join(d.root, "bkt", "obj", "xl.meta"), "wb").close()


DOCS = {"one_version": _one_version, "several_versions": _several_versions,
        "inline_shard": _inline_shard, "empty": _empty}


@pytest.fixture()
def drive(tmp_path):
    d = LocalStorage(str(tmp_path / "d0"))
    d.make_volume("bkt")
    return d


@pytest.mark.parametrize("doc", sorted(DOCS))
def test_same_bytes_as_python(drive, monkeypatch, doc):
    DOCS[doc](drive)
    got, want = _both(drive, monkeypatch,
                      lambda d: d.read_xl("bkt", "obj"))
    assert type(got) is bytes and got == want
    with open(drive._meta_path("bkt", "obj"), "rb") as f:
        assert got == f.read()


@pytest.mark.parametrize("version_id,read_data", [
    ("", False), ("", True), ("v1", True), ("v0", False), ("gone", False)])
@pytest.mark.parametrize("doc", sorted(DOCS))
def test_same_file_info_as_python(drive, monkeypatch, doc, version_id,
                                  read_data):
    DOCS[doc](drive)
    got, want = _both(drive, monkeypatch, lambda d: d.read_version(
        "bkt", "obj", version_id, read_data))
    if isinstance(want, Exception):
        assert type(got) is type(want) and str(got) == str(want)
        return
    assert got == want and got.xl_bytes == want.xl_bytes
    assert got.xl_bytes == os.path.getsize(drive._meta_path("bkt", "obj"))
    if doc == "inline_shard" and read_data:
        assert type(got.data) is bytes and len(got.data) == SMALL_FILE_THRESHOLD


def test_read_version_owns_what_it_returns(drive, monkeypatch):
    """Parsed from the thread's buffer, the FileInfo keeps its values
    when the next read overwrites the buffer."""
    _inline_shard(drive)
    other = LocalStorage(drive.root + "-other")
    other.make_volume("bkt")
    other.write_metadata("bkt", "obj", _fi("w", 2.0, data=b"\1" * 70000))
    fi = drive.read_version("bkt", "obj", read_data=True)
    data = bytes(fi.data)
    doc = drive.read_xl("bkt", "obj")
    assert other.read_version("bkt", "obj", read_data=True).data \
        == b"\1" * 70000
    assert fi.data == data and fi.version_id == "v1"
    assert drive.read_xl("bkt", "obj") == doc


def _enotdir(d):
    with open(os.path.join(d.root, "bkt", "file"), "wb") as f:
        f.write(b"x")
    return "file/obj"


def _directory(d):
    os.makedirs(os.path.join(d.root, "bkt", "obj", "xl.meta"))
    return "obj"


def _eloop(d):
    os.makedirs(os.path.join(d.root, "bkt", "obj"))
    p = os.path.join(d.root, "bkt", "obj", "xl.meta")
    os.symlink(p, p)
    return "obj"


@pytest.mark.parametrize("make,exc,err", [
    (lambda d: "nothing", errors.FileNotFound, None),
    (_enotdir, errors.FileNotFound, None),
    (_directory, IsADirectoryError, errno.EISDIR),
    # any other errno: an OSError with it, which the drive's breaker and
    # is_drive_fault read as they read the Python path's
    (_eloop, OSError, errno.ELOOP),
])
@pytest.mark.parametrize("op", ["read_xl", "read_version"])
def test_errors_map_as_python(drive, monkeypatch, make, exc, err, op):
    path = make(drive)
    got, want = _both(drive, monkeypatch,
                      lambda d: getattr(d, op)("bkt", path))
    assert isinstance(want, exc) and type(got) is type(want)
    assert str(got) == str(want)
    if err is not None:
        assert got.errno == want.errno == err
        assert got.filename == want.filename


def _meta_native():
    snap = stagestats.snapshot()["meta_native"]
    return snap["seconds"], snap["bytes"]


def test_no_library_takes_the_python_path(drive, monkeypatch):
    _several_versions(drive)
    _python(monkeypatch)

    def refused(path):
        raise AssertionError("native read without the library")

    monkeypatch.setattr(host, "read_file", refused)
    before = _meta_native()
    fi = drive.read_version("bkt", "obj")
    assert fi.version_id == "v4" and fi.xl_bytes > 0
    assert drive.read_xl("bkt", "obj")
    assert _meta_native() == before


def test_one_native_read_books_meta_native_once(drive, monkeypatch):
    _one_version(drive)
    size = os.path.getsize(drive._meta_path("bkt", "obj"))
    booked = []
    add = stagestats.add

    def spy(stage, seconds, nbytes=0):
        if stage == "meta_native":
            booked.append((seconds, nbytes))
        add(stage, seconds, nbytes)

    monkeypatch.setattr(stagestats, "add", spy)
    drive.read_version("bkt", "obj")
    assert len(booked) == 1
    seconds, nbytes = booked[0]
    assert nbytes == size and 0 < seconds < 1
    # the other readers of a document (a commit's, the RPC server's)
    # book nothing
    drive.read_xl("bkt", "obj")
    assert len(booked) == 1


def test_read_file_grows_the_buffer_once(tmp_path, monkeypatch):
    monkeypatch.setattr(host, "_file_tls", threading.local())
    small, large = tmp_path / "small", tmp_path / "large"
    small.write_bytes(b"s" * 100)
    large.write_bytes(os.urandom(3 * host.FILE_BUF_BYTES + 1))
    view, ns = host.read_file(str(small))
    assert bytes(view) == b"s" * 100 and ns > 0
    first = host._file_tls.fb
    assert first.buf.size == host.FILE_BUF_BYTES
    view, _ = host.read_file(str(large))
    assert bytes(view) == large.read_bytes()
    grown = host._file_tls.fb
    assert grown.buf.size == 4 * host.FILE_BUF_BYTES
    view, _ = host.read_file(str(small))
    assert bytes(view) == b"s" * 100 and host._file_tls.fb is grown


@pytest.mark.parametrize("op,args", [
    ("read_xl", {}), ("read_version", {"read_data": True})])
def test_remote_drive_answers_as_python(drive, monkeypatch, op, args):
    """The RPC server's handlers return what the Python path returns."""
    _inline_shard(drive)
    router = RpcRouter("secret")
    register_storage_rpc(router, {"drv": drive})
    handler = router.methods[f"storage.{op}"]
    call = {"drive": "drv", "volume": "bkt", "path": "obj", **args}
    got, want = _both(drive, monkeypatch, lambda d: handler(call, b""))
    assert got == want


@pytest.mark.parametrize("away", [(), (1, 7)])
def test_fan_out_books_every_document_as_native(tmp_path, monkeypatch, away):
    """A sixteen-drive set's hedged quorum read (what a GET and a STAT
    run): `meta_native`'s bytes equal `meta_read`'s, the documents of
    the drives that answered."""
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(16)]
    for d in disks:
        d.make_volume("bkt")
    es = ErasureObjects(disks, default_parity=4)
    body = os.urandom(3 << 20)
    es.put_object("bkt", "obj", io.BytesIO(body), len(body))
    for i in away:
        es.disks[i] = None
    # no straggler abandoned: its read would book `meta_native` and no
    # answer of the fan-out's
    monkeypatch.setattr(objects, "STRAGGLER_GRACE", 60.0)
    sizes = [os.path.getsize(d._meta_path("bkt", "obj"))
             for i, d in enumerate(disks) if i not in away]
    before = stagestats.snapshot()
    info = es.get_object_info("bkt", "obj")
    after = stagestats.snapshot()
    assert info.size == len(body)
    moved = {s: after[s]["bytes"] - before[s]["bytes"]
             for s in ("meta_read", "meta_native")}
    assert moved["meta_read"] == moved["meta_native"] == sum(sizes) > 0
    assert after["meta_native"]["seconds"] > before["meta_native"]["seconds"]
