"""Server-mode disk cache: CacheLayer wrapping the ERASURE object layer
(VERDICT r4 #5; reference cmd/disk-cache.go:103 cacheObjects wraps any
ObjectLayer when cache drives are configured)."""

import json
import time

import pytest

from minio_tpu.erasure.sets import ErasureSets, ErasureServerPools
from minio_tpu.gateway.cache import CacheLayer
from minio_tpu.storage.local import LocalStorage

from .s3_harness import S3TestServer


@pytest.fixture()
def cached_srv(tmp_path):
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
    pools = ErasureServerPools([ErasureSets(disks)])
    layer = CacheLayer(pools, str(tmp_path / "ssd-cache"),
                       max_size=1 << 20)
    s = S3TestServer(str(tmp_path / "unused"), pools=layer)
    yield s, layer, pools
    s.close()


def _wait_filled(cache, entries=1):
    """A miss tees the body into the cache and commits the entry once
    the last chunk has gone out, so the client can hold the whole body
    before the server gets to the commit: wait for it before counting
    on a hit."""
    deadline = time.monotonic() + 10
    while cache.stats()["entries"] < entries \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert cache.stats()["entries"] >= entries, "the miss never filled"


class TestServerModeCache:
    def test_erasure_get_hits_cache(self, cached_srv):
        srv, cache, pools = cached_srv
        srv.request("PUT", "/cbk")
        data = b"cache me " * 1000
        assert srv.request("PUT", "/cbk/obj", data=data).status == 200
        r1 = srv.request("GET", "/cbk/obj")
        assert r1.status == 200 and r1.body == data
        _wait_filled(cache)
        m0 = cache.misses
        h0 = cache.hits
        r2 = srv.request("GET", "/cbk/obj")
        assert r2.body == data
        assert cache.hits == h0 + 1 and cache.misses == m0
        r3 = srv.request("GET", "/cbk/obj")
        assert r3.body == data and cache.hits == h0 + 2

    def test_overwrite_invalidates(self, cached_srv):
        srv, cache, _ = cached_srv
        srv.request("PUT", "/cbk2")
        srv.request("PUT", "/cbk2/k", data=b"v1")
        assert srv.request("GET", "/cbk2/k").body == b"v1"
        srv.request("PUT", "/cbk2/k", data=b"v2-new")
        assert srv.request("GET", "/cbk2/k").body == b"v2-new"
        assert srv.request("GET", "/cbk2/k").body == b"v2-new"

    def test_delete_invalidates(self, cached_srv):
        srv, cache, _ = cached_srv
        srv.request("PUT", "/cbk3")
        srv.request("PUT", "/cbk3/k", data=b"gone soon")
        srv.request("GET", "/cbk3/k")
        srv.request("DELETE", "/cbk3/k")
        assert srv.request("GET", "/cbk3/k").status == 404

    def test_eviction_respects_size_cap(self, cached_srv):
        srv, cache, _ = cached_srv  # max_size = 1 MiB
        srv.request("PUT", "/cbk4")
        blob = b"x" * (300 << 10)
        for i in range(8):
            srv.request("PUT", f"/cbk4/o{i}", data=blob)
            srv.request("GET", f"/cbk4/o{i}")   # fill
            srv.request("GET", f"/cbk4/o{i}")
        st = cache.stats()
        assert st["bytes"] <= (1 << 20), st
        assert st["entries"] < 8

    def test_range_reads_through_cache(self, cached_srv):
        srv, cache, _ = cached_srv
        srv.request("PUT", "/cbk5")
        data = bytes(range(256)) * 1000
        srv.request("PUT", "/cbk5/r", data=data)
        srv.request("GET", "/cbk5/r")  # warm the cache
        r = srv.request("GET", "/cbk5/r",
                        headers={"Range": "bytes=1000-1999"})
        assert r.status == 206
        assert r.body == data[1000:2000]

    def test_admin_info_reports_cache_stats(self, cached_srv):
        srv, cache, _ = cached_srv
        srv.request("PUT", "/cbk6")
        srv.request("PUT", "/cbk6/x", data=b"stat me")
        srv.request("GET", "/cbk6/x")
        _wait_filled(cache)
        srv.request("GET", "/cbk6/x")
        r = srv.request("GET", "/minio/admin/v3/info")
        assert r.status == 200
        info = json.loads(r.body)
        assert "cache" in info, info.keys()
        assert info["cache"]["hits"] >= 1
        assert info["cache"]["maxBytes"] == 1 << 20

    def test_versioned_reads_bypass_cache(self, cached_srv):
        srv, cache, _ = cached_srv
        srv.request("PUT", "/cbk7")
        body = (b'<VersioningConfiguration><Status>Enabled</Status>'
                b'</VersioningConfiguration>')
        srv.request("PUT", "/cbk7", query=[("versioning", "")], data=body)
        r = srv.request("PUT", "/cbk7/v", data=b"ver1")
        vid = r.headers.get("x-amz-version-id")
        srv.request("PUT", "/cbk7/v", data=b"ver2")
        r = srv.request("GET", "/cbk7/v", query=[("versionId", vid)])
        assert r.body == b"ver1"
        assert srv.request("GET", "/cbk7/v").body == b"ver2"


class TestCopyInvalidation:
    """ISSUE 7 satellite: a copy overwriting a cached destination must
    invalidate it — pre-fix, CacheLayer delegated copy_object through
    __getattr__ and a GET after the copy served the stale cached
    bytes."""

    def test_server_side_copy_invalidates_destination(self, tmp_path):
        class Inner:
            """Minimal object layer with a server-side copy_object
            (reference CopyObject ordering: src pair, then dst)."""

            def __init__(self):
                self.objs = {}

            def get_object_info(self, bucket, obj, version_id=""):
                from minio_tpu.erasure.objects import ObjectInfo

                data, etag = self.objs[(bucket, obj)]
                return ObjectInfo(bucket=bucket, name=obj,
                                  size=len(data), etag=etag)

            def get_object(self, bucket, obj, offset=0, length=-1,
                           version_id=""):
                data, _ = self.objs[(bucket, obj)]
                end = len(data) if length < 0 else offset + length
                return (self.get_object_info(bucket, obj),
                        iter([data[offset:end]]))

            def put_object(self, bucket, obj, reader, size=-1,
                           opts=None):
                data = reader.read()
                self.objs[(bucket, obj)] = (data, f"e{len(data)}")
                return self.get_object_info(bucket, obj)

            def copy_object(self, sb, so, db, do):
                self.objs[(db, do)] = self.objs[(sb, so)]
                return self.get_object_info(db, do)

        import io as io_mod

        inner = Inner()
        layer = CacheLayer(inner, str(tmp_path / "dcache"),
                           max_size=1 << 20)
        layer.put_object("b", "dst", io_mod.BytesIO(b"old destination"))
        layer.put_object("b", "src", io_mod.BytesIO(b"fresh source!!"))
        # warm the cache with the destination's old bytes
        _, s = layer.get_object("b", "dst")
        assert b"".join(s) == b"old destination"
        _, s = layer.get_object("b", "dst")
        assert b"".join(s) == b"old destination"
        assert layer.hits >= 1
        # server-side copy overwrites the cached destination
        layer.copy_object("b", "src", "b", "dst")
        _, s = layer.get_object("b", "dst")
        assert b"".join(s) == b"fresh source!!", \
            "stale cached destination served after copy_object"

    def test_inner_layer_rewrite_invalidates_via_ns_hook(self, tmp_path):
        """A write that BYPASSES the wrapper (heal/replication writing
        through the inner erasure layer) must still invalidate: the
        CacheLayer now registers on the same ns_updated choke point as
        the hot tier."""
        import io as io_mod

        disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
        pools = ErasureServerPools([ErasureSets(disks)])
        layer = CacheLayer(pools, str(tmp_path / "dcache2"),
                           max_size=1 << 20)
        pools.make_bucket("nsb")
        layer.put_object("nsb", "k", io_mod.BytesIO(b"version-one"))
        _, s = layer.get_object("nsb", "k")
        assert b"".join(s) == b"version-one"
        # bypass the wrapper: write straight to the inner pools
        pools.put_object("nsb", "k", io_mod.BytesIO(b"version-TWO"))
        _, s = layer.get_object("nsb", "k")
        assert b"".join(s) == b"version-TWO", \
            "inner-layer rewrite served stale disk-cache bytes"
