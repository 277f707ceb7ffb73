"""Disk cache: local read cache wrapped around any object layer.

Reference: cmd/disk-cache.go + cmd/disk-cache-backend.go (cacheObjects
wrapping the ObjectLayer — GETs tee through local SSD cache dirs with
ETag validation, LRU eviction between low/high watermarks, write paths
invalidating).  Wraps ANY ObjectLayer: the S3 gateway (saving WAN round
trips) or the erasure server pools (--cache-dir in server mode, where a
local SSD shortcuts the quorum read path; the background services keep
operating on the inner erasure layer).
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Iterator

from minio_tpu.utils.deadline import service_thread
from minio_tpu.utils.logger import log

# eviction watermarks, percent of max_size (reference cache watermarks)
LOW_WATERMARK = 0.7
HIGH_WATERMARK = 0.9


class _Entry:
    __slots__ = ("etag", "size", "atime")

    def __init__(self, etag: str, size: int, atime: float):
        self.etag = etag
        self.size = size
        self.atime = atime


class CacheLayer:
    """Transparent read-through cache.

    Delegates EVERYTHING to `inner`; only GETs consult/populate the
    cache, keyed by (bucket, object) and validated by ETag.  Writes and
    deletes invalidate.  Total cache bytes stay under `max_size` via
    LRU eviction to the low watermark once past the high watermark.
    """

    def __init__(self, inner, cache_dir: str, max_size: int = 10 << 30):
        self.inner = inner
        self.dir = cache_dir
        self.max_size = max_size
        self.hits = 0
        self.misses = 0
        self._mu = threading.Lock()
        self._entries: dict[str, _Entry] = {}
        self._filling: set[str] = set()  # in-flight fill dedup
        # bound TOTAL concurrent background fills (ranged-miss scans over
        # many cold objects must not spawn unbounded WAN downloads)
        self._fill_slots = threading.Semaphore(4)
        self._total = 0
        os.makedirs(cache_dir, exist_ok=True)
        self._load_index()
        # when the inner layer is the erasure server, register on its
        # ns_updated choke point (erasure/objects.py) — the same one
        # the in-RAM hot tier uses — so mutations that bypass this
        # wrapper (background heal rewrites, replication writes,
        # peer-applied deletes) invalidate too, not only the write
        # methods routed through CacheLayer itself
        try:
            from minio_tpu.erasure.objects import (add_ns_update_hook,
                                                   invalidation_plane)

            if invalidation_plane(inner)[0]:
                add_ns_update_hook(inner, self._invalidate)
        except Exception:
            pass  # pure gateway inner: method-level invalidation only

    # -- delegation ----------------------------------------------------------
    def __getattr__(self, name):
        return getattr(self.inner, name)

    # -- index ---------------------------------------------------------------
    def _key(self, bucket: str, obj: str) -> str:
        return hashlib.sha256(f"{bucket}/{obj}".encode()).hexdigest()

    def _data_path(self, key: str) -> str:
        return os.path.join(self.dir, key[:2], key + ".data")

    def _meta_path(self, key: str) -> str:
        return os.path.join(self.dir, key[:2], key + ".json")

    def _load_index(self) -> None:
        for root, _, files in os.walk(self.dir):
            for f in files:
                if not f.endswith(".json"):
                    continue
                try:
                    doc = json.loads(
                        open(os.path.join(root, f),
                             encoding="utf-8").read())
                    key = f[:-5]
                    dp = self._data_path(key)
                    size = os.path.getsize(dp)
                    self._entries[key] = _Entry(
                        doc["etag"], size, os.path.getatime(dp))
                    self._total += size
                except (OSError, ValueError, KeyError):
                    continue

    # -- read path -----------------------------------------------------------
    def open_object(self, bucket: str, obj: str, version_id: str = ""):
        # through get_object below, not past it to the inner layer's own
        # open (which __getattr__ would hand out): a served GET reads
        # the cache
        from minio_tpu.erasure.objects import open_by_info

        return open_by_info(self, bucket, obj, version_id)

    def get_object(self, bucket: str, obj: str, offset: int = 0,
                   length: int = -1, version_id: str = ""):
        if version_id:
            # versioned reads bypass the cache (cache is latest-only,
            # like the reference)
            return self.inner.get_object(bucket, obj, offset, length,
                                         version_id)
        oi = self.inner.get_object_info(bucket, obj)
        key = self._key(bucket, obj)
        with self._mu:
            ent = self._entries.get(key)
        if ent is not None and ent.etag == oi.etag:
            try:
                stream = self._read_cached(key, offset, length)
                # concurrent GETs race the bare += (read-modify-write
                # loses updates); counters ride the entry-table lock
                with self._mu:
                    self.hits += 1
                return oi, stream
            except OSError:
                self._evict_one(key)
        with self._mu:
            self.misses += 1
        if offset == 0 and length < 0:
            # full-object miss: tee the backend stream into the cache
            _, stream = self.inner.get_object(bucket, obj, 0, -1)
            return oi, self._tee(key, oi, stream)
        # ranged miss: serve the range directly, fill the cache in the
        # background so the next reader hits (deduped: one fill per key)
        _, stream = self.inner.get_object(bucket, obj, offset, length)
        with self._mu:
            start_fill = key not in self._filling
            if start_fill:
                start_fill = self._fill_slots.acquire(blocking=False)
                if start_fill:
                    self._filling.add(key)
        if start_fill:
            # background cache fill: deliberately budget-free — the
            # fill must finish even if the triggering request times out
            service_thread(self._fill, bucket, obj, key, oi,
                           name="cache-fill")
        return oi, stream

    def _read_cached(self, key: str, offset: int,
                     length: int) -> Iterator[bytes]:
        f = open(self._data_path(key), "rb")

        def chunks():
            try:
                f.seek(offset)
                remaining = length if length >= 0 else None
                while True:
                    n = 1 << 20 if remaining is None \
                        else min(1 << 20, remaining)
                    if n <= 0:
                        break
                    data = f.read(n)
                    if not data:
                        break
                    if remaining is not None:
                        remaining -= len(data)
                    yield data
            finally:
                f.close()

        with self._mu:
            ent = self._entries.get(key)
            if ent is not None:
                ent.atime = time.time()
        return chunks()

    def _tee(self, key: str, oi, stream) -> Iterator[bytes]:
        import uuid

        dp = self._data_path(key)
        os.makedirs(os.path.dirname(dp), exist_ok=True)
        # unique per writer: concurrent fills of the same key must never
        # interleave into one file (os.replace keeps commits atomic)
        tmp = dp + f".tmp.{uuid.uuid4().hex[:8]}"
        try:
            f = open(tmp, "wb")
        except OSError:
            yield from stream
            return
        ok = True
        try:
            for chunk in stream:
                try:
                    f.write(chunk)
                except OSError:
                    ok = False
                yield chunk
        except BaseException:
            ok = False
            raise
        finally:
            f.close()
            if ok:
                self._commit(key, oi, tmp, dp)
            else:
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    def _fill(self, bucket: str, obj: str, key: str, oi) -> None:
        try:
            _, stream = self.inner.get_object(bucket, obj, 0, -1)
            for _ in self._tee(key, oi, stream):
                pass
        except Exception:
            pass
        finally:
            with self._mu:
                self._filling.discard(key)
            self._fill_slots.release()

    def _commit(self, key: str, oi, tmp: str, dp: str) -> None:
        try:
            size = os.path.getsize(tmp)
            if size > self.max_size:
                os.remove(tmp)
                return
            os.replace(tmp, dp)
            with open(self._meta_path(key), "w", encoding="utf-8") as m:
                json.dump({"etag": oi.etag, "size": size}, m)
            with self._mu:
                old = self._entries.get(key)
                if old is not None:
                    self._total -= old.size
                self._entries[key] = _Entry(oi.etag, size, time.time())
                self._total += size
            self._maybe_evict()
        except OSError:
            pass

    # -- invalidation --------------------------------------------------------
    def _evict_one(self, key: str) -> None:
        with self._mu:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self._total -= ent.size
        for p in (self._data_path(key), self._meta_path(key)):
            try:
                os.remove(p)
            except OSError:
                pass

    def _maybe_evict(self) -> None:
        with self._mu:
            if self._total <= self.max_size * HIGH_WATERMARK:
                return
            victims = sorted(self._entries.items(),
                             key=lambda kv: kv[1].atime)
        target = self.max_size * LOW_WATERMARK
        for key, _ in victims:
            with self._mu:
                if self._total <= target:
                    return
            self._evict_one(key)
            log.debug("cache evicted", key=key)

    def _invalidate(self, bucket: str, obj: str) -> None:
        """The single write-path invalidation choke point: every
        mutation of (bucket, obj) — direct method or inner-layer
        ns_updated hook — routes through here, mirroring the in-RAM hot
        tier's invalidate() (serving/hotcache.py)."""
        self._evict_one(self._key(bucket, obj))

    def put_object(self, bucket: str, obj: str, *a, **kw):
        self._invalidate(bucket, obj)
        return self.inner.put_object(bucket, obj, *a, **kw)

    def copy_object(self, src_bucket: str, src_obj: str,
                    dst_bucket: str, dst_obj: str, *a, **kw):
        """Server-side copy ONTO a cached destination must invalidate
        it (reference CopyObject ordering: source pair, then
        destination).  Today's server implements CopyObject as
        get+put, which routes through put_object's invalidation — but
        the reference ObjectLayer has CopyObject as a first-class op
        (a layer may short-circuit to a metadata-only copy), and if an
        inner grows one, bare __getattr__ delegation would silently
        serve the stale cached destination.  This wrapper closes that
        protocol hole (regression test drives a copy-capable inner)."""
        fn = getattr(self.inner, "copy_object")
        self._invalidate(dst_bucket, dst_obj)
        return fn(src_bucket, src_obj, dst_bucket, dst_obj, *a, **kw)

    def delete_object(self, bucket: str, obj: str, *a, **kw):
        self._invalidate(bucket, obj)
        return self.inner.delete_object(bucket, obj, *a, **kw)

    def delete_objects(self, bucket: str, dels: list, *a, **kw):
        for d in dels:
            self._invalidate(bucket, d.get("obj", ""))
        return self.inner.delete_objects(bucket, dels, *a, **kw)

    def complete_multipart_upload(self, bucket: str, obj: str, *a, **kw):
        self._invalidate(bucket, obj)
        return self.inner.complete_multipart_upload(bucket, obj, *a, **kw)

    def stats(self) -> dict:
        with self._mu:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._entries), "bytes": self._total,
                    "maxBytes": self.max_size}
