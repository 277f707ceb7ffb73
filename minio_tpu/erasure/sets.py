"""Erasure sets and server pools: the full ObjectLayer composition.

Reference topology (cmd/erasure-sets.go:53, cmd/erasure-server-pool.go:42):
pools -> erasure sets (4..16 drives) -> per-set erasureObjects.  Objects
route to a set by SipHash-2-4 of the name keyed with the deployment id
(cmd/erasure-sets.go:747); new objects route to the pool with available
capacity (cmd/erasure-server-pool.go:222); reads probe pools in order.
Drive membership is pinned by a per-drive `format.json`
(cmd/format-erasure.go:111) written on first boot.
"""

from __future__ import annotations

import io
import json
import random
import uuid
from dataclasses import dataclass, field
from typing import Iterator, Sequence

from minio_tpu.storage import errors
from minio_tpu.storage.api import StorageAPI
from minio_tpu.storage.local import SYSTEM_VOL
from minio_tpu.utils.hashing import sip_hash_mod
from .objects import (
    ErasureObjects, HealResult, NamespaceLock, ObjectInfo, PutObjectOptions,
    default_parity_count,
)
from . import multipart  # noqa: F401  (binds multipart methods)

FORMAT_FILE = "format.json"
FORMAT_VERSION = 1
DIST_ALGO = "SIPMOD+PARITY"  # reference formatErasureVersionV3DistributionAlgoV3

MIN_SET_SIZE = 1
MAX_SET_SIZE = 16


def _format_doc(deployment_id: str, set_layout: list[list[str]],
                this_disk: str) -> dict:
    return {
        "version": FORMAT_VERSION,
        "format": "erasure-tpu",
        "id": deployment_id,
        "erasure": {
            "version": 3,
            "this": this_disk,
            "sets": set_layout,
            "distributionAlgo": DIST_ALGO,
        },
    }


def choose_set_layout(n_drives: int, set_size: int | None = None) -> tuple[int, int]:
    """(set_count, set_drive_count) — largest legal set size dividing the
    drive count (simplified ellipses solver, cmd/endpoint-ellipses.go)."""
    if set_size:
        if n_drives % set_size:
            raise errors.InvalidArgument(
                f"{n_drives} drives not divisible into sets of {set_size}"
            )
        return n_drives // set_size, set_size
    for size in range(min(MAX_SET_SIZE, n_drives), 0, -1):
        if n_drives % size == 0:
            return n_drives // size, size
    return 1, n_drives


def _versioning_status_of(meta: dict) -> str:
    """Normalize the stored versioning value: legacy bool True reads as
    Enabled; otherwise the stored status string ('' | Enabled | Suspended)."""
    v = meta.get("versioning")
    if v is True:
        return "Enabled"
    return v or ""


def _versioning_status_arg(status) -> str:
    return ("Enabled" if status else "Suspended") \
        if isinstance(status, bool) else status


class ErasureSets:
    """One pool: drives split into erasure sets, sipHashMod routing."""

    def __init__(self, disks: Sequence[StorageAPI], set_size: int | None = None,
                 deployment_id: str | None = None, pool_index: int = 0,
                 default_parity: int | None = None, ns_lock=None):
        self.all_disks = list(disks)
        self.set_count, self.set_drive_count = choose_set_layout(
            len(self.all_disks), set_size
        )
        self.deployment_id = self._init_format(deployment_id)
        self.ns = ns_lock if ns_lock is not None else NamespaceLock()
        parity = (default_parity if default_parity is not None
                  else default_parity_count(self.set_drive_count))
        self.sets: list[ErasureObjects] = []
        for s in range(self.set_count):
            sd = self.all_disks[s * self.set_drive_count:(s + 1) * self.set_drive_count]
            self.sets.append(
                ErasureObjects(sd, default_parity=parity, set_index=s,
                               pool_index=pool_index, ns_lock=self.ns)
            )

    # -- format bootstrap (waitForFormatErasure analogue) -------------------
    def _init_format(self, deployment_id: str | None) -> str:
        existing: str | None = None
        unformatted = []
        for d in self.all_disks:
            try:
                doc = json.loads(d.read_all(SYSTEM_VOL, FORMAT_FILE))
                existing = existing or doc["id"]
                d.set_disk_id(doc["erasure"]["this"])
            except (errors.FileNotFound, errors.StorageError, KeyError,
                    json.JSONDecodeError):
                unformatted.append(d)
        dep_id = existing or deployment_id or str(uuid.uuid4())
        if unformatted:
            layout = [
                [f"d{s}-{i}" for i in range(self.set_drive_count)]
                for s in range(self.set_count)
            ]
            for idx, d in enumerate(self.all_disks):
                if d not in unformatted:
                    continue
                if not d.is_local():
                    # a peer's drive: its owning node formats it (the
                    # deployment id is deterministic across nodes, so the
                    # results agree — waitForFormatErasure analogue)
                    continue
                s, i = divmod(idx, self.set_drive_count)
                this = layout[s][i]
                try:
                    d.write_all(
                        SYSTEM_VOL, FORMAT_FILE,
                        json.dumps(_format_doc(dep_id, layout,
                                               this)).encode())
                except errors.StorageError:
                    # faulty drive at boot: quorum still carries the set;
                    # the drive monitor re-stamps it when it comes back
                    continue
                d.set_disk_id(this)
        return dep_id

    @property
    def _dep_bytes(self) -> bytes:
        return uuid.UUID(self.deployment_id).bytes

    def get_hashed_set(self, obj: str) -> ErasureObjects:
        return self.sets[sip_hash_mod(obj, self.set_count, self._dep_bytes)]

    # -- buckets ------------------------------------------------------------
    def make_bucket(self, bucket: str) -> None:
        made, exists = 0, 0
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                d.make_volume(bucket)
                made += 1
            except errors.VolumeExists:
                exists += 1
            except errors.StorageError:
                continue  # faulty drive: the others carry the bucket
        if made == 0 and exists == 0:
            raise errors.ErasureWriteQuorum("no drives for make_bucket")
        if made == 0 and exists > 0:
            raise errors.BucketExists(bucket)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        found = 0
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                d.delete_volume(bucket, force=force)
                found += 1
            except errors.VolumeNotFound:
                pass
        if found == 0:
            raise errors.BucketNotFound(bucket)
        # drop bucket metadata so a recreated bucket starts clean
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                d.delete(SYSTEM_VOL, f"buckets/{bucket}", recursive=True)
            except errors.StorageError:
                pass

    def list_buckets(self):
        seen = {}
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                for v in d.list_volumes():
                    seen.setdefault(v.name, v)
            except Exception:
                continue
        return [seen[k] for k in sorted(seen)]

    def bucket_exists(self, bucket: str) -> bool:
        last_fault: Exception | None = None
        saw_answer = False
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                d.stat_volume(bucket)
                return True
            except errors.VolumeNotFound:
                saw_answer = True
            except errors.StorageError as e:
                last_fault = e  # faulty drive: others decide
        if not saw_answer and last_fault is not None:
            # EVERY drive errored: "no such bucket" would be a lie —
            # surface the fault as a 5xx instead
            raise last_fault
        return False

    # -- object ops (delegate to hashed set) --------------------------------
    def put_object(self, bucket, obj, reader, size=-1, opts=None) -> ObjectInfo:
        return self.get_hashed_set(obj).put_object(bucket, obj, reader, size, opts)

    def get_object(self, bucket, obj, offset=0, length=-1, version_id=""):
        return self.get_hashed_set(obj).get_object(bucket, obj, offset, length,
                                                   version_id)

    def open_object(self, bucket, obj, version_id=""):
        return self.get_hashed_set(obj).open_object(bucket, obj, version_id)

    def get_object_info(self, bucket, obj, version_id="") -> ObjectInfo:
        return self.get_hashed_set(obj).get_object_info(bucket, obj, version_id)

    def contains(self, bucket, obj) -> bool:
        return self.get_hashed_set(obj).contains(bucket, obj)

    def delete_object(self, bucket, obj, version_id="", versioned=False,
                      suspended=False):
        return self.get_hashed_set(obj).delete_object(bucket, obj, version_id,
                                                      versioned, suspended)

    def put_delete_marker(self, bucket, obj, version_id, mod_time) -> None:
        self.get_hashed_set(obj).put_delete_marker(
            bucket, obj, version_id, mod_time)

    def heal_object(self, bucket, obj, version_id="", deep=False) -> HealResult:
        return self.get_hashed_set(obj).heal_object(bucket, obj, version_id, deep)

    def transition_version(self, bucket, obj, version_id, meta_updates,
                           expected_mod_time=0.0):
        return self.get_hashed_set(obj).transition_version(
            bucket, obj, version_id, meta_updates, expected_mod_time)

    def delete_objects(self, bucket, dels: list) -> list:
        """Bulk delete grouped per erasure set."""
        results = [None] * len(dels)
        by_set: dict[int, list] = {}
        for j, d0 in enumerate(dels):
            idx = sip_hash_mod(d0["obj"], self.set_count, self._dep_bytes)
            by_set.setdefault(idx, []).append(j)
        for idx, js in by_set.items():
            out = self.sets[idx].delete_objects(
                bucket, [dels[j] for j in js])
            for j, r in zip(js, out):
                results[j] = r
        return results

    def update_object_metadata(self, bucket, obj, updates, version_id=""):
        return self.get_hashed_set(obj).update_object_metadata(
            bucket, obj, updates, version_id)

    def put_object_tags(self, bucket, obj, tags, version_id=""):
        return self.get_hashed_set(obj).put_object_tags(
            bucket, obj, tags, version_id)

    def get_object_tags(self, bucket, obj, version_id=""):
        return self.get_hashed_set(obj).get_object_tags(
            bucket, obj, version_id)

    def delete_object_tags(self, bucket, obj, version_id=""):
        return self.get_hashed_set(obj).delete_object_tags(
            bucket, obj, version_id)

    def list_objects(self, bucket: str, prefix: str = "") -> list[str]:
        names: set[str] = set()
        any_vol = False
        for s in self.sets:
            try:
                names.update(s.list_objects(bucket, prefix))
                any_vol = True
            except errors.VolumeNotFound:
                continue
        if not any_vol and not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        return sorted(names)

    def list_entries(self, bucket: str, prefix: str = "", marker: str = "",
                     include_marker: bool = False):
        """Merged sorted (name, versions) stream across this pool's sets
        (cmd/metacache-set.go listPath per set, merged)."""
        from . import listing

        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)

        # set_list_entries raises VolumeNotFound lazily on first iteration;
        # a set whose drives all lost the bucket dir must not kill the merge
        def safe(it):
            try:
                yield from it
            except errors.VolumeNotFound:
                return

        return listing.merge_entry_streams([
            safe(listing.set_list_entries(s, bucket, prefix, marker,
                                          include_marker))
            for s in self.sets
        ])

    # -- multipart ----------------------------------------------------------
    def new_multipart_upload(self, bucket, obj, opts=None) -> str:
        return self.get_hashed_set(obj).new_multipart_upload(bucket, obj, opts)

    def put_object_part(self, bucket, obj, upload_id, part_number, reader,
                        size=-1):
        return self.get_hashed_set(obj).put_object_part(
            bucket, obj, upload_id, part_number, reader, size
        )

    def list_object_parts(self, bucket, obj, upload_id):
        return self.get_hashed_set(obj).list_object_parts(bucket, obj, upload_id)

    def list_all_multipart_uploads(self, bucket, prefix=""):
        out = []
        for es in self.sets:
            out += es.list_all_multipart_uploads(bucket, prefix)
        out.sort(key=lambda u: (u.object, u.upload_id))
        return out

    def abort_multipart_upload(self, bucket, obj, upload_id):
        return self.get_hashed_set(obj).abort_multipart_upload(bucket, obj,
                                                               upload_id)

    def complete_multipart_upload(self, bucket, obj, upload_id, parts):
        return self.get_hashed_set(obj).complete_multipart_upload(
            bucket, obj, upload_id, parts
        )

    # -- bucket metadata (bucket-metadata-sys lite) -------------------------
    # Reference: per-bucket .metadata.bin aggregate (cmd/bucket-metadata.go);
    # here a JSON doc persisted under the system volume on every drive.
    def _bucket_meta_path(self, bucket: str) -> str:
        return f"buckets/{bucket}/.metadata.json"

    def get_bucket_metadata(self, bucket: str) -> dict:
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                return json.loads(d.read_all(SYSTEM_VOL,
                                             self._bucket_meta_path(bucket)))
            except errors.StorageError:
                continue
        return {}

    def set_bucket_metadata(self, bucket: str, meta: dict) -> None:
        raw = json.dumps(meta).encode()
        wrote = 0
        for d in self.all_disks:
            if d is None or not d.is_online():
                continue
            try:
                d.write_all(SYSTEM_VOL, self._bucket_meta_path(bucket), raw)
                wrote += 1
            except errors.StorageError:
                continue
        if wrote == 0:
            raise errors.ErasureWriteQuorum("bucket metadata write failed")

    def update_bucket_metadata(self, bucket: str, **kv) -> None:
        meta = self.get_bucket_metadata(bucket)
        meta.update(kv)
        self.set_bucket_metadata(bucket, meta)

    def versioning_status(self, bucket: str) -> str:
        return _versioning_status_of(self.get_bucket_metadata(bucket))

    def versioning_enabled(self, bucket: str) -> bool:
        return self.versioning_status(bucket) == "Enabled"

    def set_versioning(self, bucket: str, status) -> None:
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        self.update_bucket_metadata(
            bucket, versioning=_versioning_status_arg(status))

    # -- info ---------------------------------------------------------------
    def storage_info(self) -> dict:
        disks = []
        for d in self.all_disks:
            try:
                di = d.disk_info()
                entry = {
                    "endpoint": di.endpoint, "total": di.total, "free": di.free,
                    "used": di.used, "online": d.is_online(), "id": di.id,
                    "healing": di.healing,
                }
                if hasattr(d, "op_stats"):
                    # instrumented wrapper: per-op counters + EWMA latency
                    entry["opStats"] = d.op_stats()
                if hasattr(d, "health_stats"):
                    # circuit-breaker state + trip/reconnect counters
                    entry["health"] = d.health_stats()
                disks.append(entry)
            except Exception as ex:
                # offline/broken drive: keep its identity and breaker
                # state visible so operators can see WHICH drive is out
                try:
                    ep = d.endpoint() or getattr(d, "root", "?")
                except Exception:
                    ep = getattr(d, "root", "?")
                entry = {"endpoint": ep, "online": False, "error": str(ex)}
                if hasattr(d, "health_stats"):
                    entry["health"] = d.health_stats()
                disks.append(entry)
        return {
            "sets": self.set_count, "drives_per_set": self.set_drive_count,
            "disks": disks, "deployment_id": self.deployment_id,
        }

    def free_space(self) -> int:
        total = 0
        for d in self.all_disks:
            try:
                total += d.disk_info().free
            except Exception:
                pass
        return total


class ErasureServerPools:
    """Multiple pools; deterministic-hash placement over non-suspended
    pools (erasure/pools.py), reads probe pools live-first so an object
    stays findable mid-drain (cmd/erasure-server-pool.go:222,289)."""

    def __init__(self, pools: Sequence[ErasureSets]):
        from . import pools as pools_mod

        if not pools:
            raise errors.InvalidArgument("no pools")
        self.pools = list(pools)
        # pools being (or finished being) decommissioned take no new
        # writes (cmd/erasure-server-pool-decom.go); state persists on
        # the pool's drives so restarts keep honoring it
        self.topology = pools_mod.TopologyState()
        for i, p in enumerate(self.pools):
            self._load_suspension(i, p)

    def _load_suspension(self, idx: int, pool: ErasureSets) -> None:
        from . import pools as pools_mod

        try:
            from minio_tpu.services.decom import load_state

            if load_state(pool).get("state") in pools_mod.SUSPEND_REASONS:
                self.topology.suspend(idx)
        except Exception:
            pass

    @property
    def _draining(self) -> set[int]:
        """Back-compat view of the suspended pool set."""
        return self.topology.suspended()

    def mark_draining(self, idx: int, draining: bool) -> None:
        if draining:
            self.topology.suspend(idx)
        else:
            self.topology.resume(idx)

    def add_pool(self, es: ErasureSets) -> int:
        """Online expansion (reference: restart with a new pool argument,
        cmd/erasure-server-pool.go — here the pool joins LIVE): existing
        buckets and their metadata are stamped onto the new pool so the
        bucket namespace stays uniform, then placement starts routing
        new objects to it.  Returns the new pool index."""
        buckets = [v.name for v in self.list_buckets()]
        for b in buckets:
            try:
                es.make_bucket(b)
            except errors.BucketExists:
                pass
            meta = self.get_bucket_metadata(b)
            if meta:
                try:
                    es.set_bucket_metadata(b, meta)
                except errors.StorageError:
                    pass  # quorum of the new pool carries it later
        self.pools.append(es)
        idx = len(self.pools) - 1
        # a pool can arrive carrying a persisted drain state (re-added
        # after a decommission): honor it, same as boot
        self._load_suspension(idx, es)
        return idx

    def _read_pools(self) -> list[ErasureSets]:
        """Pools in read-probe order: live pools first, suspended last —
        mid-drain both may hold a version, and the destination copy is
        the authoritative one (write-fence: it is quorum-committed
        before the source copy dies)."""
        from . import pools as pools_mod

        order = pools_mod.read_order(len(self.pools),
                                     self.topology.suspended())
        return [self.pools[i] for i in order]

    # -- bucket ops over all pools -----------------------------------------
    def make_bucket(self, bucket: str) -> None:
        if self.bucket_exists(bucket):
            raise errors.BucketExists(bucket)
        for p in self.pools:
            p.make_bucket(bucket)

    def delete_bucket(self, bucket: str, force: bool = False) -> None:
        if not force:
            for p in self.pools:
                if p.list_objects(bucket):
                    raise errors.BucketNotEmpty(bucket)
        for p in self.pools:
            p.delete_bucket(bucket, force=force)

    def list_buckets(self):
        return self.pools[0].list_buckets()

    def bucket_exists(self, bucket: str) -> bool:
        return any(p.bucket_exists(bucket) for p in self.pools)

    # -- placement ----------------------------------------------------------
    def _pool_of(self, bucket: str, obj: str) -> ErasureSets | None:
        """Pool already holding the object — ANY version counts, including
        a delete-marker latest (else a marker-topped object could never be
        version-addressed or permanently deleted).  Probes in read order
        (live pools first) so mid-drain the destination copy wins."""
        for p in self._read_pools():
            if p.contains(bucket, obj):
                return p
        return None

    def _marker_pool(self, bucket: str, obj: str) -> ErasureSets:
        """Pool for a FRESH delete marker (versioned DELETE of an
        object no pool holds): placement-routed, so it can never land
        in a suspended pool and keep a drained pool non-empty."""
        try:
            return self._pool_for_new(obj, 0, bucket=bucket)
        except errors.StorageError:
            return self.pools[0]

    def _pool_of_write(self, bucket: str, obj: str) -> ErasureSets | None:
        """Write-routing probe: like _pool_of but NEVER a suspended pool
        — an overwrite landing mid-drain must go to a live pool, or the
        drain chases a moving target (the new version would land behind
        the drain cursor and be left, or worse re-moved, by it)."""
        suspended = self.topology.suspended()
        for i, p in enumerate(self.pools):
            if i in suspended:
                continue
            if p.contains(bucket, obj):
                return p
        return None

    # per-drive free-space floor a PUT may not dip under (reference
    # diskMinFreeSpace, internal/disk/disk.go)
    MIN_FREE = 1 << 20

    def _pool_available(self, obj: str, size: int) -> list[int]:
        """Available bytes per pool on the set `obj` hashes to, 0 when the
        pool cannot hold `size` more bytes
        (cmd/erasure-server-pool.go:241 getServerPoolsAvailableSpace)."""
        out = []
        suspended = self.topology.suspended()
        for pi, p in enumerate(self.pools):
            if pi in suspended:
                out.append(0)  # decommissioning pools take no new data
                continue
            s = p.get_hashed_set(obj)
            infos = []
            for d in s.disks:
                try:
                    if d is not None and d.is_online():
                        infos.append(d.disk_info())
                except errors.StorageError:
                    pass
                except Exception:
                    pass
            if not infos:
                out.append(0)
                continue
            # an erasure write lands ~size/K bytes on every drive of the
            # set; every reporting drive must fit that with MIN_FREE left
            k = max(len(s.disks) - s.default_parity, 1)
            per_drive = (max(size, 0) + k - 1) // k
            if any(i.free < per_drive + self.MIN_FREE for i in infos):
                out.append(0)
                continue
            out.append(sum(max(i.total - i.used, 0) for i in infos))
        return out

    def _pool_for_new(self, obj: str = "", size: int = 0,
                      bucket: str = "") -> ErasureSets:
        """Pool for a NEW object.  Default: deterministic SipHash over
        the non-suspended pools with rotated capacity fallback
        (erasure/pools.py — stable across restarts and identical on
        every node, which is what makes "suspended from placement"
        enforceable during a drain).  The hash keys on bucket/object —
        same-named objects in different buckets must not co-locate.
        MINIO_TPU_POOL_PLACEMENT=space restores the seed's
        weighted-random-by-free-space choice
        (cmd/erasure-server-pool.go:222 getAvailablePoolIdx)."""
        from . import pools as pools_mod

        if len(self.pools) == 1:
            return self.pools[0]
        avail = self._pool_available(obj, size)
        if pools_mod.placement_mode() == "hash":
            # index domain = len(avail), NOT len(self.pools): a
            # concurrent add_pool can append between the two reads and
            # an index past avail would IndexError an in-flight PUT
            eligible = pools_mod.eligible_indices(
                len(avail), self.topology.suspended())
            key = f"{bucket}/{obj}" if bucket else obj
            for idx in pools_mod.placement_order(
                    key, eligible, self.pools[0]._dep_bytes):
                if avail[idx] > 0:
                    return self.pools[idx]
            raise errors.DiskFull(
                f"no pool has space for {size} more bytes")
        total = sum(avail)
        if total == 0:
            raise errors.DiskFull(
                f"no pool has space for {size} more bytes")
        choose = random.randrange(total)
        at = 0
        for p, a in zip(self.pools, avail):
            at += a
            if at > choose and a > 0:
                return p
        return max(zip(self.pools, avail), key=lambda t: t[1])[0]

    # -- object ops ---------------------------------------------------------
    def put_object(self, bucket, obj, reader, size=-1, opts=None) -> ObjectInfo:
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        pool = self._pool_of_write(bucket, obj) \
            if len(self.pools) > 1 else self.pools[0]
        if pool is None:
            pool = self._pool_for_new(obj, max(size, 0), bucket=bucket)
        return pool.put_object(bucket, obj, reader, size, opts)

    def _read_pools_first(self, bucket, obj, call):
        """`call(pool)` of the first pool, in read order, that holds the
        object (the routing of every read entry point)."""
        last: Exception = errors.ObjectNotFound(f"{bucket}/{obj}")
        for p in self._read_pools():
            try:
                return call(p)
            except (errors.ObjectNotFound, errors.VersionNotFound) as ex:
                last = ex
        # error path only: a miss in a bucket that does not exist is
        # NoSuchBucket, not NoSuchKey (AWS + reference semantics)
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        raise last

    def get_object(self, bucket, obj, offset=0, length=-1, version_id=""):
        return self._read_pools_first(bucket, obj, lambda p: p.get_object(
            bucket, obj, offset, length, version_id))

    def open_object(self, bucket, obj, version_id=""):
        return self._read_pools_first(
            bucket, obj, lambda p: p.open_object(bucket, obj, version_id))

    def get_object_info(self, bucket, obj, version_id="") -> ObjectInfo:
        return self._read_pools_first(
            bucket, obj, lambda p: p.get_object_info(bucket, obj, version_id))

    def delete_objects(self, bucket, dels: list) -> list:
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        if len(self.pools) == 1:
            return self.pools[0].delete_objects(bucket, dels)
        # multi-pool: group by owning pool, idempotent-miss for absent
        results: list = [None] * len(dels)
        by_pool: dict[int, list] = {}
        for j, d0 in enumerate(dels):
            p = self._pool_of(bucket, d0["obj"])
            if p is None:
                if (d0.get("versioned") or d0.get("suspended")) \
                        and not d0.get("version_id"):
                    p = self._marker_pool(bucket, d0["obj"])
                else:
                    results[j] = ObjectInfo(
                        bucket=bucket, name=d0["obj"],
                        version_id=d0.get("version_id", ""))
                    continue
            by_pool.setdefault(self.pools.index(p), []).append(j)
        for pi, js in by_pool.items():
            out = self.pools[pi].delete_objects(bucket,
                                                [dels[j] for j in js])
            for j, r in zip(js, out):
                results[j] = r
        return results

    def delete_object(self, bucket, obj, version_id="", versioned=False,
                      suspended=False):
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        pool = self._pool_of(bucket, obj)
        if pool is None:
            if (versioned or suspended) and not version_id:
                pool = self._marker_pool(bucket, obj)
            else:
                return ObjectInfo(bucket=bucket, name=obj, version_id=version_id)
        # NOTE: when the owning pool is suspended the marker still
        # lands THERE — a marker must shadow its versions within one
        # pool (the read fan-out treats a pool's marker-latest as
        # not-found and would otherwise keep probing and serve the
        # undeleted versions).  A marker landing behind the drain
        # cursor is an entry the verification sweep re-lists and moves.
        return pool.delete_object(bucket, obj, version_id, versioned, suspended)

    def put_delete_marker(self, bucket, obj, version_id, mod_time) -> None:
        """Replay a delete marker with its id + mod time pinned (decom
        move_version, georep apply).  Same routing rule as
        delete_object: the marker must shadow its versions within the
        OWNING pool, falling back to the deterministic marker pool for
        an object this deployment never held."""
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        pool = self._pool_of(bucket, obj) or self._marker_pool(bucket, obj)
        pool.put_delete_marker(bucket, obj, version_id, mod_time)

    def heal_object(self, bucket, obj, version_id="", deep=False) -> HealResult:
        for p in self.pools:
            res = p.heal_object(bucket, obj, version_id, deep)
            if not res.failed:
                return res
        return HealResult(failed=True)

    def transition_version(self, bucket, obj, version_id, meta_updates,
                           expected_mod_time=0.0):
        p = self._pool_of(bucket, obj)
        if p is None:
            raise errors.ObjectNotFound(f"{bucket}/{obj}")
        return p.transition_version(bucket, obj, version_id, meta_updates,
                                    expected_mod_time)

    def update_object_metadata(self, bucket, obj, updates, version_id=""):
        p = self._pool_of(bucket, obj)
        if p is None:
            raise errors.ObjectNotFound(f"{bucket}/{obj}")
        return p.update_object_metadata(bucket, obj, updates, version_id)

    def put_object_tags(self, bucket, obj, tags, version_id=""):
        return self.update_object_metadata(
            bucket, obj, {ErasureObjects.TAGS_KEY: tags}, version_id)

    def get_object_tags(self, bucket, obj, version_id=""):
        return self.get_object_info(
            bucket, obj, version_id).metadata.get(ErasureObjects.TAGS_KEY, "")

    def delete_object_tags(self, bucket, obj, version_id=""):
        return self.update_object_metadata(
            bucket, obj, {ErasureObjects.TAGS_KEY: None}, version_id)

    def list_objects(self, bucket: str, prefix: str = "") -> list[str]:
        names: set[str] = set()
        found = False
        for p in self.pools:
            try:
                names.update(p.list_objects(bucket, prefix))
                found = True
            except errors.BucketNotFound:
                continue
        if not found:
            raise errors.BucketNotFound(bucket)
        return sorted(names)

    def list_entries(self, bucket: str, prefix: str = "", marker: str = "",
                     include_marker: bool = False):
        """Globally sorted entry stream across pools; same-name collisions
        resolve to the newest version (pool-probe semantics)."""
        from . import listing

        streams = []
        found = False
        for p in self.pools:
            try:
                streams.append(
                    p.list_entries(bucket, prefix, marker, include_marker)
                )
                found = True
            except errors.BucketNotFound:
                continue
        if not found:
            raise errors.BucketNotFound(bucket)
        return listing.merge_entry_streams(streams)

    # -- multipart (route to the pool that will own the object) -------------
    def list_all_multipart_uploads(self, bucket, prefix=""):
        out = []
        for p in self.pools:
            out += p.list_all_multipart_uploads(bucket, prefix)
        out.sort(key=lambda u: (u.object, u.upload_id))
        return out

    def new_multipart_upload(self, bucket, obj, opts=None) -> str:
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        pool = self._pool_of_write(bucket, obj) \
            or self._pool_for_new(obj, bucket=bucket)
        return pool.new_multipart_upload(bucket, obj, opts)

    def _pool_with_upload(self, bucket, obj, upload_id) -> ErasureSets:
        for p in self.pools:
            try:
                p.get_hashed_set(obj)._upload_meta(bucket, obj, upload_id)
                return p
            except errors.StorageError:
                continue
        raise errors.InvalidArgument(f"upload id {upload_id} not found")

    def put_object_part(self, bucket, obj, upload_id, part_number, reader,
                        size=-1):
        return self._pool_with_upload(bucket, obj, upload_id).put_object_part(
            bucket, obj, upload_id, part_number, reader, size
        )

    def list_object_parts(self, bucket, obj, upload_id):
        return self._pool_with_upload(bucket, obj, upload_id).list_object_parts(
            bucket, obj, upload_id
        )

    def abort_multipart_upload(self, bucket, obj, upload_id):
        return self._pool_with_upload(bucket, obj, upload_id).abort_multipart_upload(
            bucket, obj, upload_id
        )

    def complete_multipart_upload(self, bucket, obj, upload_id, parts):
        return self._pool_with_upload(bucket, obj, upload_id).complete_multipart_upload(
            bucket, obj, upload_id, parts
        )

    def storage_info(self) -> dict:
        return {"pools": [p.storage_info() for p in self.pools]}

    # -- bucket metadata ----------------------------------------------------
    def get_bucket_metadata(self, bucket: str) -> dict:
        for p in self.pools:
            meta = p.get_bucket_metadata(bucket)
            if meta:
                return meta
        return {}

    def set_bucket_metadata(self, bucket: str, meta: dict) -> None:
        for p in self.pools:
            p.set_bucket_metadata(bucket, meta)

    def update_bucket_metadata(self, bucket: str, **kv) -> None:
        for p in self.pools:
            p.update_bucket_metadata(bucket, **kv)

    def versioning_status(self, bucket: str) -> str:
        return _versioning_status_of(self.get_bucket_metadata(bucket))

    def versioning_enabled(self, bucket: str) -> bool:
        return self.versioning_status(bucket) == "Enabled"

    def set_versioning(self, bucket: str, status) -> None:
        if not self.bucket_exists(bucket):
            raise errors.BucketNotFound(bucket)
        for p in self.pools:
            p.update_bucket_metadata(
                bucket, versioning=_versioning_status_arg(status))
