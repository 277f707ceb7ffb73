"""Multi-process data plane differential + lifecycle suite (ISSUE 8).

The worker plane (minio_tpu/parallel/workers.py) must be INVISIBLE
except for speed: with MINIO_TPU_WORKERS=N every PUT's shard files,
xl.meta and etag are byte-identical to the workers=0 in-process
reference across aligned/unaligned/inline/multipart objects; a worker
killed mid-PUT degrades the write (surviving quorum commits, MRF heal
converges the missing shards) instead of corrupting it; deadline
budgets ride the job messages; and shutdown leaves zero worker
processes and zero /dev/shm segments (the conftest session check
enforces the same globally).
"""

from __future__ import annotations

import hashlib
import io
import os
import shutil
import tempfile
import time

import numpy as np
import pytest

from minio_tpu.erasure import multipart  # noqa: F401  (binds methods)
from minio_tpu.erasure.objects import ErasureObjects, PutObjectOptions
from minio_tpu.parallel import workers as workers_mod
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import deadline as deadline_mod

PINNED_DD = "d1d1d1d1-1111-4111-8111-111111111111"


def _shm_count() -> int:
    """Ring segments of this process (other pytest processes of the
    same run have planes of their own)."""
    try:
        return sum(1 for f in os.listdir("/dev/shm")
                   if f.startswith(workers_mod.segment_prefix()))
    except OSError:
        return 0


def _mp_children():
    import multiprocessing as mp

    return [p for p in mp.active_children()
            if (p.name or "").startswith("mtpu-")]


@pytest.fixture()
def plane_env(monkeypatch):
    """Enable a 2-worker plane for the test; the plane itself is a
    process-wide singleton reused across tests (spawn cost paid once),
    torn down by the session leak check."""
    monkeypatch.setenv("MINIO_TPU_WORKERS", "2")
    yield


def _mk_set(root: str, ndrives: int = 6, parity=None) -> ErasureObjects:
    disks = [LocalStorage(os.path.join(root, f"d{i}"))
             for i in range(ndrives)]
    for d in disks:
        d.make_volume("bkt")
    return ErasureObjects(disks, default_parity=parity)


def _drive_files(root: str) -> dict[str, bytes]:
    out = {}
    for dirpath, _, files in sorted(os.walk(root)):
        for f in sorted(files):
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


# --------------------------------------------------------- byte identity
class TestMpDifferential:
    @pytest.fixture()
    def two_sets(self, monkeypatch):
        roots = [tempfile.mkdtemp(prefix="mp-diff-") for _ in range(2)]
        monkeypatch.setattr("minio_tpu.erasure.objects.new_data_dir",
                            lambda: PINNED_DD)
        apis = [_mk_set(r) for r in roots]
        yield roots, apis
        for r in roots:
            shutil.rmtree(r, ignore_errors=True)

    @pytest.mark.parametrize("size", [
        100,                 # inline: shards live in xl.meta (plane bypassed
                             # by design — identical because same code path)
        200_000,             # non-inline single block
        (1 << 20) * 3 + 17,  # unaligned multi-block
        (4 << 20),           # aligned multi-block
    ])
    def test_put_object_identical(self, two_sets, monkeypatch, size):
        roots, apis = two_sets
        data = np.random.default_rng(size).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        opts = PutObjectOptions(mod_time=1_700_000_000.0)
        monkeypatch.setenv("MINIO_TPU_WORKERS", "2")
        oi_mp = apis[0].put_object("bkt", "o", io.BytesIO(data), size,
                                   opts)
        monkeypatch.setenv("MINIO_TPU_WORKERS", "0")
        oi_ref = apis[1].put_object("bkt", "o", io.BytesIO(data), size,
                                    opts)
        assert oi_mp.etag == oi_ref.etag == hashlib.md5(data).hexdigest()
        files_mp = _drive_files(roots[0])
        files_ref = _drive_files(roots[1])
        assert files_mp.keys() == files_ref.keys()
        for name in files_mp:
            assert files_mp[name] == files_ref[name], name
        # and the object reads back through the normal GET path
        _, stream = apis[0].get_object("bkt", "o")
        assert b"".join(stream) == data

    def test_multipart_identical(self, two_sets, monkeypatch):
        roots, apis = two_sets
        rng = np.random.default_rng(8)
        p1 = rng.integers(0, 256, 6 << 20, dtype=np.uint8).tobytes()
        p2 = rng.integers(0, 256, (1 << 20) + 13,
                          dtype=np.uint8).tobytes()
        etags = []
        for idx, workers in ((0, "2"), (1, "0")):
            monkeypatch.setenv("MINIO_TPU_WORKERS", workers)
            api = apis[idx]
            uid = api.new_multipart_upload("bkt", "mp")
            pi1 = api.put_object_part("bkt", "mp", uid, 1,
                                      io.BytesIO(p1), len(p1))
            pi2 = api.put_object_part("bkt", "mp", uid, 2,
                                      io.BytesIO(p2), len(p2))
            oi = api.complete_multipart_upload(
                "bkt", "mp", uid, [(1, pi1.etag), (2, pi2.etag)])
            etags.append((pi1.etag, pi2.etag, oi.etag))
            _, stream = api.get_object("bkt", "mp")
            assert b"".join(stream) == p1 + p2
        assert etags[0] == etags[1]
        assert etags[0][0] == hashlib.md5(p1).hexdigest()
        # shard part files byte-identical (xl.meta carries per-upload
        # timestamps/ids, same normalization as the PR 5 suite)
        vals_mp = sorted(v for k, v in _drive_files(roots[0]).items()
                         if k.endswith(("part.1", "part.2")))
        vals_ref = sorted(v for k, v in _drive_files(roots[1]).items()
                          if k.endswith(("part.1", "part.2")))
        assert vals_mp == vals_ref

    def test_chunked_reader_source(self, two_sets, monkeypatch):
        """read()-only sources (chunked-signature decoders, SSE
        transforms) must stream through the ring unchanged."""
        roots, apis = two_sets

        class ChunkReader:
            def __init__(self, data, chunk=77_777):
                self.bio = io.BytesIO(data)
                self.chunk = chunk

            def read(self, n=-1):
                want = self.chunk if n < 0 else min(n, self.chunk)
                return self.bio.read(want)

        size = (1 << 20) + 4242
        data = np.random.default_rng(4).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        opts = PutObjectOptions(mod_time=1_700_000_000.0)
        monkeypatch.setenv("MINIO_TPU_WORKERS", "2")
        oi = apis[0].put_object("bkt", "c", ChunkReader(data), size, opts)
        monkeypatch.setenv("MINIO_TPU_WORKERS", "0")
        oi2 = apis[1].put_object("bkt", "c", ChunkReader(data), size,
                                 opts)
        assert oi.etag == oi2.etag
        assert _drive_files(roots[0]) == _drive_files(roots[1])


# ----------------------------------------------------- worker-kill drill
class TestWorkerKillConvergence:
    def test_kill_worker_mid_put_degrades_and_heals(self, tmp_path,
                                                    monkeypatch):
        """SIGKILL one I/O worker while its PUT streams: the surviving
        workers' shards meet write quorum, the PUT acks, the missing
        shards are MRF-queued and heal_object converges them — and the
        supervisor respawns the worker so the NEXT put takes the plane
        again."""
        monkeypatch.setenv("MINIO_TPU_WORKERS", "3")
        heals = []
        api = _mk_set(str(tmp_path), ndrives=6, parity=2)  # k=4, wq=4
        api.heal_queue = lambda *a, **kw: heals.append(a)
        plane = workers_mod.get_plane()
        assert plane is not None and plane.ping()
        victim = plane.io[2]  # owns shards 4,5 — n - wq survivable
        victim_pid = victim.proc.pid

        size = 8 << 20
        data = np.random.default_rng(5).integers(
            0, 256, size, dtype=np.uint8).tobytes()

        class KillingReader:
            """Yields one chunk, kills the victim, yields the rest."""

            def __init__(self):
                self.bio = io.BytesIO(data)
                self.killed = False

            def read(self, n=-1):
                out = self.bio.read(min(n if n > 0 else 1 << 20, 1 << 20))
                if not self.killed:
                    self.killed = True
                    os.kill(victim_pid, 9)
                    deadline = time.monotonic() + 10
                    while victim.alive and time.monotonic() < deadline:
                        time.sleep(0.01)
                return out

        oi = api.put_object("bkt", "victim", KillingReader(), size)
        assert oi.etag == hashlib.md5(data).hexdigest()
        assert heals, "degraded PUT must enqueue an MRF heal"
        assert plane.stats()["workerDeaths"] >= 1

        # the committed copies read back clean even before heal
        _, stream = api.get_object("bkt", "victim")
        assert b"".join(stream) == data

        # heal converges the killed worker's shards
        res = api.heal_object("bkt", "victim")
        assert not res.failed
        assert res.healed_drives >= 1
        fi, missing = api.object_health("bkt", "victim")
        assert missing == 0

        # supervisor respawned the worker: the next PUT rides the plane
        deadline = time.monotonic() + 15
        while not victim.alive and time.monotonic() < deadline:
            time.sleep(0.05)
        assert victim.alive, "supervisor must respawn a killed worker"
        before_jobs = plane.stats()["jobs"]
        api.put_object("bkt", "after", io.BytesIO(data), size)
        assert plane.stats()["jobs"] == before_jobs + 1
        _, stream = api.get_object("bkt", "after")
        assert b"".join(stream) == data


# ------------------------------------------------- lifecycle and budgets
class TestPlaneLifecycle:
    def test_shutdown_leaves_no_processes_or_segments(self, tmp_path,
                                                      plane_env):
        api = _mk_set(str(tmp_path))
        data = os.urandom(1 << 20)
        for _ in range(3):
            api.put_object("bkt", "o", io.BytesIO(data), len(data))
        assert workers_mod.get_plane(create=False) is not None
        assert _mp_children()
        workers_mod.shutdown_plane()
        assert _shm_count() == 0, "shm segments must be unlinked"
        deadline = time.monotonic() + 10
        while _mp_children() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not _mp_children(), "worker processes must be reaped"

    def test_ring_pool_reuses_segments(self, tmp_path, plane_env):
        api = _mk_set(str(tmp_path))
        data = os.urandom(2 << 20)
        api.put_object("bkt", "o", io.BytesIO(data), len(data))
        count_after_one = _shm_count()
        for _ in range(4):
            api.put_object("bkt", "o", io.BytesIO(data), len(data))
        assert _shm_count() <= count_after_one + 1, \
            "per-PUT segment churn: the ring pool is not reusing"

    def test_service_manager_owns_plane_lifecycle(self, tmp_path,
                                                  plane_env):
        from minio_tpu.erasure.sets import ErasureServerPools, ErasureSets
        from minio_tpu.services import ServiceManager

        disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
        pools = ErasureServerPools([ErasureSets(disks)])
        mgr = ServiceManager(pools, scan_interval=3600,
                             heal_interval=3600)
        assert workers_mod.get_plane(create=False) is not None, \
            "ServiceManager must warm the plane at boot"
        mgr.close()
        assert workers_mod.get_plane(create=False) is None
        assert _shm_count() == 0

    def test_inline_and_remote_pass_through(self, tmp_path, plane_env):
        """Eligibility: inline-small objects and non-LocalStorage
        drives never enter the plane."""
        api = _mk_set(str(tmp_path))
        plane = workers_mod.get_plane()
        jobs0 = plane.stats()["jobs"]
        api.put_object("bkt", "small", io.BytesIO(b"x" * 100), 100)
        assert plane.stats()["jobs"] == jobs0, "inline PUT used the plane"
        assert workers_mod.plane_roots([None] + api.disks[1:]) is None

        class NotLocal:
            def is_online(self):
                return True

        assert workers_mod.plane_roots([NotLocal()]) is None

    def test_deadline_rides_job_messages(self, tmp_path, plane_env):
        """The cross-process twin of x-minio-tpu-deadline-ms: a bounded
        request budget lands in every job message as deadline_ms."""
        api = _mk_set(str(tmp_path))
        plane = workers_mod.get_plane()
        seen = []
        for h in plane.io + [plane.hash]:
            orig = h.send

            def wrap(msg, _orig=orig):
                seen.append((msg.get("op"), msg.get("deadline_ms")))
                return _orig(msg)

            h.send = wrap
        try:
            data = os.urandom(1 << 20)
            with deadline_mod.scope(deadline_mod.Budget(30.0)):
                api.put_object("bkt", "d", io.BytesIO(data), len(data))
        finally:
            for h in plane.io + [plane.hash]:
                if hasattr(h.send, "__wrapped__"):
                    pass
                h.send = type(h).send.__get__(h)
        puts = [ms for op, ms in seen if op in ("put_data", "hash")]
        commits = [ms for op, ms in seen if op == "commit"]
        assert puts and commits
        for ms in puts + commits:
            assert ms is not None and 0 < ms <= 30_000

    def test_wire_ms_helpers(self):
        assert deadline_mod.to_wire_ms() is None
        with deadline_mod.scope(deadline_mod.Budget(5.0)):
            ms = deadline_mod.to_wire_ms()
            assert ms is not None and 0 < ms <= 5000
            b = deadline_mod.from_wire_ms(ms)
            assert b is not None and b.remaining() <= 5.0
        assert deadline_mod.from_wire_ms(None) is None


# ------------------------------------------- node-batched remote commits
class TestBatchedRemoteCommit:
    def test_commit_all_groups_sibling_drives_by_node(self, tmp_path,
                                                      monkeypatch):
        """With MINIO_TPU_COMMIT_BATCH_RPC=1, _commit_all sends ONE
        rename_data_batch per remote node; the per-item results map
        back to per-drive commit slots.  (Default is OFF: a hung drive
        would convoy its node's whole batch — see _commit_all.)"""
        monkeypatch.setenv("MINIO_TPU_COMMIT_BATCH_RPC", "1")
        calls = []

        class FakeClient:
            pass

        class FakeRemote:
            def __init__(self, client, drive):
                self.client = client
                self.drive = drive

            def rename_data_batch(self, src_vol, src_path, items,
                                  dst_vol, dst_path):
                calls.append((self.drive, [dr for dr, _fi in items]))
                out = []
                from minio_tpu.storage import errors as st

                for dr, _fi in items:
                    out.append(st.FaultyDisk("boom") if dr == "bad"
                               else None)
                return out

        class Wrapped:
            def __init__(self, inner):
                self._inner = inner

            def unwrap(self):
                return self._inner

        api = _mk_set(str(tmp_path), ndrives=4)
        node_a = FakeClient()
        node_b = FakeClient()
        disks = [Wrapped(FakeRemote(node_a, "a1")),
                 Wrapped(FakeRemote(node_a, "bad")),
                 Wrapped(FakeRemote(node_b, "b1")),
                 Wrapped(FakeRemote(node_b, "b2"))]
        committed = []

        def commit(i):
            committed.append(i)

        errs = api._commit_all(commit, lambda i: f"fi{i}", disks,
                               inline=False, failed_shards=set(),
                               tmp_prefix="tmp/x", bucket="b", obj="o")
        assert len(calls) == 2  # one batch RPC per node
        assert sorted(len(dr) for _d, dr in calls) == [2, 2]
        assert not committed, "batched drives must not re-commit"
        assert errs[1] is not None and errs[0] is None
        assert errs[2] is None and errs[3] is None

    def test_batching_defaults_off(self, tmp_path):
        """Without the env gate the commit fan-out must stay strictly
        per-drive (hung-drive isolation is the default contract)."""
        calls = []

        class FakeClient:
            pass

        class FakeRemote:
            def __init__(self, client, drive):
                self.client = client
                self.drive = drive

            def rename_data_batch(self, *a, **kw):
                calls.append(a)
                return []

        class Wrapped:
            def __init__(self, inner):
                self._inner = inner

            def unwrap(self):
                return self._inner

        api = _mk_set(str(tmp_path), ndrives=2)
        cl = FakeClient()
        disks = [Wrapped(FakeRemote(cl, "a")), Wrapped(FakeRemote(cl, "b"))]
        committed = []
        api._commit_all(committed.append, lambda i: f"fi{i}", disks,
                        inline=False, failed_shards=set(),
                        tmp_prefix="tmp/x", bucket="b", obj="o")
        assert not calls, "batch RPC must be opt-in"
        assert sorted(committed) == [0, 1]

    def test_rpc_handler_round_trip(self, tmp_path):
        """Server-side rename_data_batch: per-item success/error slots
        against real LocalStorage drives."""
        from minio_tpu.distributed.rpc import RpcRouter
        from minio_tpu.distributed.storage_rpc import (_fi_to_wire,
                                                       register_storage_rpc)
        from minio_tpu.storage.xlmeta import FileInfo

        d = LocalStorage(str(tmp_path / "drv"))
        d.make_volume("bkt")
        d.append_file(".minio_tpu.sys", "tmp/u1/part.1", b"shard")
        router = RpcRouter("secret")
        register_storage_rpc(router, {"drv": d})
        fi = FileInfo(volume="bkt", name="o", version_id="",
                      data_dir="dd1", mod_time=1.0, size=5,
                      metadata={"etag": "x"}, parts=[])
        handler = router.methods["storage.rename_data_batch"]
        out = handler({
            "src_volume": ".minio_tpu.sys", "src_path": "tmp/u1",
            "dst_volume": "bkt", "dst_path": "o",
            "items": [{"drive": "drv", "fi": _fi_to_wire(fi)},
                      {"drive": "missing", "fi": _fi_to_wire(fi)}],
        }, b"")
        assert out["results"][0] is None
        assert out["results"][1]["type"] == "DiskNotFound"
        assert os.path.exists(str(tmp_path / "drv/bkt/o/xl.meta"))


# --------------------------------------- hot tier distributed gate flip
class TestHotcacheDistributedGateFlip:
    """ISSUE 8 satellite: the hot tier used to auto-disable when any
    drive was remote; with the hotcache_invalidate broadcast + TTL
    backstop it flips ON once the cluster wiring arrives."""

    @pytest.fixture()
    def pending_srv(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_HOTCACHE_BYTES", str(8 << 20))
        # make the (all-local) test layer LOOK distributed
        monkeypatch.setattr(
            "minio_tpu.erasure.objects.invalidation_plane",
            lambda layer: (True, False))
        from .s3_harness import S3TestServer

        srv = S3TestServer(str(tmp_path / "drives"), n_drives=4)
        yield srv
        srv.close()

    def test_disabled_until_peer_wiring_then_enabled(self, pending_srv):
        srv = pending_srv
        assert srv.server.hotcache is None
        assert srv.server._hotcache_pending_distributed is not None

        broadcasts = []
        assert srv.server.enable_distributed_hotcache(
            lambda b, o: broadcasts.append((b, o)))
        hc = srv.server.hotcache
        assert hc is not None
        # best-effort broadcast demands the TTL backstop
        assert hc.ttl_s > 0

        # a local mutation invalidates locally AND broadcasts to peers
        srv.request("PUT", "/bkt", data=b"")
        srv.request("PUT", "/bkt/k", data=b"hello world")
        assert ("bkt", "k") in broadcasts

        # a second enable is a no-op (idempotent wiring)
        assert not srv.server.enable_distributed_hotcache(lambda b, o: 0)

    def test_ttl_backstop_expires_entries(self):
        from minio_tpu.serving.hotcache import HotObjectCache

        hc = HotObjectCache(1 << 20, min_hits=1, ttl_s=0.05)
        oi = ObjectInfoStub()
        with hc._mu:
            hc._admit_locked(("b", "o", ""), oi, b"bytes",
                             hc._gen_of_locked(("b", "o")))
        assert hc.lookup("b", "o") is not None
        time.sleep(0.08)
        assert hc.probe("b", "o") is False
        assert hc.lookup("b", "o") is None

    def test_peer_rpc_handler_invalidates(self, tmp_path, monkeypatch):
        """peer.hotcache_invalidate drops the object on the receiving
        node's tier (the server half of the broadcast)."""
        monkeypatch.setenv("MINIO_TPU_HOTCACHE_BYTES", str(8 << 20))
        from .s3_harness import S3TestServer

        srv = S3TestServer(str(tmp_path / "drives"), n_drives=4)
        try:
            hc = srv.server.hotcache
            assert hc is not None
            oi = ObjectInfoStub()
            with hc._mu:
                hc._admit_locked(("b", "o", ""), oi, b"bytes",
                                 hc._gen_of_locked(("b", "o")))
            assert hc.probe("b", "o")
            from minio_tpu.distributed.peers import register_peer_rpc
            from minio_tpu.distributed.rpc import RpcRouter

            router = RpcRouter("secret")
            register_peer_rpc(router, srv.server)
            router.methods["peer.hotcache_invalidate"](
                {"bucket": "b", "obj": "o"}, b"")
            assert not hc.probe("b", "o")
        finally:
            srv.close()


def ObjectInfoStub():
    from minio_tpu.erasure.objects import ObjectInfo

    return ObjectInfo(bucket="b", name="o", size=5, etag="e",
                      mod_time=1.0)
