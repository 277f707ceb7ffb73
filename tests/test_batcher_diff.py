"""Device-resident erasure batcher differential + lifecycle suite
(erasure/batcher.py, ISSUE 11).

The batcher must be INVISIBLE except for dispatch count: with
MINIO_TPU_BATCHER=1 every PUT's shard files/xl.meta/etag, every GET
body, every healed/repaired frame is byte-identical to the gate-off
per-request reference across aligned/unaligned/inline/multipart/heal
shapes; N concurrent same-geometry submissions within one tick produce
EXACTLY one fused dispatch (counter-asserted); an item whose deadline
budget expires in queue is shed; a tick-thread death fails queued items
retryable and the caller falls back to the per-request plane; gate-off
restores the legacy path bit for bit; and shutdown leaves zero batcher
threads.

The tick/submit/quiesce protocol itself is model-checked in
tests/test_modelcheck.py (analysis/concurrency/models/batcher.py);
this suite keeps the IMPLEMENTATION honest against that spec.
"""

from __future__ import annotations

import glob
import hashlib
import io
import os
import threading

import numpy as np
import pytest

from minio_tpu.erasure import batcher as batcher_mod
from minio_tpu.erasure import coding, multipart  # noqa: F401  (binds methods)
from minio_tpu.erasure.objects import ErasureObjects, PutObjectOptions
from minio_tpu.storage import errors
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import deadline as deadline_mod

PINNED_DD = "b11b11b1-1111-4111-8111-111111111111"
HSIZE = 32  # HighwayHash-256 frame hash

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rng(seed=0):
    return np.random.default_rng(seed)


@pytest.fixture(autouse=True)
def _batcher_teardown(monkeypatch):
    """Every test leaves no batcher (and no batcher thread) behind;
    a wide tick keeps coalescing deterministic under load."""
    monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "20000")
    yield
    batcher_mod.shutdown()
    assert not [t for t in threading.enumerate()
                if t.name == "erasure-batcher"], "batcher thread leaked"


def _mk_set(root: str, ndrives: int = 6, parity=None) -> ErasureObjects:
    disks = [LocalStorage(os.path.join(root, f"d{i}"))
             for i in range(ndrives)]
    for d in disks:
        d.make_volume("bkt")
    return ErasureObjects(disks, default_parity=parity)


def _drive_files(root: str) -> dict[str, bytes]:
    out = {}
    for dirpath, dirs, files in sorted(os.walk(root)):
        if ".minio_tpu.sys" in dirpath:
            # system volume churns asynchronously (trash sweeper
            # unlinks between walk and open) and its uuid-named paths
            # can never be byte-compared across sets anyway
            dirs[:] = []
            continue
        for f in sorted(files):
            p = os.path.join(dirpath, f)
            try:
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = fh.read()
            except FileNotFoundError:
                continue  # async cleanup won the race: not object data
    return out


@pytest.fixture()
def two_sets(tmp_path, monkeypatch):
    roots = [str(tmp_path / "on"), str(tmp_path / "off")]
    monkeypatch.setattr("minio_tpu.erasure.objects.new_data_dir",
                        lambda: PINNED_DD)
    yield roots, [_mk_set(r) for r in roots]


# --------------------------------------------------------- byte identity
class TestBatcherDifferential:
    @pytest.mark.parametrize("size", [
        100,                 # inline: shards live in xl.meta
        200_000,             # non-inline single block
        (1 << 20) * 3 + 17,  # unaligned multi-block
        (4 << 20),           # aligned multi-block
    ])
    def test_put_object_identical(self, two_sets, monkeypatch, size):
        roots, apis = two_sets
        data = _rng(size).integers(0, 256, size, dtype=np.uint8).tobytes()
        opts = PutObjectOptions(mod_time=1_700_000_000.0)
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        oi_on = apis[0].put_object("bkt", "o", io.BytesIO(data), size,
                                   opts)
        monkeypatch.setenv("MINIO_TPU_BATCHER", "0")
        oi_off = apis[1].put_object("bkt", "o", io.BytesIO(data), size,
                                    opts)
        assert oi_on.etag == oi_off.etag == hashlib.md5(data).hexdigest()
        files_on = _drive_files(roots[0])
        files_off = _drive_files(roots[1])
        assert files_on.keys() == files_off.keys()
        for name in files_on:
            assert files_on[name] == files_off[name], name
        # and the object reads back batched too
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        _, stream = apis[0].get_object("bkt", "o")
        assert b"".join(bytes(c) for c in stream) == data

    def test_multipart_identical(self, two_sets, monkeypatch):
        roots, apis = two_sets
        rng = _rng(11)
        p1 = rng.integers(0, 256, 6 << 20, dtype=np.uint8).tobytes()
        p2 = rng.integers(0, 256, (5 << 20) + 313, dtype=np.uint8).tobytes()
        etags = []
        for gate, api in (("1", apis[0]), ("0", apis[1])):
            monkeypatch.setenv("MINIO_TPU_BATCHER", gate)
            up = api.new_multipart_upload("bkt", "mp")
            pi1 = api.put_object_part("bkt", "mp", up, 1,
                                      io.BytesIO(p1), len(p1))
            pi2 = api.put_object_part("bkt", "mp", up, 2,
                                      io.BytesIO(p2), len(p2))
            oi = api.complete_multipart_upload(
                "bkt", "mp", up, [(1, pi1.etag), (2, pi2.etag)])
            etags.append((pi1.etag, pi2.etag, oi.etag))
            _, stream = api.get_object("bkt", "mp")
            assert b"".join(bytes(c) for c in stream) == p1 + p2
        assert etags[0] == etags[1]
        # shard part files byte-identical (xl.meta carries per-upload
        # timestamps/ids, same normalization as the PR 5/8 suites)
        vals_on = sorted(v for k, v in _drive_files(roots[0]).items()
                         if k.endswith(("part.1", "part.2")))
        vals_off = sorted(v for k, v in _drive_files(roots[1]).items()
                          if k.endswith(("part.1", "part.2")))
        assert vals_on == vals_off

    def test_degraded_get_identical(self, two_sets, monkeypatch):
        """A reconstructing GET (one shard file gone) through the
        batcher returns the exact payload."""
        roots, apis = two_sets
        data = _rng(3).integers(0, 256, (2 << 20) + 99,
                                dtype=np.uint8).tobytes()
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        apis[0].put_object("bkt", "o", io.BytesIO(data), len(data),
                           PutObjectOptions())
        # kill a drive that holds a DATA shard, so the GET must
        # reconstruct (a lost parity shard decodes without the codec)
        fi, _, _ = apis[0]._quorum_info("bkt", "o")
        victim = next(i for i, pos in enumerate(fi.erasure.distribution)
                      if pos - 1 < fi.erasure.data_blocks)
        for p in glob.glob(os.path.join(roots[0], f"d{victim}", "bkt",
                                        "**", "part.*"), recursive=True):
            os.unlink(p)
        st0 = batcher_mod.stats_snapshot()
        _, stream = apis[0].get_object("bkt", "o")
        assert b"".join(bytes(c) for c in stream) == data
        st1 = batcher_mod.stats_snapshot()
        # the reconstruct went THROUGH the batcher, not around it
        assert st1["items"] > st0["items"]

    def test_heal_identical_and_repaired_frames(self, two_sets,
                                                monkeypatch):
        """Latent-damage deep heal (the sub-shard repair executor) and
        the legacy full decode both converge to pristine bytes with the
        gate on — and the twin gate-off heal produces the same files."""
        roots, apis = two_sets
        size = (1 << 20) + 137 * 4
        data = _rng(7).integers(0, 256, size, dtype=np.uint8).tobytes()
        opts = PutObjectOptions(mod_time=1_700_000_000.0)
        frame = HSIZE + coding.Erasure(4, 2).shard_size
        snaps = {}
        for gate, api, root in (("1", apis[0], roots[0]),
                                ("0", apis[1], roots[1])):
            monkeypatch.setenv("MINIO_TPU_BATCHER", gate)
            api.put_object("bkt", "h", io.BytesIO(data), size, opts)
            files = sorted(glob.glob(os.path.join(
                root, "d1", "bkt", "**", "part.*"), recursive=True))
            assert files
            pristine = {p: open(p, "rb").read() for p in files}
            for p in files:
                buf = bytearray(pristine[p])
                buf[HSIZE + 3] ^= 0xA5  # frame 0 payload corruption
                with open(p, "wb") as f:
                    f.write(bytes(buf))
            res = api.heal_object("bkt", "h", deep=True)
            assert not res.failed and res.healed_drives == 1
            healed = {p: open(p, "rb").read() for p in files}
            assert healed == pristine, f"gate={gate} heal diverged"
            snaps[gate] = _drive_files(root)  # sys volume excluded
        assert snaps["1"] == snaps["0"]


# ---------------------------------------------------- collapse accounting
class TestCollapse:
    def test_same_tick_submissions_one_dispatch(self, monkeypatch):
        """N concurrent same-geometry submissions inside one tick = 1
        fused device dispatch, counter-asserted on BOTH the batcher and
        the codec backend stats (the ISSUE 11 acceptance clause)."""
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "100000")
        e = coding.Erasure(8, 4)
        batch = _rng(0).integers(0, 256, (4, 8, 8192), dtype=np.uint8)
        ref = e._encode_shards_raw(batch)
        st0 = batcher_mod.get().stats_snapshot()
        n = 6
        with coding._stats_lock:
            disp0 = sum(v["dispatches"]
                        for v in coding.backend_stats.values())
        outs = [None] * n
        bar = threading.Barrier(n)

        def run(i):
            bar.wait()
            outs[i] = e._encode_shards(batch)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for o in outs:
            np.testing.assert_array_equal(o, ref)
        st1 = batcher_mod.get().stats_snapshot()
        assert st1["items"] - st0["items"] == n
        assert st1["dispatches"] - st0["dispatches"] == 1, (
            "same-tick same-geometry submissions did not collapse: "
            f"{st1}")
        assert st1["coalesced_items"] - st0["coalesced_items"] == n
        with coding._stats_lock:
            disp1 = sum(v["dispatches"]
                        for v in coding.backend_stats.values())
        assert disp1 - disp0 == 1, "codec saw more than one dispatch"

    def test_mixed_geometry_tick_subdispatches(self, monkeypatch):
        """Two geometries inside one tick produce one dispatch EACH —
        never a cross-signature pad (model invariant
        single-signature-tick)."""
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "100000")
        e1 = coding.Erasure(8, 4)
        e2 = coding.Erasure(4, 2)
        b1 = _rng(1).integers(0, 256, (2, 8, 8192), dtype=np.uint8)
        b2 = _rng(2).integers(0, 256, (2, 4, 8192), dtype=np.uint8)
        r1 = e1._encode_shards_raw(b1)
        r2 = e2._encode_shards_raw(b2)
        st0 = batcher_mod.get().stats_snapshot()
        outs = {}
        bar = threading.Barrier(2)

        def run(key, e, b):
            bar.wait()
            outs[key] = e._encode_shards(b)

        ts = [threading.Thread(target=run, args=("a", e1, b1)),
              threading.Thread(target=run, args=("b", e2, b2))]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        np.testing.assert_array_equal(outs["a"], r1)
        np.testing.assert_array_equal(outs["b"], r2)
        st1 = batcher_mod.get().stats_snapshot()
        assert st1["items"] - st0["items"] == 2
        assert st1["dispatches"] - st0["dispatches"] == 2

    def test_backlog_chunked_at_byte_watermark(self, monkeypatch):
        """A same-signature backlog larger than MAX_BYTES splits into
        multiple fused dispatches — one unbounded concatenation would
        double peak RAM and blow device memory (code-review pin)."""
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "100000")
        # floor of max_batch_bytes is 1 MiB; 4 x 512 KiB items = 2 MiB
        monkeypatch.setenv("MINIO_TPU_BATCH_MAX_BYTES", str(1 << 20))
        e = coding.Erasure(8, 4)
        batch = _rng(5).integers(0, 256, (8, 8, 8192), dtype=np.uint8)
        ref = e._encode_shards_raw(batch)
        st0 = batcher_mod.get().stats_snapshot()
        outs = [None] * 4
        bar = threading.Barrier(4)

        def run(i):
            bar.wait()
            outs[i] = e._encode_shards(batch)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for o in outs:
            np.testing.assert_array_equal(o, ref)
        st1 = batcher_mod.get().stats_snapshot()
        assert st1["items"] - st0["items"] == 4
        # 4 x 512 KiB at a 1 MiB cap = 2 fused dispatches, never 1
        assert 2 <= st1["dispatches"] - st0["dispatches"] <= 4

    def test_set_major_order(self):
        order = batcher_mod.set_major_order([3, 1, 3, 0, 1])
        assert [int(i) for i in order] == [3, 1, 4, 0, 2]  # stable


# ------------------------------------------------------ failure semantics
class TestLifecycle:
    def test_deadline_expired_in_queue_shed(self, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        e = coding.Erasure(4, 2)
        batch = _rng(0).integers(0, 256, (1, 4, 8192), dtype=np.uint8)
        with deadline_mod.scope(deadline_mod.Budget(0.0)):
            with pytest.raises(errors.DeadlineExceeded):
                e._encode_shards(batch)
        st = batcher_mod.stats_snapshot()
        assert st["shed_deadline"] >= 1

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_batcher_death_falls_back_per_request(self, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        e = coding.Erasure(4, 2)
        batch = _rng(0).integers(0, 256, (2, 4, 8192), dtype=np.uint8)
        ref = e._encode_shards_raw(batch)
        b = batcher_mod.get()
        assert b is not None and b.alive()

        def boom(self, bucket):
            raise RuntimeError("injected tick fault")

        monkeypatch.setattr(batcher_mod.Batcher, "_flush_bucket", boom)
        # the queued item fails retryable; the caller falls back to the
        # per-request plane and the PUT-side encode still succeeds
        out = e._encode_shards(batch)
        np.testing.assert_array_equal(out, ref)
        monkeypatch.undo()
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "20000")
        st = batcher_mod.stats_snapshot()
        assert st["deaths"] == 1 and st["failed_retryable"] >= 1
        # the next submission mints a fresh batcher and batches again
        b2 = batcher_mod.get()
        assert b2 is not None and b2 is not b and b2.alive()
        np.testing.assert_array_equal(e._encode_shards(batch), ref)

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_async_resolve_falls_back_after_death(self, monkeypatch):
        """A BatcherClosed surfacing at RESOLVE time (tick-thread death
        after the enqueue) must also fall back per-request — the PUT
        pipeline's emit_one calls resolve() with no handler of its own
        (code-review finding, pinned here)."""
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "20000")
        e = coding.Erasure(4, 2)
        batch = _rng(0).integers(0, 256, (2, 4, 8192), dtype=np.uint8)
        ref = e._encode_shards_raw(batch)

        def boom(self, bucket):
            raise RuntimeError("injected tick fault")

        monkeypatch.setattr(batcher_mod.Batcher, "_flush_bucket", boom)
        resolve = e._encode_shards_async(batch)
        out = np.asarray(resolve())  # fails retryable -> inline encode
        monkeypatch.undo()
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "20000")
        np.testing.assert_array_equal(out, ref)
        assert batcher_mod.stats_snapshot()["deaths"] >= 1

    def test_close_drains_queued_items(self, monkeypatch):
        """Quiesce: an item queued at close() time still resolves (the
        modelled shutdown drains-or-fails-retryable contract)."""
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "200000")
        e = coding.Erasure(4, 2)
        batch = _rng(0).integers(0, 256, (1, 4, 8192), dtype=np.uint8)
        ref = e._encode_shards_raw(batch)
        resolve = e._encode_shards_async(batch)
        batcher_mod.shutdown()  # closes the 200 ms tick window early
        np.testing.assert_array_equal(np.asarray(resolve()), ref)

    def test_close_timeout_force_fails_queue(self, monkeypatch):
        """A wedged fused dispatch must not let close() strand queued
        submitters: after the join timeout the queue is force-failed
        retryable (code-review pin on the quiesce contract)."""
        import time as time_mod

        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        monkeypatch.setenv("MINIO_TPU_BATCH_TICK_US", "1000")
        b = batcher_mod.get()
        batch = _rng(0).integers(0, 256, (1, 4, 8192), dtype=np.uint8)
        release = threading.Event()

        def wedge(cat):
            release.wait(30)  # a hung device dispatch
            return np.zeros((cat.shape[0], 2, cat.shape[2]), np.uint8)

        r1 = b.enqueue_async(("wedge-sig",), batch, wedge, 0)
        time_mod.sleep(0.1)  # let the tick collect the wedged item
        r2 = b.enqueue_async(("other-sig",), batch, wedge, 0)
        b.close(timeout=0.3)
        for resolve in (r1, r2):
            with pytest.raises(batcher_mod.BatcherClosed):
                resolve()
        release.set()  # unwedge so the tick thread can exit
        b._thread.join(10)

    def test_submit_after_close_falls_back(self, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_BATCHER", "1")
        e = coding.Erasure(4, 2)
        batch = _rng(0).integers(0, 256, (1, 4, 8192), dtype=np.uint8)
        b = batcher_mod.get()
        b.close()
        with pytest.raises(batcher_mod.BatcherClosed):
            b.enqueue(("enc", 4, 2, "auto", 8192), batch,
                      e._encode_shards_raw, 0)
        # the routed path transparently falls back (fresh batcher or
        # raw): the caller never sees the closed instance
        np.testing.assert_array_equal(
            e._encode_shards(batch), e._encode_shards_raw(batch))

    def test_gate_off_restores_legacy_path(self, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_BATCHER", "0")
        e = coding.Erasure(4, 2)
        assert e._batcher() is None
        assert batcher_mod.get() is None
        batch = _rng(0).integers(0, 256, (1, 4, 8192), dtype=np.uint8)

        def items_now() -> int:
            st = batcher_mod.stats_snapshot()
            return 0 if st is None else st["items"]

        before = items_now()
        e._encode_shards(batch)
        assert items_now() == before, "gate-off encode touched the batcher"


# ------------------------------------------------------------- gate pins
class TestGatePins:
    def test_batcher_source_pragma_free(self):
        """ISSUE 11 satellite: erasure/batcher.py stays in the analysis
        gate (WORKER_SURFACE — worker processes import it through
        coding.py) with ZERO pragmas: findings there get fixed, not
        suppressed."""
        path = os.path.join(REPO, "minio_tpu", "erasure", "batcher.py")
        with open(path, encoding="utf-8") as fh:
            assert "# lint: allow" not in fh.read(), (
                "pragma crept into erasure/batcher.py")
        from minio_tpu.analysis.rules.shared_state import WORKER_SURFACE

        assert "erasure/batcher.py" in WORKER_SURFACE
        assert "ops/residency.py" in WORKER_SURFACE

    def test_batcher_metrics_declared(self):
        """The minio_batcher_* / matrix-residency families are declared
        in server/metrics.py (the metrics-drift registry's source of
        truth)."""
        from minio_tpu.analysis.core import Project

        declared = Project([]).declared_metrics()
        for name in ("minio_batcher_ticks_total",
                     "minio_batcher_dispatches_total",
                     "minio_batcher_items_total",
                     "minio_batcher_coalesced_items_total",
                     "minio_batcher_shed_deadline_total",
                     "minio_batcher_failed_retryable_total",
                     "minio_batcher_deaths_total",
                     "minio_batcher_queue_length",
                     "minio_erasure_matrix_residency_hits_total",
                     "minio_erasure_matrix_residency_misses_total"):
            assert name in declared, name

    def test_matrix_residency_hit_counters(self, monkeypatch):
        """Satellite 2: repeated signatures hit the ONE shared cache on
        every call path (repair rows included) — no re-build."""
        from minio_tpu.erasure import repair
        from minio_tpu.ops import residency

        a = repair.repair_matrix(4, 2, (0, 1, 2, 3), (4,))
        before = residency.matrices.stats()
        b = repair.repair_matrix(4, 2, (0, 1, 2, 3), (4,))
        after = residency.matrices.stats()
        assert a is b
        assert after["hits"] == before["hits"] + 1
        assert after["misses"] == before["misses"]
