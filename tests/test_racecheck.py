"""Lockset race detector: self-tests, deterministic-interleaving
regression pins for the fixed races, and the replay drills over the
designated concurrent suites (hotcache / stagestats / brownout / MRF /
replication) — ISSUE 10.

The drills construct the REAL product objects under tracked
synchronization (`racecheck.patched()`), hammer them from threads, and
assert the Eraser lockset pass reports zero unwaived findings.  The
negative drills run the PRE-FIX access shapes and assert the detector
flags them — a detector that cannot fail is decoration, same contract
as the model checker's seeded mutations.
"""

from __future__ import annotations

import threading
import time

import pytest

from minio_tpu.analysis.concurrency import racecheck as rc


@pytest.fixture(autouse=True)
def _clean_tracker():
    rc.TRACKER.reset()
    yield
    rc.unwatch_all()
    rc.uninstall()
    rc.TRACKER.reset()
    if rc.enabled():
        # suite-wide replay mode (MINIO_TPU_RACECHECK=1): restore the
        # session-scoped instrumentation these tests tore down
        rc.install()
        rc.install_default_watches()


def _run_threads(*targets, n_each: int = 1):
    ts = []
    for i, fn in enumerate(targets):
        for j in range(n_each):
            ts.append(threading.Thread(target=fn, name=f"t{i}-{j}"))
    for t in ts:
        t.start()
    for t in ts:
        t.join(30)
        assert not t.is_alive(), "drill thread hung"


def _keys(findings):
    return {f.key for f in findings}


# ------------------------------------------------------------ detector
class _Plain:
    def __init__(self):
        self.unlocked = 0
        self.locked = 0
        self.mu = None


class _WaivedFixture:
    def __init__(self):
        # lint: allow(racecheck): advisory snapshot counter, read lock-free by design (fixture)
        self.snap = 0


class TestDetector:
    def test_unlocked_counter_flagged_locked_clean(self):
        rc.watch(_Plain, "unlocked", "locked")
        with rc.patched():
            p = _Plain()
            p.mu = threading.Lock()

            def racy():
                for _ in range(200):
                    p.unlocked += 1

            def safe():
                for _ in range(200):
                    with p.mu:
                        p.locked += 1

            _run_threads(racy, safe, n_each=2)
        keys = _keys(rc.TRACKER.findings())
        assert rc.key_of(_Plain, "unlocked") in keys, (
            "the seeded unlocked counter escaped the lockset pass")
        assert rc.key_of(_Plain, "locked") not in keys, (
            "false positive on a consistently locked counter")

    def test_single_thread_never_flagged(self):
        rc.watch(_Plain, "unlocked")
        p = _Plain()
        for _ in range(100):
            p.unlocked += 1  # exclusive phase: init by one thread
        assert not rc.TRACKER.findings()

    def test_two_locks_alternating_flagged(self):
        """Check-then-act wearing two different locks: lockset
        intersection is empty even though every access is 'locked'."""
        rc.watch(_Plain, "unlocked")
        with rc.patched():
            p = _Plain()
            mu_a, mu_b = threading.Lock(), threading.Lock()

            def via_a():
                for _ in range(50):
                    with mu_a:
                        p.unlocked += 1

            def via_b():
                for _ in range(50):
                    with mu_b:
                        p.unlocked += 1

            _run_threads(via_a, via_b)
        assert rc.key_of(_Plain, "unlocked") in _keys(
            rc.TRACKER.findings())

    def test_condition_wait_releases_lockset(self):
        with rc.patched():
            cv = threading.Condition()
            seen = []

            def waiter():
                with cv:
                    cv.wait(1.0)
                    seen.append(len(rc.held_locks()))

            t = threading.Thread(target=waiter)
            t.start()
            time.sleep(0.1)
            with cv:
                cv.notify_all()
            t.join(5)
        assert seen == [1]  # re-acquired after wait, dropped during

    def test_pragma_waiver_scanned_from_source(self):
        rc.watch(_WaivedFixture, "snap")
        key = rc.key_of(_WaivedFixture, "snap")
        assert key in rc.TRACKER.waived(), (
            "the `# lint: allow(racecheck): reason` pragma on the "
            "attribute assignment was not honored")
        f = _WaivedFixture()

        def bump():
            for _ in range(100):
                f.snap += 1

        _run_threads(bump, bump)
        assert key not in _keys(rc.TRACKER.findings())

    def test_waive_requires_reason(self):
        with pytest.raises(ValueError):
            rc.TRACKER.waive("some.key", "   ")


# ----------------------------------------- deterministic interleavings
class TestSchedulerHooks:
    """The checker's scheduler hooks: gate() parks a thread between the
    load and the store of a `+=`, making the lost-update interleaving a
    deterministic two-thread schedule instead of a stress lottery."""

    def _adversarial_increment(self, obj, key, bump_a, bump_b):
        """Run bump_a/bump_b with A parked between its read and its
        write of `key` while B runs to completion."""
        ev_read, ev_go = threading.Event(), threading.Event()
        state = {"armed": True}

        def gate(is_write):
            if state["armed"] and is_write \
                    and threading.current_thread().name == "A":
                state["armed"] = False
                ev_read.set()
                ev_go.wait(0.5)

        rc.TRACKER.gate(key, gate)
        try:
            ta = threading.Thread(target=bump_a, name="A")

            def b():
                ev_read.wait(2)
                bump_b()
                ev_go.set()

            tb = threading.Thread(target=b, name="B")
            ta.start()
            tb.start()
            ta.join(10)
            tb.join(10)
            assert not ta.is_alive() and not tb.is_alive()
        finally:
            rc.TRACKER.gate(key, None)

    def test_bare_increment_loses_update_deterministically(self):
        """The PRE-FIX shape: `stats.queued += 1` with no lock.  Under
        the adversarial schedule the lost update happens every time —
        this is the reproducer the fix below is pinned against."""
        rc.watch(_Plain, "unlocked")
        p = _Plain()

        def bump():
            p.unlocked += 1

        self._adversarial_increment(
            p, rc.key_of(_Plain, "unlocked"), bump, bump)
        assert p.unlocked == 1, "expected the deterministic lost update"

    def test_replication_stats_inc_survives_adversarial_schedule(self):
        """Regression pin for the fixed race: ReplicationPool counters
        (stats.queued et al) were bare `+=` from two worker threads +
        API threads; inc() serializes under the stats lock, so the SAME
        schedule that loses an update above must count 2 here."""
        from minio_tpu.services.replication import ReplicationStats

        rc.watch(ReplicationStats, "queued")
        with rc.patched():
            stats = ReplicationStats()
            # the dataclass default_factory bound threading.Lock before
            # the patch; hand it a tracked lock so the lockset pass
            # sees inc()'s discipline
            stats._lock = rc.Lock()

            def bump():
                stats.inc(queued=1)

            self._adversarial_increment(
                stats, rc.key_of(ReplicationStats, "queued"), bump, bump)
        assert stats.queued == 2, (
            "ReplicationStats.inc lost an update under the adversarial "
            "schedule — the lock regressed")
        assert rc.key_of(ReplicationStats, "queued") not in _keys(
            rc.TRACKER.findings())

    def test_drive_resync_counter_survives_adversarial_schedule(self):
        """Regression pin for the ServiceManager.drive_resyncs fix:
        concurrent on_online probe callbacks bump it under _resync_mu
        now."""
        class _SM:  # the fixed access shape, lock included
            def __init__(self):
                self._resync_mu = threading.Lock()
                self.drive_resyncs = 0

            def reconnected(self):
                with self._resync_mu:
                    self.drive_resyncs += 1

        rc.watch(_SM, "drive_resyncs")
        with rc.patched():
            sm = _SM()
            self._adversarial_increment(
                sm, rc.key_of(_SM, "drive_resyncs"),
                sm.reconnected, sm.reconnected)
        assert sm.drive_resyncs == 2


# -------------------------------------------------------------- drills
class TestReplayDrills:
    """The designated concurrent-suite replays: real product objects,
    tracked locks, thread fan-in, zero unwaived findings."""

    def test_hotcache_drill_clean(self):
        from minio_tpu.erasure.objects import ObjectInfo
        from minio_tpu.serving import hotcache as hc_mod

        rc.watch(hc_mod.HotObjectCache, "hits", "misses", "fills",
                 "collapsed", "evictions", "invalidations", "_bytes",
                 "_prot_bytes", "_fill_bytes", "_freq_ops")
        with rc.patched():
            cache = hc_mod.HotObjectCache(1 << 20, min_hits=1)
            body = b"x" * 1024

            def info_fn():
                return ObjectInfo("b", "o", size=len(body), etag="e1")

            def data_fn():
                return info_fn(), iter([body])

            def getter():
                for _ in range(30):
                    kind, oi, payload = cache.serve(
                        "b", "o", "", info_fn, data_fn)
                    if kind == "collapsed":
                        assert b"".join(payload) == body
                    elif kind in ("hit", "filled"):
                        assert bytes(payload) == body

            def invalidator():
                for _ in range(20):
                    cache.invalidate("b", "o")
                    time.sleep(0.001)

            def prober():
                for _ in range(50):
                    cache.probe("b", "o")
                    cache.lookup("b", "o", count_miss=False)

            _run_threads(getter, getter, invalidator, prober)
        bad = [f for f in rc.TRACKER.findings()
               if "HotObjectCache" in f.key]
        assert not bad, f"hotcache lockset findings: {bad}"

    def test_brownout_drill_clean(self):
        from minio_tpu.services.brownout import BrownoutController

        rc.watch(BrownoutController, "_engaged", "_last_pressure",
                 "engagements", "releases", "sheds_seen", "deferrals",
                 "hot_bypasses")
        with rc.patched():
            bc = BrownoutController(engage_depth=2, release_after=0.01)

            def front():
                for i in range(100):
                    bc.note_pressure(i % 5)
                    if i % 7 == 0:
                        bc.note_shed()
                    bc.note_hot_bypass()

            def background():
                for _ in range(100):
                    bc.background_allowed()
                    bc.engaged()

            _run_threads(front, front, background, background)
        bad = [f for f in rc.TRACKER.findings()
               if "BrownoutController" in f.key]
        assert not bad, f"brownout lockset findings: {bad}"

    def test_mrf_drill_clean(self):
        from minio_tpu.services.mrf import MRFQueue, MRFStats

        rc.watch(MRFStats, "enqueued", "healed", "failed", "dropped",
                 "pending")

        class _OL:
            def heal_object(self, bucket, obj, version_id="", deep=False):
                return type("R", (), {"failed": False})()

        with rc.patched():
            q = MRFQueue(_OL(), delay=0.0)
            try:
                def producer(tag):
                    def run():
                        for i in range(40):
                            q.enqueue("b", f"o{tag}-{i % 7}")
                    return run

                _run_threads(producer(0), producer(1), producer(2))
                assert q.drain(timeout=20)
            finally:
                q.close()
        bad = [f for f in rc.TRACKER.findings() if "MRFStats" in f.key]
        assert not bad, f"MRF lockset findings: {bad}"

    def test_stagestats_drill_clean(self, monkeypatch):
        """The real add()/snapshot() paths over traced tables under a
        tracked lock: the counter aggregation discipline, checked."""
        from minio_tpu.erasure import stagestats

        traced_s = rc.TracedDict("erasure.stagestats._seconds",
                                 {s: 0.0 for s in stagestats.STAGES})
        traced_b = rc.TracedDict("erasure.stagestats._bytes",
                                 {s: 0 for s in stagestats.STAGES})
        monkeypatch.setattr(stagestats, "_seconds", traced_s)
        monkeypatch.setattr(stagestats, "_bytes", traced_b)
        monkeypatch.setattr(stagestats, "_lock", rc.Lock())

        def adder():
            for i in range(200):
                stagestats.add(stagestats.STAGES[i % 7], 0.001, 10)

        def reader():
            for _ in range(50):
                stagestats.snapshot()

        _run_threads(adder, adder, reader)
        bad = [f for f in rc.TRACKER.findings()
               if "stagestats" in f.key]
        assert not bad, f"stagestats lockset findings: {bad}"

    def test_replication_stats_drill_clean_and_prefix_shape_flagged(self):
        from minio_tpu.services.replication import ReplicationStats

        rc.watch(ReplicationStats, "queued", "completed", "failed",
                 "deletes", "proxied")
        with rc.patched():
            stats = ReplicationStats()
            stats._lock = rc.Lock()  # see the scheduler-hook test

            def api_enqueue():
                for _ in range(100):
                    stats.inc(queued=1)

            def worker():
                for _ in range(60):
                    stats.inc(completed=1)
                    stats.inc_target("arn:a", completed=1)

            def proxy():
                for _ in range(60):
                    stats.inc(proxied=1)

            _run_threads(api_enqueue, api_enqueue, worker, proxy)
            assert not [f for f in rc.TRACKER.findings()
                        if "ReplicationStats" in f.key]
            assert stats.queued == 200 and stats.completed == 60 \
                and stats.proxied == 60

            # the PRE-FIX shape on a fresh instance: bare `+=` from
            # two threads — the detector must flag what the fix removed
            rc.TRACKER.reset()
            stats2 = ReplicationStats()

            def bare():
                for _ in range(200):
                    stats2.queued += 1

            _run_threads(bare, bare)
        assert rc.key_of(ReplicationStats, "queued") in _keys(
            rc.TRACKER.findings()), (
            "the pre-fix bare-increment shape escaped the detector")

    def test_controller_drill_clean(self):
        """ISSUE 19: the overload controller's ladder vector and
        counters under the real tick/scrape/admin/stand-down fan-in —
        one ticker (production is a single daemon thread), a stats
        scraper, an admin reconfigure racing the sample-decide window,
        and close() from the main thread (which zeroes every ladder)."""
        from minio_tpu.server.controller import OverloadController, _Ladder
        from minio_tpu.server.qos import TenantRule

        from .test_controller import HOT, burning, calm, make_controller

        rc.watch(OverloadController, "ticks", "skipped_stale",
                 "qos_admin_resets", "offender_switches",
                 "pool_add_events", "pool_add_recommended",
                 "_sat_streak", "_calm_streak")
        rc.watch(_Ladder, "depth", "streak_high", "streak_low",
                 "cooldown", "engagements", "reverts")
        with rc.patched():
            c, srv, qos, clk = make_controller(hysteresis=1, cooldown=0)

            def ticker():
                for i in range(40):
                    (burning if i % 4 < 2 else calm)(srv.slo)
                    clk.now += 1.0
                    c.tick()

            def scraper():
                for _ in range(80):
                    c.stats()

            def admin():
                for _ in range(10):
                    qos.reconfigure(rules={HOT: TenantRule(weight=16)},
                                    max_queue=qos.max_queue)
                    time.sleep(0.001)

            _run_threads(ticker, scraper, admin)
            c.close()  # main-thread stand-down: the second writer
        bad = [f for f in rc.TRACKER.findings()
               if "controller" in f.key]
        assert not bad, f"controller lockset findings: {bad}"
        assert c.ticks == 40  # the drill actually ticked

    def test_georep_stats_drill_clean_and_prefix_shape_flagged(self,
                                                               monkeypatch):
        """ISSUE 19: georep's module-level stats table — no class
        attribute to watch, so the TracedDict swap (the stagestats
        pattern).  The real `_bump` path under a tracked lock stays
        clean; the pre-fix bare `stats[k] += n` shape must flag."""
        from minio_tpu.services import georep

        traced = rc.TracedDict("services.georep.stats",
                               dict.fromkeys(georep.stats, 0))
        monkeypatch.setattr(georep, "stats", traced)
        monkeypatch.setattr(georep, "_stats_mu", rc.Lock())

        def pusher():
            for _ in range(100):
                georep._bump("pushed_objects")
                georep._bump("pushed_bytes", 1024)

        def receiver():
            for _ in range(100):
                georep._bump("applied")
                georep._bump("already")

        def scraper():
            # the status() totals read, minus the server plumbing
            for _ in range(50):
                with georep._stats_mu:
                    dict(georep.stats)

        _run_threads(pusher, pusher, receiver, scraper)
        assert not [f for f in rc.TRACKER.findings()
                    if "georep" in f.key]
        assert traced["pushed_objects"] == 200

        # the PRE-FIX shape: bare read-modify-write, no _stats_mu
        rc.TRACKER.reset()
        bare = rc.TracedDict("services.georep.stats", {"pushed_objects": 0})
        monkeypatch.setattr(georep, "stats", bare)

        both_alive = threading.Barrier(2)

        def racy():
            # a thread that ends before the other starts hands it its
            # ident, and the detector then sees one thread
            both_alive.wait(30)
            for _ in range(200):
                georep.stats["pushed_objects"] += 1
            both_alive.wait(30)

        _run_threads(racy, racy)
        assert "services.georep.stats" in _keys(rc.TRACKER.findings()), (
            "the pre-fix unlocked stats bump escaped the detector")

    def test_metajournal_drill_clean(self, tmp_path, monkeypatch):
        """ISSUE 19: the metadata journal's flush counters and the
        index spill counter — concurrent producers enqueue commits,
        the committer thread flushes (counter writes under the journal
        lock), spills fire on a tiny memtable bound, and a metrics
        thread reads the counters lock-free (the advisory-snapshot
        idiom: reads never refine the lockset)."""
        from minio_tpu.storage import metajournal as mj

        rc.watch(mj.MetaJournal, "commits", "batches", "last_batch",
                 "flush_ns", "rotations", "journal_bytes")
        rc.watch(mj.MetaIndex, "spills")
        monkeypatch.setattr(mj, "MEMTABLE_SPILL", 8)
        with rc.patched():
            j = mj.MetaJournal(str(tmp_path / "d0"),
                               lambda b, p, d: None, lambda b, p: None,
                               fsync=False)
            try:
                def producer(tag):
                    def run():
                        for i in range(40):
                            j.commit("bkt", f"o{tag}-{i}", b"x" * 16)
                    return run

                def scraper():
                    for _ in range(100):
                        (j.commits, j.batches, j.last_batch,
                         j.journal_bytes, j.index.spills)

                _run_threads(producer(0), producer(1), producer(2),
                             scraper)
            finally:
                j.close()
        bad = [f for f in rc.TRACKER.findings()
               if "MetaJournal" in f.key or "MetaIndex" in f.key]
        assert not bad, f"metajournal lockset findings: {bad}"
        assert j.commits == 120
        assert j.index.spills > 0, "the drill never exercised a spill"

    def test_drills_actually_observed_concurrency(self):
        """Meta-check: a drill that never leaves the Eraser exclusive
        phase tests nothing — prove the harness records multi-thread
        access."""
        rc.watch(_Plain, "locked")
        with rc.patched():
            p = _Plain()
            p.mu = threading.Lock()

            def safe():
                for _ in range(50):
                    with p.mu:
                        p.locked += 1

            _run_threads(safe, safe)
        locs = [v for k, v in rc.TRACKER._locs.items()
                if k[0] == rc.key_of(_Plain, "locked")]
        assert locs, "no location recorded for the watched attribute"
        loc = max(locs, key=lambda lo: len(lo.threads))
        assert len(loc.threads) >= 2
        assert loc.state in (rc.SHARED, rc.MODIFIED)
        assert loc.lockset, "the shared lock should be in the lockset"
