"""Scenario engine (minio_tpu/simulator/, ISSUE 15): the determinism
pin (same seed => identical arrival schedule + request sequence), the
schedule's structural contract, and the tier-1 smoke scenario — a real
replay against a real HTTP server with the SLO plane closing the loop.
"""

from __future__ import annotations

import json
import threading
import time

import pytest

from minio_tpu.simulator import (Scenario, ScenarioEngine,
                                 build_schedule, builtin_scenarios,
                                 georep_scenarios, schedule_digest)
from minio_tpu.simulator.engine import OPS, catalog
from minio_tpu.simulator.scenarios import smoke_scenario

from .s3_harness import S3TestServer


class TestDeterminism:
    def test_same_seed_identical_schedule(self):
        sc = smoke_scenario()
        s1, s2 = build_schedule(sc), build_schedule(sc)
        assert s1 == s2
        assert schedule_digest(s1) == schedule_digest(s2)

    def test_all_builtin_schedules_reproduce(self):
        for sc in builtin_scenarios(scale=0.25):
            assert schedule_digest(build_schedule(sc)) == \
                schedule_digest(build_schedule(sc)), sc.name

    def test_different_seed_differs(self):
        a = smoke_scenario()
        b = Scenario(**{**a.__dict__, "seed": a.seed + 1})
        assert schedule_digest(build_schedule(a)) != \
            schedule_digest(build_schedule(b))

    def test_catalog_and_bodies_deterministic(self):
        from minio_tpu.simulator.engine import body_bytes

        sc = smoke_scenario()
        assert catalog(sc) == catalog(sc)
        assert body_bytes(sc, "t", 64) == body_bytes(sc, "t", 64)
        assert body_bytes(sc, "t", 64) != body_bytes(sc, "u", 64)


class TestScheduleContract:
    def test_shape(self):
        sc = smoke_scenario()
        sched = build_schedule(sc)
        assert sched, "schedule must not be empty"
        declared = {op for op, _ in sc.ops}
        last_t = -1.0
        for ent in sched:
            assert ent["op"] in OPS and ent["op"] in declared
            assert 0 <= ent["t"] < sc.duration_s
            assert ent["t"] >= last_t  # arrivals are ordered
            last_t = ent["t"]
            assert 0 <= ent["client"] < sc.clients
            assert ent["bucket"] in sc.buckets
            if ent["op"] in ("get", "head"):
                assert ent["key"] in catalog(sc)[ent["bucket"]]
            elif ent["op"] == "list":
                # every scheduled prefix must walk real entries — an
                # empty-listing LIST measures nothing
                assert any(k.startswith(ent["prefix"])
                           for k in catalog(sc)[ent["bucket"]])

    def test_hot_bucket_skew(self):
        sc = [s for s in builtin_scenarios(scale=0.25)
              if s.name == "multi_tenant_qos_mix"][0]
        sched = build_schedule(sc)
        hot = sum(1 for e in sched if e["bucket"] == sc.buckets[0])
        frac = hot / len(sched)
        assert 0.8 < frac < 0.98  # scheduled 0.9

    def test_delete_targets_prior_writes(self):
        sc = Scenario(name="d", seed=3, duration_s=4.0, clients=2,
                      rate=30.0, ops=(("put", 5), ("delete", 5)),
                      nobjects=4)
        sched = build_schedule(sc)
        written: set[str] = set()
        for ent in sched:
            if ent["op"] == "put":
                written.add(ent["key"])
            elif ent["op"] == "delete" \
                    and not ent["key"].startswith("w-missing-"):
                assert ent["key"] in written

    def test_builtin_set_meets_acceptance_shape(self):
        scs = builtin_scenarios()
        assert len(scs) >= 5
        assert sum(1 for s in scs if s.chaos) >= 2
        assert len({s.seed for s in scs}) == len(scs)

    def test_georep_family_meets_acceptance_shape(self):
        """ISSUE 16: the multi-region family — four named scenarios,
        each owning its bucket (convergence checks must not bleed
        across scenarios), every one graded by server-side SLO
        classes, chaos limited to the hooks a harness registers."""
        scs = georep_scenarios()
        assert [s.name for s in scs] == [
            "replication_burst", "peer_kill_mid_push", "worker_kill",
            "read_your_writes_across_sites"]
        buckets = [s.buckets[0] for s in scs]
        assert len(set(buckets)) == len(scs)
        assert all(s.slo.get("classes") for s in scs)
        assert {s.chaos for s in scs if s.chaos} == \
            {"peer_kill", "worker_kill"}
        # seeds must not collide with the builtin set: a schedule's
        # digest follows its seed
        seeds = {s.seed for s in scs} | \
            {s.seed for s in builtin_scenarios()}
        assert len(seeds) == len(scs) + len(builtin_scenarios())

    def test_georep_schedules_reproduce(self):
        for sc in georep_scenarios(scale=0.25):
            a = build_schedule(sc)
            b = build_schedule(sc)
            assert a == b
            assert schedule_digest(a) == schedule_digest(b)

    def test_controller_family_meets_acceptance_shape(self):
        """ISSUE 18: the regime-shift family — three named scenarios,
        each pairing a PUT-flood offender (slot-TIME monopoly: a PUT
        holds an admission slot for ~10 serialized drive ops against a
        GET's ~2) with a GET-only victim whose SLO clauses are the
        static-vs-controller discriminator."""
        from minio_tpu.simulator import controller_scenarios

        scs = controller_scenarios()
        assert [s.name for s in scs] == [
            "flash_crowd", "tenant_mix_flip", "brownout_noisy_stacked"]
        for sc in scs:
            assert sc.bucket_ops, sc.name
            flood = [b for b, mix in sc.bucket_ops.items()
                     if any(op == "put" for op, _ in mix)]
            victims = [b for b, mix in sc.bucket_ops.items()
                       if all(op == "get" for op, _ in mix)]
            assert flood and victims, sc.name
            # the graded victims are GET-only buckets, each carrying
            # the budget clauses static must fail and the controller
            # must hold (a flip scenario may have extra ungraded
            # GET-only buckets — the pre/post-flip flood roles)
            graded = sc.slo["buckets"]
            assert set(graded) <= set(victims), sc.name
            for v, clause in graded.items():
                assert "shed_frac_max" in clause \
                    and "p50_ms" in clause, (sc.name, v)
            # the offender starts privileged: static weights alone
            # must not be what rescues the victim
            for v in graded:
                assert sc.qos["tenants"][f"bucket:{flood[0]}"]["weight"] \
                    > sc.qos["tenants"][f"bucket:{v}"]["weight"]
            # the victim drives from its OWN closed-loop client pool
            # (a shared pool lets the flood throttle the victim's
            # offered load and hides the starvation) — pools disjoint
            # and inside the client count
            used: set[int] = set()
            for b, (lo, n) in sc.bucket_clients.items():
                pool = set(range(lo, lo + n))
                assert pool and not (pool & used), (sc.name, b)
                assert lo >= 0 and lo + n <= sc.clients, (sc.name, b)
                used |= pool
            assert set(sc.bucket_clients) == set(sc.buckets), sc.name
        assert [s.name for s in scs if s.mix_flip_at_frac] \
            == ["tenant_mix_flip"]
        assert [s.name for s in scs if s.chaos] \
            == ["brownout_noisy_stacked"]
        # a schedule's digest follows its seed: no collisions inside
        # the family or with the other sets
        seeds = {s.seed for s in scs} \
            | {s.seed for s in builtin_scenarios()} \
            | {s.seed for s in georep_scenarios()}
        assert len(seeds) == len(scs) + len(builtin_scenarios()) \
            + len(georep_scenarios())

    def test_controller_schedules_reproduce(self):
        from minio_tpu.simulator import controller_scenarios

        for sc in controller_scenarios(scale=0.25):
            a = build_schedule(sc)
            b = build_schedule(sc)
            assert a == b
            assert schedule_digest(a) == schedule_digest(b)

    def test_bucket_ops_overrides_only_named_buckets(self):
        """The bucket_ops field is gated: a victim bucket draws ONLY
        its own mix, other buckets draw the scenario mix, and a
        scenario without the field keeps its exact RNG stream (the
        pre-existing digests must never move)."""
        base = Scenario(
            name="bo", seed=77, duration_s=6.0, clients=4, rate=40.0,
            ops=(("put", 50), ("get", 50)), buckets=("hot", "quiet"),
            nobjects=8)
        plain = build_schedule(base)
        over = Scenario(**{**base.__dict__, "bucket_ops": {
            "quiet": (("get", 100),)}})
        sched = build_schedule(over)
        quiet_ops = {e["op"] for e in sched if e["bucket"] == "quiet"}
        hot_ops = {e["op"] for e in sched if e["bucket"] == "hot"}
        assert quiet_ops == {"get"}
        assert hot_ops == {"put", "get"}
        # gate check: bucket_ops=None reproduces the original stream
        again = Scenario(**{**base.__dict__, "bucket_ops": None})
        assert schedule_digest(build_schedule(again)) == \
            schedule_digest(plain)


@pytest.fixture()
def sim_srv(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_FSYNC", "0")
    monkeypatch.setenv("MINIO_TPU_SLO", "1")
    monkeypatch.setenv("MINIO_TPU_SLO_SLOT_S", "1")
    s = S3TestServer(str(tmp_path / "sim"))
    yield s
    s.close()


class TestSmokeScenario:
    def test_replay_closes_the_loop(self, sim_srv):
        """The tier-1 smoke: a real mixed-op replay against the real
        server, verdict sourced from the server's own SLO endpoint."""
        eng = ScenarioEngine("127.0.0.1", sim_srv.port, sim_srv.ak,
                             sim_srv.sk, slo_slot_s=1.0)
        sc = smoke_scenario()
        doc = eng.run(sc)
        assert doc["scheduleRequests"] == len(build_schedule(sc))
        assert doc["scheduleSha256"] == \
            schedule_digest(build_schedule(sc))
        by_class = doc["byClass"]
        assert sum(d["count"] for d in by_class.values()) == \
            doc["scheduleRequests"]
        assert by_class["GET"]["count"] > 0
        # zero transport/5xx errors against a healthy server
        assert all(d["errors"] == 0 for d in by_class.values()), \
            by_class
        # the loop is closed: the verdict came from the server's plane
        assert doc["serverSlo"]["enabled"] is True
        assert doc["serverSlo"]["classes"]["GET"]["requests"] > 0
        assert doc["verdict"] == "pass", doc["violations"]
        assert doc["attribution"] is None
        # no engine threads left behind
        time.sleep(0.1)
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("sim-") and t.is_alive()]

    def test_violation_pulls_stage_attribution(self, sim_srv,
                                               monkeypatch):
        """An impossible objective must fail AND carry a trace-derived
        dominant-stage attribution."""
        monkeypatch.setenv("MINIO_TPU_TRACE_SLOW_MS", "0")  # keep all
        eng = ScenarioEngine("127.0.0.1", sim_srv.port, sim_srv.ak,
                             sim_srv.sk, slo_slot_s=1.0)
        base = smoke_scenario()
        sc = Scenario(**{
            **base.__dict__, "name": "impossible", "duration_s": 2.0,
            "slo": {"classes": {
                "GET": {"p99_ms": 0.000001, "availability": 1.0}}}})
        doc = eng.run(sc)
        assert doc["verdict"] == "fail"
        assert any("latency" in v for v in doc["violations"])
        att = doc["attribution"]
        assert att is not None and "dominantStage" in att, att
        assert att["count"] > 0
        assert att["top"], "ranked stage list must not be empty"

    def test_chaos_hook_arming(self, sim_srv):
        """A named chaos hook starts inside the replay window and is
        always cleared, even on the happy path."""
        events = []
        hooks = {"t": (lambda: events.append(("start", time.time())),
                       lambda: events.append(("stop", time.time())))}
        eng = ScenarioEngine("127.0.0.1", sim_srv.port, sim_srv.ak,
                             sim_srv.sk, chaos_hooks=hooks,
                             slo_slot_s=1.0)
        base = smoke_scenario()
        sc = Scenario(**{
            **base.__dict__, "name": "chaos_smoke", "duration_s": 2.0,
            "chaos": "t", "chaos_at_frac": 0.25,
            "chaos_dur_frac": 0.25})
        t0 = time.time()
        doc = eng.run(sc)
        assert doc["chaos"] == "t"
        kinds = [k for k, _ in events]
        assert kinds == ["start", "stop"]
        start_at = events[0][1] - t0
        # armed after the scheduled fraction (setup shifts it right,
        # never left)
        assert start_at >= 0.25 * sc.duration_s * 0.9

    def test_unregistered_chaos_hook_is_an_error(self, sim_srv):
        """A chaos scenario whose hook name has no registration must
        fail loudly — a silent no-op would record chaos verdicts in
        which the fault never happened."""
        eng = ScenarioEngine("127.0.0.1", sim_srv.port, sim_srv.ak,
                             sim_srv.sk, slo_slot_s=1.0)
        base = smoke_scenario()
        sc = Scenario(**{
            **base.__dict__, "name": "missing_hook",
            "duration_s": 1.0, "chaos": "nope"})
        with pytest.raises(ValueError, match="nope"):
            eng.run(sc)
        # the raise happens BEFORE any client thread starts — nothing
        # may be left parked on the replay barrier
        time.sleep(0.1)
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("sim-") and t.is_alive()]

    def test_qos_scenario_applies_and_reverts(self, sim_srv):
        """A scenario carrying a qos doc flips the plane on for the
        replay and off after; tenant splits appear in the server SLO."""
        eng = ScenarioEngine("127.0.0.1", sim_srv.port, sim_srv.ak,
                             sim_srv.sk, slo_slot_s=1.0)
        base = smoke_scenario()
        sc = Scenario(**{
            **base.__dict__, "name": "qos_smoke", "duration_s": 2.0,
            "rate": 20.0,
            "qos": {"enable": True, "tenants": {
                "bucket:sim": {"weight": 4}}}})
        doc = eng.run(sc)
        assert doc["verdict"] == "pass", doc["violations"]
        tenants = doc["serverSlo"]["tenants"] or {}
        assert "bucket:sim" in tenants
        # reverted: the live plane is off again
        assert sim_srv.server.qos is None
        q = json.loads(sim_srv.request(
            "GET", "/minio/admin/v3/qos").body)
        assert q["enabled"] is False
