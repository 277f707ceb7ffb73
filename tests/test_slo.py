"""Closed-loop SLO plane (server/slo.py, ISSUE 15): classification,
ring-buffer burn-rate math, the admin status endpoint, the metrics
families, and — load-bearing — the gate-off differential: MINIO_TPU_SLO
unset must leave the server byte- and metrics-identical to before.

Also covers this PR's satellite admin surfaces: GET /trace/summary
(per-stage aggregation over the retained trace store),
POST /profile?seconds=N (one-shot sampled-stack capture, sampler thread
never leaks), and the per-bucket minio_usage_* scanner families.
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from minio_tpu.server.slo import (DEFAULT_OBJECTIVES, LAT_BUCKETS,
                                  SloPlane, classify, parse_objectives,
                                  percentile)

from .s3_harness import S3TestServer


class TestClassify:
    @pytest.mark.parametrize("api,cls", [
        ("get_object", "GET"), ("head_object", "GET"),
        ("select_object", "GET"), ("put_object", "PUT"),
        ("copy_object", "PUT"), ("make_bucket", "PUT"),
        ("post_policy_upload", "MULTIPART"),
        ("list_objects", "LIST"), ("list_buckets", "LIST"),
        ("delete_object", "DELETE"), ("delete_objects", "DELETE"),
        ("create_upload", "MULTIPART"), ("upload_part", "MULTIPART"),
        ("complete_upload", "MULTIPART"), ("abort_upload", "MULTIPART"),
        ("list_parts", "MULTIPART"), ("list_uploads", "MULTIPART"),
        ("admin_ServerInfo", "ADMIN"), ("sts_handler", "ADMIN"),
        ("cors_preflight", "OTHER"),
    ])
    def test_table(self, api, cls):
        assert classify(api) == cls

    def test_every_class_has_default_objective(self):
        for cls in ("GET", "PUT", "LIST", "DELETE", "MULTIPART",
                    "ADMIN", "OTHER"):
            assert cls in DEFAULT_OBJECTIVES


class TestObjectiveGrammar:
    def test_overrides_merge_over_defaults(self):
        obj = parse_objectives(
            '{"GET": {"p99_ms": 100}, "PUT": {"availability": 0.99}}')
        assert obj["GET"]["p99_ms"] == 100
        assert obj["GET"]["availability"] == \
            DEFAULT_OBJECTIVES["GET"]["availability"]
        assert obj["PUT"]["availability"] == 0.99
        assert obj["LIST"] == DEFAULT_OBJECTIVES["LIST"]

    @pytest.mark.parametrize("raw", [
        "not json", "[1,2]", '{"GET": {"p99_ms": "NaN"}}',
        '{"GET": {"availability": 1.5}}',
        '{"GET": {"p99_ms": -5}}'])
    def test_malformed_degrades_to_defaults(self, raw):
        assert parse_objectives(raw) == {
            c: dict(o) for c, o in DEFAULT_OBJECTIVES.items()}

    def test_unknown_class_ignored(self):
        assert "WAT" not in parse_objectives('{"WAT": {"p99_ms": 1}}')

    def test_bool_values_degrade_to_defaults(self):
        # float(True) == 1.0: a typo'd `true` must not install a 1ms
        # objective (or a 1.0 availability the grammar forbids anyway)
        obj = parse_objectives(
            '{"GET": {"p99_ms": true, "availability": false}}')
        assert obj["GET"] == DEFAULT_OBJECTIVES["GET"]


class TestPercentile:
    def test_empty_is_none(self):
        assert percentile([0] * (len(LAT_BUCKETS) + 1), 0.99) is None

    def test_interpolates_inside_bucket(self):
        counts = [0] * (len(LAT_BUCKETS) + 1)
        counts[0] = 100  # all in (0, 5ms]
        p50 = percentile(counts, 0.5)
        assert 0 < p50 <= LAT_BUCKETS[0]

    def test_overflow_answers_last_bound(self):
        counts = [0] * (len(LAT_BUCKETS) + 1)
        counts[-1] = 10  # all past 30s
        assert percentile(counts, 0.99) == LAT_BUCKETS[-1]


class TestBurnRateMatrix:
    """Google-SRE multi-window burn math on an injected clock."""

    def _plane(self, t):
        return SloPlane(slot_s=5.0, fast_s=300.0, slow_s=3600.0,
                        now=lambda: t[0])

    def test_burn_one_means_spending_exactly_the_budget(self):
        t = [1000.0]
        p = self._plane(t)
        # availability target 0.999 -> budget 0.1%; 1 error per 1000
        for _ in range(999):
            p.record("get_object", 200, 0.01)
        p.record("get_object", 503, 0.01)
        burn = p.status()["classes"]["GET"]["burn"]
        assert burn["fast"] == pytest.approx(1.0, abs=1e-6)
        assert burn["slow"] == pytest.approx(1.0, abs=1e-6)

    def test_budget_exhaustion(self):
        t = [1000.0]
        p = self._plane(t)
        for _ in range(90):
            p.record("get_object", 200, 0.01)
        for _ in range(10):
            p.record("get_object", 500, 0.01)
        g = p.status()["classes"]["GET"]
        # 10% errors vs 0.1% budget = 100x burn; budget fully spent
        assert g["burn"]["fast"] == pytest.approx(100.0)
        assert g["budget"]["remainingFraction"] < 0
        assert "availability" in g["violations"]
        assert g["ok"] is False

    def test_window_rollover_forgets_old_errors(self):
        t = [1000.0]
        p = self._plane(t)
        for _ in range(10):
            p.record("get_object", 500, 0.01)
        assert p.status()["classes"]["GET"]["burn"]["fast"] > 0
        # past the fast window: fast burn clears, slow still remembers
        t[0] += 400.0
        for _ in range(100):
            p.record("get_object", 200, 0.01)
        burn = p.status()["classes"]["GET"]["burn"]
        assert burn["fast"] == 0.0
        assert burn["slow"] > 0.0
        # past the slow window too: all forgiven
        t[0] += 3700.0
        p.record("get_object", 200, 0.01)
        burn = p.status()["classes"]["GET"]["burn"]
        assert burn["slow"] == 0.0

    def test_ring_prunes_past_slow_window(self):
        t = [0.0]
        p = self._plane(t)
        for i in range(2000):
            t[0] += 5.0
            p.record("get_object", 200, 0.01)
        ring = p._cls["GET"]
        assert len(ring.slots) <= ring.max_slots + 1

    def test_499_not_recorded(self):
        t = [1000.0]
        p = self._plane(t)
        p.record("get_object", 499, 0.01)
        assert "GET" not in p.status()["classes"]

    def test_latency_violation(self):
        t = [1000.0]
        p = self._plane(t)
        for _ in range(100):
            p.record("get_object", 200, 2.0)  # vs 250ms objective
        g = p.status()["classes"]["GET"]
        assert "latency" in g["violations"]
        assert g["window"]["p99Ms"] > 250

    def test_window_param_scopes_measurement(self):
        t = [1000.0]
        p = self._plane(t)
        p.record("get_object", 500, 0.01)
        t[0] += 100.0
        p.record("get_object", 200, 0.01)
        # 10s window sees only the success; full window sees both
        assert p.status(window_s=10.0)["classes"]["GET"]["window"][
            "errors"] == 0
        assert p.status()["classes"]["GET"]["window"]["errors"] == 1

    def test_tenant_split_and_cardinality_bound(self):
        t = [1000.0]
        p = SloPlane(slot_s=5.0, max_tenants=3, now=lambda: t[0])
        for i in range(6):
            p.record("get_object", 200, 0.01, tenant=f"bucket:b{i}")
        st = p.status(tenants=True)
        assert "bucket:b0" in st["tenants"]
        assert "~other" in st["tenants"]
        assert len(st["tenants"]) <= 4  # 3 named + ~other

    def test_metrics_snapshot_shape(self):
        t = [1000.0]
        p = self._plane(t)
        for _ in range(50):
            p.record("get_object", 200, 0.04)
        snap = p.snapshot_for_metrics()["GET"]
        assert snap["count"] == 50
        # cumulative buckets end at the total
        assert snap["buckets"][-1][1] == 50
        assert snap["ratios"]["availability"] >= 1.0
        assert snap["ratios"]["latency_p99"] > 1.0  # 40ms vs 250ms


@pytest.fixture()
def slo_srv(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_FSYNC", "0")
    monkeypatch.setenv("MINIO_TPU_SLO", "1")
    monkeypatch.setenv("MINIO_TPU_SLO_SLOT_S", "1")
    monkeypatch.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
    s = S3TestServer(str(tmp_path / "slo"))
    yield s
    s.close()


@pytest.fixture()
def plain_srv(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIO_TPU_FSYNC", "0")
    monkeypatch.delenv("MINIO_TPU_SLO", raising=False)
    monkeypatch.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
    s = S3TestServer(str(tmp_path / "plain"))
    yield s
    s.close()


class TestSloEndToEnd:
    def _traffic(self, srv):
        srv.request("PUT", "/sbkt")
        srv.request("PUT", "/sbkt/k1", data=b"x" * 1024)
        srv.request("GET", "/sbkt/k1")
        srv.request("GET", "/sbkt/missing")          # 404: not budget
        srv.request("GET", "/sbkt", query=[("list-type", "2")])
        time.sleep(0.3)  # finally-block recording settles

    def test_admin_slo_live_status(self, slo_srv):
        self._traffic(slo_srv)
        r = slo_srv.request("GET", "/minio/admin/v3/slo")
        assert r.status == 200
        doc = json.loads(r.body)
        assert doc["enabled"] is True
        g = doc["classes"]["GET"]
        assert g["window"]["requests"] >= 2
        assert g["window"]["errors"] == 0   # the 404 is a client outcome
        assert g["window"]["availability"] == 1.0
        assert g["burn"]["fast"] == 0.0
        assert doc["classes"]["PUT"]["window"]["requests"] >= 2
        assert doc["classes"]["LIST"]["window"]["requests"] >= 1
        # window param must be accepted and scope the answer; this
        # second call also proves admin ops record (the first /slo GET
        # recorded into the ADMIN class after its response was built)
        r = slo_srv.request("GET", "/minio/admin/v3/slo",
                            query=[("window", "60")])
        doc2 = json.loads(r.body)
        assert doc2["classes"]["GET"]["window"]["seconds"] == 60.0
        assert doc2["classes"]["ADMIN"]["window"]["requests"] >= 1
        # malformed, non-finite and non-positive windows are all 400
        # (float('nan') parses but would poison the slot arithmetic)
        for bad in ("wat", "nan", "inf", "-inf", "0", "-5"):
            r = slo_srv.request("GET", "/minio/admin/v3/slo",
                                query=[("window", bad)])
            assert r.status == 400, bad

    def test_slo_metrics_families_rendered(self, slo_srv):
        self._traffic(slo_srv)
        body = slo_srv.raw_request(
            "GET", "/minio/v2/metrics/cluster").body.decode()
        assert 'minio_slo_latency_bucket{class="GET",le="0.25"}' in body
        assert 'minio_slo_latency_bucket{class="GET",le="+Inf"}' in body
        assert 'minio_slo_requests_count{class="GET"}' in body
        assert 'minio_slo_objective_ratio{class="GET",' \
               'objective="availability"}' in body
        assert 'minio_slo_error_budget_burn{class="GET",' \
               'window="fast"}' in body

    def test_gate_on_zero_traffic_emits_no_families(self, tmp_path,
                                                    monkeypatch):
        """Presence guard: a gate-ON server that has recorded nothing
        emits no minio_slo_* families (headers included) — consistent
        with every other conditional family in metrics.py."""
        monkeypatch.setenv("MINIO_TPU_FSYNC", "0")
        monkeypatch.setenv("MINIO_TPU_SLO", "1")
        monkeypatch.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
        s = S3TestServer(str(tmp_path / "zero"))
        try:
            assert s.server.slo is not None
            body = s.raw_request(
                "GET", "/minio/v2/metrics/cluster").body.decode()
            assert "minio_slo_" not in body
            s.request("PUT", "/zbkt")
            time.sleep(0.2)
            body = s.raw_request(
                "GET", "/minio/v2/metrics/cluster").body.decode()
            assert "minio_slo_latency_bucket" in body
        finally:
            s.close()

    def test_shed_counts_against_budget(self, slo_srv):
        # a 503 is server budget spend; drive one through the plane
        # directly (the HTTP shed path needs saturation)
        slo_srv.server.slo.record("get_object", 503, 0.01)
        doc = json.loads(slo_srv.request(
            "GET", "/minio/admin/v3/slo").body)
        assert doc["classes"]["GET"]["window"]["errors"] >= 1

    def test_tenant_split_with_qos(self, slo_srv, monkeypatch):
        r = slo_srv.request(
            "PUT", "/minio/admin/v3/qos",
            data=json.dumps({"enable": True}).encode())
        assert r.status == 200
        try:
            self._traffic(slo_srv)
            doc = json.loads(slo_srv.request(
                "GET", "/minio/admin/v3/slo").body)
            assert "tenants" in doc
            assert "bucket:sbkt" in doc["tenants"]
            assert doc["tenants"]["bucket:sbkt"]["GET"]["window"][
                "requests"] >= 1
        finally:
            slo_srv.request(
                "PUT", "/minio/admin/v3/qos",
                data=json.dumps({"enable": False}).encode())


class TestGateOffDifferential:
    """MINIO_TPU_SLO unset = the pre-SLO server, byte for byte."""

    def test_no_plane_no_metrics(self, plain_srv):
        assert plain_srv.server.slo is None
        plain_srv.request("PUT", "/gbkt")
        plain_srv.request("PUT", "/gbkt/k", data=b"y" * 512)
        plain_srv.request("GET", "/gbkt/k")
        time.sleep(0.2)
        body = plain_srv.raw_request(
            "GET", "/minio/v2/metrics/cluster").body.decode()
        assert "minio_slo_" not in body
        assert "minio_usage_" not in body  # idle scanner: no families
        r = plain_srv.request("GET", "/minio/admin/v3/slo")
        assert r.status == 200
        assert json.loads(r.body) == {"enabled": False}

    def test_s3_bytes_identical_on_vs_off(self, slo_srv, plain_srv):
        """Same PUT/GET/LIST against a gate-on and a gate-off server:
        identical status, bodies, and headers (minus the per-run
        volatile ones)."""
        volatile = {"date", "last-modified", "x-minio-tpu-trace-id",
                    "x-amz-request-id"}

        def drive(srv):
            out = []
            srv.request("PUT", "/dbkt")
            r = srv.request("PUT", "/dbkt/k", data=b"z" * 2048)
            out.append((r.status, r.body,
                        {k.lower(): v for k, v in r.headers.items()
                         if k.lower() not in volatile}))
            r = srv.request("GET", "/dbkt/k")
            out.append((r.status, r.body,
                        {k.lower(): v for k, v in r.headers.items()
                         if k.lower() not in volatile}))
            r = srv.request("GET", "/dbkt",
                            query=[("list-type", "2")])
            # listing bodies carry mod times; compare status only
            out.append((r.status,))
            return out

        a = drive(slo_srv)
        b = drive(plain_srv)
        # ETags differ? No: same bytes, same algorithm. Mod times in
        # the GET Last-Modified header are excluded as volatile.
        assert a == b


class TestTraceSummary:
    def test_aggregates_retained_stages(self, slo_srv, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_TRACE_SLOW_MS", "0")  # keep all
        srv = slo_srv
        srv.request("PUT", "/tbkt")
        srv.request("PUT", "/tbkt/k", data=b"q" * 1024)
        srv.request("GET", "/tbkt/k")
        time.sleep(0.2)
        r = srv.request("GET", "/minio/admin/v3/trace/summary")
        assert r.status == 200
        doc = json.loads(r.body)
        assert doc["traces"] >= 2
        spans = doc["spans"]
        # the request roots are flagged so attribution can skip them
        assert spans["put_object"]["isRoot"] is True
        assert spans["put_object"]["count"] >= 1
        assert spans["put_object"]["p99Ms"] >= spans["put_object"][
            "p50Ms"] >= 0
        # at least one non-root stage exists to attribute against
        assert any(not d["isRoot"] for d in spans.values())
        assert "totalS" in next(iter(spans.values()))
        # the served path's leaf stages reach the request trees as
        # dp.<stage> spans, and the process-wide counters stand beside
        # them with wall time (ISSUE 26); the admission wait is booked
        # from the front's own reading, so it has seconds and no span
        assert {"dp.auth", "dp.meta_read", "dp.commit"} <= set(spans)
        assert "dp.admit" not in spans
        plane = doc["dataplane"]
        for stage in ("auth", "meta_read", "commit"):
            assert 0 < plane[stage]["wallSeconds"] <= plane[stage]["seconds"]
        assert plane["admit"]["seconds"] > 0
        assert plane["admit"]["wallSeconds"] == 0

    def test_since_scopes_the_aggregate(self, slo_srv, monkeypatch):
        """?since= restricts to traces started at/after the instant —
        the simulator scopes a violation's attribution to its own
        scenario this way."""
        monkeypatch.setenv("MINIO_TPU_TRACE_SLOW_MS", "0")
        srv = slo_srv
        srv.request("PUT", "/sincebkt")
        srv.request("PUT", "/sincebkt/old", data=b"o" * 512)
        time.sleep(0.3)
        cut = time.time()
        time.sleep(0.1)
        srv.request("GET", "/sincebkt/old")
        time.sleep(0.2)
        r = srv.request("GET", "/minio/admin/v3/trace/summary",
                        query=[("since", f"{cut:.3f}")])
        spans = json.loads(r.body)["spans"]
        assert "get_object" in spans
        assert "put_object" not in spans  # before the cut
        # non-finite since is a 400, not a 500
        for bad in ("nan", "-1", "wat"):
            r = srv.request("GET", "/minio/admin/v3/trace/summary",
                            query=[("since", bad)])
            assert r.status == 400, bad

    def test_summary_unit_shapes(self):
        from minio_tpu.utils.tracing import summarize_stages

        docs = [{"name": "get_object",
                 "stages": {"read": 0.5},
                 "spans": [
                     {"id": "a", "parent": None, "name": "get_object",
                      "dur": 1.0},
                     {"id": "b", "parent": "a", "name": "drive.read",
                      "dur": 0.8},
                     {"id": "c", "parent": "a", "name": "drive.read",
                      "dur": 0.2, "error": "Boom"}]}] * 3
        out = summarize_stages(docs)
        assert out["traces"] == 3
        assert out["spans"]["drive.read"]["count"] == 6
        assert out["spans"]["drive.read"]["errors"] == 3
        assert out["spans"]["drive.read"]["isRoot"] is False
        assert out["spans"]["get_object"]["isRoot"] is True
        assert out["stages"]["read"]["seconds"] == pytest.approx(1.5)


class TestOneShotProfile:
    def test_profile_returns_stacks_and_no_thread_leak(self, slo_srv):
        before = {t.name for t in threading.enumerate()}
        r = slo_srv.request("POST", "/minio/admin/v3/profile",
                            query=[("seconds", "0.3")])
        assert r.status == 200
        text = r.body.decode()
        assert text.startswith("# minio-tpu cpu profile:")
        # the server has live threads (event loop, executor): samples
        # must exist and be collapsed-stack formatted
        assert ";" in text or " " in text.splitlines()[-1]
        # sampler thread must be gone (never leaks past the response)
        deadline = time.time() + 5
        while time.time() < deadline:
            alive = [t for t in threading.enumerate()
                     if t.name == "admin-profiler" and t.is_alive()]
            if not alive:
                break
            time.sleep(0.05)
        assert not alive
        after = {t.name for t in threading.enumerate()}
        assert "admin-profiler" not in after - before

    def test_profile_conflicts_with_running_capture(self, slo_srv):
        r = slo_srv.request("POST",
                            "/minio/admin/v3/profiling/start",
                            query=[("local", "true")])
        assert r.status == 200
        try:
            r = slo_srv.request("POST", "/minio/admin/v3/profile",
                                query=[("seconds", "0.2")])
            assert r.status == 409
        finally:
            r = slo_srv.request("POST",
                                "/minio/admin/v3/profiling/stop",
                                query=[("local", "true")])
            assert r.status == 200

    def test_profile_rejects_bad_seconds(self, slo_srv):
        for bad in ("wat", "nan", "inf"):
            r = slo_srv.request("POST", "/minio/admin/v3/profile",
                                query=[("seconds", bad)])
            assert r.status == 400, bad

    def test_cancelled_capture_stops_sampler(self, slo_srv):
        """A capture cancelled mid-sleep (server shutdown, or client
        disconnect under aiohttp handler-cancellation) must not leave
        the sampler running forever — that would 409-block every
        future capture."""
        import asyncio
        import types

        server = slo_srv.server
        sampler = server._profiler()
        req = types.SimpleNamespace(
            rel_url=types.SimpleNamespace(query={"seconds": "30"}))

        async def drive():
            task = asyncio.get_running_loop().create_task(
                server.admin_profile(req, b""))
            deadline = time.time() + 5
            while not sampler.running and time.time() < deadline:
                await asyncio.sleep(0.02)
            assert sampler.running, "capture never started"
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task

        asyncio.run(drive())
        deadline = time.time() + 10
        while sampler.running and time.time() < deadline:
            time.sleep(0.05)
        assert not sampler.running, \
            "sampler kept running after cancellation"
        # and a fresh capture is not 409-blocked
        r = slo_srv.request("POST", "/minio/admin/v3/profile",
                            query=[("seconds", "0.2")])
        assert r.status == 200


class TestAdminWrapRecording:
    """The admin wrapper's SLO recording: client-gone is 499 (skipped
    by the plane), streaming/deliberate-wall ops are exempt — neither
    may poison the ADMIN objective."""

    def _fake_self(self, plane):
        import types

        from minio_tpu.server.admin import AdminMixin

        async def auth(request, body, op):
            return None

        return types.SimpleNamespace(
            slo=plane, _admin_auth=auth,
            _SLO_EXEMPT_OPS=AdminMixin._SLO_EXEMPT_OPS)

    def _fake_request(self):
        import types

        async def read():
            return b""

        return types.SimpleNamespace(read=read)

    def test_cancelled_admin_not_recorded(self):
        import asyncio

        from minio_tpu.server.admin import AdminMixin

        plane = SloPlane(slot_s=1.0)

        async def fn(request, body):
            raise asyncio.CancelledError

        handler = AdminMixin._admin_wrap(
            self._fake_self(plane), fn, "ServerInfo")
        with pytest.raises(asyncio.CancelledError):
            asyncio.run(handler(self._fake_request()))
        # 499 carve-out: no ADMIN sample, no fake 500
        assert "ADMIN" not in plane.status()["classes"]

    def test_exempt_streaming_op_not_recorded(self):
        import asyncio

        from aiohttp import web

        from minio_tpu.server.admin import AdminMixin

        plane = SloPlane(slot_s=1.0)

        async def fn(request, body):
            return web.Response(status=200)

        for op in ("ServerTrace", "ConsoleLog", "Profiling",
                   "SpeedTest"):
            handler = AdminMixin._admin_wrap(
                self._fake_self(plane), fn, op)
            asyncio.run(handler(self._fake_request()))
        assert "ADMIN" not in plane.status()["classes"]
        # a normal op still records
        handler = AdminMixin._admin_wrap(
            self._fake_self(plane), fn, "ServerInfo")
        asyncio.run(handler(self._fake_request()))
        assert plane.status()["classes"]["ADMIN"]["window"][
            "requests"] == 1


class TestUsageMetrics:
    def test_per_bucket_usage_families(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_FSYNC", "0")
        monkeypatch.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
        s = S3TestServer(str(tmp_path / "usage"), start_services=True,
                         scan_interval=3600.0)
        try:
            s.request("PUT", "/ubkt")
            s.request("PUT", "/ubkt/a", data=b"a" * 1000)
            s.request("PUT", "/ubkt/b", data=b"b" * 2000)
            s.request("DELETE", "/ubkt/b")
            s.server.services.scanner.scan_cycle()
            body = s.raw_request(
                "GET", "/minio/v2/metrics/cluster").body.decode()
            assert 'minio_usage_objects{bucket="ubkt"}' in body
            assert 'minio_usage_bytes{bucket="ubkt"} 1000' in body
            assert 'minio_usage_versions{bucket="ubkt"}' in body
            assert 'minio_usage_delete_markers{bucket="ubkt"}' in body
        finally:
            s.close()

    def test_idle_scanner_emits_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MINIO_TPU_FSYNC", "0")
        monkeypatch.setenv("MINIO_PROMETHEUS_AUTH_TYPE", "public")
        s = S3TestServer(str(tmp_path / "idle"), start_services=True,
                         scan_interval=3600.0)
        try:
            body = s.raw_request(
                "GET", "/minio/v2/metrics/cluster").body.decode()
            assert "minio_usage_" not in body
        finally:
            s.close()


class TestSloRuntimeFlip:
    """The SLO gate flips at runtime like QoS (ISSUE 16 satellite):
    admin PUT /minio/admin/v3/slo persists through the dynamic `slo`
    config subsystem and applies live — no restart."""

    def test_admin_put_flips_gate_live(self, plain_srv):
        s = plain_srv
        assert s.server.slo is None
        r = s.request("PUT", "/minio/admin/v3/slo",
                      data=json.dumps({"enable": True}).encode())
        assert r.status == 200, r.text()
        assert json.loads(r.body) == {"enabled": True}
        assert s.server.slo is not None
        # traffic against the flipped-on plane records
        s.request("PUT", "/flipb")
        s.request("PUT", "/flipb/k", data=b"x" * 256)
        s.request("GET", "/flipb/k")
        time.sleep(0.3)
        doc = json.loads(s.request("GET", "/minio/admin/v3/slo").body)
        assert doc["enabled"] is True
        # flip off: plane gone, admin answers disabled again — and the
        # S3 surface keeps working throughout
        r = s.request("PUT", "/minio/admin/v3/slo",
                      data=json.dumps({"enable": False}).encode())
        assert r.status == 200
        assert json.loads(r.body) == {"enabled": False}
        assert s.server.slo is None
        assert json.loads(s.request(
            "GET", "/minio/admin/v3/slo").body) == {"enabled": False}
        assert s.request("GET", "/flipb/k").body == b"x" * 256

    def test_strict_bool_validation(self, plain_srv):
        # '"on"' is truthy in Python — a stringly flip must bounce, not
        # silently enable (the QoS admin rule)
        r = plain_srv.request("PUT", "/minio/admin/v3/slo",
                              data=json.dumps({"enable": "on"}).encode())
        assert r.status == 400
        r = plain_srv.request("PUT", "/minio/admin/v3/slo", data=b"{}")
        assert r.status == 400
        r = plain_srv.request("PUT", "/minio/admin/v3/slo",
                              data=b"not-json")
        assert r.status == 400
        assert plain_srv.server.slo is None

    def test_env_pin_wins_over_config(self, slo_srv):
        """MINIO_TPU_SLO=1 pins the gate: a config 'off' cannot kill
        the plane (env > stored config, the subsystem-wide rule)."""
        r = slo_srv.request("PUT", "/minio/admin/v3/slo",
                            data=json.dumps({"enable": False}).encode())
        assert r.status == 200
        assert slo_srv.server.slo is not None
        assert json.loads(r.body) == {"enabled": True}
