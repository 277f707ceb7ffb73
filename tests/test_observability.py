"""Trace streaming, console log, pubsub, structured logger, audit webhook.

Reference: cmd/http-tracer.go:39 + cmd/admin-handlers.go:1108 (trace),
internal/pubsub/pubsub.go, internal/logger + cmd/consolelogger.go,
internal/logger audit entries.
"""

import http.client
import io
import json
import os
import threading
import time
import urllib.parse

import pytest

from minio_tpu.utils.logger import Logger
from minio_tpu.utils.pubsub import PubSub
from tests.s3_harness import S3TestServer


class TestPubSub:
    def test_fanout_and_filter(self):
        ps = PubSub()
        a = ps.subscribe()
        b = ps.subscribe(filter_fn=lambda x: x % 2 == 0)
        for i in range(4):
            ps.publish(i)
        assert [a.get(0.1) for _ in range(4)] == [0, 1, 2, 3]
        assert [b.get(0.1) for _ in range(2)] == [0, 2]
        a.close()
        assert ps.num_subscribers == 1
        b.close()

    def test_no_subscribers_is_free(self):
        ps = PubSub()
        ps.publish("x")  # must not raise or queue anywhere
        assert ps.num_subscribers == 0

    def test_slow_subscriber_drops(self):
        ps = PubSub()
        s = ps.subscribe(maxsize=2)
        for i in range(5):
            ps.publish(i)
        assert s.dropped == 3


class TestLogger:
    def test_ring_and_stream(self):
        buf = io.StringIO()
        lg = Logger(ring_size=3, stream=buf)
        lg.min_level = "INFO"
        for i in range(5):
            lg.info(f"msg{i}", n=i)
        assert [e["message"] for e in lg.recent()] == ["msg2", "msg3", "msg4"]
        lines = [json.loads(l) for l in buf.getvalue().splitlines()]
        assert lines[0]["message"] == "msg0" and lines[0]["level"] == "INFO"

    def test_level_filter(self):
        buf = io.StringIO()
        lg = Logger(stream=buf)
        lg.min_level = "ERROR"
        lg.info("hidden")
        lg.error("shown")
        assert [e["message"] for e in lg.recent()] == ["shown"]

    def test_live_subscription(self):
        lg = Logger(stream=io.StringIO())
        sub = lg.pubsub.subscribe()
        lg.info("hello")
        assert sub.get(0.5)["message"] == "hello"
        sub.close()


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    os.environ["MINIO_TPU_FSYNC"] = "0"
    s = S3TestServer(str(tmp_path_factory.mktemp("obs")))
    yield s
    s.close()


def _stream_lines(host, port, path_qs, headers, n_lines, timeout=10.0):
    """Collect up to n_lines non-empty NDJSON lines from a streaming GET."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("GET", path_qs, headers=headers)
    resp = conn.getresponse()
    out, buf = [], b""
    t0 = time.time()
    while len(out) < n_lines and time.time() - t0 < timeout:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if line.strip():
                out.append(json.loads(line))
    conn.close()
    return resp.status, out


def _signed_headers(srv, path, query):
    from minio_tpu.server import sigv4

    return sigv4.sign_request(
        "GET", path, query, {"host": srv.host}, b"", srv.ak, srv.sk)


class TestAdminTrace:
    def test_trace_stream_records_requests(self, srv):
        path = "/minio/admin/v3/trace"
        headers = _signed_headers(srv, path, [])
        got = {}

        def collect():
            got["r"] = _stream_lines("127.0.0.1", srv.port, path,
                                     headers, 2, timeout=8.0)

        t = threading.Thread(target=collect)
        t.start()
        time.sleep(0.5)  # let the subscriber attach
        srv.request("PUT", "/trcbkt")
        srv.request("PUT", "/trcbkt/obj", data=b"traced")
        t.join(10)
        status, lines = got["r"]
        assert status == 200
        apis = [l["api"] for l in lines]
        assert "make_bucket" in apis or "put_object" in apis
        entry = lines[0]
        assert entry["method"] == "PUT"
        assert entry["statusCode"] == 200
        assert entry["accessKey"] == srv.ak
        assert entry["durationMs"] >= 0

    def test_trace_err_filter(self, srv):
        path = "/minio/admin/v3/trace"
        q = [("err", "true")]
        headers = _signed_headers(srv, path, q)
        got = {}

        def collect():
            got["r"] = _stream_lines("127.0.0.1", srv.port,
                                     path + "?err=true", headers, 1,
                                     timeout=8.0)

        t = threading.Thread(target=collect)
        t.start()
        time.sleep(0.5)
        srv.request("HEAD", "/trcbkt")                # 200 -> filtered out
        srv.request("GET", "/trcbkt/ok-missing")      # 404 -> matches
        t.join(10)
        status, lines = got["r"]
        assert status == 200
        assert lines and all(l["statusCode"] >= 400 for l in lines)

    def test_trace_requires_admin(self, srv):
        r = srv.raw_request("GET", "/minio/admin/v3/trace")
        assert r.status == 403


class TestConsoleLog:
    def test_recent_entries_served(self, srv):
        from minio_tpu.utils.logger import log

        log.info("observability test line", marker="obs-123")
        path = "/minio/admin/v3/log"
        headers = _signed_headers(srv, path, [("limit", "1000")])
        status, lines = _stream_lines("127.0.0.1", srv.port,
                                      path + "?limit=1000", headers,
                                      1000, timeout=5.0)
        assert status == 200
        assert any(e.get("marker") == "obs-123" for e in lines)


class TestAuditWebhook:
    def test_audit_delivery(self, tmp_path):
        """Spin an HTTP sink, point the audit env at it, and check a
        request produces an audit entry with the right fields."""
        received = []
        import http.server

        class Sink(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                ln = int(self.headers.get("Content-Length", 0))
                received.append(json.loads(self.rfile.read(ln)))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        sinkd = http.server.HTTPServer(("127.0.0.1", 0), Sink)
        threading.Thread(target=sinkd.serve_forever, daemon=True).start()
        os.environ["MINIO_AUDIT_WEBHOOK_ENDPOINT"] = (
            f"http://127.0.0.1:{sinkd.server_address[1]}/audit")
        os.environ["MINIO_TPU_FSYNC"] = "0"
        # fresh Logger state: the module singleton may already exist
        from minio_tpu.utils.logger import log

        log.close()
        try:
            s = S3TestServer(str(tmp_path / "audit"))
            try:
                s.request("PUT", "/audbkt")
                s.request("PUT", "/audbkt/obj", data=b"audited")
                t0 = time.time()
                while len(received) < 2 and time.time() - t0 < 8:
                    time.sleep(0.1)
                assert received, "no audit entries delivered"
                apis = {e["api"] for e in received}
                assert "make_bucket" in apis or "put_object" in apis
                e = received[0]
                assert e["accessKey"] == s.ak
                assert e["statusCode"] == 200
                assert e["version"] == "1"
            finally:
                s.close()
        finally:
            os.environ.pop("MINIO_AUDIT_WEBHOOK_ENDPOINT", None)
            log.close()
            sinkd.shutdown()


class TestDriveHardwareInfo:
    """SMART/mountinfo diagnostics in admin storage info (VERDICT r5
    #10; reference internal/smart + internal/mountinfo)."""

    def test_storage_info_has_hardware_and_shared_mount_warning(
            self, tmp_path):
        import json as json_mod

        from tests.s3_harness import S3TestServer

        srv = S3TestServer(str(tmp_path / "drv"))
        try:
            r = srv.request("GET", "/minio/admin/v3/storageinfo")
            assert r.status == 200
            si = json_mod.loads(r.body)
            disks = [d for p in si["pools"] for d in p["disks"]]
            assert disks
            hw = disks[0].get("hardware")
            assert hw is not None
            assert "mountPoint" in hw and "fsType" in hw
            # all four test drives live under one tmp filesystem: the
            # shared-mount check must call that out
            assert any("share one filesystem" in w
                       for w in si.get("warnings", [])), si.get("warnings")
        finally:
            srv.close()

    def test_mount_resolution(self, tmp_path):
        from minio_tpu.storage.driveinfo import drive_hardware, mount_of

        mp, src, fstype = mount_of(str(tmp_path))
        assert mp and fstype
        hw = drive_hardware(str(tmp_path))
        assert hw["mountPoint"] == mp

    def test_distinct_filesystems_no_warning(self):
        from minio_tpu.storage.driveinfo import shared_mount_warnings

        # /proc and / are different filesystems on any Linux
        assert shared_mount_warnings(["/proc", "/"]) == []
        assert shared_mount_warnings([]) == []


class TestCodecBackendObservability:
    """VERDICT r5 #8: probe verdict + per-backend dispatch/byte counters
    are visible in Prometheus and admin info, and the auto path's
    device-wins branch is pinned end-to-end."""

    def test_counters_and_admin_info(self, tmp_path):
        import json as json_mod

        from minio_tpu.erasure import coding as ec
        from tests.s3_harness import S3TestServer

        srv = S3TestServer(str(tmp_path / "drv"))
        try:
            before = ec.backend_stats["host"]["dispatches"]
            srv.request("PUT", "/ecobkt")
            srv.request("PUT", "/ecobkt/o", data=b"z" * 300_000)
            assert ec.backend_stats["host"]["dispatches"] > before
            r = srv.request("GET", "/minio/admin/v3/info")
            info = json_mod.loads(r.body)
            assert info["erasure"]["dispatch"]["host"]["bytes"] > 0
            assert "deviceProbe" in info["erasure"]
            r = srv.request("GET", "/minio/v2/metrics/cluster")
            body = r.text()
            assert 'minio_erasure_backend_dispatches_total{backend="host"}' \
                in body
            assert "minio_erasure_backend_bytes_total" in body
        finally:
            srv.close()

    def test_forced_device_win_pins_auto_path(self, tmp_path, monkeypatch):
        """With the probe verdict forced to 'device wins', the AUTO
        backend routes big PUT/GET/heal batches through the device codec
        end-to-end (here a stub wrapping the host codec, since tests run
        CPU-only)."""
        import io

        import numpy as np

        from minio_tpu.erasure import coding as ec
        from minio_tpu.erasure.objects import ErasureObjects
        from minio_tpu.ops import host as host_mod
        from minio_tpu.storage.local import LocalStorage

        class StubDeviceCodec:
            def __init__(self, k, m):
                self._h = host_mod.HostRSCodec(k, m)
                self.calls = 0

            def encode(self, batch, blocks=None):
                self.calls += 1
                return self._h.encode(batch)

            def reconstruct(self, batch, available, wanted, blocks=None):
                self.calls += 1
                return self._h.reconstruct(batch, available, wanted)

        monkeypatch.setenv("MINIO_TPU_ERASURE_BACKEND", "auto")
        stub = StubDeviceCodec(2, 2)
        monkeypatch.setitem(ec._DeviceCodec._cache, (2, 2), (stub, True))
        monkeypatch.setitem(ec._DeviceCodec._ready, (2, 2), stub)

        disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
        for d in disks:
            d.make_volume("bkt")
        api = ErasureObjects(disks)
        dev_before = ec.backend_stats["device"]["dispatches"]
        data = np.random.default_rng(9).integers(
            0, 256, 24 << 20, dtype=np.uint8).tobytes()  # > DEVICE_MIN
        api.put_object("bkt", "obj", io.BytesIO(data), len(data))
        assert stub.calls > 0, "auto never dispatched to the device stub"
        assert ec.backend_stats["device"]["dispatches"] > dev_before
        _, stream = api.get_object("bkt", "obj")
        assert b"".join(stream) == data
        assert ec.probe_verdicts().get("2+2") is True
