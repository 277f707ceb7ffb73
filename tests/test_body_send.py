"""ISSUE 36: a GET's body leaves from the thread that pulled it
(server/app.py `_BodySender`, `S3Server._pump_stream`): one executor job
a response writes each piece to the connection's socket, the event loop
writes only what a job hands back.

Everything goes through the served path (tests/s3_harness.py, real
sockets, `http.client` readers); the stage `send` says who wrote the
bytes: a worker books them, the loop books nothing.  CPU only, host
codec: no number here is a device number.
"""

import gc
import http.client
import json
import os
import socket
import sys
import threading
import time
import weakref

import numpy as np
import pytest

from minio_tpu.erasure import coding, stagestats
from minio_tpu.server import app as app_mod
from minio_tpu.server import sigv4

from .s3_harness import S3TestServer

MIB = 1 << 20
# name -> size: nothing, one byte, an inline object (< 128 KiB), a tail
# block alone, groups of 32 + 32 full blocks and a tail of one byte
SIZES = {"empty": 0, "one": 1, "inline": 40 * 1024, "tail": 300 * 1024,
         "large": 64 * MIB + 1}
# for a client that leaves or stalls mid-body: more than a loopback
# connection's two socket buffers hold (16 full blocks and a tail)
MID = 16 * MIB + 4321


def _body(name: str, size: int) -> bytes:
    seed = sum(name.encode())
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def _sent() -> int:
    return stagestats.snapshot()["send"]["bytes"]


def _settle(reading, want, timeout: float = 10.0):
    """`reading()` once it is `want`, or what it is after `timeout`: a
    client has its last byte before the server's thread has left the
    write, booked the stage and closed what it held."""
    deadline = time.monotonic() + timeout
    while reading() != want and time.monotonic() < deadline:
        time.sleep(0.02)
    return reading()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    srv = S3TestServer(str(tmp_path_factory.mktemp("bodysend")))
    bodies = {name: _body(name, size) for name, size in SIZES.items()}
    bodies["mid"] = _body("mid", MID)
    try:
        assert srv.request("PUT", "/sendbkt").status == 200
        for name, body in bodies.items():
            assert srv.request("PUT", f"/sendbkt/{name}",
                               data=body).status == 200
        yield srv, bodies
    finally:
        srv.close()


def _open(srv, path: str, headers: dict | None = None, rcvbuf: int = 0,
          conn=None):
    """A signed GET sent, its response's head read: (connection,
    response).  `rcvbuf` keeps the client's socket buffer small, so that
    the server cannot finish a body nobody reads."""
    if conn is None:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
        if rcvbuf:
            conn.sock = socket.create_connection(("127.0.0.1", srv.port),
                                                 timeout=60)
            conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF,
                                 rcvbuf)
    signed = sigv4.sign_request(
        "GET", path, [], {"host": srv.host, **(headers or {})}, b"",
        srv.ak, srv.sk)
    conn.request("GET", path, headers=signed)
    return conn, conn.getresponse()


@pytest.mark.parametrize("name", list(SIZES))
def test_whole_body_is_exact_and_left_from_a_worker(served, name):
    srv, bodies = served
    before = _sent()
    r = srv.request("GET", f"/sendbkt/{name}")
    assert r.status == 200
    assert int(r.headers["Content-Length"]) == SIZES[name]
    assert r.body == bodies[name]
    sent = _settle(lambda: _sent() - before, SIZES[name], 2.0)
    if name == "large":
        # a reader this box held up for the fixed wait gets the rest of
        # a piece from the loop: never more than the body, never nothing
        assert 0 < sent <= SIZES[name]
    else:
        assert sent == SIZES[name]  # one piece, one write


@pytest.mark.parametrize("first,last", [
    (32 * MIB - 5, 32 * MIB + 5),        # across two groups
    (MIB - 10, MIB + 9),                 # across two blocks of a group
    (64 * MIB - 3, 64 * MIB),            # into the tail block
])
def test_range_is_exact(served, first, last):
    srv, bodies = served
    before = _sent()
    r = srv.request("GET", "/sendbkt/large",
                    headers={"Range": f"bytes={first}-{last}"})
    assert r.status == 206
    assert r.headers["Content-Range"] == \
        f"bytes {first}-{last}/{SIZES['large']}"
    assert r.body == bodies["large"][first:last + 1]
    want = last - first + 1
    assert _settle(lambda: _sent() - before, want) == want


def test_keep_alive_connection_is_framed_after_a_direct_body(served):
    """The worker's bytes are in the writer's `length` and `output_size`:
    the response ends where Content-Length says, the connection stays,
    and the next response on it starts at its own status line."""
    srv, bodies = served
    before = _sent()
    conn, resp = _open(srv, "/sendbkt/mid")
    try:
        sock = conn.sock
        assert resp.status == 200 and resp.read() == bodies["mid"]
        assert not resp.will_close
        for name in ("tail", "empty", "inline"):
            _, resp = _open(srv, f"/sendbkt/{name}", conn=conn)
            assert resp.status == 200
            assert resp.read() == bodies[name]
            assert conn.sock is sock  # no reconnect in between
    finally:
        conn.close()
    assert 0 < _sent() - before <= MID + sum(
        SIZES[n] for n in ("tail", "empty", "inline"))


def _decode_threads() -> int:
    # erasure/objects.py starts one a part: threading.Thread(target=
    # decode_ctx.run), which the interpreter names "Thread-N (run)"
    return sum(t.name.endswith("(run)") for t in threading.enumerate())


def _abort(srv) -> None:
    """Take the head and a little of the body, then leave with the rest
    unread: the server's next write meets a reset."""
    conn, resp = _open(srv, "/sendbkt/mid", rcvbuf=64 * 1024)
    try:
        assert resp.status == 200
        assert len(resp.read(64 * 1024)) == 64 * 1024
    finally:
        conn.close()


def test_client_that_leaves_mid_body_leaks_nothing(served, monkeypatch):
    """50 GETs broken off mid-body: every decode thread ends, every
    shard reader and every duplicate of a socket is closed, and nothing
    refers to a response block any more (its buffer is the pool's)."""
    srv, bodies = served
    blocks = []
    acquire = coding._block_acquire

    def recording(nblocks, block_len):
        block = acquire(nblocks, block_len)
        blocks.append(weakref.ref(block))
        return block

    monkeypatch.setattr(coding, "_block_acquire", recording)
    _abort(srv)  # what starts lazily (executor threads) starts here
    assert _settle(_decode_threads, 0) == 0
    time.sleep(0.2)  # the server's side of that connection is closed
    fds = len(os.listdir("/proc/self/fd"))
    for _ in range(50):
        _abort(srv)
    assert _settle(_decode_threads, 0) == 0
    # no more than before: a connection of an earlier test may have
    # closed since
    assert _settle(lambda: len(os.listdir("/proc/self/fd")) <= fds, True)
    assert len(blocks) >= 51

    def blocks_alive() -> int:
        gc.collect()
        return sum(ref() is not None for ref in blocks)

    assert _settle(blocks_alive, 0) == 0

    def pooled() -> bool:
        # a block's finalizer runs a moment after its weak references
        # are cleared, on the thread that dropped it
        with coding._arena_lock:
            coding._pool_take_in()
            return bool(coding._arena_pool.get(16 * MIB))

    assert _settle(pooled, True)
    # and the server serves on
    r = srv.request("GET", "/sendbkt/mid")
    assert r.status == 200 and r.body == bodies["mid"]


def _threads_inside_send() -> int:
    inside = 0
    for frame in sys._current_frames().values():
        while frame is not None:
            if frame.f_code is app_mod._BodySender.run.__code__:
                inside += 1
                break
            frame = frame.f_back
    return inside


def test_reader_that_stalls_gets_the_rest_from_the_loop(served):
    """A client that stops reading for longer than the fixed wait: the
    job hands the unsent rest of its piece to the event loop and the
    executor thread comes back; the body is whole all the same, and
    not all of it was a worker's."""
    srv, bodies = served
    before = _sent()
    conn, resp = _open(srv, "/sendbkt/mid", rcvbuf=64 * 1024)
    try:
        assert resp.status == 200
        got = resp.read(MIB)
        stall_s = 5 * app_mod._SEND_STALL_MS / 1000
        assert _settle(_threads_inside_send, 0, stall_s * 4) == 0
        time.sleep(stall_s)
        assert _threads_inside_send() == 0
        got += resp.read()
    finally:
        conn.close()
    assert got == bodies["mid"]
    assert 0 < _sent() - before < MID


@pytest.fixture
def pump_app(served):
    """The pump alone behind a handler of the test's own: an aiohttp
    application on its own loop and port whose handler streams `chunks`
    through the served server's `_pump_stream`."""
    import asyncio

    from aiohttp import web

    srv, _ = served
    chunks = [b"a" * 70_000, memoryview(b"b" * 3), b"", b"c" * MIB]
    started = []

    def start(ssl_ctx=None, length: bool = False):
        async def handler(request):
            headers = {"Content-Length": str(sum(map(len, chunks)))} \
                if length else {}
            resp = web.StreamResponse(headers=headers)
            await resp.prepare(request)
            await srv.server._pump_stream(resp, iter(chunks), request)
            await resp.write_eof()
            return resp

        app = web.Application()
        app.router.add_get("/", handler)
        loop = asyncio.new_event_loop()
        ready = threading.Event()
        box = {}

        async def up():
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0, ssl_context=ssl_ctx)
            await site.start()
            box["runner"] = runner
            box["port"] = runner.addresses[0][1]
            ready.set()

        def serve():
            asyncio.set_event_loop(loop)
            loop.run_until_complete(up())
            loop.run_forever()

        thread = threading.Thread(target=serve, daemon=True)
        thread.start()
        assert ready.wait(10)
        started.append((loop, thread, box["runner"]))
        return box["port"]

    yield start, b"".join(bytes(c) for c in chunks)
    for loop, thread, runner in started:
        asyncio.run_coroutine_threadsafe(runner.cleanup(), loop).result(10)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(10)
        assert not thread.is_alive()
        loop.close()


def _tls_contexts(tmp_path):
    import datetime
    import ipaddress
    import ssl

    pytest.importorskip("cryptography")
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.x509.oid import NameOID

    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(NameOID.COMMON_NAME, "127.0.0.1")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (x509.CertificateBuilder()
            .subject_name(name).issuer_name(name)
            .public_key(key.public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(now - datetime.timedelta(days=1))
            .not_valid_after(now + datetime.timedelta(days=1))
            .add_extension(x509.SubjectAlternativeName(
                [x509.IPAddress(ipaddress.ip_address("127.0.0.1"))]),
                critical=False)
            .sign(key, hashes.SHA256()))
    pem = tmp_path / "server.pem"
    pem.write_bytes(
        cert.public_bytes(serialization.Encoding.PEM)
        + key.private_bytes(serialization.Encoding.PEM,
                            serialization.PrivateFormat.PKCS8,
                            serialization.NoEncryption()))
    server = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    server.load_cert_chain(str(pem))
    client = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    client.load_verify_locations(str(pem))
    return server, client


@pytest.mark.parametrize("how", ["plain", "chunked", "tls", "no_native"])
def test_only_a_plain_socket_with_a_length_goes_direct(
        served, pump_app, tmp_path, monkeypatch, how):
    """What the pump sees on the response decides, nothing else: a
    chunked payload writer (no Content-Length), a TLS transport and a
    server without the native library keep the loop's write and book
    no `send` bytes; the same chunks with a length on a plain socket
    are all the worker's."""
    start, body = pump_app
    if how == "no_native":
        monkeypatch.setattr(served[0].server, "native_send", False)
    if how == "tls":
        server_ctx, client_ctx = _tls_contexts(tmp_path)
        port = start(ssl_ctx=server_ctx, length=True)
        conn = http.client.HTTPSConnection("127.0.0.1", port, timeout=30,
                                           context=client_ctx)
    else:
        port = start(length=how != "chunked")
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    before = _sent()
    try:
        conn.request("GET", "/")
        resp = conn.getresponse()
        assert resp.status == 200
        assert (resp.getheader("Transfer-Encoding") == "chunked") \
            == (how == "chunked")
        assert resp.read() == body
        # framed to its end: the connection takes a second request
        conn.request("GET", "/")
        assert conn.getresponse().read() == body
    finally:
        conn.close()
    want = 2 * len(body) if how == "plain" else 0
    assert _settle(lambda: _sent() - before, want, 2.0) == want


def test_native_send_stops_at_a_stall_and_raises_at_a_reset():
    """`ops/host.py` `sock_send` on a socket pair: all of a piece that
    fits, a part where the peer stops reading (after the fixed wait, not
    before), the rest once it reads again, an error once it is gone."""
    from minio_tpu.ops import host

    if not host.available():
        pytest.skip("no native library on this box")
    ours, peer = socket.socketpair()
    try:
        ours.setblocking(False)
        assert host.sock_send(ours.fileno(), b"abc", 50) == 3
        assert host.sock_send(ours.fileno(), memoryview(b""), 50) == 0
        assert peer.recv(16) == b"abc"
        piece = _body("piece", 8 * MIB)
        t0 = time.monotonic()
        sent = host.sock_send(ours.fileno(), memoryview(piece), 50)
        assert 0 < sent < len(piece)
        assert time.monotonic() - t0 >= 0.05
        got = bytearray()

        def drain():
            while len(got) < len(piece):
                got.extend(peer.recv(1 << 20))

        reader = threading.Thread(target=drain)
        reader.start()
        assert host.sock_send(ours.fileno(), memoryview(piece)[sent:],
                              5000) == len(piece) - sent
        reader.join(10)
        assert not reader.is_alive() and bytes(got) == piece
        peer.close()
        with pytest.raises(OSError):
            for _ in range(64):  # the first writes may still be buffered
                host.sock_send(ours.fileno(), piece, 50)
    finally:
        ours.close()
        peer.close()


def test_qos_debits_the_body_the_worker_sent(tmp_path, monkeypatch):
    """With QoS on every chunk is charged to its tenant from the job
    that sends it: the egress debit is the body's length, and a tenant
    without a limit is not paced, so all of it goes direct."""
    monkeypatch.setenv("MINIO_TPU_QOS", "1")
    monkeypatch.setenv("MINIO_TPU_QOS_TENANTS",
                       json.dumps({"bucket:paced": {"bandwidth": 2 << 20}}))
    srv = S3TestServer(str(tmp_path / "qos"))
    try:
        body = _body("qos", 2 * MIB + 17)
        for bucket in ("free", "paced"):
            assert srv.request("PUT", f"/{bucket}").status == 200
            assert srv.request("PUT", f"/{bucket}/obj",
                               data=body).status == 200
        before = _sent()
        r = srv.request("GET", "/free/obj")
        assert r.status == 200 and r.body == body
        assert _settle(lambda: _sent() - before, len(body)) == len(body)
        # the PUT took the burst of 2 MiB: at 2 MiB/s the tenant owes about
        # a second for the group, which the loop sleeps and writes
        before = _sent()
        t0 = time.monotonic()
        r = srv.request("GET", "/paced/obj")
        assert r.status == 200 and r.body == body
        assert time.monotonic() - t0 > 0.5
        assert _sent() - before < len(body)
        tenants = srv.server.qos.stats()["tenants"]
        for bucket in ("free", "paced"):
            assert tenants[f"bucket:{bucket}"]["throttledOutBytes"] \
                == len(body)
    finally:
        srv.close()


@pytest.mark.parametrize("name,reader,unit,better", [
    ("get_send_s_per_GiB", "stage_s_per_GiB", "s/GiB", "lower"),
    ("send_bytes_per_byte.get", "stage_bytes_per_byte", "B/B", "higher"),
])
def test_benchmark_reads_the_stage(name, reader, unit, better):
    """The two per-layer metrics are data files over readers that the
    benchmark has: they read `send` per byte of `respond`, in the five
    get cells, and nothing (no error) from a program without the
    stage."""
    import importlib

    from benchmark import manifest

    (spec,) = [m for m in manifest.benchmark()["per_layer"]
               if m["name"] == name]
    assert spec["layer"] == "HTTP, SigV4, admission"
    assert spec["moves"] == "get_MiBps" and spec["better"] == better
    assert spec["source"] == "program_counter" and spec["unit"] == unit
    assert spec["workloads"] == [
        "ec2p2-4d.get-degraded", "ec2p2-4d.get-healthy",
        "ec12p4-16d.get-degraded", "ec8p4-12d.get-degraded",
        "ec12p4-16d-warp.get-degraded"]
    read, args = manifest.reader(name)
    assert read is importlib.import_module(
        f"benchmark.readers.{reader}").read
    assert args == {"stage": "send", "per": "respond"}
    scrape = {"stage_bytes": {"respond": 8 * MIB, "send": 6 * MIB},
              "stage_seconds": {"respond": 0.01, "send": 0.5}}
    window = {"stage_bytes": {"respond": 72 * MIB, "send": 70 * MIB},
              "stage_seconds": {"respond": 0.09, "send": 0.53125}}
    got = read({"counters": {"before": scrape, "after": window}}, **args)
    assert got == pytest.approx(1.0 if unit == "B/B" else 0.5)
    for row in (scrape, window):
        del row["stage_bytes"]["send"], row["stage_seconds"]["send"]
    assert read({"counters": {"before": scrape, "after": window}},
                **args) is None
