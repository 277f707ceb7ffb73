"""ISSUE 27: the PUT body pipe (server/app.py `_QueuePipeReader`) moves a
body byte once on its way from the socket's chunk to the caller.

The pipe alone, fed from a thread in chunks of every size, read in every
way its callers read it; then multi-batch PUTs (more than one 32 MiB
arena) through the in-process server, sent in small pieces, one for each
reader that stands on the pipe.  What is asserted about cost is a count
of bytes from the pipe's own counter, never a time: no test of the box.
"""

import base64
import hashlib
import http.client
import threading
import time

import numpy as np
import pytest

from minio_tpu.crypto._aead import HAVE_AESGCM
from minio_tpu.erasure import stagestats
from minio_tpu.server import sigv4
from minio_tpu.server.app import _QueuePipeReader

from .s3_harness import S3TestServer

KIB, MIB = 1 << 10, 1 << 20


def _body(n: int, seed: int = 27) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _cut(body: bytes, sizes: list[int]) -> list[bytes]:
    """`body` in chunks of `sizes`, cycled; a size of 0 is an empty chunk
    in the queue (a keep-alive of the socket), which holds no byte."""
    out, at, i = [], 0, 0
    while at < len(body):
        n = sizes[i % len(sizes)]
        out.append(body[at:at + n])
        at += n
        i += 1
    return out


# name -> (body bytes, chunk sizes); 1-byte chunks on a short body
CHUNKINGS = {
    "1B": (4099, [1]),
    "64KiB": (3 * MIB + 17, [64 * KIB]),
    "128KiB": (3 * MIB + 17, [128 * KIB]),
    "1MiB": (3 * MIB + 17, [MIB]),
    "mixed": (3 * MIB + 17, [1, 64 * KIB, 7, MIB, 300, 128 * KIB, 70001]),
    "empty_chunks": (3 * MIB + 17, [0, 64 * KIB, 0, 0, 5, 128 * KIB]),
}

# name -> the calls made in turn, cycled until the body has ended:
# ("read", n) or ("readinto", buffer bytes)
PATTERNS = {
    "read_small": [("read", 1000)],
    "read_large": [("read", MIB + MIB // 2)],
    "read_all": [("read", -1)],
    "readinto_small": [("readinto", 1000)],
    "readinto_large": [("readinto", MIB + MIB // 2)],
    "interleaved": [("read", 777), ("readinto", 300 * KIB), ("read", 200000),
                    ("readinto", 5), ("read", 64 * KIB)],
    "end_inside_buffer": [("readinto", 8 * MIB)],
}


def _feed(pipe: _QueuePipeReader, chunks: list[bytes]) -> threading.Thread:
    def run():
        for c in chunks:
            pipe.q.put(c)
        pipe.q.put(None)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _moved() -> int:
    return stagestats.snapshot()["body_copy"]["bytes"]


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("chunking", sorted(CHUNKINGS))
def test_pipe_gives_the_body_back_with_one_copy(chunking, pattern):
    size, sizes = CHUNKINGS[chunking]
    body = _body(size)
    pipe = _QueuePipeReader()
    moved0 = _moved()
    feeder = _feed(pipe, _cut(body, sizes))
    got = bytearray()
    calls = PATTERNS[pattern]
    i = 0
    while True:
        how, n = calls[i % len(calls)]
        i += 1
        left = len(body) - len(got)
        if how == "read":
            data = pipe.read(n)
            # exactly n, short only where the body ends
            assert len(data) == (left if n < 0 else min(n, left))
        else:
            buf = bytearray(n)
            k = pipe.readinto(buf)
            # never 0 before the end; this pipe fills the buffer
            assert k == min(n, left)
            data = bytes(buf[:k])
            assert buf[k:] == bytes(n - k)  # nothing written past k
        if not data:
            break
        got += data
    feeder.join(10)
    assert not feeder.is_alive()
    assert bytes(got) == body
    # the end of the body stays the end
    assert pipe.read(10) == b"" and pipe.read() == b""
    assert pipe.readinto(bytearray(10)) == 0
    # each byte moved once: into the caller's buffer, or in one join
    assert _moved() - moved0 == len(body)


def test_pipe_holds_the_queue_and_one_chunk_and_no_more():
    """Back-pressure: with the reader gone quiet after one byte, the
    feeder gets rid of the chunk being read, a full queue, and then
    stands."""
    pipe = _QueuePipeReader()
    chunk = _body(64 * KIB)
    put = []

    def run():
        for _ in range(100):
            pipe.q.put(chunk)
            put.append(1)
        pipe.q.put(None)

    feeder = threading.Thread(target=run, daemon=True)
    feeder.start()
    assert pipe.read(1) == chunk[:1]
    held = 1 + pipe.q.maxsize
    deadline = time.monotonic() + 10
    while len(put) < held and time.monotonic() < deadline:
        time.sleep(0.001)
    time.sleep(0.05)  # and there it stands
    assert pipe.q.maxsize == 16 and len(put) == held and pipe.q.full()
    rest = pipe.read()
    feeder.join(10)
    assert chunk[:1] + rest == chunk * 100


# ---------------------------------------------------- through the server
BIG = 33 * MIB + 12345   # two arenas of the object layer, the second short
PIECE = 16 * KIB         # what the client hands the socket at a time


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    s = S3TestServer(str(tmp_path_factory.mktemp("pipe-drives")))
    assert s.request("PUT", "/pipebkt").status == 200
    yield s
    s.close()


@pytest.fixture(scope="module")
def big_body():
    return _body(BIG, seed=2727)


def _send_in_pieces(srv, path: str, headers: dict, wire: bytes):
    """One PUT whose body goes to the socket PIECE bytes at a time."""
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    try:
        conn.putrequest("PUT", path, skip_host=True,
                        skip_accept_encoding=True)
        for k, v in headers.items():
            conn.putheader(k, v)
        conn.putheader("content-length", str(len(wire)))
        conn.endheaders()
        view = memoryview(wire)
        for at in range(0, len(wire), PIECE):
            conn.send(view[at:at + PIECE])
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _aws_chunked(srv, path: str, body: bytes, chunk: int):
    """-> (headers, framed body) of a signed aws-chunked upload with real
    chained chunk signatures (tests/test_s3_server.py's, for any size)."""
    headers = {"host": srv.host,
               "x-amz-decoded-content-length": str(len(body)),
               "content-encoding": "aws-chunked"}
    signed = sigv4.sign_request("PUT", path, [], headers, None, srv.ak,
                                srv.sk, payload_hash=sigv4.STREAMING_PAYLOAD)
    auth = signed["authorization"]
    prev = auth.split("Signature=")[1]
    amz_date = signed["x-amz-date"]
    scope = auth.split("Credential=")[1].split(",")[0].split("/", 1)[1]
    skey = sigv4.signing_key(srv.sk, amz_date[:8], "us-east-1")
    framed = []
    for at in list(range(0, len(body), chunk)) + [len(body)]:
        c = body[at:at + chunk]
        prev = sigv4.chunk_signature(skey, prev, amz_date, scope,
                                     hashlib.sha256(c).hexdigest())
        framed += [f"{len(c):x};chunk-signature={prev}\r\n".encode(), c,
                   b"\r\n"]
    return signed, b"".join(framed)


def _ssec_triple() -> dict:
    key = b"\x27" * 32
    return {
        "x-amz-server-side-encryption-customer-algorithm": "AES256",
        "x-amz-server-side-encryption-customer-key":
            base64.b64encode(key).decode(),
        "x-amz-server-side-encryption-customer-key-md5":
            base64.b64encode(hashlib.md5(key).digest()).decode()}


@pytest.mark.parametrize("kind", [
    "unsigned_payload", "aws_chunked_signed", "content_md5",
    pytest.param("sse_c", marks=pytest.mark.skipif(
        not HAVE_AESGCM,
        reason="optional 'cryptography' wheel not installed")),
])
def test_multi_batch_put_through_the_server(srv, big_body, kind):
    """A body of more than one arena, each way a reader stands on the
    pipe: alone (`readinto`), under the chunked-signature decoder, under
    a tee hasher, under the SSE reader (`read(n)`)."""
    path = f"/pipebkt/{kind}.bin"
    md5 = hashlib.md5(big_body)
    get_headers = {}
    if kind == "aws_chunked_signed":
        headers, wire = _aws_chunked(srv, path, big_body, MIB)
    else:
        extra = {"host": srv.host}
        if kind == "content_md5":
            extra["Content-MD5"] = base64.b64encode(md5.digest()).decode()
        if kind == "sse_c":
            get_headers = _ssec_triple()
            extra.update(get_headers)
        headers = sigv4.sign_request(
            "PUT", path, [], extra, None, srv.ak, srv.sk,
            payload_hash=sigv4.UNSIGNED_PAYLOAD)
        wire = big_body
    read0 = stagestats.snapshot()["read"]["bytes"]
    moved0 = _moved()
    status, reply, text = _send_in_pieces(srv, path, headers, wire)
    assert status == 200, text
    stored = stagestats.snapshot()["read"]["bytes"] - read0
    moved = _moved() - moved0
    assert stored >= len(big_body)          # SSE stores the tags too
    assert len(wire) <= moved <= 2 * len(wire)
    if kind == "unsigned_payload":
        # nothing between the pipe and the arena: one copy a byte
        assert moved == stored == len(big_body)
    if kind != "sse_c":
        assert reply["ETag"] == f'"{md5.hexdigest()}"'
    r = srv.request("GET", path, headers=get_headers)
    assert r.status == 200
    assert r.body == big_body
    assert r.headers["ETag"] == reply["ETag"]
