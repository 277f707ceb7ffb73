"""S3-compatible HTTP server over the erasure ObjectLayer.

Equivalent of the reference's router + handler stack (cmd/api-router.go:188,
cmd/object-handlers.go, cmd/bucket-handlers.go): bucket CRUD, object
CRUD with ranges, ListObjectsV1/V2, ListBuckets, multipart, batch delete,
SigV4 header + presigned auth (incl. aws-chunked streaming uploads).

Async front (aiohttp) with the blocking object layer driven on a thread
pool — the asyncio analogue of the reference's goroutine-per-request
model with the global API throttle (cmd/handler-api.go).
"""

from __future__ import annotations

import asyncio
import base64
import contextvars
import functools
import hashlib
import io
import os
import queue as queue_mod
import re
import secrets
import threading
import time
import urllib.parse
import xml.etree.ElementTree as ET
from datetime import datetime, timezone
from urllib.parse import quote
from xml.sax.saxutils import escape

from aiohttp import web

from minio_tpu.storage import errors as st
from minio_tpu.erasure import stagestats
from minio_tpu.erasure.objects import PutObjectOptions
from minio_tpu.ops import host
from . import sigv4
from .bucket_meta import BucketMetaHandlers
from .object_extras import (
    LOCK_HOLD_KEY, LOCK_MODE_KEY, LOCK_UNTIL_KEY, TAGS_KEY,
    ObjectExtraHandlers, parse_tag_query,
)
from .s3errors import S3Error, from_storage_error
from minio_tpu.utils import tracing
from minio_tpu.utils.logger import log
from minio_tpu.utils.pubsub import PubSub
from .admin import AdminMixin
from .metrics import MetricsMixin
from .qos import QosPlane, TenantQueueFull
from .sse_handlers import SSEMixin, load_kms
from .zip_extract import ZipExtractMixin

XMLNS = "http://s3.amazonaws.com/doc/2006-03-01/"
VALID_BUCKET = re.compile(r"^[a-z0-9][a-z0-9.\-]{2,62}$")
# "minio" is reserved: the admin plane lives under /minio/... so a bucket
# of that name would shadow it (reference isMinioReservedBucket,
# cmd/generic-handlers.go guardReservedBucket)
RESERVED_BUCKETS = frozenset({"minio"})


def _iso(ts: float) -> str:
    return datetime.fromtimestamp(ts, tz=timezone.utc).strftime(
        "%Y-%m-%dT%H:%M:%S.000Z"
    )


def _cert_identity(der: bytes) -> tuple[str, float]:
    """(subject common name, not-valid-after unix time) of a DER client
    certificate.  Raises ImportError when the optional `cryptography`
    wheel is absent (the caller degrades to NotImplemented) and
    ValueError for anything unparseable/CN-less."""
    from cryptography import x509  # optional dep: gated like crypto/_aead
    from cryptography.x509.oid import NameOID

    try:
        cert = x509.load_der_x509_certificate(der)
        cns = cert.subject.get_attributes_for_oid(NameOID.COMMON_NAME)
        # not_valid_after_utc replaced not_valid_after in newer wheels
        exp = getattr(cert, "not_valid_after_utc", None)
        if exp is None:
            import datetime as _dt

            exp = cert.not_valid_after.replace(tzinfo=_dt.timezone.utc)
    except Exception as e:
        raise ValueError(str(e))
    if not cns or not cns[0].value:
        raise ValueError("certificate subject has no common name")
    return str(cns[0].value), exp.timestamp()


def _http_date(ts: float) -> str:
    return datetime.fromtimestamp(ts, tz=timezone.utc).strftime(
        "%a, %d %b %Y %H:%M:%S GMT"
    )


class _ChunkedSigReader(io.RawIOBase):
    """Decode and VERIFY aws-chunked (STREAMING-AWS4-HMAC-SHA256-PAYLOAD)
    framing: `hex-size;chunk-signature=...\r\n<bytes>\r\n` (reference
    cmd/streaming-signature-v4.go).  Each chunk's signature is chained from
    the previous one starting at the request's seed signature; a mismatch
    aborts the upload.

    ctx=None decodes WITHOUT per-chunk signature checks — the
    STREAMING-UNSIGNED-PAYLOAD-TRAILER mode modern SDKs default to
    (request auth still rides the signed headers).  Trailer lines after
    the final zero chunk (`x-amz-checksum-*` et al) land in
    `self.trailers`."""

    def __init__(self, raw: io.RawIOBase, ctx: sigv4.V4Context | None):
        self.raw = raw
        self.ctx = ctx
        self.prev_sig = ctx.seed_signature if ctx else ""
        self.buf = b""
        self.out = b""  # decoded-but-undelivered bytes (read(n) contract)
        self.eof = False
        self.trailers: dict[str, str] = {}

    def _read_line(self) -> bytes:
        while b"\r\n" not in self.buf:
            chunk = self.raw.read(65536)
            if not chunk:
                raise S3Error("IncompleteBody")
            self.buf += chunk
        line, self.buf = self.buf.split(b"\r\n", 1)
        return line

    def _read_n(self, n: int) -> bytes:
        while len(self.buf) < n:
            chunk = self.raw.read(max(65536, n - len(self.buf)))
            if not chunk:
                raise S3Error("IncompleteBody")
            self.buf += chunk
        out, self.buf = self.buf[:n], self.buf[n:]
        return out

    def _next_chunk(self) -> None:
        header = self._read_line()
        parts = header.split(b";", 1)
        try:
            size = int(parts[0], 16)
        except ValueError:
            raise S3Error("IncompleteBody")
        sig = b""
        if len(parts) == 2 and parts[1].startswith(b"chunk-signature="):
            sig = parts[1][len(b"chunk-signature="):].strip()
        data = self._read_n(size) if size else b""
        if self.ctx is not None:
            want = sigv4.chunk_signature(
                self.ctx.signing_key, self.prev_sig, self.ctx.amz_date,
                self.ctx.scope, hashlib.sha256(data).hexdigest(),
            )
            if sig.decode(errors="replace") != want:
                raise S3Error("SignatureDoesNotMatch",
                              "chunk signature mismatch")
            self.prev_sig = want
        if size == 0:
            self.eof = True
            self._read_trailers()
        else:
            self.out += data
            self._read_n(2)  # trailing \r\n

    # trailer section is small by construction; anything bigger is abuse
    _MAX_TRAILER = 16 << 10

    def _read_trailers(self) -> None:
        """Consume `name:value` lines after the zero chunk (aws-chunked
        trailers).  For signed streams (ctx set) the
        x-amz-trailer-signature line is verified over the canonical
        trailer section chained from the final chunk's signature — a
        forged or truncated trailer block fails here instead of passing
        silently (reference readTrailers,
        cmd/streaming-signature-v4.go)."""
        while len(self.buf) < self._MAX_TRAILER:
            chunk = self.raw.read(65536)
            if not chunk:
                break
            self.buf += chunk
        ordered: list[tuple[str, str]] = []
        for line in self.buf.split(b"\r\n"):
            line = line.strip()
            if not line or b":" not in line:
                continue
            name, _, value = line.partition(b":")
            k = name.decode(errors="replace").strip().lower()
            v = value.decode(errors="replace").strip()
            self.trailers[k] = v
            if k != "x-amz-trailer-signature":
                ordered.append((k, v))
        self.buf = b""
        if self.ctx is not None and ordered:
            canon = "".join(f"{k}:{v}\n" for k, v in ordered)
            want = sigv4.trailer_signature(
                self.ctx.signing_key, self.prev_sig, self.ctx.amz_date,
                self.ctx.scope, hashlib.sha256(canon.encode()).hexdigest())
            got = self.trailers.get("x-amz-trailer-signature", "")
            if got != want:
                raise S3Error("SignatureDoesNotMatch",
                              "trailer signature mismatch")

    def read(self, n: int = -1) -> bytes:
        while not self.eof and (n < 0 or len(self.out) < n):
            self._next_chunk()
        if n < 0:
            out, self.out = self.out, b""
        else:
            out, self.out = self.out[:n], self.out[n:]
        return out


class _IterStream(io.RawIOBase):
    """Read()-able view over an iterator of byte chunks."""

    def __init__(self, it):
        self.it = it
        self.buf = b""

    def readable(self) -> bool:
        return True

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            parts = [self.buf]
            self.buf = b""
            parts.extend(self.it)
            return b"".join(parts)
        while len(self.buf) < n:
            chunk = next(self.it, None)
            if chunk is None:
                break
            self.buf += chunk
        out, self.buf = self.buf[:n], self.buf[n:]
        return out


class _TeeHashReader(io.RawIOBase):
    """Pass-through reader feeding every byte into a hash object."""

    def __init__(self, r: io.RawIOBase, h):
        self.r = r
        self.h = h

    def read(self, n: int = -1) -> bytes:
        data = self.r.read(n)
        if data:
            self.h.update(data)
        return data


class _QueuePipeReader(io.RawIOBase):
    """Bridges async body chunks into the sync object layer: the event
    loop puts what the socket gave into a bounded queue (16 chunks, so a
    slow consumer holds the client back), the handler's thread reads.

    What a caller may count on:
    - `read(n)` returns exactly `n` bytes unless the body ends first,
      `read(-1)` all that is left, `b""` only at the end of the body (the
      chunked-signature decoder, the tee hashers and the SSE and
      compression readers ask with small and large `n`);
    - `readinto(b)` fills `b` unless the body ends first, straight from
      the queued chunks; 0 only at the end of the body;
    - a body byte is moved once on either path: `readinto` copies it
      into the caller's buffer, `read` joins the collected pieces once.
      Both copy with the interpreter lock held: a socket's chunk is
      64-128 KiB, some 10 us of memcpy, and letting go of the lock for
      that (numpy's assignment) cost more in getting it back than the
      other streams gained, on the CPU box and on the chip's host;
    - besides the queue the pipe holds the unread rest of one chunk (a
      view, no copy) and nothing else.

    Every call books its wait for the socket and its own work into the
    stage counters `body_wait` and `body_copy` (leaves of `read`)."""

    def __init__(self):
        self.q: queue_mod.Queue = queue_mod.Queue(maxsize=16)
        self._rest: memoryview | None = None  # of the chunk partly read
        self._waited = 0.0  # seconds in q.get() since the last _book()
        self.eof = False

    def _pieces(self, n: int):
        """Views of the queued chunks in order, `n` bytes in all (`n` < 0:
        to the end of the body); fewer only where the body ends."""
        while n:
            rest = self._rest
            if rest is None:
                if self.eof:
                    return
                try:
                    item = self.q.get_nowait()
                except queue_mod.Empty:
                    t0 = time.perf_counter()
                    item = self.q.get()
                    self._waited += time.perf_counter() - t0
                if item is None:
                    self.eof = True
                    return
                if not item:
                    continue
                rest = memoryview(item)
            if n < 0 or len(rest) <= n:
                piece, self._rest = rest, None
            else:
                piece, self._rest = rest[:n], rest[n:]
            n -= len(piece)
            yield piece

    def _book(self, t0: float, moved: int) -> None:
        spent = time.perf_counter() - t0
        stagestats.add("body_wait", self._waited)
        stagestats.add("body_copy", spent - self._waited, moved)
        self._waited = 0.0

    def readinto(self, b) -> int:
        t0 = time.perf_counter()
        dst = memoryview(b).cast("B")
        got = 0
        for piece in self._pieces(len(dst)):
            dst[got:got + len(piece)] = piece
            got += len(piece)
        self._book(t0, got)
        return got

    def read(self, n: int = -1) -> bytes:
        t0 = time.perf_counter()
        out = b"".join(self._pieces(-1 if n is None else n))
        self._book(t0, len(out))
        return out


# how long a response's executor thread waits on a socket that takes
# nothing before it gives the rest of the piece back to the event loop
_SEND_STALL_MS = 100


class _BodySender:
    """The executor's side of a response body (`S3Server._pump_stream`).

    `run` is the job: it pulls the iterator and, where the connection is
    a plain socket and the payload writer neither chunks nor compresses,
    writes each piece to the socket itself, from the thread that pulled
    it, in one native call (`ops/host.py` `sock_send`: `send` until the
    piece is out, `poll` where the socket is full).  The call lets go of
    the interpreter lock once a piece, so every stream's copy into its
    socket runs on its own thread and a 32 MiB group costs one lock
    hand-over where the event loop took one per partial send, about a
    hundred.  Anywhere else (TLS, chunked, compressed, no raw socket, no
    native library) the job pulls and the loop writes.

    One owner of the socket at a time: a job writes only while the
    transport's own buffer is empty (`settled`), and the loop writes
    only what a job handed back, while no job runs.  What a job sent it
    books into the writer's `length` and `output_size`, so `write_eof`,
    keep-alive and the access log read as if the loop had written it.

    A job works on a duplicate of the descriptor, made on the loop
    (`settled`) and closed by the job: the loop may close the
    transport's socket while the job is inside `write`, and a number it
    closed may be another connection's by then."""

    def __init__(self, it, resp: web.StreamResponse, writer, debit,
                 native: bool):
        self.it = it
        self.writer = writer  # aiohttp's StreamWriter of `resp`
        self.debit = debit  # bytes -> seconds of pacing the tenant owes
        self.stop = False   # the pump is leaving: a job ends at once
        self.fd = -1        # the duplicate the next job takes over
        tr = writer.transport
        self.direct = (
            tr is not None
            and tr.get_extra_info("socket") is not None
            and tr.get_extra_info("sslcontext") is None
            and not writer.chunked and not resp.compression
            and native)

    async def settled(self) -> None:
        """Event loop, before a job: the head is on the wire and the
        transport holds nothing, so that what the job writes follows
        what the loop wrote; then the job's descriptor.  `drain` alone
        returns at the low-water mark; with the mark at 0 it returns
        once the buffer is empty."""
        tr = self.writer.transport
        if not self.direct or tr is None:
            return
        self.writer.send_headers()
        if tr.get_write_buffer_size():
            low, high = tr.get_write_buffer_limits()
            tr.set_write_buffer_limits(high=0)
            try:
                await self.writer.drain()
            finally:
                tr.set_write_buffer_limits(high=high, low=low)
        sock = tr.get_extra_info("socket")
        if not tr.is_closing() and sock.fileno() >= 0:
            self.fd = os.dup(sock.fileno())

    def close(self) -> None:
        """Event loop, once no job runs: a duplicate no job took."""
        fd, self.fd = self.fd, -1
        if fd >= 0:
            os.close(fd)

    def run(self):
        """One executor job.  Returns `(pause_s, piece)` for the loop to
        sleep, write and come back, or None at the iterator's end.  The
        loop gets a piece where it has to write all of them, where the
        tenant owes pacing for it (QoS: a paced piece is a slow one),
        and the unsent rest of one that the socket stopped taking: no
        executor thread waits for a client that does not read."""
        fd, self.fd = self.fd, -1
        try:
            for chunk in self.it:
                view = memoryview(chunk)
                if view.nbytes != len(view):
                    view = view.cast("B")
                if not len(view):
                    continue
                pause = self.debit(len(view))
                if fd < 0 or pause > 0:
                    return pause, view
                rest = self._send(fd, view)
                if rest is not None:
                    return 0.0, rest
            return None
        finally:
            if fd >= 0:
                os.close(fd)

    def _send(self, fd: int, view: memoryview) -> memoryview | None:
        """Write `view` to the socket; the rest of it where the socket
        took nothing for `_SEND_STALL_MS`.  A connection that is gone
        raises, as a write on a closing transport does."""
        writer = self.writer
        if writer.length is not None:
            # as StreamWriter.write: never past Content-Length
            view = view[:writer.length]
        tr = writer.transport
        if self.stop or tr is None or tr.is_closing():
            raise ConnectionResetError("Cannot write to closing transport")
        with stagestats.timed("send") as leaf:
            leaf.nbytes = sent = host.sock_send(fd, view, _SEND_STALL_MS)
        writer.output_size += sent
        if writer.length is not None:
            writer.length -= sent
        return view[sent:] if sent < len(view) else None


class S3Server(BucketMetaHandlers, ObjectExtraHandlers, SSEMixin, AdminMixin,
               MetricsMixin, ZipExtractMixin):
    def __init__(self, object_layer, access_key: str = "minioadmin",
                 secret_key: str = "minioadmin", region: str = "us-east-1",
                 max_concurrency: int = 64, iam=None):
        import concurrent.futures as cf
        import time as time_mod
        from minio_tpu.bucket import BucketMetadataSys
        from minio_tpu.events.notifier import EventNotifier
        from minio_tpu.events.targets import load_targets_from_env
        from minio_tpu.iam import IAMSys

        self.api = object_layer
        self.iam = iam if iam is not None else IAMSys(
            object_layer, access_key, secret_key
        )
        self.meta = BucketMetadataSys(object_layer)
        self.kms = load_kms(object_layer)
        from minio_tpu.iam.oidc import OpenIDProvider
        self.oidc = OpenIDProvider.from_env()
        from minio_tpu.iam.ldap import LDAPProvider
        self.ldap = LDAPProvider.from_env()
        self.notifier = EventNotifier(
            self.meta, targets=load_targets_from_env(),
            queue_dir=_event_queue_dir(object_layer), region=region)
        self.region = region
        self.services = None   # ServiceManager, via attach_services()
        self.locker = None     # LocalLocker, set by ClusterNode
        self._start_time = time_mod.time()
        from minio_tpu.config import ServerConfig

        self.config = ServerConfig(object_layer)
        cfg_max = self.config.get("api", "requests_max")
        if cfg_max not in ("", "auto"):
            try:
                max_concurrency = max(1, int(cfg_max))
            except ValueError:
                pass
        self.sem = asyncio.Semaphore(max_concurrency)
        self.max_concurrency = max_concurrency
        # hot-object serving tier (ISSUE 7, serving/hotcache.py): an
        # in-RAM cache above the erasure layer, invalidated through the
        # ns_updated choke point on every mutation.  Hits ride a
        # dedicated admission lane (hot_sem) so RAM-served reads never
        # queue behind drive-bound work, never count as admission
        # pressure, and never engage brownout.
        from minio_tpu.serving import from_env as _hotcache_from_env

        self.hotcache = _hotcache_from_env()
        self._hotcache_pending_distributed = None
        # the ns_updated hook this server registered for its hot tier —
        # kept so online pool expansion can re-register the SAME
        # callable onto the new pool's sets (add_ns_update_hook dedups
        # by identity/equality; a fresh closure would double-fire)
        self._hotcache_ns_hook = None
        if self.hotcache is not None:
            from minio_tpu.erasure.objects import (add_ns_update_hook,
                                                   invalidation_plane)

            has_sets, all_local = invalidation_plane(object_layer)
            if has_sets and all_local:
                self._hotcache_ns_hook = self.hotcache.invalidate
                add_ns_update_hook(object_layer,
                                   self._hotcache_ns_hook)
            elif has_sets:
                # distributed deployment: a peer's write fires
                # ns_updated only on that node, so the tier stays OFF
                # until the cluster wiring provides the cross-node
                # hotcache_invalidate broadcast + TTL backstop
                # (enable_distributed_hotcache, called by ClusterNode
                # once the PeerNotifier exists — ISSUE 8 satellite)
                self._hotcache_pending_distributed = self.hotcache
                self.hotcache = None
            else:
                # no erasure invalidation plane below (pure gateway):
                # serving stale bytes is worse than serving slowly
                self.hotcache = None
        self.hot_sem = asyncio.Semaphore(max(max_concurrency, 4) * 2)
        # end-to-end deadline budget (reference requests_deadline,
        # cmd/handler-api.go:108): admission waits at most this long for
        # an API slot before shedding 503 SlowDown; the remainder rides
        # the request into storage/RPC as a budget
        from minio_tpu.utils import deadline as deadline_mod

        try:
            self.requests_deadline = deadline_mod.parse_duration(
                self.config.get("api", "requests_deadline"))
        except ValueError:
            self.requests_deadline = 60.0  # typo'd knob: keep the default
        self._waiters = 0  # event-loop-only counter of admission waiters
        # event-loop-only legacy-plane claim counters: slots HELD via
        # self.sem plus waiters PARKED on it.  A runtime QoS gate flip
        # seeds the new plane with held+parked (every parked waiter is
        # a claim on a slot a release will hand it), and each claim
        # that dissolves — a release no waiter takes, a parked waiter
        # shedding/disconnecting — frees one seeded plane slot, so
        # combined admissions never exceed max_concurrency.
        self._sem_held = 0
        self._sem_waiters = 0
        self._srv_loop = None  # serving loop, captured at first request
        # per-tenant QoS plane (ISSUE 13, server/qos.py): weighted
        # deficit-round-robin admission + per-tenant bandwidth buckets
        # replacing the single semaphore above when MINIO_TPU_QOS=1.
        # Default OFF: self.sem stays the byte- and metrics-identical
        # reference plane (pinned by tests/test_qos.py).
        self.qos = QosPlane.from_config(self.config, max_concurrency)
        self.config.on_change("qos", self._apply_qos_config)
        # closed-loop SLO plane (ISSUE 15, server/slo.py): per-class
        # latency/outcome accounting against declarative objectives
        # with multi-window error-budget burn rates.  Default OFF:
        # self.slo stays None and the server is byte- and metrics-
        # identical to before (pinned by tests/test_slo.py).
        from .slo import SloPlane

        self.slo = SloPlane.from_config(self.config)
        self.config.on_change("slo", self._apply_slo_config)
        # self-driving overload plane (ISSUE 18, server/controller.py):
        # a burn-rate feedback loop actuating QoS weights, GET hedging
        # and background brownout.  Constructed in attach_services (it
        # needs the brownout hook); None here keeps the gate-off server
        # byte- and metrics-identical (pinned by tests/test_controller)
        self.controller = None
        self.config.on_change("controller", self._apply_controller_config)
        # Dedicated pool sized to the request semaphore so a full house of
        # blocking object-layer calls can never starve body-feed tasks
        # (reference analogue: maxClients semaphore, cmd/handler-api.go:108).
        self.executor = cf.ThreadPoolExecutor(
            max_workers=max_concurrency + 4, thread_name_prefix="s3-api"
        )
        # a response body goes to its socket from the executor thread
        # that pulled it only through the native library (_BodySender);
        # asked here, where loading it (its first use builds it) holds
        # no event loop
        self.native_send = host.available()
        self.trace = PubSub()
        from minio_tpu.services.site import SiteReplicationSys

        self.site = SiteReplicationSys(object_layer, self.meta, self.iam)
        # geo-replication of object DATA (ISSUE 16, services/georep.py):
        # per-peer push queues over the site plane's peer registry.
        # Default OFF: self.georep is None and the server is byte- and
        # metrics-identical (pinned by tests/test_georep.py).
        from minio_tpu.services.georep import GeoRepSys

        self.georep = GeoRepSys.from_env(object_layer, self.site)
        if self.georep is not None:
            from minio_tpu.erasure.objects import add_ns_update_hook

            # a local write nudges the push workers through the same
            # ns_updated choke point that feeds hot tier/metacache/bloom
            add_ns_update_hook(object_layer, self.georep.on_ns_update)
        eq = _event_queue_dir(object_layer)
        log.init_audit(queue_dir=os.path.join(os.path.dirname(eq), "audit")
                       if eq else None, config=self.config)
        self.app = web.Application(client_max_size=1 << 30)
        self.app.on_startup.append(self._watch_loop)
        self.init_metrics()
        # fixed-prefix routes (admin + metrics/health) win over the S3
        # catch-alls
        self.register_admin_routes(self.app)
        self.register_metrics_routes(self.app)
        # CORS headers ride on on_response_prepare so STREAMED responses
        # (prepared inside their handlers) are decorated too
        self.app.on_response_prepare.append(self._cors_on_prepare)
        # every response — 200s, errors AND 503 sheds — carries the
        # request's trace id so a user report is greppable against the
        # captured store (ISSUE 12; absent entirely with tracing off)
        self.app.on_response_prepare.append(self._trace_on_prepare)
        self.app.router.add_route("*", "/", self.dispatch_root)
        self.app.router.add_route("*", "/{bucket}", self.dispatch_bucket)
        self.app.router.add_route("*", "/{bucket}/{key:.*}", self.dispatch_object)

    @staticmethod
    async def _watch_loop(app) -> None:
        """Runs on the event loop's thread as it starts to serve: that
        thread's CPU clock is the row `loop_cpu` of the stage seconds
        (one loop feeds every PUT's body and answers every small
        request; near 1 s a second it is full)."""
        stagestats.watch_thread("loop_cpu", threading.get_ident())

    def _emit(self, name, bucket: str, key: str, *, size: int = 0,
              etag: str = "", version_id: str = "", request=None) -> None:
        """Fire-and-forget S3 event emission (reference sendEvent,
        cmd/event-notification.go:248).  Matching + delivery happen on
        the thread pool so the response path never blocks on targets."""
        if not self.notifier.target_ids():
            return
        from minio_tpu.events.event import new_event

        ev = new_event(name, bucket, key, size=size, etag=etag,
                       version_id=version_id,
                       host=(request.remote or "") if request else "")
        if request is not None:
            ev.user_agent = request.headers.get("User-Agent", "")
        # lint: allow(budget-propagation): fire-and-forget event delivery must outlive the request's budget
        self.executor.submit(self.notifier.notify, ev)

    def close(self) -> None:
        """Release every resource this server owns: background services,
        the site-replication worker, the event notifier, and the request
        executor (leak-checked by tests/test_leaks.py)."""
        if self.controller is not None:
            # first: the controller's close() reverts every live
            # actuation, and it must do so while the planes it touched
            # are still alive
            try:
                self.controller.close()
            except Exception:
                pass
            self.controller = None
        if self.services is not None:
            try:
                self.services.close()
            except Exception:
                pass
            self.services = None
        if self.georep is not None:
            try:
                self.georep.close()
            except Exception:
                pass
        try:
            self.site.close()
        except Exception:
            pass
        try:
            self.notifier.close()
        except Exception:
            pass
        self.executor.shutdown(wait=False, cancel_futures=True)
        # worker plane: terminate I/O worker + hash-lane processes and
        # unlink their shm rings (no-op when MINIO_TPU_WORKERS unset;
        # a sibling server lazily restarts the plane if it needs it)
        try:
            from minio_tpu.parallel import workers as _workers

            _workers.shutdown_plane()
        except Exception:
            pass

    #: TTL backstop a distributed hot tier must run with when the
    #: operator set none: a peer that misses an invalidation broadcast
    #: (down / partitioned) serves stale bytes for at most this long
    HOTCACHE_DISTRIBUTED_TTL_S = 30.0

    def enable_distributed_hotcache(self, broadcast) -> bool:
        """Light the hot-object tier on a DISTRIBUTED deployment
        (ROADMAP item 3 follow-up): local mutations keep invalidating
        this node's tier through the ns_updated choke point AND
        broadcast `hotcache_invalidate` to every peer, so a write
        anywhere drops the object's cached bytes everywhere.  The
        broadcast is best-effort (fire-and-forget like every peer
        reload), so a nonzero TTL backstop is forced — a node that
        misses a broadcast converges within HOTCACHE_DISTRIBUTED_TTL_S.
        Returns True when the tier flipped on."""
        hc = self._hotcache_pending_distributed
        if hc is None or broadcast is None:
            return False
        from minio_tpu.erasure.objects import add_ns_update_hook

        if hc.ttl_s <= 0:
            hc.ttl_s = self.HOTCACHE_DISTRIBUTED_TTL_S

        def on_update(bucket: str, obj: str) -> None:
            hc.invalidate(bucket, obj)
            broadcast(bucket, obj)

        self._hotcache_ns_hook = on_update
        add_ns_update_hook(self.api, on_update)
        self.hotcache = hc
        self._hotcache_pending_distributed = None
        return True

    def rewire_topology_hooks(self) -> None:
        """Re-register every ns_updated choke-point consumer across the
        (possibly grown) pool set — called after an online pool
        expansion so the new pool's sets invalidate the hot tier,
        metacache and bloom tracker exactly like the boot-time pools.
        Every registration is idempotent (add_ns_update_hook dedups),
        so re-walking existing pools is free."""
        from minio_tpu.erasure.objects import add_ns_update_hook

        if self._hotcache_ns_hook is not None:
            add_ns_update_hook(self.api, self._hotcache_ns_hook)
        mc = getattr(self.api, "_metacache", None)
        if mc is not None:
            add_ns_update_hook(self.api, mc.on_ns_update)
        if self.georep is not None:
            add_ns_update_hook(self.api, self.georep.on_ns_update)
        svcs = self.services
        if svcs is not None:
            svcs._attach_heal_queue()

    def attach_services(self, services) -> None:
        """Adopt the background ServiceManager (heal/MRF/scanner) so the
        admin plane can reach it (reference: serverMain starting
        initAutoHeal/initHealMRF/initDataScanner, cmd/server-main.go:528)."""
        self.services = services
        if services is not None and self.georep is not None:
            # steady-state delta discovery rides the scanner's bloom
            # change tracker (first sweep is full regardless)
            self.georep.attach_tracker(
                getattr(services, "tracker", None))
        if services is not None and getattr(services, "tier", None) is None:
            from minio_tpu.services.tier import TierManager

            eq = _event_queue_dir(self.api)
            services.tier = TierManager(
                self.api,
                journal_dir=os.path.join(os.path.dirname(eq),
                                         "tier-journal") if eq else None)
        if services is not None and services.scanner.lifecycle_fn is None:
            # scanner applies this server's stored ILM configs
            # (cmd/data-scanner.go:891 applyActions)
            from minio_tpu.services.lifecycle import LifecycleRunner

            services.scanner.lifecycle_fn = LifecycleRunner(
                self.api, self.meta,
                transition_fn=services.tier.transition)
        if services is not None \
                and getattr(services, "replication", None) is None:
            from minio_tpu.services.replication import ReplicationPool

            services.replication = ReplicationPool(
                self.api, self.meta,
                workers=self.config.get_int("replication", "workers", 2))
        if services is not None \
                and getattr(services, "brownout", None) is not None:
            # brownout thresholds from config (api.brownout_*): depth
            # "auto" = half the API slots — queue depth beyond that means
            # the foreground is saturated and background work must yield
            from minio_tpu.utils import deadline as deadline_mod

            bo = services.brownout
            depth_raw = self.config.get("api", "brownout_depth", "auto")
            if depth_raw not in ("", "auto"):
                try:
                    bo.engage_depth = max(1, int(depth_raw))
                except ValueError:
                    pass
            else:
                bo.engage_depth = max(2, self.max_concurrency // 2)
            try:
                rel = deadline_mod.parse_duration(
                    self.config.get("api", "brownout_release", "5s"))
                if rel is not None:
                    bo.release_after = rel
            except ValueError:
                pass
        if services is not None:
            # dynamic config application (reference applyDynamicConfig)
            def _apply_scanner(cfg):
                services.scanner.interval = cfg.get_int(
                    "scanner", "interval", 60)

            def _apply_heal(cfg):
                services.bg_heal.interval = cfg.get_int(
                    "heal", "interval", 3600)

            self.config.on_change("scanner", _apply_scanner)
            self.config.on_change("heal", _apply_heal)
            # persisted dynamic settings must take effect NOW, not only
            # on the next admin write — but only when explicitly set:
            # registry defaults must not stomp CLI/env-chosen intervals
            if self.config.is_set("scanner", "interval"):
                _apply_scanner(self.config)
            if self.config.is_set("heal", "interval"):
                _apply_heal(self.config)
        # overload controller (ISSUE 18): built here, not in __init__ —
        # its background-shed actuator is services.brownout
        if self.controller is None:
            from .controller import OverloadController

            self.controller = OverloadController.from_config(
                self, self.config)
            if self.controller is not None:
                self.controller.start()

    def _quota_check(self, bucket: str, size: int) -> None:
        """Hard-quota enforcement against the scanner's usage cache
        (reference enforceBucketQuota, cmd/bucket-quota.go:112)."""
        quota = self.meta.quota(bucket)
        if quota <= 0:
            return
        usage = 0
        if self.services is not None:
            bu = self.services.scanner.usage.buckets.get(bucket)
            if bu is not None:
                usage = bu.size
        if usage + max(size, 0) > quota:
            raise S3Error("XMinioAdminBucketQuotaExceeded", resource=bucket)

    # ------------------------------------------------------------------ util
    async def _hop(self, ctx, job):
        """`job()` on an `s3-api` thread under the copied context `ctx`
        (run_in_executor alone drops contextvars), with the hop's two
        waits booked as stages: `exec_wait`, from the hand-over to the
        executor until the job's first line on its thread, and
        `loop_wait`, from its last line there until this coroutine's
        next line on the loop.  Both are booked inside the request's
        context, so a captured trace shows them."""
        loop = asyncio.get_running_loop()
        done = 0.0
        t0 = time.perf_counter()

        def on_thread():
            nonlocal done
            stagestats.add("exec_wait", time.perf_counter() - t0)
            try:
                return job()
            finally:
                done = time.perf_counter()

        try:
            return await loop.run_in_executor(
                self.executor, lambda: ctx.run(on_thread))
        finally:
            if done:  # else cancelled here before the job's end
                stagestats.add("loop_wait", time.perf_counter() - done)

    async def _run(self, fn, *args, **kw):
        # copy_context carries the request's deadline budget into the
        # executor thread
        return await self._hop(contextvars.copy_context(),
                               functools.partial(fn, *args, **kw))

    async def _run_nobudget(self, fn, *args, **kw):
        """_run WITHOUT the request's deadline budget: body streaming and
        other whole-payload phases (PUT bodies, multipart assembly, GET
        streaming, Select scans) must not be killed mid-transfer when the
        admission budget — which bounds queue wait and time-to-first-byte
        work — runs out.

        The rest of the context DOES travel — in particular the request
        trace (utils/tracing.py): a whole-payload phase is budget-free
        by contract but its time must still be attributable, so the
        copied context runs with ONLY the Budget var cleared."""
        from minio_tpu.utils import deadline as deadline_mod

        def nobudget():
            token = deadline_mod.set_current(None)
            try:
                return fn(*args, **kw)
            finally:
                deadline_mod.reset(token)

        return await self._hop(contextvars.copy_context(), nobudget)

    async def _pump_stream(self, resp: web.StreamResponse, stream,
                           request: web.Request) -> None:
        """Stream an iterator's chunks to the prepared response: one
        executor job a response pulls each chunk and writes it to the
        connection's socket from that thread, and the event loop awaits
        the job alone (`_BodySender`).  The producer runs ahead of the
        job by itself (a decode thread fills `_IterSink`, up to 8
        groups), so the socket's round trips never hold the decode
        pipeline (ISSUE 5 overlapped GET).  The loop writes what a job
        hands back: the rest of a piece a slow reader did not take, a
        piece its tenant is paced for (QoS: every chunk is debited
        against the tenant's egress bucket), and every piece of a
        response that cannot go direct (TLS, chunked, compressed),
        where the next job pulls chunk N+1 while the loop writes N."""
        send = _BodySender(
            iter(stream), resp, await resp.prepare(request),
            functools.partial(self._qos_debit, request, direction="out"),
            self.native_send)
        job = None
        try:
            while True:
                if job is None:
                    await send.settled()
                    job = asyncio.ensure_future(self._run_nobudget(send.run))
                # shielded: a cancelled pump leaves the thread running,
                # and waits for it below before anyone closes the stream
                back = await asyncio.shield(job)
                job = None
                if back is None:
                    return
                pause, piece = back
                if not send.direct:
                    job = asyncio.ensure_future(self._run_nobudget(send.run))
                if pause > 0:
                    await asyncio.sleep(pause)
                await resp.write(piece)
        finally:
            send.stop = True
            if job is not None:
                # a job may still be inside the iterator or the socket:
                # the caller's cleanup closes both
                try:
                    await job
                except Exception:
                    pass
            send.close()

    async def _feed(self, pipe: "_QueuePipeReader", item, task) -> None:
        """Non-blocking queue feed from the event loop; aborts if the
        consuming task already finished (e.g. it errored before draining)."""
        while True:
            if task is not None and task.done():
                return
            try:
                pipe.q.put_nowait(item)
                return
            except queue_mod.Full:
                await asyncio.sleep(0.005)

    def _xml(self, status: int, body: str,
             headers: dict | None = None) -> web.Response:
        h = {"Server": "MinIO-TPU"}
        if headers:
            h.update(headers)
        return web.Response(
            status=status, body=body.encode(),
            content_type="application/xml", headers=h,
        )

    async def _auth(self, request: web.Request, payload_hash: str | None,
                    action: str = "", bucket: str = "", obj: str = ""):
        """SigV4 verification + IAM/bucket-policy authorization for
        `action` on the resource (reference checkRequestAuthType,
        cmd/auth-handler.go).  Decision combines the IAM layer with the
        bucket policy; an explicit Deny in either layer wins."""
        # stage `auth`: signature check and policy, for every caller.  The
        # policy look-up may await the executor; its wait is in the span
        with stagestats.timed("auth"):
            query = [(k, v) for k, v in urllib.parse.parse_qsl(
                request.rel_url.query_string, keep_blank_values=True
            )]
            headers = dict(request.headers)
            headers["host"] = request.headers.get("Host", request.host)
            path = urllib.parse.unquote(request.rel_url.raw_path)
            conditions = self._request_conditions(request)

            if self._is_anonymous(request):
                # anonymous request: the bucket policy alone decides
                # (reference cmd/auth-handler.go authTypeAnonymous path)
                if action and bucket and await self._authorized(
                        "*", action, bucket, obj, conditions):
                    return sigv4.V4Context("", b"", "", "", "")
                raise S3Error("AccessDenied", "anonymous access denied",
                              resource=request.path)

            try:
                qd = dict(query)
                auth_hdr = request.headers.get("Authorization", "")
                if "X-Amz-Signature" in qd:
                    ctx = sigv4.verify_v4_presigned(
                        request.method, path, query, headers,
                        self.iam.get_secret, self.region,
                    )
                elif "Signature" in qd and "AWSAccessKeyId" in qd:
                    # legacy V2 presigned (reference cmd/signature-v2.go)
                    ctx = sigv4.verify_v2_presigned(
                        request.method, path, query, headers,
                        self.iam.get_secret,
                    )
                elif auth_hdr.startswith("AWS ") \
                        and not auth_hdr.startswith("AWS4-"):
                    # legacy V2 header form
                    ctx = sigv4.verify_v2(
                        request.method, path, query, headers,
                        self.iam.get_secret,
                    )
                else:
                    ctx = sigv4.verify_v4(
                        request.method, path, query, headers, payload_hash,
                        self.iam.get_secret, self.region,
                    )
            except sigv4.SigV4Error as e:
                raise S3Error(e.code, str(e))
            request["accessKey"] = ctx.access_key  # for audit/trace entries
            if action:
                if not await self._authorized(ctx.access_key, action, bucket,
                                              obj, conditions):
                    raise S3Error("AccessDenied", f"not allowed to {action}",
                                  resource=request.path)
            return ctx

    @staticmethod
    def _is_anonymous(request: web.Request) -> bool:
        q = request.rel_url.query
        return ("Authorization" not in request.headers
                and "X-Amz-Signature" not in q
                and not ("Signature" in q and "AWSAccessKeyId" in q))

    @staticmethod
    def _request_conditions(request: web.Request) -> dict:
        """Policy condition context shared by every authorization path
        (single-object _auth and per-key bulk checks must not diverge)."""
        return {"aws:SourceIp": request.remote or ""}

    async def _authorized(self, access_key: str, action: str, bucket: str,
                          obj: str, conditions: dict) -> bool:
        """Combined IAM + bucket-policy decision, deny-wins across layers.
        Used by _auth and by per-key authorization in bulk operations so
        both paths enforce identical semantics.  access_key '*' (or empty)
        means anonymous: the bucket policy alone decides."""
        if not access_key or access_key == "*":
            decision = await self._run(
                self._bucket_policy_decision, "*", action, bucket, obj,
                conditions)
            return decision == "allow"
        iam_decision = self.iam.evaluate(
            access_key, action, bucket, obj, conditions=conditions,
        )
        allowed = iam_decision == "allow"
        if iam_decision == "none" and bucket:
            # no IAM statement matched: the bucket policy may grant
            # (an explicit IAM Deny is final and never reaches here)
            decision = await self._run(
                self._bucket_policy_decision, access_key, action,
                bucket, obj, conditions)
            allowed = decision == "allow"
        elif allowed and bucket:
            # bucket-policy Deny overrides an IAM allow (deny-wins
            # across layers), except for the root account
            if access_key != self.iam.root.access_key:
                decision = await self._run(
                    self._bucket_policy_decision, access_key, action,
                    bucket, obj, conditions)
                allowed = decision != "deny"
        return allowed

    def _bucket_policy_decision(self, account: str, action: str, bucket: str,
                                obj: str, conditions: dict) -> str:
        from minio_tpu.iam.policy import PolicyArgs

        try:
            pol = self.meta.policy(bucket)
        except Exception:
            return "none"
        if pol is None:
            return "none"
        return pol.evaluate(PolicyArgs(
            action=action, bucket=bucket, object=obj, account=account,
            conditions=conditions,
        ))

    def _apply_qos_config(self, cfg) -> None:
        """Dynamic `qos` subsystem apply (admin PUT /minio/admin/v3/qos
        or set-config-kv): weights/caps/limits take effect without a
        restart, and the gate itself can flip at runtime.  In-flight
        requests release against the plane instance they were admitted
        by (captured per-request in _handle), so a flip never strands a
        slot."""
        if not QosPlane.gate_enabled(cfg):
            self.qos = None
            return
        plane = self.qos
        if plane is not None:
            plane.load_config(cfg)
            return
        plane = QosPlane.from_config(cfg, self.max_concurrency)
        loop = self._srv_loop
        if loop is None or loop.is_closed():
            # no request has ever run: nothing is in flight to seed
            self.qos = plane
            return

        def install() -> None:
            # on the serving loop, where the claim counters are
            # maintained: the seed exactly matches the claim-dissolve
            # credits that will follow (external_release), so combined
            # admissions never exceed the pool
            plane.seed_external(self._sem_held + self._sem_waiters)
            self.qos = plane

        loop.call_soon_threadsafe(install)

    def _apply_slo_config(self, cfg) -> None:
        """Dynamic `slo` subsystem apply (admin PUT /minio/admin/v3/slo
        or set-config-kv): the gate flips at runtime like QoS.  Requests
        record against the plane captured at THEIR start (_handle /
        _admin_wrap), so a flip mid-request neither loses the sample to
        a vanished plane nor seeds a fresh plane with pre-flip time.
        No slot seeding is needed — the SLO plane only observes."""
        from .slo import SloPlane

        if not SloPlane.gate_enabled(cfg):
            self.slo = None
            return
        if self.slo is None:
            self.slo = SloPlane.from_config(cfg)

    def _apply_controller_config(self, cfg) -> None:
        """Dynamic `controller` subsystem apply: the overload
        controller starts/stops at runtime.  Stopping reverts every
        live actuation (OverloadController.close is a stand-down, not
        an abandonment)."""
        from .controller import OverloadController

        if not OverloadController.gate_enabled(cfg):
            if self.controller is not None:
                ctrl = self.controller
                self.controller = None
                ctrl.close()
            return
        if self.controller is None:
            self.controller = OverloadController.from_config(self, cfg)
            if self.controller is not None:
                self.controller.start()

    def _qos_debit(self, request: web.Request, n: int,
                   direction: str) -> float:
        """Charge `n` data-plane bytes (PUT-body ingest direction="in",
        GET streaming direction="out") to the request tenant's
        bandwidth bucket: the seconds of pacing it owes.  0.0 with QoS
        off.  Any thread."""
        qos = self.qos
        if qos is None or n <= 0:
            return 0.0
        tenant = request.get("qosTenant") or qos.classify(request)
        return qos.bw_wait(tenant, n, direction)

    async def _qos_throttle(self, request: web.Request, n: int,
                            direction: str) -> None:
        """`_qos_debit` for the event loop: paces with asyncio.sleep so
        a throttled tenant never blocks it."""
        wait = self._qos_debit(request, n, direction)
        if wait > 0:
            await asyncio.sleep(wait)

    def _request_budget(self, request: web.Request):
        """Deadline budget for one request: `api.requests_deadline`
        clamped down by an `x-amz-request-timeout` header (the client may
        only SHORTEN its budget — a raise would bypass shedding)."""
        from minio_tpu.utils import deadline as deadline_mod

        seconds = self.requests_deadline
        hdr = request.headers.get("x-amz-request-timeout")
        if hdr:
            try:
                v = deadline_mod.parse_duration(hdr)
            except ValueError:
                v = None  # malformed header: ignore, keep the config knob
            if v is not None:
                seconds = v if seconds is None else min(seconds, v)
        return deadline_mod.Budget(seconds)

    def _shed_response(self, api: str, reason: str = "",
                       note_brownout: bool = True) -> web.Response:
        """503 SlowDown for a request shed at admission (reference sheds
        with 503 after requests_deadline, cmd/handler-api.go:108).
        `reason` distinguishes the per-tenant QoS sheds; unset keeps the
        legacy message byte-identical.  `note_brownout=False` for QoS
        sheds fired while the node still had free slots: a capped/full
        tenant's PRIVATE backlog is isolation working, and must not
        brown out background heal/scanner on an otherwise idle node."""
        self._m_shed.inc()
        svcs = self.services
        if note_brownout and svcs is not None \
                and getattr(svcs, "brownout", None) is not None:
            svcs.brownout.note_shed()
        msg = ("request shed: admission queue wait exceeded the "
               "request deadline")
        if reason == "tenant-queue-full":
            msg = ("request shed: this tenant's admission queue is "
                   "full (per-tenant QoS)")
        elif reason == "deadline":
            msg = ("request shed: budget expired in the tenant "
                   "admission queue (per-tenant QoS)")
        e = S3Error("SlowDown", msg)
        return web.Response(
            status=e.status, body=e.to_xml(secrets.token_hex(8)),
            content_type="application/xml",
            headers={"Retry-After": "1"},
        )

    async def _admit_qos(self, request: web.Request, qos, tenant: str,
                         hot: bool, budget, root, t0: float, api: str,
                         svcs):
        """Weighted-DRR admission (server/qos.py, ISSUE 13).

        Returns ``(admitted, lane, shed_resp)``:
        * ``lane is None``      — granted a QoS slot (release through
                                  qos.release);
        * ``lane is hot_sem``   — probable RAM hit rode the hot lane;
        * ``shed_resp``         — 503 SlowDown (full tenant queue, or
                                  the budget expired while queued);
        ``admitted`` is True for the no-wait fast paths (feeds the
        trace's queued= tag, mirroring the legacy plane)."""
        # byte-estimated admission cost (ISSUE 14 satellite): one
        # multipart PUT spends Content-Length/cost_unit deficit points
        # (clamped), so it is priced honestly against N small GETs
        cost = qos.cost_of(request)
        if qos.try_admit(tenant, cost):
            return True, None, None
        if hot and not self.hot_sem.locked() \
                and qos.hot_lane_try(tenant):
            # same hot-lane economics as the legacy plane (RAM hits
            # spend no drive IOPs), with the re-probe after acquire;
            # admits and re-probe REJECTIONS both fold into per-tenant
            # stats so hit-ratio and shed counters stay honest under
            # QoS (ISSUE 13 satellite).  hot_lane_try is the per-tenant
            # cap (ISSUE 16 satellite): a tenant already holding its
            # share of the lane falls through to normal QoS admission,
            # so one tenant's flood of RAM hits can't crowd hot_sem
            # itself — the slot claim is released on the reject path
            # here and in _handle's finally on the served path
            await self.hot_sem.acquire()
            if self._hot_probe(request):
                self._m_hot_lane.inc()
                qos.note_hot_admit(tenant)
                if svcs is not None and getattr(
                        svcs, "brownout", None) is not None:
                    svcs.brownout.note_hot_bypass()
                return True, self.hot_sem, None
            self.hot_sem.release()
            qos.hot_lane_release(tenant)
            qos.note_hot_reject(tenant)
        try:
            fut, depth = qos.enqueue(tenant, cost)
        except TenantQueueFull:
            if root is not None:
                root.defer_child("admission", time.monotonic() - t0,
                                 lane="qos", queued=True, shed=True,
                                 reason="tenant-queue-full")
            return False, None, self._shed_response(
                api, reason="tenant-queue-full",
                note_brownout=qos.saturated())
        self._waiters += 1
        self._m_queue_waiting.inc()
        try:
            if svcs is not None \
                    and getattr(svcs, "brownout", None) is not None:
                # brownout pressure rides the AGGREGATE cross-tenant
                # depth: one tenant's private backlog is isolation
                # working, total backlog is the node overloaded
                svcs.brownout.note_pressure(depth)
            wait = budget.remaining()
            try:
                if wait == float("inf"):
                    await fut
                else:
                    await asyncio.wait_for(fut, timeout=wait)
            except asyncio.TimeoutError:
                if fut.done() and not fut.cancelled():
                    # the grant landed in the very tick the timeout
                    # fired: give the slot back before shedding
                    qos.release(tenant)
                qos.abandon(tenant, fut, deadline=True)
                if root is not None:
                    root.defer_child("admission",
                                     time.monotonic() - t0,
                                     lane="qos", queued=True,
                                     shed=True, reason="deadline")
                return False, None, self._shed_response(
                    api, reason="deadline",
                    note_brownout=qos.saturated())
            except asyncio.CancelledError:
                if fut.done() and not fut.cancelled():
                    qos.release(tenant)
                else:
                    qos.abandon(tenant, fut)
                raise
        finally:
            self._waiters -= 1
            self._m_queue_waiting.dec()
        return False, None, None

    async def _handle(self, request: web.Request, fn,
                      hot: bool = False) -> web.StreamResponse:
        from minio_tpu.utils import deadline as deadline_mod

        t0 = time.monotonic()
        api = getattr(fn, "__name__", "unknown")
        if self._srv_loop is None:
            self._srv_loop = asyncio.get_running_loop()
        self._m_inflight.inc()
        status = 500
        tx = 0
        budget = self._request_budget(request)
        lane = self.sem
        # per-tenant QoS (ISSUE 13): classify BEFORE tracing so the
        # root span carries tenant=, and stash the tenant for the
        # data-path bandwidth metering (put_object/_pump_stream)
        qos = self.qos
        # SLO plane captured at request START: a runtime gate flip
        # mid-request must record this request against the plane that
        # watched it begin, not whatever the flip installed
        slo = self.slo
        tenant = None
        qos_admitted = False
        if qos is not None:
            tenant = qos.classify(request)
            request["qosTenant"] = tenant
        # root span of the request trace (utils/tracing.py): minted
        # BEFORE admission so a 503 shed still has a greppable trace id;
        # the id is stamped on every response by _trace_on_prepare
        root = tracing.begin_request(api, method=request.method,
                                     path=request.path)
        if root is not None:
            request["traceId"] = root.trace.trace_id
            if tenant is not None:
                root.tag(tenant=tenant)
        try:
            # ---- admission: bounded queue wait, shed on expiry --------
            # fast path first: a free slot must not count as queue
            # pressure — only requests that actually find the semaphore
            # exhausted become waiters (a same-tick burst on an idle
            # server would otherwise spuriously engage brownout)
            svcs = self.services
            if qos is not None:
                try:
                    admitted, lane, resp = await self._admit_qos(
                        request, qos, tenant, hot, budget, root, t0,
                        api, svcs)
                except asyncio.CancelledError:
                    status = 499  # client gave up while queued
                    raise
                if resp is not None:
                    status = 503
                    return resp
                qos_admitted = lane is None
            elif not self.sem.locked():
                await self.sem.acquire()
                admitted = True
            else:
                admitted = False
                if hot and not self.hot_sem.locked():
                    # probable cache hit while the API lane is
                    # saturated: serve from the hot lane.  A RAM hit
                    # performs zero storage calls, so it must not queue
                    # behind drive-bound requests, count toward
                    # brownout pressure, or charge the drive-deadline
                    # plane (ISSUE 7 economics wiring).  The probe
                    # re-runs AFTER the acquire: a writer may have
                    # invalidated the entry since dispatch, and a
                    # request that will now do drive-bound work must
                    # pay normal admission below, not ride the
                    # unmetered hot lane.
                    await self.hot_sem.acquire()
                    if self._hot_probe(request):
                        lane = self.hot_sem
                        admitted = True
                        self._m_hot_lane.inc()
                        if svcs is not None and getattr(
                                svcs, "brownout", None) is not None:
                            svcs.brownout.note_hot_bypass()
                    else:
                        self.hot_sem.release()
            if not admitted and qos is None:
                self._waiters += 1
                self._sem_waiters += 1
                self._m_queue_waiting.inc()
                try:
                    if svcs is not None \
                            and getattr(svcs, "brownout", None) is not None:
                        svcs.brownout.note_pressure(self._waiters)
                    wait = budget.remaining()
                    if wait == float("inf"):
                        await self.sem.acquire()
                    else:
                        try:
                            await asyncio.wait_for(self.sem.acquire(),
                                                   timeout=wait)
                        except asyncio.TimeoutError:
                            status = 503
                            qos_now = self.qos
                            if qos_now is not None:
                                # the gate flipped while we were
                                # parked: this waiter's slot claim
                                # dissolves — credit the live plane
                                qos_now.external_release()
                            if root is not None:
                                root.defer_child(
                                    "admission",
                                    time.monotonic() - t0,
                                    lane="api", queued=True, shed=True)
                            return self._shed_response(api)
                except asyncio.CancelledError:
                    status = 499  # client gave up while queued
                    qos_now = self.qos
                    if qos_now is not None:
                        qos_now.external_release()
                    raise
                finally:
                    self._waiters -= 1
                    self._sem_waiters -= 1
                    self._m_queue_waiting.dec()
            if qos is None and lane is self.sem:
                # slots held via the legacy semaphore are tracked so a
                # runtime gate flip can seed the new plane with them
                self._sem_held += 1
            wait_dt = time.monotonic() - t0
            self._m_queue_wait.observe(wait_dt)
            stagestats.add("admit", wait_dt)
            if root is not None:
                # admission-wait child: ~0 on the fast path, the queue
                # wait otherwise — the first place a slow request's
                # time can hide.  Deferred: materialized only if the
                # trace is captured (defer_child is a tuple stash)
                # queued = actually waited on a semaphore: False for
                # the fast path AND the (uncontended by construction)
                # hot-lane admit
                root.defer_child(
                    "admission", wait_dt,
                    lane="hot" if lane is self.hot_sem
                    else ("qos" if qos_admitted else "api"),
                    queued=not admitted)
            token = deadline_mod.set_current(budget)
            try:
                try:
                    resp = await fn(request)
                    status = resp.status
                    tx = resp.content_length or 0
                    return resp
                except asyncio.CancelledError:
                    # client went away mid-request: not a server error
                    status = 499
                    raise
                except S3Error as e:
                    status = e.status
                    return web.Response(
                        status=e.status,
                        body=e.to_xml(secrets.token_hex(8)),
                        content_type="application/xml",
                    )
                except Exception as e:  # storage & unexpected errors
                    s3e = from_storage_error(e, request.path)
                    status = s3e.status
                    if status >= 500:
                        # traceId attaches via the logger's ambient-
                        # trace hook (utils/logger.py)
                        log.error("request failed", api=api,
                                  path=request.path, error=repr(e))
                    return web.Response(
                        status=s3e.status,
                        body=s3e.to_xml(secrets.token_hex(8)),
                        content_type="application/xml",
                    )
            finally:
                deadline_mod.reset(token)
                if qos_admitted:
                    # release against the plane that granted the slot
                    # (captured above — a runtime gate flip must not
                    # strand it); runs the DRR dispatch sweep
                    qos.release(tenant)
                else:
                    lane.release()
                    if qos is not None and lane is self.hot_sem:
                        # hand back the per-tenant hot-lane slot the
                        # admit claimed (ISSUE 16 satellite)
                        qos.hot_lane_release(tenant)
                    if qos is None and lane is self.sem:
                        self._sem_held -= 1
                        qos_now = self.qos
                        if qos_now is not None \
                                and self._sem_waiters == 0:
                            # a legacy-admitted request finished after
                            # a gate flip with no parked waiter to
                            # hand its slot to: the claim dissolves —
                            # free its seeded slot in the live plane.
                            # (With waiters parked, the release hands
                            # the slot over and total claims stand.)
                            qos_now.external_release()
        finally:
            dt = time.monotonic() - t0
            self._m_inflight.dec()
            rx = request.content_length or 0
            self.record_api(api, status, dt, rx=rx, tx=tx)
            # the handler's whole time, admission included: what the
            # per-request stages are subtracted from
            stagestats.add("request", dt, rx + tx)
            if slo is not None:
                # outcome vs the class objective; the tenant label (QoS
                # on) buys the per-tenant split in /minio/admin/v3/slo
                slo.record(api, status, dt, tenant=tenant)
            if root is not None:
                # tail capture: 5xx (incl. the 503 shed) and anything
                # past the slow threshold is retained; the rest lives
                # or dies by the head-sampling draw
                tracing.end_request(root, status=status,
                                    error=status >= 500, duration=dt)
            # live trace + audit (reference httpTraceAll publishing
            # madmin.TraceInfo, cmd/http-tracer.go:39; audit entries,
            # internal/logger/audit.go)
            if self.trace.num_subscribers or log.audit_enabled:
                entry = {
                    "node": getattr(self, "node_addr", "local"),
                    "api": api,
                    "method": request.method,
                    "path": request.path,
                    "query": request.rel_url.query_string,
                    "statusCode": status,
                    "durationMs": round(dt * 1e3, 3),
                    "remotehost": request.remote or "",
                    "userAgent": request.headers.get("User-Agent", ""),
                    "accessKey": request.get("accessKey", ""),
                }
                if root is not None:
                    # span summary on the live stream: where the time
                    # went, without shipping the whole tree
                    entry["traceId"] = root.trace.trace_id
                    entry["spans"] = tracing.summary(root)
                self.trace.publish(entry)
                if log.audit_enabled:
                    # queue-store I/O must not run on the event loop
                    # lint: allow(budget-propagation): audit QueueStore write is post-response, budget-free by design
                    self.executor.submit(log.audit, entry)

    # -------------------------------------------------------------- dispatch
    async def dispatch_root(self, request: web.Request) -> web.StreamResponse:
        if request.method == "POST":
            return await self._handle(request, self.sts_handler)
        return await self._handle(request, self.list_buckets)

    # ------------------------------------------------------------------ STS
    async def sts_handler(self, request: web.Request) -> web.Response:
        """AssumeRole: temporary credentials for the signing identity
        (reference AssumeRole, cmd/sts-handlers.go)."""
        body = await request.read()
        form = dict(urllib.parse.parse_qsl(body.decode("utf-8", "replace")))
        action = form.get("Action", "")
        try:
            duration = int(form.get("DurationSeconds", "3600") or "3600")
        except ValueError:
            raise S3Error("InvalidArgument", "malformed DurationSeconds")
        session_policy = form.get("Policy", "")
        from minio_tpu.iam import IAMError

        if action == "AssumeRole":
            ctx = await self._auth(request, hashlib.sha256(body).hexdigest())
            try:
                ident = await self._run(
                    self.iam.assume_role, ctx.access_key, duration,
                    session_policy
                )
            except IAMError as e:
                raise S3Error("AccessDenied", str(e))
            return self._sts_creds_xml("AssumeRole", ident)
        if action == "AssumeRoleWithWebIdentity":
            # the bearer token IS the credential: no SigV4 auth
            # (reference cmd/sts-handlers.go AssumeRoleWithWebIdentity)
            return await self._sts_oidc_exchange(
                form, duration, session_policy,
                token_field="WebIdentityToken",
                action="AssumeRoleWithWebIdentity",
                subject_element="SubjectFromWebIdentityToken",
                invalid_code="AccessDenied",
                invalid_prefix="invalid web identity: ")
        if action == "AssumeRoleWithClientGrants":
            # legacy alias of the web-identity exchange (reference
            # cmd/sts-handlers.go AssumeRoleWithClientGrants): same JWT
            # validation plane, but the token arrives in the `Token`
            # form field and the response wraps ClientGrants elements
            return await self._sts_oidc_exchange(
                form, duration, session_policy,
                token_field="Token",
                action="AssumeRoleWithClientGrants",
                subject_element="SubjectFromToken",
                invalid_code="InvalidClientGrantsToken")
        if action == "AssumeRoleWithCertificate":
            # the mTLS client certificate IS the credential (reference
            # cmd/sts-handlers.go:679 AssumeRoleWithCertificate): the
            # TLS handshake already verified it against the server's
            # client CA, and the policy is named by the subject CN
            return await self._sts_certificate(request, duration,
                                               session_policy)
        if action == "AssumeRoleWithLDAPIdentity":
            # username+password ARE the credential: no SigV4 auth
            # (reference cmd/sts-handlers.go AssumeRoleWithLDAPIdentity)
            if self.ldap is None:
                raise S3Error("NotImplemented",
                              "no LDAP identity provider configured")
            username = form.get("LDAPUsername", "")
            password = form.get("LDAPPassword", "")
            if not username or not password:
                raise S3Error("InvalidArgument",
                              "missing LDAPUsername/LDAPPassword")
            from minio_tpu.iam.ldap import LDAPError

            try:
                user_dn, groups = await self._run(
                    self.ldap.authenticate, username, password)
            except LDAPError as e:
                raise S3Error("AccessDenied", f"LDAP auth failed: {e}")
            except OSError as e:
                # directory down/unreachable is an availability problem,
                # not a credentials one
                raise S3Error("ServiceUnavailable",
                              f"LDAP server unreachable: {e}")
            policies = await self._run(
                self.iam.ldap_policies, user_dn, groups)
            try:
                ident = await self._run(
                    self.iam.assume_role_web_identity, f"ldap:{user_dn}",
                    policies, duration, session_policy
                )
            except IAMError as e:
                raise S3Error("AccessDenied", str(e))
            return self._sts_creds_xml("AssumeRoleWithLDAPIdentity", ident)
        raise S3Error("InvalidArgument", f"unsupported STS action {action}")

    async def _sts_certificate(self, request: web.Request, duration: int,
                               session_policy: str) -> web.Response:
        """mTLS credential issue (reference AssumeRoleWithCertificate,
        cmd/sts-handlers.go:679): the verified client certificate's CN
        names the IAM policy the minted credentials carry, and the
        credential lifetime is clamped to the certificate's remaining
        validity (creds must not outlive the identity that minted
        them).  Degrades cleanly: no TLS -> InvalidRequest, no client
        cert -> AccessDenied, no `cryptography` wheel -> NotImplemented
        (minimal containers keep a working server)."""
        from minio_tpu.iam import IAMError

        transport = request.transport
        ssl_obj = transport.get_extra_info("ssl_object") \
            if transport is not None else None
        if ssl_obj is None:
            raise S3Error("InvalidRequest",
                          "AssumeRoleWithCertificate requires an mTLS "
                          "connection")
        try:
            der = ssl_obj.getpeercert(binary_form=True)
        except Exception:
            der = None
        if not der:
            raise S3Error("AccessDenied",
                          "no client certificate presented (the server "
                          "must require client certificates)")
        try:
            cn, not_after = _cert_identity(der)
        except ImportError:
            raise S3Error("NotImplemented",
                          "certificate STS requires the optional "
                          "'cryptography' package")
        except ValueError as e:
            raise S3Error("AccessDenied",
                          f"malformed client certificate: {e}")
        cert_ttl = int(not_after - time.time())
        if cert_ttl <= 0:
            raise S3Error("AccessDenied", "client certificate expired")
        duration = max(1, min(duration, cert_ttl))
        try:
            ident = await self._run(
                self.iam.assume_role_web_identity, f"tls:{cn}", [cn],
                duration, session_policy)
        except IAMError as e:
            raise S3Error("AccessDenied", str(e))
        return self._sts_creds_xml("AssumeRoleWithCertificate", ident)

    async def _sts_oidc_exchange(self, form: dict, duration: int,
                                 session_policy: str, *,
                                 token_field: str, action: str,
                                 subject_element: str,
                                 invalid_code: str,
                                 invalid_prefix: str = ""):
        """The OIDC token exchange shared by AssumeRoleWithWebIdentity
        and its legacy ClientGrants alias: validate the JWT, resolve
        its policy claim, clamp the credential lifetime to the token's
        remaining lifetime (creds must not outlive the identity token
        that minted them), and mint STS creds.  The two actions differ
        only in form field, error code, and response element names."""
        from minio_tpu.iam import IAMError
        from minio_tpu.iam.oidc import OIDCError

        if self.oidc is None:
            raise S3Error("NotImplemented",
                          "no OpenID provider configured")
        token = form.get(token_field, "")
        if not token:
            raise S3Error("InvalidArgument", f"missing {token_field}")
        try:
            claims = await self._run(self.oidc.validate, token)
        except OIDCError as e:
            raise S3Error(invalid_code, invalid_prefix + str(e))
        subject = str(claims.get("sub", ""))
        policies = self.oidc.policies_for(claims)
        token_ttl = int(claims["exp"] - time.time())
        duration = max(1, min(duration, token_ttl))
        try:
            ident = await self._run(
                self.iam.assume_role_web_identity, subject, policies,
                duration, session_policy
            )
        except IAMError as e:
            raise S3Error("AccessDenied", str(e))
        return self._sts_creds_xml(
            action, ident,
            extra=(f"<{subject_element}>{escape(subject)}"
                   f"</{subject_element}>"))

    def _sts_creds_xml(self, action: str, ident, extra: str = ""):
        exp = _iso(ident.expiry)
        return self._xml(200, (
            '<?xml version="1.0" encoding="UTF-8"?>'
            f'<{action}Response xmlns='
            '"https://sts.amazonaws.com/doc/2011-06-15/">'
            f"<{action}Result><Credentials>"
            f"<AccessKeyId>{escape(ident.access_key)}</AccessKeyId>"
            f"<SecretAccessKey>{escape(ident.secret_key)}</SecretAccessKey>"
            f"<SessionToken>{escape(ident.session_token)}</SessionToken>"
            f"<Expiration>{exp}</Expiration>"
            f"</Credentials>{extra}</{action}Result></{action}Response>"
        ))

    # bucket sub-resources routed by query parameter (reference
    # cmd/api-router.go Queries(...) matchers)
    _BUCKET_GET = {
        "location": "bucket_location", "versioning": "get_versioning",
        "uploads": "list_uploads", "versions": "list_object_versions",
        "policy": "get_bucket_policy", "lifecycle": "get_bucket_lifecycle",
        "tagging": "get_bucket_tagging", "encryption": "get_bucket_encryption",
        "object-lock": "get_object_lock_config",
        "notification": "get_bucket_notification",
        "replication": "get_bucket_replication", "quota": "get_bucket_quota",
        "acl": "get_bucket_acl", "cors": "get_bucket_cors",
    }
    _BUCKET_PUT = {
        "cors": "put_bucket_cors",
        "versioning": "put_versioning", "policy": "put_bucket_policy",
        "lifecycle": "put_bucket_lifecycle", "tagging": "put_bucket_tagging",
        "encryption": "put_bucket_encryption",
        "object-lock": "put_object_lock_config",
        "notification": "put_bucket_notification",
        "replication": "put_bucket_replication", "quota": "put_bucket_quota",
        "acl": "put_bucket_acl",
    }
    _BUCKET_DELETE = {
        "cors": "delete_bucket_cors",
        "policy": "delete_bucket_policy",
        "lifecycle": "delete_bucket_lifecycle",
        "tagging": "delete_bucket_tagging",
        "encryption": "delete_bucket_encryption",
        "replication": "delete_bucket_replication",
    }
    # every S3 bucket sub-resource: an unhandled one must answer
    # NotImplemented, NEVER fall through to make/delete-bucket
    _BUCKET_SUBRESOURCES = frozenset({
        "accelerate", "acl", "analytics", "cors", "encryption",
        "intelligent-tiering", "inventory", "lifecycle", "location",
        "logging", "metrics", "notification", "object-lock",
        "ownershipControls", "policy", "policyStatus", "publicAccessBlock",
        "quota", "replication", "requestPayment", "tagging", "uploads",
        "versioning", "versions", "website",
    })

    @staticmethod
    async def _not_implemented(request: web.Request) -> web.Response:
        raise S3Error("NotImplemented", resource=request.path)

    def _subresource_route(self, q, table):
        for param, handler in table.items():
            if param in q:
                return getattr(self, handler)
        for param in q:
            if param in self._BUCKET_SUBRESOURCES:
                return self._not_implemented
        return None

    async def dispatch_bucket(self, request: web.Request) -> web.StreamResponse:
        q = request.rel_url.query
        m = request.method
        if m == "OPTIONS":
            return await self._handle(request, self.cors_preflight)
        if m == "GET":
            fn = self._subresource_route(q, self._BUCKET_GET)
            return await self._handle(request, fn or self.list_objects)
        if m == "PUT":
            fn = self._subresource_route(q, self._BUCKET_PUT)
            return await self._handle(request, fn or self.make_bucket)
        if m == "DELETE":
            fn = self._subresource_route(q, self._BUCKET_DELETE)
            return await self._handle(request, fn or self.delete_bucket)
        if m == "HEAD":
            return await self._handle(request, self.head_bucket)
        if m == "POST":
            if "delete" in q:
                return await self._handle(request, self.delete_objects)
            ctype = request.headers.get("Content-Type", "")
            if ctype.startswith("multipart/form-data"):
                return await self._handle(request, self.post_policy_upload)
        return await self._handle(request, self._method_not_allowed)

    async def dispatch_object(self, request: web.Request) -> web.StreamResponse:
        q = request.rel_url.query
        m = request.method
        if m == "OPTIONS":
            return await self._handle(request, self.cors_preflight)
        if m == "GET":
            if "uploadId" in q:
                return await self._handle(request, self.list_parts)
            if "tagging" in q:
                return await self._handle(request, self.get_object_tagging)
            if "retention" in q:
                return await self._handle(request, self.get_object_retention)
            if "legal-hold" in q:
                return await self._handle(request, self.get_object_legal_hold)
            if "acl" in q:
                return await self._handle(request, self.get_object_acl)
            if "attributes" in q:
                return await self._handle(request,
                                          self.get_object_attributes)
            return await self._handle(request, self.get_object,
                                      hot=self._hot_probe(request))
        if m == "HEAD":
            return await self._handle(request, self.head_object,
                                      hot=self._hot_probe(request))
        if m == "PUT":
            if "uploadId" in q and "partNumber" in q:
                return await self._handle(request, self.upload_part)
            if "tagging" in q:
                return await self._handle(request, self.put_object_tagging)
            if "retention" in q:
                return await self._handle(request, self.put_object_retention)
            if "legal-hold" in q:
                return await self._handle(request, self.put_object_legal_hold)
            return await self._handle(request, self.put_object)
        if m == "DELETE":
            if "uploadId" in q:
                return await self._handle(request, self.abort_upload)
            if "tagging" in q:
                return await self._handle(request, self.delete_object_tagging)
            return await self._handle(request, self.delete_object)
        if m == "POST":
            if "uploads" in q:
                return await self._handle(request, self.create_upload)
            if "uploadId" in q:
                return await self._handle(request, self.complete_upload)
            if "select" in q:
                return await self._handle(request, self.select_object_content)
            if "restore" in q:
                return await self._handle(request, self.restore_object)
        return await self._handle(request, self._method_not_allowed)

    @staticmethod
    async def _method_not_allowed(request: web.Request) -> web.Response:
        raise S3Error("MethodNotAllowed", resource=request.path)

    # ------------------------------------------------------------- service
    async def list_buckets(self, request: web.Request) -> web.Response:
        await self._auth(request, None, "s3:ListAllMyBuckets")
        vols = await self._run(self.api.list_buckets)
        buckets = "".join(
            f"<Bucket><Name>{escape(v.name)}</Name>"
            f"<CreationDate>{_iso(v.created)}</CreationDate></Bucket>"
            for v in vols
        )
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<ListAllMyBucketsResult xmlns="{XMLNS}">'
            f"<Owner><ID>minio-tpu</ID><DisplayName>minio-tpu</DisplayName></Owner>"
            f"<Buckets>{buckets}</Buckets></ListAllMyBucketsResult>"
        ))

    # ------------------------------------------------------------- buckets
    def _bucket(self, request: web.Request) -> str:
        b = request.match_info["bucket"]
        if not VALID_BUCKET.match(b) or b in RESERVED_BUCKETS:
            raise S3Error("InvalidBucketName", resource=b)
        return b

    async def make_bucket(self, request: web.Request) -> web.Response:
        bucket = self._bucket(request)
        await self._auth(request, None, "s3:CreateBucket", bucket)
        await request.read()
        await self._run(self.api.make_bucket, bucket)
        if request.headers.get(
                "x-amz-bucket-object-lock-enabled", "").lower() == "true":
            # CreateBucket with lock enables object lock AND versioning
            # (reference: ObjectLockEnabledForBucket -> versioned WORM)
            from minio_tpu.bucket import metadata as bm

            await self._run(
                self.meta.set_config, bucket, bm.OBJECT_LOCK,
                '<ObjectLockConfiguration>'
                '<ObjectLockEnabled>Enabled</ObjectLockEnabled>'
                '</ObjectLockConfiguration>')
            setter = getattr(self.api, "set_versioning", None)
            if setter is not None:
                await self._run(setter, bucket, True)
        self.site.on_bucket_created(bucket)
        return web.Response(status=200, headers={"Location": f"/{bucket}"})

    async def head_bucket(self, request: web.Request) -> web.Response:
        bucket = self._bucket(request)
        await self._auth(request, None, "s3:ListBucket", bucket)
        if not await self._run(self.api.bucket_exists, bucket):
            raise S3Error("NoSuchBucket", resource=bucket)
        return web.Response(status=200)

    async def delete_bucket(self, request: web.Request) -> web.Response:
        bucket = self._bucket(request)
        await self._auth(request, None, "s3:DeleteBucket", bucket)
        await self._run(self.api.delete_bucket, bucket)
        self.site.on_bucket_deleted(bucket)
        return web.Response(status=204)

    async def bucket_location(self, request: web.Request) -> web.Response:
        bucket = self._bucket(request)
        await self._auth(request, None, "s3:GetBucketLocation", bucket)
        if not await self._run(self.api.bucket_exists, bucket):
            raise S3Error("NoSuchBucket", resource=bucket)
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<LocationConstraint xmlns="{XMLNS}">{self.region}'
            f"</LocationConstraint>"
        ))

    async def get_versioning(self, request: web.Request) -> web.Response:
        bucket = self._bucket(request)
        await self._auth(request, None, "s3:GetBucketVersioning", bucket)
        status = await self._vstatus(bucket)
        inner = f"<Status>{status}</Status>" if status else ""
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<VersioningConfiguration xmlns="{XMLNS}">{inner}'
            f"</VersioningConfiguration>"
        ))

    async def put_versioning(self, request: web.Request) -> web.Response:
        body = await request.read()
        bucket = self._bucket(request)
        await self._auth(request, hashlib.sha256(body).hexdigest(),
                   "s3:PutBucketVersioning", bucket)
        try:
            root = ET.fromstring(body)
            status = root.findtext(f"{{{XMLNS}}}Status") or root.findtext("Status")
        except ET.ParseError:
            raise S3Error("MalformedXML")
        if status not in ("Enabled", "Suspended"):
            raise S3Error("MalformedXML")
        if status != "Enabled":
            # suspending versioning on a lock-enabled bucket would let an
            # unversioned DELETE hard-delete WORM-protected objects
            # (reference guard: cmd/bucket-versioning-handler.go:66)
            if await self._run(self.meta.object_lock_enabled, bucket):
                raise S3Error(
                    "InvalidBucketState",
                    "An Object Lock configuration is present on this bucket,"
                    " so the versioning state cannot be changed.")
            if await self._run(self.meta.replication_config, bucket):
                raise S3Error(
                    "InvalidBucketState",
                    "A replication configuration is present on this bucket,"
                    " so the versioning state cannot be suspended.")
        setter = getattr(self.api, "set_versioning", None)
        if setter is None:
            raise S3Error("NotImplemented")
        await self._run(setter, bucket, status)
        self.meta.changed(bucket)
        return web.Response(status=200)

    @staticmethod
    def _enc_key(s: str, enc: str) -> str:
        if enc == "url":
            return quote(s, safe="")
        return escape(s)

    async def list_objects(self, request: web.Request) -> web.Response:
        """ListObjectsV1 + V2 (cmd/bucket-handlers.go ListObjects*Handler)."""
        from minio_tpu.erasure import listing as listing_mod

        bucket = self._bucket(request)
        await self._auth(request, None, "s3:ListBucket", bucket)
        q = request.rel_url.query
        prefix = q.get("prefix", "")
        delimiter = q.get("delimiter", "")
        enc = q.get("encoding-type", "")
        try:
            max_keys = min(int(q.get("max-keys", "1000") or "1000"), 1000)
        except ValueError:
            raise S3Error("InvalidArgument", "invalid max-keys")
        if max_keys < 0:
            raise S3Error("InvalidArgument", "invalid max-keys")
        v2 = q.get("list-type") == "2"
        if v2:
            marker = q.get("continuation-token", "") or q.get("start-after", "")
        else:
            marker = q.get("marker", "")

        # x-minio-extract on a prefix into a .zip: list the ARCHIVE's
        # members through the cached central directory instead of the
        # bucket namespace (server/zip_extract.py; reference
        # cmd/s3-zip-handlers.go listObjectsV2InArchive)
        resp = await self._maybe_zip_list(request, bucket, prefix,
                                          delimiter, marker, max_keys,
                                          v2, enc)
        if resp is not None:
            return resp

        res = await self._run(
            listing_mod.list_objects, self.api, bucket, prefix, delimiter,
            marker, "", max_keys, False,
        )
        parts = []
        for oi in res.entries:
            parts.append(
                f"<Contents><Key>{self._enc_key(oi.name, enc)}</Key>"
                f"<LastModified>{_iso(oi.mod_time)}</LastModified>"
                f'<ETag>&quot;{oi.etag}&quot;</ETag>'
                f"<Size>{self._display_size(oi)}</Size>"
                f"<Owner><ID>minio-tpu</ID>"
                f"<DisplayName>minio-tpu</DisplayName></Owner>"
                f"<StorageClass>STANDARD</StorageClass></Contents>"
            )
        for cp in res.common_prefixes:
            parts.append(
                f"<CommonPrefixes><Prefix>{self._enc_key(cp, enc)}</Prefix>"
                f"</CommonPrefixes>"
            )
        extra = ""
        if v2:
            extra += f"<KeyCount>{len(res.entries) + len(res.common_prefixes)}</KeyCount>"
            if q.get("continuation-token"):
                extra += (f"<ContinuationToken>"
                          f"{escape(q['continuation-token'])}"
                          f"</ContinuationToken>")
            if res.is_truncated:
                extra += (f"<NextContinuationToken>"
                          f"{escape(res.next_marker)}"
                          f"</NextContinuationToken>")
        else:
            extra += f"<Marker>{self._enc_key(marker, enc)}</Marker>"
            if res.is_truncated and delimiter:
                extra += (f"<NextMarker>{self._enc_key(res.next_marker, enc)}"
                          f"</NextMarker>")
        if enc:
            extra += f"<EncodingType>{escape(enc)}</EncodingType>"
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<ListBucketResult xmlns="{XMLNS}">'
            f"<Name>{escape(bucket)}</Name>"
            f"<Prefix>{self._enc_key(prefix, enc)}</Prefix>"
            f"<MaxKeys>{max_keys}</MaxKeys>"
            f"<Delimiter>{self._enc_key(delimiter, enc)}</Delimiter>"
            f"<IsTruncated>{'true' if res.is_truncated else 'false'}</IsTruncated>"
            f"{extra}{''.join(parts)}</ListBucketResult>"
        ))

    async def list_object_versions(self, request: web.Request) -> web.Response:
        """ListObjectVersions (cmd/bucket-handlers.go:188)."""
        from minio_tpu.erasure import listing as listing_mod

        bucket = self._bucket(request)
        await self._auth(request, None, "s3:ListBucketVersions", bucket)
        q = request.rel_url.query
        prefix = q.get("prefix", "")
        delimiter = q.get("delimiter", "")
        enc = q.get("encoding-type", "")
        try:
            max_keys = min(int(q.get("max-keys", "1000") or "1000"), 1000)
        except ValueError:
            raise S3Error("InvalidArgument", "invalid max-keys")
        if max_keys < 0:
            raise S3Error("InvalidArgument", "invalid max-keys")
        key_marker = q.get("key-marker", "")
        vid_marker = q.get("version-id-marker", "")

        res = await self._run(
            listing_mod.list_objects, self.api, bucket, prefix, delimiter,
            key_marker, vid_marker, max_keys, True,
        )
        parts = []
        for oi in res.entries:
            vid = oi.version_id or "null"
            latest = "true" if oi.is_latest else "false"
            if oi.delete_marker:
                parts.append(
                    f"<DeleteMarker><Key>{self._enc_key(oi.name, enc)}</Key>"
                    f"<VersionId>{vid}</VersionId>"
                    f"<IsLatest>{latest}</IsLatest>"
                    f"<LastModified>{_iso(oi.mod_time)}</LastModified>"
                    f"<Owner><ID>minio-tpu</ID>"
                    f"<DisplayName>minio-tpu</DisplayName></Owner>"
                    f"</DeleteMarker>"
                )
            else:
                parts.append(
                    f"<Version><Key>{self._enc_key(oi.name, enc)}</Key>"
                    f"<VersionId>{vid}</VersionId>"
                    f"<IsLatest>{latest}</IsLatest>"
                    f"<LastModified>{_iso(oi.mod_time)}</LastModified>"
                    f'<ETag>&quot;{oi.etag}&quot;</ETag>'
                    f"<Size>{self._display_size(oi)}</Size>"
                    f"<Owner><ID>minio-tpu</ID>"
                    f"<DisplayName>minio-tpu</DisplayName></Owner>"
                    f"<StorageClass>STANDARD</StorageClass></Version>"
                )
        for cp in res.common_prefixes:
            parts.append(
                f"<CommonPrefixes><Prefix>{self._enc_key(cp, enc)}</Prefix>"
                f"</CommonPrefixes>"
            )
        extra = ""
        if res.is_truncated:
            extra += (f"<NextKeyMarker>{self._enc_key(res.next_marker, enc)}"
                      f"</NextKeyMarker>")
            if res.next_version_marker:
                extra += (f"<NextVersionIdMarker>{res.next_version_marker}"
                          f"</NextVersionIdMarker>")
        if enc:
            extra += f"<EncodingType>{escape(enc)}</EncodingType>"
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<ListVersionsResult xmlns="{XMLNS}">'
            f"<Name>{escape(bucket)}</Name>"
            f"<Prefix>{self._enc_key(prefix, enc)}</Prefix>"
            f"<KeyMarker>{self._enc_key(key_marker, enc)}</KeyMarker>"
            f"<VersionIdMarker>{escape(vid_marker)}</VersionIdMarker>"
            f"<MaxKeys>{max_keys}</MaxKeys>"
            f"<Delimiter>{self._enc_key(delimiter, enc)}</Delimiter>"
            f"<IsTruncated>{'true' if res.is_truncated else 'false'}</IsTruncated>"
            f"{extra}{''.join(parts)}</ListVersionsResult>"
        ))

    async def delete_objects(self, request: web.Request) -> web.Response:
        body = await request.read()
        bucket = self._bucket(request)
        if self._is_anonymous(request):
            # anonymous bulk delete: allowed iff the bucket policy grants
            # s3:DeleteObject, checked per key below — same as anonymous
            # single-object DELETE
            account = "*"
        else:
            ctx = await self._auth(request, hashlib.sha256(body).hexdigest())
            account = ctx.access_key
        try:
            root = ET.fromstring(body)
        except ET.ParseError:
            raise S3Error("MalformedXML")
        ns = f"{{{XMLNS}}}"
        conditions = self._request_conditions(request)
        vstatus = await self._vstatus(bucket)
        repl_pool = None
        rcfg_for_delete = None
        if self.services is not None \
                and getattr(self.services, "replication", None) is not None:
            rcfg_for_delete = await self._run(
                self.meta.replication_config, bucket)
            if rcfg_for_delete is not None:
                repl_pool = self.services.replication
        results = []
        to_delete: list[tuple[str, str]] = []  # (key, vid) passing auth
        for obj in root.findall(f"{ns}Object") + root.findall("Object"):
            key = obj.findtext(f"{ns}Key") or obj.findtext("Key") or ""
            vid = obj.findtext(f"{ns}VersionId") or obj.findtext("VersionId") or ""
            # per-key authorization: the combined IAM + bucket-policy
            # decision, exactly as for single-object DELETE (bucket-policy
            # grants honored, object-scoped Denies enforced)
            if not await self._authorized(
                    account, "s3:DeleteObject", bucket, key, conditions):
                results.append(
                    f"<Error><Key>{escape(key)}</Key>"
                    f"<Code>AccessDenied</Code>"
                    f"<Message>Access Denied</Message></Error>"
                )
                continue
            try:
                await self.enforce_retention_for_delete(
                    request, bucket, key, vid, account)
            except S3Error as s3e:
                results.append(
                    f"<Error><Key>{escape(key)}</Key><Code>{s3e.code}</Code>"
                    f"<Message>{escape(s3e.message)}</Message></Error>"
                )
                continue
            to_delete.append((key, vid))
        # one batched delete: a single delete_versions round per drive
        # (reference DeleteObjects -> DeleteVersions,
        # cmd/bucket-handlers.go DeleteMultipleObjectsHandler)
        if to_delete:
            dels = [{"obj": k, "version_id": v,
                     "versioned": vstatus == "Enabled",
                     "suspended": vstatus == "Suspended"}
                    for k, v in to_delete]
            outs = await self._run(self.api.delete_objects, bucket, dels)
            from minio_tpu.events.event import EventName

            for (key, vid), doi in zip(to_delete, outs):
                if isinstance(doi, Exception):
                    s3e = from_storage_error(doi)
                    results.append(
                        f"<Error><Key>{escape(key)}</Key>"
                        f"<Code>{s3e.code}</Code>"
                        f"<Message>{escape(s3e.message)}</Message></Error>"
                    )
                    continue
                results.append(
                    f"<Deleted><Key>{escape(key)}</Key></Deleted>")
                if repl_pool is not None \
                        and rcfg_for_delete.match(key) is not None:
                    repl_pool.replicate_delete(
                        bucket, key, vid, delete_marker=doi.delete_marker)
                self._emit(
                    EventName.OBJECT_REMOVED_DELETE_MARKER
                    if doi.delete_marker else EventName.OBJECT_REMOVED_DELETE,
                    bucket, key, version_id=doi.version_id, request=request)
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<DeleteResult xmlns="{XMLNS}">{"".join(results)}</DeleteResult>'
        ))

    # ------------------------------------------------------------- objects
    def _object(self, request: web.Request) -> tuple[str, str]:
        bucket = self._bucket(request)
        key = request.match_info["key"]
        if not key:
            raise S3Error("InvalidArgument", "empty object key")
        return bucket, key

    def _hot_probe(self, request: web.Request) -> bool:
        """Advisory pre-admission hit test for the hot-lane dispatch
        (cheap dict lookup, no auth — auth still runs in the handler)."""
        hc = self.hotcache
        if hc is None:
            return False
        bucket = request.match_info.get("bucket", "")
        key = request.match_info.get("key", "")
        if not bucket or not key:
            return False
        return hc.probe(bucket, key,
                        request.rel_url.query.get("versionId", ""))

    @staticmethod
    def _obj_headers(oi) -> dict[str, str]:
        h = {
            "ETag": f'"{oi.etag}"',
            "Last-Modified": _http_date(oi.mod_time),
            "Content-Type": oi.content_type or "application/octet-stream",
            "Accept-Ranges": "bytes",
        }
        restore_exp = oi.metadata.get("x-minio-internal-restore-expiry")
        if restore_exp:
            from .object_extras import _http_date_parse

            t = _http_date_parse(restore_exp)
            if t is None or t >= time.time():
                # expired windows disappear, matching AWS behavior
                h["x-amz-restore"] = (
                    f'ongoing-request="false", '
                    f'expiry-date="{restore_exp}"')
        if oi.version_id:
            h["x-amz-version-id"] = oi.version_id
        for k, v in oi.metadata.items():
            if k.startswith("x-amz-meta-"):
                h[k] = v
        tag_str = oi.metadata.get(TAGS_KEY, "")
        if tag_str:
            h["x-amz-tagging-count"] = str(len(parse_tag_query(tag_str)))
        for lk in (LOCK_MODE_KEY, LOCK_UNTIL_KEY, LOCK_HOLD_KEY):
            if oi.metadata.get(lk):
                h[lk] = oi.metadata[lk]
        from minio_tpu.services.replication import REPL_STATUS_KEY

        if oi.metadata.get(REPL_STATUS_KEY):
            h["x-amz-replication-status"] = oi.metadata[REPL_STATUS_KEY]
        return h

    @staticmethod
    def _checksum_headers(request, oi) -> dict[str, str]:
        """x-amz-checksum-<algo> when the client asked with
        x-amz-checksum-mode: ENABLED (reference hash.Checksum
        AddChecksumHeader)."""
        if request.headers.get("x-amz-checksum-mode", "").upper() \
                != "ENABLED":
            return {}
        from minio_tpu.utils import checksum as cksum_mod

        stored = oi.metadata.get(cksum_mod.META_CHECKSUM, "")
        got = cksum_mod.load(stored) if stored else None
        if got is None:
            return {}
        return {cksum_mod.header_name(got[0]): got[1]}

    async def put_object(self, request: web.Request) -> web.Response:
        bucket, key = self._object(request)
        sha_claim = request.headers.get("x-amz-content-sha256", "")
        copy_src = request.headers.get("x-amz-copy-source")
        if copy_src:
            ctx = await self._auth(request, sha_claim or sigv4.EMPTY_SHA256,
                             "s3:PutObject", bucket, key)
            return await self.copy_object(request, bucket, key, copy_src, ctx)

        size = request.content_length
        streaming = sha_claim.startswith("STREAMING-")
        ctx = await self._auth(request, sha_claim or None, "s3:PutObject", bucket, key)

        decoded_len = request.headers.get("x-amz-decoded-content-length")
        real_size = int(decoded_len) if streaming and decoded_len else (
            size if size is not None else -1
        )
        await self._run(self._quota_check, bucket, real_size)
        user_meta = {
            k.lower(): v for k, v in request.headers.items()
            if k.lower().startswith("x-amz-meta-")
        }
        tag_hdr = request.headers.get("x-amz-tagging", "")
        if tag_hdr:
            parse_tag_query(tag_hdr)  # validates
            user_meta[TAGS_KEY] = tag_hdr
        await self._apply_lock_headers(request, bucket, user_meta)
        # bucket default retention applies when the request sets none
        # (reference filterObjectLockMetadata + default retention)
        await self._apply_default_retention(bucket, user_meta)
        # replication decision (reference mustReplicate,
        # cmd/bucket-replication.go:169): a matching rule marks the version
        # PENDING and enqueues after commit; an incoming replica PUT from a
        # source cluster is marked REPLICA and never re-replicated
        from minio_tpu.services import replication as repl

        must_replicate = False
        if request.headers.get(repl.REPLICA_HEADER):
            # only a principal holding s3:ReplicateObject may mark a PUT as
            # an incoming replica (otherwise any writer could suppress the
            # bucket's outbound replication with one header — reference
            # checks ReplicateObjectAction, cmd/object-handlers.go)
            if not await self._authorized(
                    ctx.access_key, "s3:ReplicateObject", bucket, key,
                    self._request_conditions(request)):
                raise S3Error("AccessDenied",
                              "s3:ReplicateObject permission required")
            user_meta[repl.REPL_STATUS_KEY] = repl.REPLICA
        else:
            rcfg = await self._run(self.meta.replication_config, bucket)
            if rcfg is not None and rcfg.match(key) is not None \
                    and self.services is not None \
                    and getattr(self.services, "replication", None) is not None:
                must_replicate = True
                user_meta[repl.REPL_STATUS_KEY] = repl.PENDING

        vstatus = await self._vstatus(bucket)
        opts = PutObjectOptions(
            content_type=request.headers.get("Content-Type", ""),
            user_metadata=user_meta,
            versioned=vstatus == "Enabled",
        )

        # Content-MD5 (base64) guards the raw request body (reference
        # hash.NewReader MD5 enforcement, internal/hash/reader.go:38);
        # malformed values must reject BEFORE the put pipeline spins up
        md5_claim = request.headers.get("Content-MD5", "")
        md5_want = None
        if md5_claim:
            try:
                md5_want = base64.b64decode(md5_claim, validate=True)
                if len(md5_want) != 16:
                    raise ValueError
            except (ValueError, TypeError):
                raise S3Error("InvalidDigest")

        pipe = _QueuePipeReader()
        # unsigned-trailer streaming (modern SDK default) decodes the
        # aws-chunked framing without per-chunk signatures; request auth
        # already rode the signed headers
        unsigned_stream = streaming and "UNSIGNED" in sha_claim
        chunk_reader = (
            _ChunkedSigReader(pipe, None if unsigned_stream else ctx)
            if streaming else None
        )
        reader: io.RawIOBase = chunk_reader if streaming else pipe
        body_md5 = None
        if md5_want is not None:
            # hash the DECODED payload (works for aws-chunked too, where
            # the raw body carries signature framing)
            body_md5 = hashlib.md5()
            reader = _TeeHashReader(reader, body_md5)
        # additional object checksums (x-amz-checksum-*, reference
        # internal/hash/checksum.go): verified against the decoded
        # payload and stored with the object
        from minio_tpu.utils import checksum as cksum_mod

        try:
            cksum = cksum_mod.from_headers(request.headers)
        except cksum_mod.ChecksumError as e:
            raise S3Error("InvalidChecksum", str(e))
        cksum_hasher = None
        if cksum is not None:
            cksum_hasher = cksum_mod.new_hasher(cksum[0])
            reader = _TeeHashReader(reader, cksum_hasher)
            opts.user_metadata[cksum_mod.META_CHECKSUM] = \
                cksum_mod.store(*cksum)
        # trailing checksum (x-amz-trailer: x-amz-checksum-<algo>): the
        # value arrives AFTER the body, so the computed digest is stored
        # via finalize_metadata and compared against the trailer below
        trailer_algo = None
        trailer_hasher = None
        trailer_decl = request.headers.get("x-amz-trailer", "") \
            .strip().lower()
        if chunk_reader is not None and cksum is None \
                and trailer_decl.startswith("x-amz-checksum-"):
            algo = trailer_decl[len("x-amz-checksum-"):]
            if algo in cksum_mod.ALGORITHMS:
                trailer_algo = algo
                trailer_hasher = cksum_mod.new_hasher(algo)
                reader = _TeeHashReader(reader, trailer_hasher)
        # server-side encryption wraps the decoded plaintext stream
        # (reference EncryptRequest, cmd/encryption-v1.go:324)
        sse_kind, customer_key = self.sse_kind_for_put(request, bucket)
        if sse_kind:
            from minio_tpu.crypto import sse as sse_mod

            # KMS may be a remote KES server: keep the HTTP round trip
            # off the event loop
            obj_key, nonce_prefix, enc_meta = await self._run(
                sse_mod.new_encryption_meta,
                sse_kind, bucket, key, self.kms, customer_key)
            opts.user_metadata.update(enc_meta)
            reader = sse_mod.EncryptingReader(
                reader, obj_key, nonce_prefix, f"{bucket}/{key}".encode())
            if real_size >= 0:
                real_size = sse_mod.enc_size(real_size)
        elif self._compress_eligible(key, opts.content_type):
            # transparent compression (reference cmd/object-api-utils.go:907;
            # never combined with SSE, matching the reference default)
            from minio_tpu.utils import compress as compress_mod

            creader = compress_mod.CompressingReader(reader)
            reader = creader
            opts.user_metadata[compress_mod.META_COMPRESSION] = (
                compress_mod.SCHEME)
            opts.finalize_metadata = lambda: {
                compress_mod.META_ACTUAL_SIZE: str(creader.actual_size),
                "etag": creader.etag,  # ETag of the ORIGINAL bytes
            }
            real_size = -1  # compressed length unknown until EOF
        if trailer_algo is not None:
            # computed digest committed with the metadata (finalize runs
            # after EOF); the client's trailer value is compared below
            prev_fin = opts.finalize_metadata

            def _with_trailer_checksum(prev=prev_fin, algo=trailer_algo,
                                       hasher=trailer_hasher):
                extra = dict(prev() or {}) if prev is not None else {}
                extra[cksum_mod.META_CHECKSUM] = cksum_mod.store(
                    algo, cksum_mod.encode(hasher.digest()))
                return extra

            opts.finalize_metadata = _with_trailer_checksum
        put_task = asyncio.ensure_future(self._run_nobudget(
            self.api.put_object, bucket, key, reader, real_size, opts
        ))
        check_hash = (
            sha_claim and not streaming
            and sha_claim != sigv4.UNSIGNED_PAYLOAD
        )
        body_sha = hashlib.sha256() if check_hash else None
        feed_err = None
        try:
            async for chunk in request.content.iter_chunked(1 << 20):
                if body_sha is not None:
                    body_sha.update(chunk)
                # per-tenant ingest metering (ISSUE 13): paces the
                # PUT body against the tenant's bandwidth bucket
                await self._qos_throttle(request, len(chunk), "in")
                await self._feed(pipe, chunk, put_task)
        except Exception as e:
            feed_err = e
        await self._feed(pipe, None, put_task)
        try:
            oi = await put_task
        except Exception:
            if feed_err is not None:
                raise S3Error("IncompleteBody")
            raise
        if feed_err is not None:
            raise S3Error("IncompleteBody")
        async def _digest_rollback(msg: str, code: str = "BadDigest"):
            # tampered/corrupted body: roll back the just-written version
            # (reference rejects digest mismatches during the stream)
            try:
                await self._run(
                    self.api.delete_object, bucket, key, oi.version_id, False
                )
            except Exception:
                pass
            raise S3Error(code, msg)

        if body_sha is not None and body_sha.hexdigest() != sha_claim:
            await _digest_rollback("x-amz-content-sha256 does not match body")
        if body_md5 is not None and body_md5.digest() != md5_want:
            await _digest_rollback("Content-MD5 does not match body")
        if cksum_hasher is not None \
                and cksum_mod.encode(cksum_hasher.digest()) != cksum[1]:
            await _digest_rollback(
                f"x-amz-checksum-{cksum[0]} does not match body",
                code="XAmzContentChecksumMismatch")
        trailer_value = None
        if chunk_reader is not None:
            # the put consumed exactly the decoded payload; the zero
            # chunk + trailer lines are still in the pipe — drain them
            # for EVERY streaming upload (not just supported checksum
            # algorithms) so chained/trailer signatures always verify
            if not chunk_reader.eof:
                try:
                    await self._run(chunk_reader.read)
                except S3Error as e:
                    # chunk/trailer-signature mismatch surfaces after
                    # the data was committed: roll the version back
                    await _digest_rollback(e.message or e.code, code=e.code)
            if trailer_decl and not chunk_reader.trailers.get(trailer_decl):
                # the PUT declared this trailer (supported algo or not);
                # a body whose trailer section omits (or blanks) it is
                # truncated/forged — do not silently accept
                await _digest_rollback(
                    f"declared trailer {trailer_decl} missing from body",
                    code="IncompleteBody")
        if trailer_algo is not None:
            trailer_value = cksum_mod.encode(trailer_hasher.digest())
            claimed = chunk_reader.trailers.get(trailer_decl, "")
            if claimed != trailer_value:
                await _digest_rollback(
                    f"{trailer_decl} trailer does not match body",
                    code="XAmzContentChecksumMismatch")
        headers = {"ETag": f'"{oi.etag}"'}
        if cksum is not None:
            headers[cksum_mod.header_name(cksum[0])] = cksum[1]
        elif trailer_value is not None:
            headers[cksum_mod.header_name(trailer_algo)] = trailer_value
        if oi.version_id:
            headers["x-amz-version-id"] = oi.version_id
        elif vstatus == "Suspended":
            # suspended bucket: the write landed as the null version
            headers["x-amz-version-id"] = "null"
        if sse_kind:
            headers.update(self.sse_response_headers(opts.user_metadata))
        if must_replicate:
            headers["x-amz-replication-status"] = repl.PENDING
            self.services.replication.replicate_object(bucket, key,
                                                       oi.version_id)
        from minio_tpu.events.event import EventName

        self._emit(EventName.OBJECT_CREATED_PUT, bucket, key, size=oi.size,
                   etag=oi.etag, version_id=oi.version_id, request=request)
        return web.Response(status=200, headers=headers)

    async def _cors_config(self, bucket: str):
        # NOTE: get_bucket_metadata degrades to {} when drives are
        # unreachable (its callers treat missing metadata as empty), so
        # a total outage presents as "no CORS config" here — the browser
        # sees a denial rather than a 5xx. Accepted trade-off: the
        # alternative (erroring metadata reads) would break every
        # config-optional caller.
        return await self._run(self.meta.cors, bucket)

    async def cors_preflight(self, request: web.Request) -> web.Response:
        """OPTIONS preflight against the bucket's CORS config (AWS
        preflight semantics; unauthenticated by design)."""
        from minio_tpu.bucket import cors as cors_mod

        bucket = self._bucket(request)
        origin = request.headers.get("Origin", "")
        method = request.headers.get("Access-Control-Request-Method", "")
        req_headers = [
            h for h in request.headers.get(
                "Access-Control-Request-Headers", "").split(",") if h]
        if not origin or not method:
            raise S3Error("BadRequest",
                          "Insufficient information. Origin and "
                          "Access-Control-Request-Method are required.")
        cfg = await self._cors_config(bucket)
        rule = cfg.find(origin, method, req_headers) if cfg else None
        if rule is None:
            raise S3Error("AccessDenied",
                          "CORSResponse: this CORS request is not allowed")
        return web.Response(status=200, headers=cors_mod.cors_headers(
            rule, origin, preflight_method=method,
            req_headers=req_headers))

    async def _cors_on_prepare(self, request: web.Request, resp) -> None:
        """Decorate ACTUAL responses with CORS headers when the bucket's
        config matches the request's Origin (fires for plain and
        streamed responses alike)."""
        try:
            origin = request.headers.get("Origin", "")
            bucket = request.match_info.get("bucket", "")
            if not origin or not bucket or request.method == "OPTIONS":
                return
            from minio_tpu.bucket import cors as cors_mod

            cfg = await self._cors_config(bucket)
            rule = cfg.find(origin, request.method) if cfg else None
            if rule is not None:
                for k, v in cors_mod.cors_headers(rule, origin).items():
                    if k not in resp.headers:
                        resp.headers[k] = v
        except Exception as e:
            # decoration must never break a response, but silence would
            # make outages look like CORS misconfiguration
            log.warning("CORS decoration failed", bucket=bucket,
                        error=repr(e))

    async def _trace_on_prepare(self, request: web.Request, resp) -> None:
        """Stamp the request's trace id on the response (fires for plain
        and streamed responses alike, AFTER the handler returned — the
        id lives on the request, not the already-reset contextvar)."""
        try:
            tid = request.get("traceId", "")
            if tid and tracing.RESPONSE_HEADER not in resp.headers:
                resp.headers[tracing.RESPONSE_HEADER] = tid
        except Exception:
            pass  # decoration must never break a response

    async def _maybe_replicate(self, request, bucket: str, key: str,
                               oi) -> str | None:
        """Post-commit replication decision for paths that bypass the
        simple-PUT pipeline (CompleteMultipartUpload, CopyObject): mark
        the new version PENDING and enqueue it.  Returns the status header
        value, or None when no rule matches (reference mustReplicate is
        checked on every write path, cmd/bucket-replication.go:169)."""
        from minio_tpu.services import replication as repl

        if request is not None and request.headers.get(repl.REPLICA_HEADER):
            return None  # incoming replica: never re-replicate
        if self.services is None \
                or getattr(self.services, "replication", None) is None:
            return None
        rcfg = await self._run(self.meta.replication_config, bucket)
        if rcfg is None or rcfg.match(key) is None:
            return None
        try:
            await self._run(self.api.update_object_metadata, bucket, key,
                            {repl.REPL_STATUS_KEY: repl.PENDING},
                            oi.version_id)
        except Exception:
            pass
        self.services.replication.replicate_object(bucket, key,
                                                   oi.version_id)
        return repl.PENDING

    async def _obj_stream(self, oi, read, offset: int, length: int):
        """Stored-bytes stream for GET/Select, of the object that
        `api.open_object` gave as `(oi, read)`: local shards normally
        (`read` returns at once and opens its shard files on the
        executor, when the pump first advances it), the warm tier for
        transitioned stubs, whose `read` is never called (reference
        getTransitionedObject read-through, cmd/bucket-lifecycle.go)."""
        svcs = self.services
        if svcs is not None and getattr(svcs, "tier", None) is not None:
            from minio_tpu.services.tier import TierManager

            if TierManager.is_transitioned(oi.metadata):
                # backend connect/open is blocking IO: off the event loop
                return await self._run(
                    svcs.tier.read, oi.metadata, offset,
                    length if length >= 0 else -1)
        return read(offset, length)

    @staticmethod
    def _check_copy_source_conditions(request: web.Request, soi) -> None:
        """x-amz-copy-source-if-* preconditions against the SOURCE, with
        the same ETag-over-date precedence and whole-second tolerance as
        check_preconditions (reference checkCopyObjectPreconditions)."""
        from .object_extras import _http_date_parse

        h = request.headers

        def tags_of(v: str) -> list[str]:
            return [t.strip().strip('"') for t in v.split(",")]

        im = h.get("x-amz-copy-source-if-match")
        if im is not None:
            tags = tags_of(im)
            if "*" not in tags and soi.etag not in tags:
                raise S3Error("PreconditionFailed")
        inm = h.get("x-amz-copy-source-if-none-match")
        if inm is not None:
            tags = tags_of(inm)
            if "*" in tags or soi.etag in tags:
                raise S3Error("PreconditionFailed")
        ums = h.get("x-amz-copy-source-if-unmodified-since")
        if ums is not None and im is None:
            # a passing if-match overrides the date check
            t = _http_date_parse(ums)
            if t is not None and soi.mod_time > t + 1:
                raise S3Error("PreconditionFailed")
        ms = h.get("x-amz-copy-source-if-modified-since")
        if ms is not None and inm is None:
            t = _http_date_parse(ms)
            if t is not None and soi.mod_time <= t + 1:
                raise S3Error("PreconditionFailed")

    async def _default_retention(self, bucket: str) -> tuple[str, str]:
        """(mode, retain-until) from the bucket's object-lock
        DefaultRetention rule, or ('', '') — parsed form is memoized on
        the bucket-metadata cache."""
        try:
            mode, seconds = await self._run(
                self.meta.default_retention, bucket)
        except st.BucketNotFound:
            return "", ""
        # any OTHER failure propagates: committing an UNPROTECTED object
        # into a WORM bucket on a transient error would be a bypass (the
        # delete path fails closed for the same reason)
        if not mode:
            return "", ""
        until = datetime.fromtimestamp(
            time.time() + seconds, timezone.utc
        ).strftime("%Y-%m-%dT%H:%M:%SZ")
        return mode, until

    async def _apply_lock_headers(self, request: web.Request, bucket: str,
                                  user_meta: dict) -> None:
        """Validate + apply explicit x-amz-object-lock-* request headers
        (shared by PUT, CopyObject and CreateMultipartUpload so every
        write path honors an explicitly requested lock)."""
        if not any(request.headers.get(lk)
                   for lk in (LOCK_MODE_KEY, LOCK_UNTIL_KEY,
                              LOCK_HOLD_KEY)):
            return
        if not await self._run(self.meta.object_lock_enabled, bucket):
            raise S3Error("InvalidRequest",
                          "bucket is not object-lock enabled")
        mode = request.headers.get(LOCK_MODE_KEY, "")
        until = request.headers.get(LOCK_UNTIL_KEY, "")
        hold = request.headers.get(LOCK_HOLD_KEY, "")
        if bool(mode) != bool(until):
            raise S3Error("InvalidArgument",
                          "lock mode and retain-until must both be set")
        if mode:
            if mode not in ("GOVERNANCE", "COMPLIANCE"):
                raise S3Error("InvalidArgument", "bad object-lock mode")
            from .object_extras import _parse_amz_date

            if _parse_amz_date(until) <= time.time():
                raise S3Error("InvalidArgument",
                              "retain-until date must be in the future")
            user_meta[LOCK_MODE_KEY] = mode
            user_meta[LOCK_UNTIL_KEY] = until
        if hold:
            if hold not in ("ON", "OFF"):
                raise S3Error("InvalidArgument", "bad legal-hold status")
            user_meta[LOCK_HOLD_KEY] = hold

    async def _apply_default_retention(self, bucket: str,
                                       user_meta: dict,
                                       mark_default: bool = False) -> None:
        """Stamp the bucket's default retention when the metadata does
        not already carry an explicit mode (PUT/copy/multipart must all
        agree — an unprotected copy into a WORM bucket would be a
        bypass).  mark_default tags the stamp so deferred commits
        (multipart complete) can recompute the window from CREATION
        time rather than initiation."""
        if LOCK_MODE_KEY in user_meta:
            return
        dmode, duntil = await self._default_retention(bucket)
        if dmode:
            user_meta[LOCK_MODE_KEY] = dmode
            user_meta[LOCK_UNTIL_KEY] = duntil
            if mark_default:
                user_meta["x-minio-internal-lock-default"] = "true"

    def _compress_eligible(self, key: str, content_type: str) -> bool:
        if not self.config.get_bool("compression", "enable"):
            return False
        from minio_tpu.utils import compress as compress_mod

        return compress_mod.eligible(
            key, content_type,
            self.config.get("compression", "extensions").split(","),
            self.config.get("compression", "mime_types").split(","))

    async def _versioned(self, bucket: str) -> bool:
        return (await self._vstatus(bucket)) == "Enabled"

    async def _vstatus(self, bucket: str) -> str:
        """Bucket versioning status: '' | 'Enabled' | 'Suspended'."""
        fn = getattr(self.api, "versioning_status", None)
        if fn is not None:
            return await self._run(fn, bucket)
        fn = getattr(self.api, "versioning_enabled", None)
        if fn is None:
            return ""
        return "Enabled" if await self._run(fn, bucket) else ""

    async def copy_object(self, request: web.Request, bucket: str, key: str,
                          copy_src: str, ctx=None) -> web.Response:
        src = urllib.parse.unquote(copy_src)
        src = src.lstrip("/")
        if "?versionId=" in src:
            src, vid = src.split("?versionId=", 1)
        else:
            vid = ""
        try:
            sbucket, skey = src.split("/", 1)
        except ValueError:
            raise S3Error("InvalidArgument", "bad x-amz-copy-source")
        if ctx is not None and not self.iam.is_allowed(
            ctx.access_key, "s3:GetObject", sbucket, skey
        ):
            raise S3Error("AccessDenied", "not allowed to read copy source")
        from minio_tpu.crypto import sse as sse_mod

        soi = await self._run(self.api.get_object_info, sbucket, skey, vid)
        self._check_copy_source_conditions(request, soi)
        await self._run(self._quota_check, bucket, soi.size)
        src_meta = dict(soi.metadata)
        # x-amz-metadata-directive: REPLACE swaps in the request's own
        # metadata/content-type (reference extractMetadata + directive
        # handling in CopyObjectHandler)
        directive = request.headers.get(
            "x-amz-metadata-directive", "COPY").upper()
        if directive not in ("COPY", "REPLACE"):
            raise S3Error("InvalidArgument", "bad x-amz-metadata-directive")
        if directive == "REPLACE":
            internal = {k: v for k, v in src_meta.items()
                        if k.startswith("x-minio-internal-")
                        or k == TAGS_KEY}
            src_meta = {k.lower(): v for k, v in request.headers.items()
                        if k.lower().startswith("x-amz-meta-")}
            src_meta.update(internal)
            soi.content_type = request.headers.get(
                "Content-Type", soi.content_type)
        # x-amz-tagging-directive mirrors the metadata one for the tag set
        tag_dir = request.headers.get(
            "x-amz-tagging-directive", "COPY").upper()
        if tag_dir not in ("COPY", "REPLACE"):
            raise S3Error("InvalidTagDirective")
        if tag_dir == "REPLACE":
            src_meta.pop(TAGS_KEY, None)
            tag_hdr = request.headers.get("x-amz-tagging", "")
            if tag_hdr:
                parse_tag_query(tag_hdr)  # validates
                src_meta[TAGS_KEY] = tag_hdr
        from .sse_handlers import parse_ssec_key as _parse_ssec

        if not src_meta.get(sse_mod.META_ALGO) \
                and _parse_ssec(request.headers,
                                copy_source=True) is not None:
            # key supplied for a plaintext source: a client key-management
            # mistake AWS rejects rather than ignores
            raise S3Error("InvalidRequest",
                          "copy-source SSE-C headers sent but the source "
                          "object is not SSE-C encrypted")
        if src_meta.get(sse_mod.META_ALGO):
            # decrypt the source; SSE-C sources are unlocked by the
            # x-amz-copy-source-sse-c header triple (reference SSECopy)
            obj_key = await self._run(
                self.sse_object_key, soi, sbucket, skey, request,
                                          copy_source=True)
            nonce_prefix = base64.b64decode(
                src_meta.get(sse_mod.META_NONCE, ""))
            plain = sse_mod.plain_size_of(soi.size)
            _, ct_stream = await self._run(
                self.api.get_object, sbucket, skey, 0, -1, vid)
            data = await self._run_nobudget(lambda: b"".join(sse_mod.decrypt_chunks(
                iter(ct_stream), obj_key, nonce_prefix,
                f"{sbucket}/{skey}".encode(), 0, 0, plain)))
            for k in (sse_mod.META_ALGO, sse_mod.META_SEALED_KEY,
                      sse_mod.META_NONCE, sse_mod.META_KMS_KEY_ID,
                      sse_mod.META_SSEC_KEY_MD5):
                src_meta.pop(k, None)
        else:
            oi, stream = await self._run(
                self.api.get_object, sbucket, skey, 0, -1, vid
            )
            data = await self._run_nobudget(lambda: b"".join(stream))
        from minio_tpu.utils import compress as compress_mod

        if src_meta.get(
                compress_mod.META_COMPRESSION) == compress_mod.SCHEME:
            # normalize compressed sources to their ORIGINAL bytes before
            # any destination transform (an SSE destination would
            # otherwise encrypt the frames while the copy kept the
            # compression metadata -> unreadable object)
            data = b"".join(compress_mod.decompress_stream(iter([data])))
            src_meta.pop(compress_mod.META_COMPRESSION, None)
            src_meta.pop(compress_mod.META_ACTUAL_SIZE, None)
        # lock metadata NEVER copies from the source (AWS semantics: an
        # expired/stale source lock must not shadow the destination
        # bucket's defaults); explicit request headers then defaults
        for lk in (LOCK_MODE_KEY, LOCK_UNTIL_KEY, LOCK_HOLD_KEY):
            src_meta.pop(lk, None)
        await self._apply_lock_headers(request, bucket, src_meta)
        await self._apply_default_retention(bucket, src_meta)
        opts = PutObjectOptions(
            content_type=soi.content_type,
            user_metadata=src_meta,
            versioned=await self._versioned(bucket),
        )
        size = len(data)
        reader: io.RawIOBase = io.BytesIO(data)
        sse_kind, customer_key = self.sse_kind_for_put(request, bucket)
        if sse_kind:
            okey, nprefix, enc_meta = await self._run(
                sse_mod.new_encryption_meta,
                sse_kind, bucket, key, self.kms, customer_key)
            opts.user_metadata.update(enc_meta)
            reader = sse_mod.EncryptingReader(
                reader, okey, nprefix, f"{bucket}/{key}".encode())
            size = sse_mod.enc_size(size)
        elif self._compress_eligible(key, soi.content_type):
            creader = compress_mod.CompressingReader(reader)
            reader = creader
            opts.user_metadata[compress_mod.META_COMPRESSION] = (
                compress_mod.SCHEME)
            opts.finalize_metadata = lambda: {
                compress_mod.META_ACTUAL_SIZE: str(creader.actual_size),
                "etag": creader.etag,
            }
            size = -1
        new_oi = await self._run_nobudget(
            self.api.put_object, bucket, key, reader, size, opts
        )
        await self._maybe_replicate(request, bucket, key, new_oi)
        from minio_tpu.events.event import EventName

        self._emit(EventName.OBJECT_CREATED_COPY, bucket, key,
                   size=new_oi.size, etag=new_oi.etag,
                   version_id=new_oi.version_id, request=request)
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<CopyObjectResult xmlns="{XMLNS}">'
            f'<ETag>&quot;{new_oi.etag}&quot;</ETag>'
            f"<LastModified>{_iso(new_oi.mod_time)}</LastModified>"
            f"</CopyObjectResult>"
        ))

    def _parse_range(self, header: str, size: int) -> tuple[int, int]:
        m = re.match(r"^bytes=(\d*)-(\d*)$", header.strip())
        if not m:
            raise S3Error("InvalidRange")
        first, last = m.group(1), m.group(2)
        if first == "" and last == "":
            raise S3Error("InvalidRange")
        if first == "":
            n = int(last)
            if n == 0:
                raise S3Error("InvalidRange")
            start = max(size - n, 0)
            end = size - 1
        else:
            start = int(first)
            end = int(last) if last else size - 1
            end = min(end, size - 1)
        if start > end or start >= size:
            raise S3Error("InvalidRange")
        return start, end

    # proxy a GET/HEAD miss to a replication target (reference
    # proxyGetToReplicationTarget, cmd/bucket-replication.go): an object
    # that has not replicated to THIS site yet is served from the remote
    # instead of 404ing, making active-active pairs read-consistent
    _PROXY_HDRS = ("content-type", "etag", "last-modified",
                   "content-length", "content-range", "cache-control",
                   "content-encoding", "content-disposition")

    async def _replication_proxy(self, request, bucket: str, key: str,
                                 vid: str, head: bool = False):
        if vid:
            return None  # replica versions have their own ids remotely
        from minio_tpu.services import replication as repl_mod

        pool = getattr(self.services, "replication", None) \
            if self.services is not None else None
        # the remote evaluates conditional requests (304/412 pass back)
        cond = {h: request.headers[h] for h in
                ("If-Match", "If-None-Match", "If-Modified-Since",
                 "If-Unmodified-Since") if h in request.headers}
        hit = await self._run(
            repl_mod.proxy_get, self.meta, bucket, key,
            request.headers.get("Range", ""),
            pool.stats if pool is not None else None, head, cond)
        if hit is None:
            return None
        _, rh, chunks = hit
        headers = {"x-minio-proxied-from-target": "true"}
        for h in self._PROXY_HDRS:
            if rh.get(h):
                headers[h.title()] = rh[h]
        for k, v in rh.items():
            if k.startswith("x-amz-meta-"):
                headers[k] = v
        remote_status = int(rh.get(":status", "200"))
        if remote_status in (304, 412):
            if chunks is not None:
                await self._run(getattr(chunks, "close", lambda: None))
            headers.pop("Content-Length", None)
            return web.Response(status=remote_status, headers=headers)
        status = 206 if rh.get("content-range") else 200
        if head:
            return web.Response(status=status, headers=headers)
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(request)
        try:
            await self._pump_stream(resp, chunks, request)
        finally:
            close = getattr(chunks, "close", None)
            if close is not None:
                await self._run(close)
        await resp.write_eof()
        return resp

    async def get_object(self, request: web.Request) -> web.StreamResponse:
        bucket, key = self._object(request)
        await self._auth(request, None, "s3:GetObject", bucket, key)
        # x-minio-extract: serve a member from inside a stored zip
        # (reference cmd/s3-zip-handlers.go:49; server/zip_extract.py)
        resp = await self._maybe_zip_extract(request, bucket, key)
        if resp is not None:
            return resp
        vid = request.rel_url.query.get("versionId", "")
        hc = self.hotcache
        if hc is not None:
            ranged = "Range" in request.headers
            # a Range miss falls through to the classic path below, so
            # lookup is its terminal tier interaction: count the miss
            # (and feed the admission sketch) there; a whole-object
            # miss is counted by serve() instead
            ent = hc.lookup(bucket, key, vid, count_miss=ranged)
            if ent is not None:
                # RAM hit: zero storage calls from here on — headers,
                # conditional 304/412 and Range slices all come from
                # the cached ObjectInfo + buffer
                return await self._serve_hot(request, bucket, key, vid,
                                             ent.oi, ent.data)
            if not ranged:
                # collapse path: concurrent GETs of one cold key share
                # ONE erasure read; late arrivals stream from the
                # filling buffer (serving/hotcache.py singleflight).
                # The quorum metadata read is time-to-first-byte work,
                # so it keeps the request's deadline budget (classic
                # _run parity); the fill streaming stays budget-free
                # like every whole-payload phase.
                from minio_tpu.utils import deadline as deadline_mod

                budget = deadline_mod.current()

                def info_fn():
                    token = deadline_mod.set_current(budget)
                    try:
                        return self.api.get_object_info(bucket, key,
                                                        vid)
                    finally:
                        deadline_mod.reset(token)

                try:
                    kind, oi, payload = await self._run_nobudget(
                        hc.serve, bucket, key, vid, info_fn,
                        lambda: self.api.get_object(
                            bucket, key, 0, -1, vid))
                except (st.ObjectNotFound, st.FileNotFound) as e:
                    resp = await self._replication_proxy(
                        request, bucket, key, vid)
                    if resp is not None:
                        return resp
                    raise e
                if kind != "miss":
                    return await self._serve_hot(request, bucket, key,
                                                 vid, oi, payload)
                # ineligible object (SSE/compressed/tiered/oversized):
                # the classic path below opens it
        return await self._get_uncached(request, bucket, key, vid)

    async def _serve_hot(self, request: web.Request, bucket: str,
                         key: str, vid: str, oi, payload,
                         head: bool = False) -> web.StreamResponse:
        """Serve a GET (or HEAD, ``head=True``) from the hot tier:
        `payload` is the resident bytes (hit / fill leader) or a
        progressive iterator over the filling buffer (collapsed
        follower).  Mirrors the classic plain-object path
        byte-for-byte (differential-tested)."""
        import dataclasses

        from minio_tpu.events.event import EventName

        if vid == "null":
            # cached ObjectInfo is shared/read-only: tweak a copy
            oi = dataclasses.replace(oi, version_id="null")
        self.check_preconditions(request, oi)
        size = oi.size
        status = 200
        offset, length = 0, size
        headers = self._obj_headers(oi)
        headers.update(self._checksum_headers(request, oi))
        if head:
            # hot HEAD: the cached ObjectInfo answers everything —
            # zero xl.meta reads (same header set as the classic
            # handler, which ignores Range on HEAD)
            headers["Content-Length"] = str(size)
            self._emit(EventName.OBJECT_ACCESSED_HEAD, bucket, key,
                       size=size, etag=oi.etag,
                       version_id=oi.version_id, request=request)
            return web.Response(status=200, headers=headers)
        rng = request.headers.get("Range")
        if rng and size > 0:
            start, end = self._parse_range(rng, size)
            offset, length = start, end - start + 1
            status = 206
            headers["Content-Range"] = f"bytes {start}-{end}/{size}"
        headers["Content-Length"] = str(length)
        self._emit(EventName.OBJECT_ACCESSED_GET, bucket, key, size=size,
                   etag=oi.etag, version_id=oi.version_id, request=request)
        if isinstance(payload, (bytes, bytearray, memoryview)):
            body = memoryview(payload)[offset:offset + length] \
                if (offset or length != size) else payload
            # RAM hits are still tenant bytes: one debit for the whole
            # body (pacing a single response write chunk-by-chunk buys
            # nothing — the debt carries into the tenant's next chunk)
            await self._qos_throttle(request, length, "out")
            return web.Response(status=status, body=bytes(body),
                                headers=headers)
        # collapsed follower: stream the fill buffer as it grows
        # (followers are only created for whole-object requests)
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(request)
        await self._pump_stream(resp, payload, request)
        await resp.write_eof()
        return resp

    async def _get_uncached(self, request: web.Request, bucket: str,
                            key: str, vid: str) -> web.StreamResponse:
        """The GET that reads drives.  It opens its object once: one
        executor hop, one quorum read of `xl.meta` and one election give
        the `oi` that the preconditions, the Range, the SSE and
        compression branches and the headers need, and the `read` that
        streams the bytes of that same election.  A 304, a 412 or a bad
        Range leaves before `read` is called: no shard file is opened."""
        from minio_tpu.crypto import sse as sse_mod

        try:
            oi, read = await self._run(self.api.open_object, bucket, key,
                                       vid)
        except (st.ObjectNotFound, st.FileNotFound) as e:
            resp = await self._replication_proxy(request, bucket, key, vid)
            if resp is not None:
                return resp
            raise e
        if vid == "null":
            oi.version_id = "null"
        self.check_preconditions(request, oi)

        from minio_tpu.utils import compress as compress_mod

        encrypted = bool(oi.metadata.get(sse_mod.META_ALGO))
        compressed = oi.metadata.get(
            compress_mod.META_COMPRESSION) == compress_mod.SCHEME
        if encrypted:
            size = sse_mod.plain_size_of(oi.size)
        elif compressed:
            size = int(oi.metadata.get(
                compress_mod.META_ACTUAL_SIZE, oi.size))
        else:
            size = oi.size

        status = 200
        offset, length = 0, size
        headers = self._obj_headers(oi)
        headers.update(self._checksum_headers(request, oi))
        rng = request.headers.get("Range")
        if rng and size > 0:
            start, end = self._parse_range(rng, size)
            offset, length = start, end - start + 1
            status = 206
            headers["Content-Range"] = f"bytes {start}-{end}/{size}"
        headers["Content-Length"] = str(length)

        if encrypted:
            obj_key = await self._run(
                self.sse_object_key, oi, bucket, key, request)
            headers.update(self.sse_response_headers(oi.metadata))
            ct_off, ct_len, first_seq, skip = sse_mod.ct_range_for(
                offset, length, size)
            nonce_prefix = base64.b64decode(
                oi.metadata.get(sse_mod.META_NONCE, ""))
            ct_stream = await self._obj_stream(oi, read, ct_off, ct_len)
            stream = sse_mod.decrypt_chunks(
                iter(ct_stream), obj_key, nonce_prefix,
                f"{bucket}/{key}".encode(), first_seq, skip, length)
            closer = ct_stream
        elif compressed:
            # stored frames are opaque: decompress from the start and
            # skip to the requested range (reference non-indexed
            # compressed reads)
            raw = await self._obj_stream(oi, read, 0, -1)
            stream = compress_mod.decompress_range(iter(raw), offset, length)
            closer = raw
        else:
            stream = await self._obj_stream(oi, read, offset, length)
            closer = stream
        from minio_tpu.events.event import EventName

        self._emit(EventName.OBJECT_ACCESSED_GET, bucket, key, size=size,
                   etag=oi.etag, version_id=oi.version_id, request=request)
        resp = web.StreamResponse(status=status, headers=headers)
        await resp.prepare(request)
        try:
            await self._pump_stream(resp, stream, request)
        finally:
            await self._run(lambda: closer.close()
                            if hasattr(closer, "close") else None)
        await resp.write_eof()
        return resp

    async def get_object_attributes(self, request: web.Request
                                    ) -> web.Response:
        """GetObjectAttributes (?attributes): the requested subset of
        ETag / Checksum / ObjectSize / StorageClass / ObjectParts
        (reference getObjectAttributesHandler,
        cmd/object-handlers.go)."""
        bucket, key = self._object(request)
        await self._auth(request, None, "s3:GetObjectAttributes",
                         bucket, key)
        wanted = {
            a.strip() for a in
            request.headers.get("x-amz-object-attributes", "").split(",")
            if a.strip()
        }
        if not wanted:
            raise S3Error("InvalidArgument",
                          "x-amz-object-attributes header is required")
        valid = {"ETag", "Checksum", "ObjectParts", "StorageClass",
                 "ObjectSize"}
        bad = wanted - valid
        if bad:
            raise S3Error("InvalidArgument",
                          f"invalid object attributes: {sorted(bad)}")
        vid = request.rel_url.query.get("versionId", "")
        oi = await self._run(self.api.get_object_info, bucket, key, vid)
        from minio_tpu.utils import checksum as cksum_mod
        from minio_tpu.utils import compress as compress_mod

        size = oi.size
        actual = oi.metadata.get(compress_mod.META_ACTUAL_SIZE)
        if actual:
            size = int(actual)
        parts_xml = ""
        if "ObjectParts" in wanted:
            nparts = len(getattr(oi, "parts", []) or [])
            parts_xml = (f"<ObjectParts><TotalPartsCount>{nparts}"
                         f"</TotalPartsCount></ObjectParts>")
        body = ['<?xml version="1.0" encoding="UTF-8"?>',
                f'<GetObjectAttributesOutput xmlns="{XMLNS}">']
        if "ETag" in wanted:
            body.append(f"<ETag>{escape(oi.etag)}</ETag>")
        if "Checksum" in wanted:
            stored = oi.metadata.get(cksum_mod.META_CHECKSUM, "")
            got = cksum_mod.load(stored) if stored else None
            if got is not None:
                body.append(
                    f"<Checksum><{cksum_mod.xml_tag(got[0])}>"
                    f"{escape(got[1])}"
                    f"</{cksum_mod.xml_tag(got[0])}></Checksum>")
        if parts_xml:
            body.append(parts_xml)
        if "StorageClass" in wanted:
            body.append("<StorageClass>"
                        + escape(oi.metadata.get(
                            "x-amz-storage-class", "STANDARD"))
                        + "</StorageClass>")
        if "ObjectSize" in wanted:
            body.append(f"<ObjectSize>{size}</ObjectSize>")
        body.append("</GetObjectAttributesOutput>")
        headers = {"Last-Modified": _http_date(oi.mod_time)}
        if oi.version_id:
            headers["x-amz-version-id"] = oi.version_id
        resp = self._xml(200, "".join(body))
        resp.headers.update(headers)
        return resp

    async def head_object(self, request: web.Request) -> web.Response:
        from minio_tpu.crypto import sse as sse_mod

        bucket, key = self._object(request)
        await self._auth(request, None, "s3:GetObject", bucket, key)
        resp = await self._maybe_zip_extract(request, bucket, key,
                                             head=True)
        if resp is not None:
            return resp
        vid = request.rel_url.query.get("versionId", "")
        hc = self.hotcache
        if hc is not None:
            # a HEAD miss never reaches serve(): lookup counts it
            ent = hc.lookup(bucket, key, vid)
            if ent is not None:
                return await self._serve_hot(request, bucket, key, vid,
                                             ent.oi, ent.data, head=True)
        try:
            oi = await self._run(self.api.get_object_info, bucket, key, vid)
        except (st.ObjectNotFound, st.FileNotFound) as e:
            resp = await self._replication_proxy(request, bucket, key, vid,
                                                 head=True)
            if resp is not None:
                return resp
            raise e
        if vid == "null":
            oi.version_id = "null"
        self.check_preconditions(request, oi)
        headers = self._obj_headers(oi)
        headers.update(self._checksum_headers(request, oi))
        from minio_tpu.utils import compress as compress_mod

        if oi.metadata.get(sse_mod.META_ALGO):
            # SSE-C objects require (and verify) the key even on HEAD
            await self._run(self.sse_object_key, oi, bucket, key, request)
            headers.update(self.sse_response_headers(oi.metadata))
            headers["Content-Length"] = str(sse_mod.plain_size_of(oi.size))
        elif oi.metadata.get(
                compress_mod.META_COMPRESSION) == compress_mod.SCHEME:
            headers["Content-Length"] = oi.metadata.get(
                compress_mod.META_ACTUAL_SIZE, str(oi.size))
        else:
            headers["Content-Length"] = str(oi.size)
        from minio_tpu.events.event import EventName

        self._emit(EventName.OBJECT_ACCESSED_HEAD, bucket, key, size=oi.size,
                   etag=oi.etag, version_id=oi.version_id, request=request)
        return web.Response(status=200, headers=headers)

    async def delete_object(self, request: web.Request) -> web.Response:
        bucket, key = self._object(request)
        ctx = await self._auth(request, None, "s3:DeleteObject", bucket, key)
        vid = request.rel_url.query.get("versionId", "")
        vstatus = await self._vstatus(bucket)
        await self.enforce_retention_for_delete(request, bucket, key, vid,
                                                ctx.access_key)
        oi = await self._run(
            self.api.delete_object, bucket, key, vid,
            vstatus == "Enabled", vstatus == "Suspended"
        )
        headers = {}
        if oi.delete_marker:
            headers["x-amz-delete-marker"] = "true"
        if oi.version_id:
            headers["x-amz-version-id"] = oi.version_id
        # delete / delete-marker replication (replicateDelete,
        # cmd/bucket-replication.go)
        if self.services is not None \
                and getattr(self.services, "replication", None) is not None:
            rcfg = await self._run(self.meta.replication_config, bucket)
            if rcfg is not None and rcfg.match(key) is not None:
                self.services.replication.replicate_delete(
                    bucket, key, vid, delete_marker=oi.delete_marker)
        from minio_tpu.events.event import EventName

        self._emit(
            EventName.OBJECT_REMOVED_DELETE_MARKER if oi.delete_marker
            else EventName.OBJECT_REMOVED_DELETE,
            bucket, key, version_id=oi.version_id, request=request)
        return web.Response(status=204, headers=headers)

    async def select_object_content(
            self, request: web.Request) -> web.StreamResponse:
        """SelectObjectContent: SQL over one CSV/JSON object, streamed
        back in AWS event-stream framing (reference
        SelectObjectContentHandler, cmd/object-handlers.go;
        internal/s3select/select.go:218)."""
        from minio_tpu.crypto import sse as sse_mod
        from minio_tpu.select import SelectRequest, run_select
        from minio_tpu.select.sql import SQLError
        from minio_tpu.utils import compress as compress_mod

        body = await request.read()
        bucket, key = self._object(request)
        await self._auth(request, hashlib.sha256(body).hexdigest(),
                         "s3:GetObject", bucket, key)
        if request.rel_url.query.get("select-type") != "2":
            raise S3Error("InvalidArgument",
                          "select-type=2 query parameter is required")
        try:
            sreq = SelectRequest.from_xml(body)
        except SQLError as e:
            raise S3Error("InvalidArgument", str(e))
        vid = request.rel_url.query.get("versionId", "")
        oi, read = await self._run(self.api.open_object, bucket, key, vid)

        # plaintext source stream (decompress / decrypt like GET)
        if oi.metadata.get(sse_mod.META_ALGO):
            obj_key = await self._run(
                self.sse_object_key, oi, bucket, key, request)
            nonce_prefix = base64.b64decode(
                oi.metadata.get(sse_mod.META_NONCE, ""))
            plain = sse_mod.plain_size_of(oi.size)
            raw = await self._obj_stream(oi, read, 0, -1)
            chunks = sse_mod.decrypt_chunks(
                iter(raw), obj_key, nonce_prefix,
                f"{bucket}/{key}".encode(), 0, 0, plain)
            src_size = plain
        elif oi.metadata.get(
                compress_mod.META_COMPRESSION) == compress_mod.SCHEME:
            raw = await self._obj_stream(oi, read, 0, -1)
            chunks = compress_mod.decompress_stream(iter(raw))
            src_size = int(oi.metadata.get(
                compress_mod.META_ACTUAL_SIZE, oi.size))
        else:
            raw = await self._obj_stream(oi, read, 0, -1)
            chunks = iter(raw)
            src_size = oi.size

        stream = _IterStream(chunks)
        try:
            gen = run_select(sreq, stream, src_size)
            # produce the FIRST message on the executor before preparing
            # the response: parse/plan errors still map to clean HTTP 4xx
            first = await self._run_nobudget(next, gen, None)
        except SQLError as e:
            raise S3Error("InvalidArgument", str(e))
        from minio_tpu.events.event import EventName

        self._emit(EventName.OBJECT_ACCESSED_GET, bucket, key,
                   size=oi.size, etag=oi.etag, version_id=oi.version_id,
                   request=request)
        resp = web.StreamResponse(status=200, headers={
            "Content-Type": "application/octet-stream"})
        await resp.prepare(request)
        try:
            msg = first
            while msg is not None:
                await resp.write(msg)
                msg = await self._run_nobudget(next, gen, None)
        finally:
            if hasattr(raw, "close"):
                await self._run(raw.close)
        await resp.write_eof()
        return resp

    async def restore_object(self, request: web.Request) -> web.Response:
        """RestoreObject for transitioned versions (reference
        PostRestoreObjectHandler, cmd/object-handlers.go; restored
        availability surfaces via the x-amz-restore header).  Data in
        this framework streams through the warm tier transparently, so a
        restore completes immediately — the API records the requested
        availability window."""
        body = await request.read()
        bucket, key = self._object(request)
        await self._auth(request, hashlib.sha256(body).hexdigest(),
                         "s3:RestoreObject", bucket, key)
        vid = request.rel_url.query.get("versionId", "")
        days = 1
        if body:
            try:
                root = ET.fromstring(body)
                days = int(root.findtext(f"{{{XMLNS}}}Days")
                           or root.findtext("Days") or "1")
            except (ET.ParseError, ValueError):
                raise S3Error("MalformedXML")
        if days < 1:
            raise S3Error("InvalidArgument", "Days must be >= 1")
        oi = await self._run(self.api.get_object_info, bucket, key, vid)
        from minio_tpu.erasure.objects import (
            TRANSITION_COMPLETE, TRANSITION_STATUS_KEY,
        )

        if oi.metadata.get(TRANSITION_STATUS_KEY) != TRANSITION_COMPLETE:
            raise S3Error("InvalidObjectState",
                          "object is not in a tiered storage class")
        expiry = time.time() + days * 86400
        expiry_str = _http_date(expiry)
        await self._run(
            self.api.update_object_metadata, bucket, key,
            {"x-minio-internal-restore-expiry": expiry_str}, vid)
        return web.Response(status=202, headers={
            "x-amz-restore":
                f'ongoing-request="false", expiry-date="{expiry_str}"'})

    # ----------------------------------------------------------- multipart
    async def create_upload(self, request: web.Request) -> web.Response:
        bucket, key = self._object(request)
        await self._auth(request, None, "s3:PutObject", bucket, key)
        opts = PutObjectOptions(
            content_type=request.headers.get("Content-Type", ""),
            user_metadata={
                k.lower(): v for k, v in request.headers.items()
                if k.lower().startswith("x-amz-meta-")
            },
        )
        await self._apply_lock_headers(request, bucket,
                                       opts.user_metadata)
        await self._apply_default_retention(bucket, opts.user_metadata,
                                            mark_default=True)
        uid = await self._run(self.api.new_multipart_upload, bucket, key, opts)
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<InitiateMultipartUploadResult xmlns="{XMLNS}">'
            f"<Bucket>{escape(bucket)}</Bucket><Key>{escape(key)}</Key>"
            f"<UploadId>{uid}</UploadId></InitiateMultipartUploadResult>"
        ))

    async def upload_part(self, request: web.Request) -> web.Response:
        bucket, key = self._object(request)
        q = request.rel_url.query
        uid = q["uploadId"]
        part_num = int(q["partNumber"])
        sha_claim = request.headers.get("x-amz-content-sha256", "")
        streaming = sha_claim.startswith("STREAMING-")
        ctx = await self._auth(request, sha_claim or None, "s3:PutObject", bucket, key)
        decoded_len = request.headers.get("x-amz-decoded-content-length")
        size = request.content_length
        real_size = int(decoded_len) if streaming and decoded_len else (
            size if size is not None else -1
        )
        await self._run(self._quota_check, bucket, real_size)
        pipe = _QueuePipeReader()
        reader: io.RawIOBase = (
            _ChunkedSigReader(
                pipe, None if "UNSIGNED" in sha_claim else ctx)
            if streaming else pipe
        )
        task = asyncio.ensure_future(self._run_nobudget(
            self.api.put_object_part, bucket, key, uid, part_num, reader,
            real_size
        ))
        try:
            async for chunk in request.content.iter_chunked(1 << 20):
                await self._qos_throttle(request, len(chunk), "in")
                await self._feed(pipe, chunk, task)
        finally:
            await self._feed(pipe, None, task)
        try:
            pi = await task
        except st.InvalidArgument as e:
            if "upload id" in str(e):
                raise S3Error("NoSuchUpload")
            raise
        return web.Response(status=200, headers={"ETag": f'"{pi.etag}"'})

    async def list_parts(self, request: web.Request) -> web.Response:
        bucket, key = self._object(request)
        await self._auth(request, None, "s3:ListMultipartUploadParts", bucket, key)
        uid = request.rel_url.query["uploadId"]
        try:
            parts = await self._run(self.api.list_object_parts, bucket, key, uid)
        except st.InvalidArgument:
            raise S3Error("NoSuchUpload")
        inner = "".join(
            f"<Part><PartNumber>{p.part_number}</PartNumber>"
            f'<ETag>&quot;{p.etag}&quot;</ETag><Size>{p.size}</Size>'
            f"<LastModified>{_iso(p.mod_time)}</LastModified></Part>"
            for p in parts
        )
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<ListPartsResult xmlns="{XMLNS}">'
            f"<Bucket>{escape(bucket)}</Bucket><Key>{escape(key)}</Key>"
            f"<UploadId>{uid}</UploadId>{inner}</ListPartsResult>"
        ))

    async def list_uploads(self, request: web.Request) -> web.Response:
        """ListMultipartUploads (reference ListMultipartUploadsHandler,
        cmd/bucket-handlers.go)."""
        bucket = self._bucket(request)
        await self._auth(request, None, "s3:ListBucketMultipartUploads", bucket)
        q = request.rel_url.query
        prefix = q.get("prefix", "")
        try:
            max_uploads = min(max(int(q.get("max-uploads", "1000")), 0), 1000)
        except ValueError:
            raise S3Error("InvalidArgument", "max-uploads must be an integer")
        key_marker = q.get("key-marker", "")
        uid_marker = q.get("upload-id-marker", "")
        lister = getattr(self.api, "list_all_multipart_uploads", None)
        uploads = await self._run(lister, bucket, prefix) \
            if lister is not None else []
        if key_marker:
            if uid_marker:
                uploads = [u for u in uploads
                           if (u.object, u.upload_id)
                           > (key_marker, uid_marker)]
            else:
                # key-marker alone: only keys strictly AFTER the marker
                uploads = [u for u in uploads if u.object > key_marker]
        truncated = len(uploads) > max_uploads
        page = uploads[:max_uploads]
        parts = []
        for u in page:
            parts.append(
                f"<Upload><Key>{escape(u.object)}</Key>"
                f"<UploadId>{u.upload_id}</UploadId>"
                f"<Initiated>{_iso(u.initiated)}</Initiated>"
                f"<StorageClass>STANDARD</StorageClass></Upload>")
        nk = page[-1].object if truncated and page else ""
        nu = page[-1].upload_id if truncated and page else ""
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<ListMultipartUploadsResult xmlns="{XMLNS}">'
            f"<Bucket>{escape(bucket)}</Bucket>"
            f"<Prefix>{escape(prefix)}</Prefix>"
            f"<KeyMarker>{escape(key_marker)}</KeyMarker>"
            f"<UploadIdMarker>{escape(uid_marker)}</UploadIdMarker>"
            f"<NextKeyMarker>{escape(nk)}</NextKeyMarker>"
            f"<NextUploadIdMarker>{nu}</NextUploadIdMarker>"
            f"<MaxUploads>{max_uploads}</MaxUploads>"
            f"<IsTruncated>{'true' if truncated else 'false'}</IsTruncated>"
            f"{''.join(parts)}"
            f"</ListMultipartUploadsResult>"
        ))

    async def abort_upload(self, request: web.Request) -> web.Response:
        bucket, key = self._object(request)
        await self._auth(request, None, "s3:AbortMultipartUpload", bucket, key)
        uid = request.rel_url.query["uploadId"]
        try:
            await self._run(self.api.abort_multipart_upload, bucket, key, uid)
        except st.InvalidArgument:
            raise S3Error("NoSuchUpload")
        return web.Response(status=204)

    async def complete_upload(self, request: web.Request) -> web.Response:
        body = await request.read()
        bucket, key = self._object(request)
        await self._auth(request, hashlib.sha256(body).hexdigest(),
                   "s3:PutObject", bucket, key)
        uid = request.rel_url.query["uploadId"]
        try:
            root = ET.fromstring(body)
        except ET.ParseError:
            raise S3Error("MalformedXML")
        ns = f"{{{XMLNS}}}"
        parts = []
        for p in root.findall(f"{ns}Part") + root.findall("Part"):
            num = p.findtext(f"{ns}PartNumber") or p.findtext("PartNumber")
            etag = (p.findtext(f"{ns}ETag") or p.findtext("ETag") or "").strip('"')
            parts.append((int(num), etag))
        from minio_tpu.erasure.multipart import EntityTooSmall

        try:
            # part assembly is O(object bytes): exempt from the admission
            # budget like the other whole-payload phases
            oi = await self._run_nobudget(
                self.api.complete_multipart_upload, bucket, key, uid, parts
            )
        except EntityTooSmall:
            raise S3Error("EntityTooSmall")
        except st.InvalidArgument as e:
            if "upload id" in str(e):
                raise S3Error("NoSuchUpload")
            if "out of order" in str(e):
                raise S3Error("InvalidPartOrder")
            raise S3Error("InvalidPart", str(e))
        if oi.metadata.get("x-minio-internal-lock-default") == "true":
            # default retention stamped at INITIATION: recompute the
            # window from object creation so a long upload does not
            # shorten the WORM period
            dmode, duntil = await self._default_retention(bucket)
            updates = {"x-minio-internal-lock-default": None}
            if dmode:
                updates[LOCK_MODE_KEY] = dmode
                updates[LOCK_UNTIL_KEY] = duntil
            try:
                await self._run(self.api.update_object_metadata, bucket,
                                key, updates, oi.version_id)
            except Exception:
                pass  # initiation-time stamp remains as a floor
        repl_status = await self._maybe_replicate(request, bucket, key, oi)
        from minio_tpu.events.event import EventName

        self._emit(EventName.OBJECT_CREATED_COMPLETE_MULTIPART, bucket, key,
                   size=oi.size, etag=oi.etag, version_id=oi.version_id,
                   request=request)
        hdrs = {"x-amz-replication-status": repl_status} if repl_status \
            else None
        return self._xml(200, (
            f'<?xml version="1.0" encoding="UTF-8"?>'
            f'<CompleteMultipartUploadResult xmlns="{XMLNS}">'
            f"<Location>/{escape(bucket)}/{escape(key)}</Location>"
            f"<Bucket>{escape(bucket)}</Bucket><Key>{escape(key)}</Key>"
            f'<ETag>&quot;{oi.etag}&quot;</ETag>'
            f"</CompleteMultipartUploadResult>"
        ), headers=hdrs)


def _event_queue_dir(object_layer) -> str | None:
    """Persist undelivered events on the first local drive's system
    volume (reference queueDir under .minio.sys); None → temp dir."""
    import os

    from minio_tpu.storage.local import SYSTEM_VOL

    for pool in getattr(object_layer, "pools", [object_layer]):
        for es in getattr(pool, "sets", [pool]):
            for d in getattr(es, "disks", []):
                root = getattr(d, "root", None)
                if root:
                    return os.path.join(root, SYSTEM_VOL, "events")
    return None


S3_SERVER_KEY = web.AppKey("s3_server", object)


def make_app(object_layer, start_services: bool = False,
             scan_interval: float = 60.0, **kw) -> web.Application:
    srv = S3Server(object_layer, **kw)
    if start_services:
        from minio_tpu.services import ServiceManager

        srv.attach_services(
            ServiceManager(object_layer, scan_interval=scan_interval))
    else:
        # no background services, but attach_services still runs the
        # post-wiring that doesn't need them (overload controller)
        srv.attach_services(None)
    srv.app[S3_SERVER_KEY] = srv
    return srv.app
