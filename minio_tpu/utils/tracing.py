"""End-to-end request tracing: Dapper-style span trees with tail-based
slow/error capture (ISSUE 12 tentpole).

The deadline plane (utils/deadline.py) proved the propagation pattern —
a contextvar carried by ``ctx_submit``, an ``x-minio-tpu-*`` header on
RPC hops, a field in worker-plane job messages.  Tracing is its
read-side twin and rides the exact same three carriers:

* **In-process**: a ``Span`` rides a ``contextvars.ContextVar`` (the
  sibling of ``deadline.Budget``); thread-pool hops inherit it through
  the existing ``deadline.ctx_submit`` / copied contexts, so no call
  site changes.

* **RPC**: the client stamps ``x-minio-tpu-trace`` (``trace:span:flag``)
  on every hop (distributed/rpc.py); the server opens a
  ``continuation``.  When the originating trace is still OPEN in this
  process (loopback peers, the test cluster) the continuation's spans
  append straight into it — one tree; otherwise a *fragment* trace is
  recorded locally under the same trace id and tail-captured on its own
  node, the classic Dapper per-node collection.

* **Worker processes / batcher ticks**: job messages carry the wire
  context; the worker records into a non-capturing fragment whose spans
  ship back in the reply and are ``graft``-ed under the front's job
  span — so one PUT yields ONE tree spanning HTTP → admission →
  erasure stage → worker encode → batcher tick.

Recording is always-on when ``MINIO_TPU_TRACE`` (default 1) is set:
tail-based capture can only keep the slow/error traces it actually
recorded.  RETENTION is what sampling controls — a finished trace is
kept in the bounded in-RAM ``store`` when it errored (5xx / 503 shed),
ran past ``MINIO_TPU_TRACE_SLOW_MS``, or won the head-sampling draw
(``MINIO_TPU_TRACE_SAMPLE``); everything else is dropped at finish.
``MINIO_TPU_TRACE=0`` disables the plane entirely (no header, no
metrics — byte- and metrics-identical to the pre-tracing server).

Span records are plain dicts (msgpack/pickle-safe for the carriers)::

    {"id", "parent", "name", "t0", "dur", <tag>: <value>, ...}

``t0``/``dur`` are seconds relative to the owning trace's start; a
captured document also holds that start on the monotonic clock
(``startMonotonic``), so a span can be laid beside a profiler trace.  The
admin surface (``GET /minio/admin/v3/trace/slow``) returns captured
traces with the tree assembled by ``span_tree``.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import random
import threading
import time
from collections import OrderedDict

TRACE_HEADER = "x-minio-tpu-trace"
RESPONSE_HEADER = "x-minio-tpu-trace-id"

_TRUTHY = ("1", "on", "true", "yes")

#: spans kept per trace; a runaway instrumented loop (a million-part
#: list walk) must bound its own trace, not the store
MAX_SPANS_PER_TRACE = 512

# observability for the tracing plane itself (read by server/metrics.py;
# bare int bumps — the GIL makes them safe enough for counters)
stats = {"traces": 0, "spans": 0, "spans_dropped": 0}


def _fast_env_reader():
    """``os.environ.get`` pays MutableMapping machinery + a KeyError
    try per read — measurable at hot-GET request rates (the knobs are
    deliberately re-read per request so tests/bench can flip them
    live).  CPython keeps the backing dict at ``os.environ._data``
    keyed by ``encodekey`` (posix and nt alike); read through it when
    available, with the public API as the fallback."""
    env = os.environ
    try:
        data = env._data
        enc = env.encodekey
        data.get(enc("MINIO_TPU_TRACE"))  # probe the fast path works

        def get(name: str, default=None, _d=data, _e=enc):
            v = _d.get(_e(name))
            if v is None:
                return default
            return v.decode() if isinstance(v, bytes) else v

        return get
    except Exception:
        return lambda name, default=None: os.environ.get(name, default)


_getenv = _fast_env_reader()


def enabled() -> bool:
    """MINIO_TPU_TRACE master switch (default 1).  Re-read per call so
    tests/bench can flip it without rebuilding servers."""
    return _getenv("MINIO_TPU_TRACE", "1").lower() in _TRUTHY


#: raw env string -> parsed float; env knobs are re-read per call (so
#: tests/bench can flip them live) but the PARSE is memoized — float()
#: on the hot path is measurable at hot-GET request rates
_parse_cache: dict = {}


def _float_knob(name: str, default: str, lo: float, hi: float) -> float:
    raw = _getenv(name, default)
    got = _parse_cache.get((name, raw))
    if got is None:
        try:
            got = min(hi, max(lo, float(raw)))
        except ValueError:
            got = float(default)
        if len(_parse_cache) > 64:
            _parse_cache.clear()
        _parse_cache[(name, raw)] = got
    return got


def sample_rate() -> float:
    """MINIO_TPU_TRACE_SAMPLE: head-sampling probability for retaining
    traces that are neither slow nor errored (default 0.01)."""
    return _float_knob("MINIO_TPU_TRACE_SAMPLE", "0.01", 0.0, 1.0)


def slow_ms() -> float:
    """MINIO_TPU_TRACE_SLOW_MS: traces at least this long are always
    retained (default 500 ms — p99-ish for drive-bound requests)."""
    return _float_knob("MINIO_TPU_TRACE_SLOW_MS", "500", 0.0,
                       float("inf"))


_ids = itertools.count(1)
#: span ids from different PROCESSES meet inside one grafted tree
#: (worker fragments ship home in replies), so a bare counter would
#: collide across workers — prefix with per-process random bytes
_ID_PREFIX = os.urandom(3).hex()


def _new_id() -> str:
    return f"{_ID_PREFIX}{next(_ids):x}"


def _new_trace_id() -> str:
    # not a secret — just collision-resistant across nodes/processes
    return f"{random.getrandbits(64):016x}"


#: guards the read-modify-write stage folds (low frequency: one per
#: pipeline batch).  Span appends and the finished flag are deliberately
#: lock-free — GIL-atomic list.append/attribute stores; the worst a race
#: can do is keep one span past the cap or drop one after finish, and
#: the hot-GET request path must not pay lock cycles (ISSUE 12 <3%
#: overhead criterion)
_stage_mu = threading.Lock()


class Trace:
    """One request's span collection: lock-free appends (see _stage_mu
    note), with per-stage seconds folded in by stagestats.  ``sampled`` is drawn LAZILY (None = undecided): the
    common drop path pays the head-sampling env read + draw once, at
    finish/to_wire, not at start."""

    __slots__ = ("trace_id", "name", "t0", "wall0", "spans", "stages",
                 "sampled", "finished", "fragment", "registered")

    def __init__(self, trace_id: str, name: str,
                 sampled: bool | None = None, fragment: bool = False):
        self.trace_id = trace_id
        self.name = name
        self.t0 = time.perf_counter()
        self.wall0 = time.time()
        self.spans: list[dict] = []
        self.stages: dict[str, float] | None = None
        self.sampled = sampled
        self.finished = False
        self.fragment = fragment
        self.registered = False  # present in _active (lazy, see to_wire)

    def head_sampled(self) -> bool:
        got = self.sampled
        if got is None:
            got = self.sampled = random.random() < sample_rate()
        return got

    def add_span(self, rec: dict) -> None:
        if self.finished or len(self.spans) >= MAX_SPANS_PER_TRACE:
            stats["spans_dropped"] += 1
            return
        self.spans.append(rec)
        stats["spans"] += 1

    def add_stage(self, stage: str, seconds: float) -> None:
        with _stage_mu:
            if self.finished:
                return
            st = self.stages
            if st is None:
                st = self.stages = {}
            st[stage] = st.get(stage, 0.0) + seconds


class Span:
    """One timed node of a trace.  Created via ``start``/``begin``/the
    ``span`` context manager — never directly."""

    __slots__ = ("trace", "span_id", "parent_id", "name", "t0", "tags",
                 "token", "deferred")

    def __init__(self, trace: Trace, name: str, parent_id: str | None,
                 tags: dict | None = None):
        self.trace = trace
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.name = name
        self.t0 = time.perf_counter()
        self.tags = tags or {}
        self.token = None      # contextvar token (begin_request)
        self.deferred = None   # deferred child spans (defer_child)

    def tag(self, **tags) -> None:
        self.tags.update(tags)

    def defer_child(self, name: str, dur: float, **tags) -> None:
        """Cheapest child span: stash (name, dur, tags) now, materialize
        the record only if the trace is actually captured.  For
        every-request children whose start coincides with the span's
        own start (the admission wait) — the hot path pays a tuple, not
        a dict + id + append."""
        d = self.deferred
        if d is None:
            d = self.deferred = []
        d.append((name, dur, tags))

    def record(self) -> dict:
        # no rounding on the hot path; renderers round at the edge
        rec = {"id": self.span_id, "parent": self.parent_id,
               "name": self.name,
               "t0": self.t0 - self.trace.t0,
               "dur": time.perf_counter() - self.t0}
        if self.tags:
            rec.update(self.tags)
        return rec

    def finish(self, error: str | None = None) -> None:
        if error is not None:
            self.tags["error"] = error
        self.trace.add_span(self.record())


# ---------------------------------------------------------------- context
_current: contextvars.ContextVar[Span | None] = contextvars.ContextVar(
    "minio_tpu_trace", default=None)


def current() -> Span | None:
    return _current.get()


def current_trace() -> Trace | None:
    sp = _current.get()
    return sp.trace if sp is not None else None


def trace_id() -> str | None:
    sp = _current.get()
    return sp.trace.trace_id if sp is not None else None


def install(sp: Span | None):
    """Install a span as current and return the reset token."""
    return _current.set(sp)


def reset(token) -> None:
    _current.reset(token)


def current_ref() -> tuple[Trace, str] | None:
    """(trace, span_id) of the ambient span — a handle other threads
    (the batcher tick) can record spans against without a contextvar."""
    sp = _current.get()
    if sp is None:
        return None
    return (sp.trace, sp.span_id)


def record_span(ref: tuple[Trace, str], name: str, dur: float,
                **tags) -> None:
    """Append a just-finished span under `ref` (its t0 is derived as
    now - dur).  Used by code that timed the work itself — the batcher
    tick, the RPC client's retry loop."""
    trace, parent = ref
    rec = {"id": _new_id(), "parent": parent, "name": name,
           "t0": time.perf_counter() - dur - trace.t0, "dur": dur}
    if tags:
        rec.update(tags)
    trace.add_span(rec)


def event(name: str, **tags) -> None:
    """Zero-duration annotation span on the current trace (hotcache
    fill/collapse verdicts, hedge decisions, repair plans).  No-op
    without an ambient trace."""
    sp = _current.get()
    if sp is None:
        return
    rec = {"id": _new_id(), "parent": sp.span_id, "name": name,
           "t0": time.perf_counter() - sp.trace.t0, "dur": 0.0}
    if tags:
        rec.update(tags)
    sp.trace.add_span(rec)


def annotate(**tags) -> None:
    """Merge tags into the CURRENT span — the cheapest possible trace
    mark (no span record, no id): the right tool on per-request hot
    paths like the RAM-hit verdict.  No-op without an ambient trace."""
    sp = _current.get()
    if sp is not None:
        sp.tags.update(tags)


class span:
    """``with span("drive.read", drive=ep) as sp:`` — child span of the
    ambient one, installed as current for the block.  Without an
    ambient trace the body runs untraced (``sp`` is None) at the cost
    of one contextvar read."""

    __slots__ = ("name", "tags", "sp", "_token")

    def __init__(self, name: str, **tags):
        self.name = name
        self.tags = tags
        self.sp = None
        self._token = None

    def __enter__(self) -> Span | None:
        parent = _current.get()
        if parent is None:
            return None
        self.sp = Span(parent.trace, self.name, parent.span_id, self.tags)
        self._token = _current.set(self.sp)
        return self.sp

    def __exit__(self, etype, exc, tb) -> bool:
        if self.sp is not None:
            _current.reset(self._token)
            self.sp.finish(
                error=etype.__name__ if etype is not None else None)
        return False


def begin(name: str, **tags) -> Span | None:
    """Explicit child span of the ambient one, NOT installed as current
    (the worker-plane job spans: begun at send, finished at reply so
    unrelated work in between is not parented under them).  Pair with
    ``sp.finish()``."""
    parent = _current.get()
    if parent is None:
        return None
    return Span(parent.trace, name, parent.span_id, tags)


# ------------------------------------------------------- trace lifecycle
#: open traces by id, so a same-process continuation (loopback RPC, the
#: test cluster) joins the ORIGINAL trace instead of recording a
#: fragment.  Mutated in place (no rebinding) — worker processes own
#: their own copies by design; fragments ship home in replies.  Plain
#: dict on purpose: str-keyed get/set/del are GIL-atomic and the
#: request path must not pay a lock.
_active: dict[str, Trace] = {}


def start(name: str, **tags) -> Span | None:
    """Mint a new trace + its root span (one per HTTP request / heal
    sequence).  Returns None when the plane is off.  The caller installs
    the root with ``install`` and MUST ``finish`` it."""
    if not enabled():
        return None
    tr = Trace(_new_trace_id(), name)
    stats["traces"] += 1
    root = Span(tr, name, None, tags)
    _active[tr.trace_id] = tr
    tr.registered = True
    return root


def begin_request(name: str, **tags) -> Span | None:
    """``start`` + ``install`` fused for the per-request hot path, with
    the _active registration DEFERRED to ``to_wire`` (a request that
    never leaves the process — the RAM-hit GET — never touches the
    registry).  Pair with ``end_request``."""
    if not enabled():
        return None
    tr = Trace(_new_trace_id(), name)
    stats["traces"] += 1
    root = Span(tr, name, None, tags)
    root.token = _current.set(root)
    return root


def end_request(root: Span, *, status: int = 200, error: bool = False,
                duration: float | None = None) -> dict | None:
    """``reset`` + ``finish`` fused (see begin_request)."""
    _current.reset(root.token)
    return finish(root, status=status, error=error, duration=duration)


def finish(root: Span, *, status: int = 200, error: bool = False,
           duration: float | None = None) -> dict | None:
    """Close a trace minted by ``start``: record the root span, decide
    retention (error / slow / head-sampled) and capture into the store.
    Returns the captured doc, or None when the trace was dropped."""
    tr = root.trace
    dur = (time.perf_counter() - root.t0) if duration is None else duration
    reason = None
    if error:
        reason = "error"
    elif dur * 1000.0 >= slow_ms():
        reason = "slow"
    elif tr.head_sampled():
        reason = "sampled"
    already = tr.finished
    tr.finished = True
    if tr.registered and _active.get(tr.trace_id) is tr:
        del _active[tr.trace_id]
    if already or reason is None:
        # dropped: no doc is built at all — the common (fast, OK,
        # unsampled) path must stay allocation-light
        return None
    root.tags.setdefault("status", status)
    rec = root.record()
    rec["dur"] = dur
    rec_list = tr.spans + [rec]
    if root.deferred:
        # materialize defer_child()ed children only now, on capture:
        # they start with their parent by contract, so t0 is the
        # parent's own offset
        for name_, dur_, tags_ in root.deferred:
            drec = {"id": _new_id(), "parent": root.span_id,
                    "name": name_, "t0": rec["t0"], "dur": dur_}
            if tags_:
                drec.update(tags_)
            rec_list.append(drec)
    for r in rec_list:
        # rounding deferred off the hot path to this rare capture edge
        r["t0"] = round(r.get("t0", 0.0), 6)
        r["dur"] = round(r.get("dur", 0.0), 6)
    doc = {
        "traceId": tr.trace_id,
        "name": tr.name,
        "start": round(tr.wall0, 3),
        # the same instant on time.perf_counter() (CLOCK_MONOTONIC, the
        # clock of a profiler trace's host lines): start + a span's t0
        # places it beside the device's own lines
        "startMonotonic": round(tr.t0, 6),
        "durationMs": round(dur * 1e3, 3),
        "status": status,
        "reason": reason,
        "fragment": tr.fragment,
        "stages": {k: round(v, 6)
                   for k, v in sorted((tr.stages or {}).items())},
        "spans": rec_list,
    }
    store.add(doc)
    return doc


def summary(root: Span, limit: int = 5) -> list[dict]:
    """Top spans by duration for the live trace stream — a compact
    where-did-the-time-go line, not the full tree."""
    spans = sorted(root.trace.spans, key=lambda r: r["dur"], reverse=True)
    return [{"name": r["name"], "durMs": round(r["dur"] * 1e3, 3)}
            for r in spans[:limit]]


# ------------------------------------------------------------ propagation
def to_wire() -> str | None:
    """Wire form of the CURRENT context (``trace:span:sampled``) — the
    value riding ``x-minio-tpu-trace`` on an RPC hop and ``trace`` in a
    worker job message; None when untraced."""
    sp = _current.get()
    if sp is None:
        return None
    tr = sp.trace
    if not tr.registered and not tr.fragment and not tr.finished:
        # lazy registry insert: only traces that actually hop out of
        # the process need to be joinable by a loopback continuation
        _active[tr.trace_id] = tr
        tr.registered = True
    return f"{tr.trace_id}:{sp.span_id}:" \
           f"{1 if tr.head_sampled() else 0}"


def _parse_wire(wire: str) -> tuple[str, str, bool] | None:
    parts = wire.split(":")
    if len(parts) != 3 or not parts[0] or not parts[1]:
        return None
    return parts[0], parts[1], parts[2] == "1"


class continuation:
    """Receiving side of a hop (RPC server, worker job): installs a
    span continuing the wire context for the block.

    If the originating trace is still open IN THIS PROCESS the span
    joins it directly (single tree).  Otherwise a fragment trace is
    recorded under the same id; with ``capture=True`` it tail-captures
    into this node's store at exit (the per-node Dapper collection),
    with ``capture=False`` the caller ships ``export()`` home in the
    reply instead (the worker plane)."""

    __slots__ = ("wire", "name", "capture", "tags", "sp", "_token",
                 "_fragment")

    def __init__(self, wire: str | None, name: str, capture: bool = True,
                 **tags):
        self.wire = wire
        self.name = name
        self.capture = capture
        self.tags = tags
        self.sp = None
        self._token = None
        self._fragment: Trace | None = None

    def __enter__(self) -> Span | None:
        if self.wire is None or not enabled():
            return None
        parsed = _parse_wire(self.wire)
        if parsed is None:
            return None
        tid, parent_id, sampled = parsed
        tr = _active.get(tid)
        if tr is None:
            tr = Trace(tid, self.name, sampled=sampled, fragment=True)
            self._fragment = tr
        self.sp = Span(tr, self.name, parent_id, self.tags)
        self._token = _current.set(self.sp)
        return self.sp

    def __exit__(self, etype, exc, tb) -> bool:
        if self.sp is None:
            return False
        _current.reset(self._token)
        err = etype.__name__ if etype is not None else None
        frag = self._fragment
        if frag is None:
            self.sp.finish(error=err)
            return False
        if not self.capture:
            # export() ships the spans home; just seal the root record
            self.sp.finish(error=err)
            return False
        finish(self.sp, status=500 if err else 200, error=err is not None)
        return False

    def export(self) -> dict | None:
        """Fragment spans + stage folds for the reply (after __exit__);
        None when the continuation joined an in-process trace (its
        spans are already in the tree) or tracing is off."""
        frag = self._fragment
        if frag is None:
            return None
        return {"spans": list(frag.spans),
                "stages": {k: round(v, 6)
                           for k, v in (frag.stages or {}).items()}}


def graft(exported: dict | None, parent: Span | None) -> None:
    """Splice a shipped fragment (a worker reply's ``trace`` field)
    under `parent` in parent's trace: fragment roots re-parent to
    `parent`, times shift by parent's offset (clocks are per-process —
    the tree shape and durations are what's meaningful), stage folds
    merge."""
    if exported is None or parent is None:
        return
    tr = parent.trace
    spans = exported.get("spans") or ()
    local = {rec.get("id") for rec in spans}
    off = round(parent.t0 - tr.t0, 6)
    for rec in spans:
        rec = dict(rec)
        if rec.get("parent") not in local:
            rec["parent"] = parent.span_id
        rec["t0"] = round(rec.get("t0", 0.0) + off, 6)
        tr.add_span(rec)
    for stage, secs in (exported.get("stages") or {}).items():
        tr.add_stage(stage, secs)


# ------------------------------------------------------------- the store
class TraceStore:
    """Size-bounded in-RAM store of captured trace docs, FIFO-evicted,
    with honest eviction/byte counters (rendered as ``minio_trace_*``
    by server/metrics.py and served by ``GET /trace/slow``)."""

    def __init__(self, max_entries: int | None = None,
                 max_bytes: int | None = None):
        self._max_entries = max_entries
        self._max_bytes = max_bytes
        self._mu = threading.Lock()
        self._docs: OrderedDict[str, dict] = OrderedDict()
        self._bytes = 0
        self.captures = 0
        self.evictions = 0
        self.by_reason = {"error": 0, "slow": 0, "sampled": 0}

    def max_entries(self) -> int:
        if self._max_entries is not None:
            return self._max_entries
        try:
            return max(1, int(os.environ.get(
                "MINIO_TPU_TRACE_STORE_MAX", "256")))
        except ValueError:
            return 256

    def max_bytes(self) -> int:
        if self._max_bytes is not None:
            return self._max_bytes
        try:
            return max(1 << 16, int(os.environ.get(
                "MINIO_TPU_TRACE_STORE_BYTES", str(8 << 20))))
        except ValueError:
            return 8 << 20

    @staticmethod
    def _weigh(doc: dict) -> int:
        # flat-ish estimate: capture is rare (slow/error/sampled), so a
        # real serialization would be affordable, but an estimate keeps
        # the capture path allocation-free
        return 256 + 192 * len(doc.get("spans", ())) \
            + 48 * len(doc.get("stages", ()))

    def add(self, doc: dict) -> None:
        nbytes = self._weigh(doc)
        with self._mu:
            old = self._docs.pop(doc["traceId"], None)
            if old is not None:
                # two fragments of one trace (or a fragment + the
                # origin) landing in one process merge into one doc
                seen = {r.get("id") for r in doc["spans"]}
                doc = dict(doc)
                doc["spans"] = doc["spans"] + [
                    r for r in old.get("spans", ())
                    if r.get("id") not in seen]
                self._bytes -= self._weigh(old)
                nbytes = self._weigh(doc)
            self._docs[doc["traceId"]] = doc
            self._bytes += nbytes
            self.captures += 1
            reason = doc.get("reason", "")
            if reason in self.by_reason:
                self.by_reason[reason] += 1
            while self._docs and (len(self._docs) > self.max_entries()
                                  or self._bytes > self.max_bytes()):
                _, evicted = self._docs.popitem(last=False)
                self._bytes -= self._weigh(evicted)
                self.evictions += 1

    def snapshot(self, n: int = 50, err_only: bool = False) -> list[dict]:
        """Newest-first captured docs (copies — the caller may decorate)."""
        with self._mu:
            docs = list(self._docs.values())
        docs.reverse()
        if err_only:
            docs = [d for d in docs if d.get("reason") == "error"]
        return [dict(d) for d in docs[:max(0, n)]]

    def get(self, tid: str) -> dict | None:
        with self._mu:
            d = self._docs.get(tid)
        return dict(d) if d is not None else None

    def stats(self) -> dict:
        with self._mu:
            return {"entries": len(self._docs), "bytes": self._bytes,
                    "captures": self.captures, "evictions": self.evictions,
                    "by_reason": dict(self.by_reason)}

    def clear(self) -> None:
        with self._mu:
            self._docs.clear()
            self._bytes = 0


#: process-wide store (mutated in place; each process owns its own —
#: worker fragments ship home in replies instead of using it)
store = TraceStore()


def quantile(sorted_vals: list, q: float):
    """Nearest-rank quantile over an already-sorted sample list; None
    on empty.  Shared by the trace summary below and the simulator's
    client-side aggregates so the two can never silently diverge."""
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(len(sorted_vals) * q))]


def summarize_stages(docs: list[dict]) -> dict:
    """Aggregate retained trace docs into per-stage timing: for every
    span NAME, count / p50 / p99 / total seconds (exact quantiles — the
    store is bounded, so the sample lists are too), flagging names that
    ever appear as a trace root (so a consumer attributing a slow
    scenario can exclude the root request spans and look at the stages
    under them); plus the stagestats fold totals per pipeline stage.
    Served by ``GET /minio/admin/v3/trace/summary``."""
    by_name: dict[str, dict] = {}
    durs: dict[str, list[float]] = {}
    stage_totals: dict[str, float] = {}
    for doc in docs:
        for stage, secs in (doc.get("stages") or {}).items():
            stage_totals[stage] = stage_totals.get(stage, 0.0) + secs
        for rec in doc.get("spans", ()):
            name = rec.get("name", "")
            d = by_name.get(name)
            if d is None:
                d = by_name[name] = {
                    "count": 0, "totalS": 0.0, "errors": 0,
                    "isRoot": False}
                durs[name] = []
            dur = rec.get("dur", 0.0)
            d["count"] += 1
            d["totalS"] += dur
            if rec.get("error"):
                d["errors"] += 1
            if rec.get("parent") is None:
                d["isRoot"] = True
            durs[name].append(dur)
    for name, d in by_name.items():
        ds = sorted(durs[name])
        d["totalS"] = round(d["totalS"], 6)
        d["p50Ms"] = round(quantile(ds, 0.50) * 1e3, 3)
        d["p99Ms"] = round(quantile(ds, 0.99) * 1e3, 3)
        d["maxMs"] = round(ds[-1] * 1e3, 3)
    return {
        "traces": len(docs),
        "spans": dict(sorted(by_name.items())),
        "stages": {k: {"seconds": round(v, 6)}
                   for k, v in sorted(stage_totals.items())},
    }


def span_tree(doc: dict) -> dict:
    """Assemble the nested tree view of a captured doc: each span gains
    a ``children`` list; the returned doc's ``tree`` holds the roots
    (orphans — grafted fragments whose parent lived on another node —
    surface as extra roots rather than vanishing)."""
    nodes = {r["id"]: dict(r, children=[]) for r in doc.get("spans", ())}
    roots = []
    for rec in nodes.values():
        parent = nodes.get(rec.get("parent"))
        if parent is None:
            roots.append(rec)
        else:
            parent["children"].append(rec)
    for rec in nodes.values():
        rec["children"].sort(key=lambda r: r.get("t0", 0.0))
    roots.sort(key=lambda r: r.get("t0", 0.0))
    out = dict(doc)
    out["tree"] = roots
    return out
