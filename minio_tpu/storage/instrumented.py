"""Instrumented per-drive decorator: counters, EWMA latencies, and a
drive-health circuit breaker.

Equivalent of the reference's xlStorageDiskIDCheck
(cmd/xl-storage-disk-id-check.go:68): wraps any StorageAPI and records,
per storage operation, the call count, error count, cumulative wall time
and an exponentially-weighted moving average latency.  The numbers feed
the admin StorageInfo plane and the Prometheus drive metrics.

On top of the timers sits the health tracker (the reference's
diskHealthTracker + storage REST client offline marking,
cmd/xl-storage-disk-id-check.go:170, internal/rest/client.go:219):
consecutive drive-level faults trip a circuit breaker that marks the
drive OFFLINE, every further call fails fast with DiskNotFound (no
quorum-path stall behind a hung drive), and a background reconnect
probe flips the drive back online — firing the `on_online` hook so the
owner can enqueue an MRF re-sync of writes the drive missed.
"""

from __future__ import annotations

import concurrent.futures as cf
import os
import threading
import time

from minio_tpu.erasure import stagestats
from minio_tpu.storage import errors
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing

# every data-plane method of StorageAPI gets a timer (control accessors
# like disk_id/is_online are left untimed on purpose — they are hot and
# trivially cheap)
TIMED_OPS = (
    "make_volume", "list_volumes", "stat_volume", "delete_volume",
    "read_all", "write_all", "delete", "rename_file", "create_file",
    "open_file_writer", "append_file", "read_file_stream", "read_file",
    "read_version", "read_xl", "write_metadata", "update_metadata",
    "delete_version", "delete_versions", "free_version_data",
    "rename_data",
    "list_dir", "walk_dir",
    "verify_file", "check_parts", "disk_info",
)

EWMA_ALPHA = 0.2  # same smoothing idea as the reference's EWMA latency
# idle decay half-life for the EWMA (seconds): a drive that stops
# getting samples — e.g. because its slow reads got it hedged out —
# decays toward healthy so it un-hedges WITHOUT needing a probe read
# to refresh the average (ROADMAP deadline/overload follow-up).  A
# hedged-out drive sees no reads, so without decay its last bad EWMA
# would pin it slow forever.  0 disables decay.
EWMA_DECAY_HALFLIFE_S = float(
    os.environ.get("MINIO_TPU_EWMA_DECAY_HALFLIFE_S", "30"))

# consecutive drive-level faults before the breaker opens (reference:
# diskMaxConcurrent/diskActiveMonitoring heuristics collapse to a small
# consecutive-failure threshold here)
BREAKER_THRESHOLD = int(os.environ.get("MINIO_TPU_BREAKER_THRESHOLD", "3"))
# reconnect probe cadence: starts fast, backs off exponentially
PROBE_INTERVAL = float(os.environ.get("MINIO_TPU_PROBE_INTERVAL", "0.5"))
PROBE_MAX_INTERVAL = float(
    os.environ.get("MINIO_TPU_PROBE_MAX_INTERVAL", "5.0"))

# drive-level faults: the transport/medium failed, as opposed to benign
# negative results (FileNotFound & friends prove the drive responded and
# therefore RESET the consecutive-fault counter)
_FAULT_TYPES = (errors.DiskNotFound, errors.FaultyDisk,
                errors.UnformattedDisk)

# read-path ops the per-op deadline worker may abandon mid-call: all
# idempotent and side-effect free, so the orphaned call finishing late
# changes nothing.  Write/commit ops are NEVER abandoned — timing out a
# rename/append the drive then completes would leave state divergent
# (same line the RPC client draws with slow/non-idempotent calls).
DEADLINE_GATED_OPS = frozenset((
    "read_all", "read_version", "read_xl", "read_file_stream",
    "read_file", "list_dir", "list_volumes", "stat_volume",
    "disk_info", "check_parts",
))

_dl_pool_lock = threading.Lock()
_dl_pool: cf.ThreadPoolExecutor | None = None

# a deadline timeout only counts as a drive FAULT (feeding the breaker)
# when the drive had at least this much time to answer — a read
# abandoned because the caller arrived with a sliver of budget proves
# nothing about the drive (a client could otherwise trip every breaker
# with x-amz-request-timeout: 1ms)
DEADLINE_FAULT_MIN = float(
    os.environ.get("MINIO_TPU_DEADLINE_FAULT_MIN", "1.0"))
# the worker-pool detour (submit + context copy + two thread handoffs
# per op) only pays for itself when the remaining budget is TIGHT
# enough that abandoning a hung call matters; relaxed budgets (the
# default 1m) run inline — the RPC per-attempt timeouts and the breaker
# already bound hangs at that horizon, and the hot path stays hop-free
DEADLINE_GATE_MAX = float(
    os.environ.get("MINIO_TPU_DEADLINE_GATE_MAX", "10.0"))


def _deadline_pool() -> cf.ThreadPoolExecutor:
    """Process-wide worker pool running deadline-gated drive reads (the
    reference's per-drive health/deadline goroutines collapse to one
    shared pool here).  Intentionally long-lived, like shard-io.  Sized
    generously: abandoned reads pin a worker until the drive answers,
    and the breaker (which trips hung drives into fast-fails) is what
    keeps that pinning bounded."""
    global _dl_pool
    with _dl_pool_lock:
        if _dl_pool is None:
            _dl_pool = cf.ThreadPoolExecutor(
                max_workers=int(os.environ.get(
                    "MINIO_TPU_DEADLINE_WORKERS", "128")),
                thread_name_prefix="drive-deadline")
        return _dl_pool


def _close_abandoned(fut: cf.Future) -> None:
    """When an abandoned read eventually returns a stream handle, close
    it — nobody else will (keeps remote HTTP conns from lingering)."""
    try:
        out = fut.result()
    except Exception:
        return
    closer = getattr(out, "close", None)
    if closer is not None:
        try:
            closer()
        except Exception:
            pass


def is_drive_fault(e: BaseException) -> bool:
    if isinstance(e, _FAULT_TYPES):
        return True
    if isinstance(e, errors.StorageError):
        return False
    # raw OSError/TimeoutError escaping a backend is a medium fault
    return isinstance(e, (OSError, TimeoutError))


class OpStats:
    __slots__ = ("count", "errors", "total_s", "ewma_s", "last_t", "mu")

    def __init__(self):
        self.count = 0
        self.errors = 0
        self.total_s = 0.0
        self.ewma_s = 0.0
        self.last_t = 0.0  # monotonic time of the last sample
        self.mu = threading.Lock()

    def record(self, dt: float, failed: bool) -> None:
        with self.mu:
            self.count += 1
            if failed:
                self.errors += 1
            self.total_s += dt
            # blend against the new sample CLAMPED into
            # [decayed, raw] history: slow evidence re-validates the
            # old (undecayed) slow average up to its own magnitude —
            # a chronically slow drive on a cold bucket keeps hedging
            # even when each fresh sample sits just under the stale
            # raw average — while a genuinely fast sample tracks the
            # decayed history, so recovery after an idle gap does not
            # resurrect stale slowness.  With no idle gap
            # (decayed == raw) this is exactly the classic EWMA.
            if self.count == 1:
                self.ewma_s = dt
            else:
                base = max(self._decayed_locked(), min(dt, self.ewma_s))
                self.ewma_s = EWMA_ALPHA * dt + (1 - EWMA_ALPHA) * base
            self.last_t = time.monotonic()

    def _decayed_locked(self, now: float | None = None) -> float:
        """EWMA with idle decay applied (caller holds self.mu): halves
        every EWMA_DECAY_HALFLIFE_S without a new sample, so a drive
        that recovered (or stopped being read because hedging steered
        around it) drifts back toward healthy instead of staying
        pinned at its last bad average."""
        if self.count == 0:
            return 0.0
        if EWMA_DECAY_HALFLIFE_S <= 0:
            return self.ewma_s
        idle = (time.monotonic() if now is None else now) - self.last_t
        if idle <= 0:
            return self.ewma_s
        return self.ewma_s * 0.5 ** (idle / EWMA_DECAY_HALFLIFE_S)

    def to_dict(self) -> dict:
        with self.mu:
            return {
                "count": self.count, "errors": self.errors,
                "totalSeconds": round(self.total_s, 6),
                "ewmaMillis": round(self._decayed_locked() * 1e3, 3),
            }


class InstrumentedStorage:
    """Timing + health wrapper around a StorageAPI instance."""

    def __init__(self, inner, breaker_threshold: int | None = None):
        self._inner = inner
        self._ops: dict[str, OpStats] = {op: OpStats() for op in TIMED_OPS}
        self._threshold = (BREAKER_THRESHOLD if breaker_threshold is None
                           else breaker_threshold)
        self._health_mu = threading.Lock()
        self._consec_faults = 0
        self._breaker_open = False
        self._offline_since = 0.0
        self._probe_thread: threading.Thread | None = None
        self._closed = False
        self.trips = 0        # breaker open events
        self.reconnects = 0   # probe-driven recoveries
        self.fast_fails = 0   # calls rejected while the breaker was open
        self.deadline_timeouts = 0  # gated reads abandoned mid-call
        self.deadline_expired = 0   # gated reads refused: budget spent
        self.on_offline = None  # callable(self), fired when the breaker trips
        self.on_online = None   # callable(self), fired when the probe recovers
        for op in TIMED_OPS:
            target = getattr(inner, op, None)
            if target is not None:
                setattr(self, op, self._wrap(op, target))

    def _wrap(self, op: str, fn):
        stats = self._ops[op]
        gated = op in DEADLINE_GATED_OPS
        span_name = f"drive.{op}"

        def timed(*a, **kw):
            if self._breaker_open:
                # fail fast: a tripped drive must cost microseconds, not a
                # full RPC timeout, or one hung drive stalls every quorum
                # write (reference: errDiskNotFound short-circuit)
                with self._health_mu:
                    self.fast_fails += 1
                raise errors.DiskNotFound(
                    f"{self._endpoint_label()}: drive offline "
                    f"(circuit breaker open)")
            budget = deadline_mod.current()
            if gated and budget is not None and budget.t_end is not None \
                    and budget.remaining() <= DEADLINE_GATE_MAX:
                return self._deadline_call(op, fn, stats, budget, a, kw)
            # per-drive op span when a request trace is ambient: the
            # where-did-this-request's-time-go attribution ISSUE 12
            # exists for (one contextvar read when untraced)
            ref = tracing.current_ref()
            t0 = time.monotonic()
            try:
                # the same interval in the profiler's trace, when one
                # is being taken (erasure/stagestats.py)
                with stagestats.annotation(span_name):
                    out = fn(*a, **kw)
            except Exception as e:
                dt = time.monotonic() - t0
                stats.record(dt, failed=True)
                if ref is not None:
                    tracing.record_span(
                        ref, span_name, dt,
                        drive=self._endpoint_label(),
                        error=type(e).__name__)
                self._note(fault=is_drive_fault(e))
                raise
            dt = time.monotonic() - t0
            stats.record(dt, failed=False)
            if ref is not None:
                tracing.record_span(ref, span_name, dt,
                                    drive=self._endpoint_label())
            self._note(fault=False)
            return out

        timed.__name__ = op
        return timed

    def _deadline_call(self, op: str, fn, stats, budget, a, kw):
        """Per-op deadline worker (reference diskHealthCheck contexts,
        cmd/xl-storage-disk-id-check.go): the read runs on the shared
        deadline pool bounded by the request's remaining budget.  A call
        the drive holds past the budget is ABANDONED — the caller gets
        DeadlineExceeded now and the hang feeds the breaker, instead of
        one slow drive holding a quorum fan-out hostage for the full RPC
        timeout."""
        rem = budget.remaining()
        if rem <= 0:
            with self._health_mu:
                self.deadline_expired += 1
            raise errors.DeadlineExceeded(
                f"{self._endpoint_label()}: {op} refused, request "
                f"deadline budget exhausted")
        ref = tracing.current_ref()
        fut = deadline_mod.ctx_submit(_deadline_pool(), fn, *a, **kw)
        t0 = time.monotonic()
        try:
            with stagestats.annotation(f"drive.{op}"):
                out = fut.result(timeout=rem)
        except cf.TimeoutError:
            if fut.cancel():
                # never started: pool backlog ate the budget — not this
                # drive's fault; no op sample either (the drive never
                # saw the call, a failed/slow sample would poison the
                # EWMA that steers hedging)
                with self._health_mu:
                    self.deadline_expired += 1
            else:
                stats.record(time.monotonic() - t0, failed=True)
                if ref is not None:
                    # ABANDONED mark only when the drive actually held
                    # the read — a cancel()ed (never-started) call is
                    # the POOL's backlog, and blaming the drive in the
                    # trace would be the exact misattribution this
                    # plane exists to prevent
                    tracing.record_span(ref, f"drive.{op}",
                                        time.monotonic() - t0,
                                        drive=self._endpoint_label(),
                                        abandoned=True)
                fut.add_done_callback(_close_abandoned)
                with self._health_mu:
                    self.deadline_timeouts += 1
                if rem >= DEADLINE_FAULT_MIN:
                    # the drive had a fair window and still held the
                    # read: that is a hang, feed the breaker.  A
                    # sliver-budget abandonment is the CALLER's poverty,
                    # not a drive fault
                    self._note(fault=True)
            raise errors.DeadlineExceeded(
                f"{self._endpoint_label()}: {op} abandoned after "
                f"{rem * 1e3:.0f} ms budget")
        except Exception as e:
            dt = time.monotonic() - t0
            stats.record(dt, failed=True)
            if ref is not None:
                tracing.record_span(ref, f"drive.{op}", dt,
                                    drive=self._endpoint_label(),
                                    error=type(e).__name__)
            self._note(fault=is_drive_fault(e))
            raise
        dt = time.monotonic() - t0
        stats.record(dt, failed=False)
        if ref is not None:
            tracing.record_span(ref, f"drive.{op}", dt,
                                drive=self._endpoint_label())
        self._note(fault=False)
        return out

    def _endpoint_label(self) -> str:
        try:
            return self._inner.endpoint() or repr(self._inner)
        except Exception:
            return repr(self._inner)

    # -- breaker ------------------------------------------------------------
    def _note(self, fault: bool) -> None:
        tripped = False
        with self._health_mu:
            if fault:
                self._consec_faults += 1
                if (not self._breaker_open
                        and self._consec_faults >= self._threshold):
                    self._breaker_open = True
                    self._offline_since = time.time()
                    self.trips += 1
                    tripped = True
            else:
                self._consec_faults = 0
        if tripped:
            self._start_probe()
            cb = self.on_offline
            if cb is not None:
                try:
                    cb(self)
                except Exception:
                    pass

    def _start_probe(self) -> None:
        with self._health_mu:
            if self._probe_thread is not None and self._probe_thread.is_alive():
                return
            t = deadline_mod.service_thread(
                self._probe_loop, start=False,
                name=f"drive-probe-{id(self):x}")
            self._probe_thread = t
        t.start()

    def _probe_loop(self) -> None:
        interval = PROBE_INTERVAL
        while not self._closed:
            time.sleep(interval)
            if self._closed or self._probe_once():
                return
            interval = min(interval * 2, PROBE_MAX_INTERVAL)

    def _probe_once(self) -> bool:
        """One reconnect attempt against the INNER drive (bypassing the
        breaker).  disk_info is the canonical cheap data-plane op; for
        remote drives the RPC client's own short-deadline ping runs
        first so a down peer costs ~nothing."""
        try:
            if not self._inner.is_online():
                return False
            self._inner.disk_info()
        except Exception:
            return False
        with self._health_mu:
            if not self._breaker_open:
                return True  # already recovered elsewhere
            self._breaker_open = False
            self._consec_faults = 0
            self.reconnects += 1
        cb = self.on_online
        if cb is not None:
            try:
                cb(self)
            except Exception:
                pass
        return True

    # -- health surface -----------------------------------------------------
    def is_online(self) -> bool:
        if self._breaker_open:
            return False
        try:
            return self._inner.is_online()
        except Exception:
            return False

    def breaker_open(self) -> bool:
        return self._breaker_open

    def health_stats(self) -> dict:
        with self._health_mu:
            return {
                "breakerOpen": self._breaker_open,
                "consecFaults": self._consec_faults,
                "trips": self.trips,
                "reconnects": self.reconnects,
                "fastFails": self.fast_fails,
                "deadlineTimeouts": self.deadline_timeouts,
                "deadlineExpired": self.deadline_expired,
                "offlineSince": (round(self._offline_since, 3)
                                 if self._breaker_open else 0),
            }

    def op_ewma(self, op: str) -> float:
        """EWMA latency (seconds) of one op, with idle decay; 0.0
        before any sample.  The read path uses this to hedge around
        chronically slow drives — decay is what lets a hedged-out
        drive (which by construction gets no new read samples)
        eventually un-hedge without a probe read."""
        s = self._ops.get(op)
        if s is None:
            return 0.0
        with s.mu:
            return s._decayed_locked()

    def close(self) -> None:
        self._closed = True
        self._inner.close()

    # untimed passthroughs (and anything a backend adds beyond the ABC)
    def __getattr__(self, name):
        return getattr(self._inner, name)

    # -- metrics surface -----------------------------------------------------
    def op_stats(self) -> dict[str, dict]:
        """{op: {count, errors, totalSeconds, ewmaMillis}} for ops used."""
        return {op: s.to_dict() for op, s in self._ops.items() if s.count}

    def unwrap(self):
        return self._inner


def instrument(disks):
    """Wrap a list of drives (None entries pass through)."""
    return [InstrumentedStorage(d) if d is not None
            and not isinstance(d, InstrumentedStorage) else d for d in disks]
