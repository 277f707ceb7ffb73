"""Device-resident erasure request batcher (ISSUE 11 tentpole).

Every PUT/GET/heal used to issue its OWN codec dispatch: small,
unbatched GF(2^8) matmuls that leave the device idle between programs —
the classic underutilization request batching solves in inference
serving.  This module coalesces concurrent codec work across requests
into ONE fused device program per tick per geometry:

* Submitters (PUT ``encode_stream`` batches, GET/heal reconstruct
  groups, the repair executor's sub-shard rebuilds — and, under
  ``MINIO_TPU_WORKERS``, each data-plane worker process's encode jobs,
  which submit to that NODE-process's batcher instead of dispatching
  privately) enqueue a ``(signature, block-batch)`` work item and wait
  on a per-item future.

* A single tick thread opens a bounded tick window when work arrives
  (``MINIO_TPU_BATCH_TICK_US``, closed early when the queued bytes
  cross the ``MINIO_TPU_BATCH_MAX_BYTES`` watermark), then groups the
  queue by geometry signature, pads/concatenates each group's batches
  along the batch axis, and dispatches ONE program per group.  A
  mixed-geometry tick therefore degrades to per-geometry sub-dispatch
  — it never pads across signatures and never blocks one geometry on
  another (model invariant ``single-signature-tick``).

* Items are laid out set-major inside a tick batch
  (``set_major_order`` below — jax-free on purpose): the mesh codec
  (parallel/mesh.py) shards the batch axis over the mesh's ``blocks``
  axis, so the per-tick batch is
  effectively sharded over the device mesh BY ERASURE SET — each set's
  contiguous span lands on the fewest devices (the named
  request-batch-axis → mesh-axis mapping of the pjit partition-rule
  exemplars, SNIPPETS [1][2]).

* Generator/reconstruct matrices stay device-resident keyed by
  signature in the shared ``ops/residency.py`` cache — a re-submitted
  geometry never re-transfers its matrix.

Protocol correctness is machine-checked FIRST
(``analysis/concurrency/models/batcher.py``, PR 10 convention): no
item dropped, none dispatched twice, no cross-signature padding,
shutdown drains or fails-retryable everything — each invariant proven
live by a seeded mutation pinned in tests/test_modelcheck.py.

Failure semantics: submissions carry the contextvar deadline Budget —
an item whose budget expires while queued is SHED with
``DeadlineExceeded`` at flush (a tick wait can never outlive the
request's admission budget), and a submitter's wait is clamped to its
budget.  A tick-thread death (or a close racing a submit) fails
queued items with the retryable ``BatcherClosed``; callers fall back
to the unchanged per-request dispatch plane.  That plane is the
default: the whole module is gated by ``MINIO_TPU_BATCHER`` (default
0, same convention as ``MINIO_TPU_WORKERS`` /
``MINIO_TPU_DATAPLANE_PIPELINE``) and kept as the differential
reference (tests/test_batcher_diff.py pins byte identity).
"""

from __future__ import annotations

import atexit
import os
import threading
import time

import numpy as np

from minio_tpu.storage import errors
from minio_tpu.utils import deadline as deadline_mod
from minio_tpu.utils import tracing

_TRUTHY = ("1", "on", "true", "yes")


def enabled() -> bool:
    """MINIO_TPU_BATCHER master switch (default 0 = per-request plane).
    Re-read per call so tests can flip it without rebuilding layers."""
    return os.environ.get(
        "MINIO_TPU_BATCHER", "0").lower() in _TRUTHY


def tick_seconds() -> float:
    """MINIO_TPU_BATCH_TICK_US: how long a tick window stays open for
    late coalescers after the first item arrives (default 250 us — two
    orders under a 1 MiB drive write, so the per-request plane's
    latency profile survives)."""
    try:
        return max(0.0, int(os.environ.get(
            "MINIO_TPU_BATCH_TICK_US", "250"))) / 1e6
    except ValueError:
        return 250 / 1e6


def max_batch_bytes() -> int:
    """MINIO_TPU_BATCH_MAX_BYTES: queued-payload watermark that closes
    the tick window early (default 64 MiB — twice the per-request
    plane's 32-block device batch)."""
    try:
        return max(1 << 20, int(os.environ.get(
            "MINIO_TPU_BATCH_MAX_BYTES", str(64 << 20))))
    except ValueError:
        return 64 << 20


def set_major_order(set_ids) -> np.ndarray:
    """Stable permutation grouping a tick batch's work items by erasure
    set id.

    The batcher concatenates same-geometry items from MANY erasure
    sets into one (B, K, S) tick batch; the mesh codec
    (parallel/mesh.py) shards B over the ``blocks`` mesh axis (the
    named request-batch-axis → mesh-axis mapping of the pjit
    partition-rule exemplars, SNIPPETS [1][2]).  Laying the batch out
    set-major means each device's contiguous block-row span covers as
    few erasure sets as possible, so a per-set span lands on (and
    returns from) the minimum number of devices — the
    sharding-by-erasure-set the tick batch rides.  Stability preserves
    submission order within a set, which keeps the split-back
    bookkeeping a pure cumulative-offset walk."""
    return np.argsort(np.asarray(set_ids, dtype=np.int64), kind="stable")


class BatcherClosed(errors.StorageError):
    """The batcher is closing/closed/dead, or its tick thread died with
    this item queued.  RETRYABLE: callers fall back to the per-request
    dispatch plane (the item was never resolved)."""


class _Item:
    __slots__ = ("sig", "batch", "dispatch", "budget", "set_id",
                 "event", "result", "error", "nbytes", "trace_ref",
                 "t_submit")

    def __init__(self, sig, batch, dispatch, set_id):
        self.sig = sig
        self.batch = batch
        self.dispatch = dispatch
        self.budget = deadline_mod.current()
        # span link: the submitting request's (trace, span) — the tick
        # thread records a batcher.tick span against it so a fused tick
        # shows up in EVERY request it served (ISSUE 12)
        self.trace_ref = tracing.current_ref()
        self.t_submit = time.perf_counter()
        self.set_id = set_id
        self.event = threading.Event()
        self.result = None
        self.error: BaseException | None = None
        self.nbytes = int(batch.nbytes)


class Batcher:
    """One tick thread + a geometry-bucketed submission queue."""

    def __init__(self):
        self._cv = threading.Condition()
        self._queue: list[_Item] = []
        self._queued_bytes = 0
        # items collected out of the queue for the in-flight tick: the
        # death handler must fail THESE too, or a fault between collect
        # and resolve strands their submitters forever (model action
        # t_crash fails queue AND bucket; mutation crash-loses-bucket
        # proves it live)
        self._inflight: list[_Item] = []
        self._phase = "run"  # run | closing | stopped | dead
        self.stats = {
            "ticks": 0,
            "dispatches": 0,
            "items": 0,
            "coalesced_items": 0,   # items that shared a dispatch
            "batched_bytes": 0,
            "shed_deadline": 0,
            "failed_retryable": 0,
            "dispatch_failures": 0,
            "deaths": 0,
            "max_items_per_tick": 0,
        }
        self._thread = deadline_mod.service_thread(
            self._tick_loop, name="erasure-batcher")

    # -- submission ---------------------------------------------------------
    def enqueue(self, sig, batch: np.ndarray, dispatch, set_id: int = 0
               ) -> np.ndarray:
        """Enqueue one (signature, (B, K, S) batch) work item and block
        for its rows of the fused result.  Raises BatcherClosed
        (retryable -> per-request fallback) or DeadlineExceeded."""
        return self.enqueue_async(sig, batch, dispatch, set_id)()

    def enqueue_async(self, sig, batch: np.ndarray, dispatch,
                     set_id: int = 0):
        """Non-blocking enqueue; returns ``resolve() -> np.ndarray``.
        The deadline Budget is captured HERE (submit time), so the tick
        wait is charged to the submitting request's budget."""
        it = _Item(sig, batch, dispatch, set_id)
        with self._cv:
            if self._phase != "run":
                raise BatcherClosed("erasure batcher is not accepting work")
            self._queue.append(it)
            self._queued_bytes += it.nbytes
            self.stats["items"] += 1
            self._cv.notify_all()

        def resolve() -> np.ndarray:
            # wait in small slices so an expired budget surfaces even
            # if the tick thread is wedged on another bucket; the flush
            # sheds the queued item on its side too
            while not it.event.wait(0.05):
                b = it.budget
                if b is not None and b.expired():
                    # give the flush one tick to post its verdict (it
                    # may already have resolved us)
                    if it.event.wait(max(0.01, 4 * tick_seconds())):
                        break
                    raise errors.DeadlineExceeded(
                        "erasure batch item outlived its budget in queue")
            if it.error is not None:
                raise it.error
            return it.result

        return resolve

    # -- tick thread --------------------------------------------------------
    def _collect(self) -> list[list[_Item]]:
        """Under the lock: take the whole queue, grouped by geometry
        signature in first-arrival order, each group CHUNKED at the
        byte watermark — a backlog that piled up behind a slow
        dispatch must not concatenate into one unbounded fused batch
        (peak-RAM doubling, device-memory blowout).  A single
        over-watermark item still dispatches alone."""
        by_sig: dict = {}
        for it in self._queue:
            by_sig.setdefault(it.sig, []).append(it)
        self._queue = []
        self._queued_bytes = 0
        cap = max_batch_bytes()
        buckets: list[list[_Item]] = []
        for group in by_sig.values():
            cur: list[_Item] = []
            cur_bytes = 0
            for it in group:
                if cur and cur_bytes + it.nbytes > cap:
                    buckets.append(cur)
                    cur, cur_bytes = [], 0
                cur.append(it)
                cur_bytes += it.nbytes
            if cur:
                buckets.append(cur)
        return buckets

    def _tick_loop(self) -> None:
        try:
            while True:
                with self._cv:
                    while not self._queue and self._phase == "run":
                        self._cv.wait()
                    if not self._queue:
                        break  # closing and drained
                    # tick window: wait for coalescers until the window
                    # closes or the byte watermark is crossed; closing
                    # flushes immediately (drain)
                    t_end = time.monotonic() + tick_seconds()
                    while self._phase == "run" \
                            and self._queued_bytes < max_batch_bytes():
                        left = t_end - time.monotonic()
                        if left <= 0:
                            break
                        self._cv.wait(left)
                    buckets = self._collect()
                    self._inflight = [it for b in buckets for it in b]
                    self.stats["ticks"] += 1
                    tick_no = self.stats["ticks"]
                    n_items = len(self._inflight)
                    if n_items > self.stats["max_items_per_tick"]:
                        self.stats["max_items_per_tick"] = n_items
                # dispatch OUTSIDE the lock: submitters keep enqueueing
                # the next tick while this one runs on the device
                for bucket in buckets:
                    self._flush_bucket(bucket, tick_no)
                with self._cv:
                    self._inflight = []
        except BaseException:
            with self._cv:
                self._phase = "dead"
                self.stats["deaths"] += 1
                stuck = self._queue + [
                    it for it in self._inflight if not it.event.is_set()]
                self._queue = []
                self._inflight = []
                self._queued_bytes = 0
                self.stats["failed_retryable"] += len(stuck)
            for it in stuck:
                it.error = BatcherClosed(
                    "erasure batcher tick thread died with this item "
                    "queued (retryable)")
                it.event.set()
            raise
        with self._cv:
            if self._phase != "dead":
                self._phase = "stopped"

    def _flush_bucket(self, bucket: list[_Item], tick_no: int = 0) -> None:
        """One geometry bucket -> at most one fused dispatch."""
        live: list[_Item] = []
        for it in bucket:
            if it.budget is not None and it.budget.expired():
                # deadline-expired-in-queue: shed, never dispatch (the
                # request already missed its admission budget)
                it.error = errors.DeadlineExceeded(
                    "erasure batch item shed: budget expired in queue")
                it.event.set()
                with self._cv:
                    self.stats["shed_deadline"] += 1
                continue
            live.append(it)
        if not live:
            return
        t_disp = time.perf_counter()
        try:
            if len(live) == 1:
                out = np.asarray(live[0].dispatch(live[0].batch))
                outs = [out]
            else:
                # set-major layout: the mesh codec shards the batch axis
                # over the mesh, so grouping rows by erasure set shards
                # the tick over the mesh BY SET (see set_major_order)
                order = set_major_order([it.set_id for it in live])
                live = [live[int(i)] for i in order]
                cat = np.concatenate([it.batch for it in live], axis=0)
                out = np.asarray(live[0].dispatch(cat))
                outs = []
                lo = 0
                for it in live:
                    b = it.batch.shape[0]
                    # copy, don't view: a view would keep the WHOLE
                    # fused output alive for as long as the slowest
                    # co-batched request holds its slice
                    outs.append(out[lo:lo + b].copy())
                    lo += b
            with self._cv:
                self.stats["dispatches"] += 1
                self.stats["batched_bytes"] += sum(
                    it.nbytes for it in live)
                if len(live) > 1:
                    self.stats["coalesced_items"] += len(live)
            # span links: the fused tick records itself into EVERY
            # served request's trace — which tick, how many co-batched
            # items, and how long the item waited in queue, so a slow
            # request can name its tick and its co-travellers
            dur = time.perf_counter() - t_disp
            for it in live:
                if it.trace_ref is not None:
                    tracing.record_span(
                        it.trace_ref, "batcher.tick", dur,
                        tick=tick_no, kind=str(it.sig[0]),
                        items=len(live),
                        wait_ms=round(
                            (t_disp - it.t_submit) * 1e3, 3))
            for it, rows in zip(live, outs):
                it.result = rows
                it.event.set()
        except BaseException as ex:
            # a failed fused program fails every item in the bucket
            # RETRYABLE — each caller re-dispatches per-request (model
            # action t_dispatch_fail)
            with self._cv:
                self.stats["dispatch_failures"] += 1
                self.stats["failed_retryable"] += len(live)
            err = BatcherClosed(
                f"fused batch dispatch failed (retryable): "
                f"{type(ex).__name__}: {ex}")
            for it in live:
                it.error = err
                it.event.set()

    # -- lifecycle ----------------------------------------------------------
    def alive(self) -> bool:
        with self._cv:
            return self._phase == "run"

    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def stats_snapshot(self) -> dict:
        with self._cv:
            snap = dict(self.stats)
            snap["queue_depth"] = len(self._queue)
            snap["phase"] = self._phase
        return snap

    def close(self, timeout: float = 10.0) -> None:
        """Quiesce: stop accepting work, drain the queue (every queued
        item dispatches or fails retryable — model terminal invariant
        ``no-item-dropped``), join the tick thread.

        If the tick thread fails to drain within `timeout` (a wedged
        fused dispatch on a hung device), the remaining queued items
        are force-failed retryable HERE — a budget-less submitter must
        not wait forever on work unrelated to the hung dispatch."""
        with self._cv:
            if self._phase == "run":
                self._phase = "closing"
            self._cv.notify_all()
        self._thread.join(timeout)
        if not self._thread.is_alive():
            return
        with self._cv:
            self._phase = "dead"
            stuck = self._queue + [
                it for it in self._inflight if not it.event.is_set()]
            self._queue = []
            self._inflight = []
            self._queued_bytes = 0
            self.stats["failed_retryable"] += len(stuck)
        for it in stuck:
            it.error = BatcherClosed(
                "erasure batcher quiesce timed out with this item "
                "queued (retryable)")
            it.event.set()


# -- process-wide singleton --------------------------------------------------
# held in dicts mutated in place: each process (HTTP front, data-plane
# worker) owns its own batcher — the per-process "node batcher".
# `_retired` accumulates the counters of replaced/closed batchers so a
# tick-thread death is never erased from the metrics by its respawn.
_holder: dict = {"batcher": None}
_retired: dict = {}
_holder_mu = threading.Lock()


def _fold_stats(dst: dict, src: dict) -> None:
    """Fold one stats snapshot into an aggregate: int counters sum,
    high-watermarks take the max, non-ints (phase) pass through —
    ONE definition shared by retirement and stats_snapshot so a new
    stat cannot silently mis-aggregate across respawns."""
    for k, v in src.items():
        if isinstance(v, int):
            if k == "max_items_per_tick":
                dst[k] = max(dst.get(k, 0), v)
            else:
                dst[k] = dst.get(k, 0) + v
        else:
            dst[k] = v


def _retire_locked(b: "Batcher") -> None:
    snap = b.stats_snapshot()
    snap.pop("phase", None)  # a retired batcher has no live phase
    snap.pop("queue_depth", None)
    _fold_stats(_retired, snap)


def get(create: bool = True) -> Batcher | None:
    """The process-wide batcher when the gate is on; None when off.  A
    dead batcher (tick-thread crash) is replaced on the next call, so
    one fault degrades exactly the items it had queued."""
    if not enabled():
        return None
    dead = None
    with _holder_mu:
        b = _holder["batcher"]
        if b is not None and b.alive():
            return b
        if not create:
            return None
        dead = b
        if dead is not None:
            _retire_locked(dead)
        b = Batcher()
        _holder["batcher"] = b
    if dead is not None:
        dead.close(timeout=1.0)
    return b


def shutdown() -> None:
    """Quiesce and drop the process batcher (S3Server/worker teardown,
    conftest, atexit); safe to call repeatedly."""
    with _holder_mu:
        b, _holder["batcher"] = _holder["batcher"], None
    if b is not None:
        b.close()  # drain first: the drain's dispatches count too
        with _holder_mu:
            _retire_locked(b)


def stats_snapshot() -> dict | None:
    """Counters of the live batcher folded with every retired one, or
    None when none was ever created in this process (metrics skip the
    family)."""
    with _holder_mu:
        b = _holder["batcher"]
        if b is None and not _retired:
            return None
        snap = dict(_retired) if _retired else {}
    live = b.stats_snapshot() if b is not None else {
        "queue_depth": 0, "phase": "stopped"}
    _fold_stats(snap, live)
    snap.setdefault("phase", "stopped")
    return snap


atexit.register(shutdown)
