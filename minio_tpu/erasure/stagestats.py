"""The leaf-span primitive of the served path: one timed interval, three
sinks.

`with timed(stage, nbytes): ...` around a piece of the PUT/GET pipeline

1. folds the interval into the process-wide per-stage counters:
   *thread-seconds* of work (summed over every thread that was inside
   the stage, so overlapping threads add up past wall time), bytes, a
   *wall-time* union (the clock runs while at least one thread is
   inside the stage), and the *CPU seconds* of those threads inside it
   (each thread's own CPU clock, `time.thread_time`, read beside the
   wall clock: thread-seconds less CPU seconds is the time the stage
   stood still, waiting for the interpreter lock, a core, a drive or a
   socket; an estimate from one interval in `CPU_EVERY`, below).
   server/metrics.py exports them as
   `minio_dataplane_stage_{seconds,bytes,wall_seconds}_total{stage}`,
   the CPU seconds as the row `<stage>_cpu` of the seconds family
   (`seconds_rows`); the benchmark's `program_counter` metrics
   (benchmark/readers/stage_*) read the seconds and the bytes;
2. lies in the profiler's trace as `dp.<stage>` for the same interval
   (`jax.profiler.TraceAnnotation`), on the clock of the device's own
   lines, so a traced run can name what the host did while the chip was
   idle (benchmark/trace.py `idle_gaps`).  The profiler being started is
   the only switch; an inactive annotation costs well under a
   microsecond;
3. where a request trace is ambient (utils/tracing.py rides the copied
   context into the pool threads), becomes a `dp.<stage>` child span of
   it and folds into its per-request stage seconds.

Stages overlap by design (the hasher folds batch N while the main thread
encodes N+1 and the pool writes N-1), so thread-seconds summed over
stages may exceed the pipeline's wall time: a sum well above wall proves
overlap, a stage whose wall time nears the request's names the
bottleneck.

Leaves only in the profiler.  The trace's reduction names an idle gap by
the span that overlaps it most, so a span that wraps other spans would
hide them.  `PARENTS` enclose other stages (`decode` = read_wait +
assemble + the codec's leaves on the stream's own thread; `encode` =
host_codec, or h2d + launch + fetch): they keep their counters and their
per-request seconds and write no span.  `add()` books a reading taken
elsewhere (the admission wait, a compile's duration, bytes that arrived,
a hop between two threads, which no one thread's span can hold) into
the counters alone; so does the body pipe for what it waited and worked
inside `read`, once a call and not once a chunk, and `dp.read` stays the
one span of a batch's body (thousands of chunk-long spans would name no
gap and fill a request's tree).  A stage that only `add()` books
(`ADD_ONLY`) has no CPU seconds: nobody was inside it.
"""

from __future__ import annotations

import contextlib
import random
import sys
import threading
import time

from minio_tpu.utils import tracing

STAGES = (
    # PUT: body read, etag fold, erasure encode (parent), frame hash,
    # shard write; GET: decode (parent), hand-over to the HTTP front
    # (`respond` is the decode thread's put into the sink's queue, not
    # the response: that is `send`)
    "read", "etag", "encode", "hash", "write", "decode", "respond",
    # GET / heal: the stream's thread waiting for its k shards; in the
    # I/O pool, a drive's bytes arriving and their frame check
    "read_wait", "shard_read", "verify",
    # host copies around a codec dispatch; the dispatch itself: hand-over
    # to the device, the jit call until it returns, waiting for the
    # device plus read-back; or the host codec's own compute
    "assemble", "h2d", "launch", "fetch", "host_codec",
    # a response body's piece written to the connection's socket by the
    # executor thread that pulled it (server/app.py _BodySender), once a
    # piece: the copy into the socket and the waits for the reader.
    # Bytes the event loop wrote (TLS, chunked, a reader that stalled)
    # book nothing: over `respond`'s bytes 1 where every served byte
    # left from a worker
    "send",
    # bytes a drive's read put straight into a dispatch's staging arena
    # (erasure/bitrot.py read_blocks(out=)), so that no host copy stands
    # between the read and the device (counter only, no seconds of its
    # own: shard_read and verify hold them)
    "staged",
    # of those, the bytes that one native call a shard's group read,
    # placed and checked (erasure/bitrot.py _read_native: a local shard
    # file's descriptor); the rest took the Python reads.  Counter only:
    # the call's seconds are shard_read's, its hash's also verify's
    "native_read",
    # what exists only because a shard is no multiple of the kernel's
    # tile or k does not divide the block: zero columns added, made rows
    # cut back, a shard's fill dropped, with the bytes that passed.
    # Seconds only where the host copies or zero-fills (a PUT's split of
    # blocks that k does not divide, the zero columns that widen an
    # object's partial block to the full shard width); where the
    # dispatch program widens on the device or another copy takes the
    # fill along, the bytes alone
    "pad",
    # the real bytes (k shards of ceil(partial / k) bytes, not the
    # zero columns that widen them) of an object's partial last block
    # that a device dispatch coded, inside its object's last group of
    # full blocks where it has one (erasure/coding.py dispatch_groups):
    # a degraded GET's, a PUT's, a heal's or a repair's.  Counter only:
    # the group's stages hold the seconds
    "tail",
    # input bytes a single-chip dispatch carried beyond its own g
    # blocks, because the program is compiled at a few batch sizes only
    # (erasure/coding.py DEVICE_BATCH_SIZES): (size - g) * k * S, what
    # the carrier's buffer last held.  Counter only: the transfer's
    # seconds are in h2d, the program's on the device
    "batch_fill",
    # bytes of a GET's response blocks that came from the arena pool,
    # their pages there already (erasure/coding.py _block_acquire); a
    # block on a fresh array books nothing.  Counter only: over
    # `respond`'s bytes 1 where every group of full blocks hit the pool,
    # 0 where none did or the objects have no full block
    "block_reuse",
    # quorum write / read of xl.meta (a read's bytes: the documents its
    # answers were parsed from); signature + policy; admission wait
    "commit", "meta_read", "auth", "admit",
    # one drive's xl.meta that `read_version` read in one native call
    # (storage/local.py _read_meta): the call's own seconds, open to
    # close, timed inside it, and the document's bytes; a process
    # without the library books nothing.  Counter only: over
    # `meta_read`'s bytes 1 where every answer took the call
    "meta_native",
    # inside `read`, the HTTP front's body pipe (server/app.py
    # _QueuePipeReader; counters only, booked once a call): waiting for
    # the socket's next chunk; the pipe's own work, with the bytes it
    # moved (over `read`'s bytes: the copies per body byte)
    "body_wait", "body_copy",
    # seconds in XLA compilation (counter only: benchmark/trace.py counts
    # every host span with "compile" in its name as a compilation)
    "compile",
    # of those, the seconds of a compilation that ran on any thread but
    # a device self-test's (boot's, the warm-up thread's): what a
    # request waited for.  0 is the only sound reading.  Counter only
    "compile_wait",
    # bytes a dispatch coded on the host codec only because its
    # geometry's device programs were not there yet
    # (erasure/coding.py _DeviceCodec.ready): the upgraded parity of a
    # PUT to a set that has just lost drives, until the background
    # self-test has passed.  Counter only: 0 in a window that began
    # ready
    "warming",
    # where a request stands still between stages (ISSUE 37).  The hops,
    # counters only since each crosses threads: from the event loop's
    # hand-over to the executor until the job's first line on an
    # `s3-api` thread; from the job's last line there until the
    # coroutine's next line on the loop (server/app.py _hop); from a
    # task's submit to the shared I/O pool until its first line on a
    # `shard-io` thread, once a task (erasure/coding.py io_submit)
    "exec_wait", "loop_wait", "pool_wait",
    # leaves: the namespace lock's wait (erasure/objects.py _LockCtx);
    # the round that opens a part's shard files, readers before a GET's
    # first group, writers before a PUT's; a PUT's own thread waiting
    # for the pool's shard writes (encode_stream under slot pressure and
    # its last drain: read_wait's mirror); a PUT's shard writers closed
    # after its last group, on its own thread one drive after another:
    # each file's last flush and its fdatasync
    "ns_lock", "open", "write_wait", "close",
    # the handler's whole time, admission included, with the bytes of
    # the request's and the response's bodies (server/app.py _handle):
    # what the per-request stages are subtracted from
    "request",
)
PARENTS = frozenset(("encode", "decode", "request"))
# booked by add() alone: readings taken elsewhere, with no thread inside
ADD_ONLY = frozenset((
    "staged", "native_read", "tail", "batch_fill", "block_reuse", "admit",
    "body_wait", "body_copy", "compile", "compile_wait", "warming",
    "exec_wait", "loop_wait", "pool_wait", "request", "meta_native"))
# booked by timed(): the leaves and the parents that enclose them
TIMED = tuple(s for s in STAGES if s not in ADD_ONLY)

# The CPU clock is read around one interval in CPU_EVERY, drawn at
# random, and what it reads counts CPU_EVERY-fold: an unbiased estimate
# that adds up over scrapes.  A thread's CPU clock has no fast path on
# the chip's sandboxed host: 6.2 us a read against 0.09 for the wall
# clock, in 10 ms ticks, and read around every interval it cost the
# served path 4-13% end to end (PERF.md section 6, PR 37).  Over a
# window's thousands of intervals the estimate is good to 10-20%; it
# says nothing of one interval.
CPU_EVERY = 16

_lock = threading.Lock()
_seconds = {s: 0.0 for s in STAGES}
_bytes = {s: 0 for s in STAGES}
_wall = {s: 0.0 for s in STAGES}
_inside = {s: 0 for s in STAGES}    # threads inside the stage now
_since = {s: 0.0 for s in STAGES}   # when _inside last left 0
_cpu = {s: 0.0 for s in TIMED}      # CPU seconds of the threads inside
# threads whose whole CPU time is a row of the seconds family:
# {row: the thread's CPU clock id}
_watched: dict[str, int] = {}

# what a leaf is called in the profiler's trace and in a request's tree
_SPAN_NAMES = {s: "dp." + s for s in STAGES if s not in PARENTS}
_NO_SPAN = contextlib.nullcontext()


def annotation(name: str):
    """A context manager that lays `name` into the profiler's trace for
    its block (`jax.profiler.TraceAnnotation`; no keyword arguments, so
    the name comes out of the trace as written).  JAX is never imported
    for this: `import jax` loads `jax.profiler`, only code that has
    imported JAX can have started a profiler, and data-plane workers on
    the host codec never do."""
    profiler = sys.modules.get("jax.profiler")
    # no attribute yet: JAX is being imported on another thread right now
    span = getattr(profiler, "TraceAnnotation", None)
    return _NO_SPAN if span is None else span(name)


def add(stage: str, seconds: float, nbytes: int = 0) -> None:
    """Fold one reading into a stage's counters (thread-safe; stages are
    bumped from pool workers, hasher tasks and the main encode thread
    alike), and into the ambient request trace's per-request stage
    seconds (ISSUE 12).  No span, no wall time: for readings taken
    elsewhere."""
    with _lock:
        _seconds[stage] += seconds
        _bytes[stage] += nbytes
    tr = tracing.current_trace()
    if tr is not None:
        tr.add_stage(stage, seconds)


class timed:
    """`with timed("write", n): ...` — one interval into all three sinks
    (module docstring)."""

    __slots__ = ("stage", "nbytes", "_t0", "_c0", "_span")

    def __init__(self, stage: str, nbytes: int = 0):
        self.stage = stage
        self.nbytes = nbytes

    def __enter__(self):
        stage = self.stage
        name = _SPAN_NAMES.get(stage)  # None: a parent, counters only
        self._span = None if name is None else annotation(name)
        if name is not None:
            self._span.__enter__()
        self._t0 = t0 = time.perf_counter()
        with _lock:
            if not _inside[stage]:
                _since[stage] = t0
            _inside[stage] += 1
        self._c0 = time.thread_time() \
            if random.random() * CPU_EVERY < 1.0 else None
        return self

    def __exit__(self, *exc) -> bool:
        stage = self.stage
        cpu = 0.0 if self._c0 is None \
            else (time.thread_time() - self._c0) * CPU_EVERY
        t1 = time.perf_counter()
        dt = t1 - self._t0
        with _lock:
            _seconds[stage] += dt
            _cpu[stage] += cpu
            _bytes[stage] += self.nbytes
            _inside[stage] -= 1
            if not _inside[stage]:
                _wall[stage] += t1 - _since[stage]
        if self._span is not None:
            self._span.__exit__(None, None, None)
        ref = tracing.current_ref()
        if ref is not None:
            ref[0].add_stage(stage, dt)
            if self._span is not None:
                tracing.record_span(ref, _SPAN_NAMES[stage], dt)
        return False


def snapshot() -> dict[str, dict[str, float]]:
    """{stage: {"seconds": thread-seconds, "bytes": n, "wall": s}, and
    "cpu": estimated CPU seconds for a stage `timed()` books} — copied
    under the lock so a metrics render never sees a half-updated row.
    An interval counts once it has ended."""
    with _lock:
        snap = {s: {"seconds": _seconds[s], "bytes": _bytes[s],
                    "wall": _wall[s]} for s in STAGES}
        for s, cpu in _cpu.items():
            snap[s]["cpu"] = cpu
    return snap


def watch_thread(row: str, ident: int) -> None:
    """From now on `seconds_rows()[row]` is the CPU seconds of thread
    `ident` (the event loop's: one thread that every request crosses and
    no stage is inside of)."""
    _watched[row] = time.pthread_getcpuclockid(ident)


def seconds_rows(snap: dict | None = None) -> dict[str, float]:
    """Every row of the seconds family by its `stage` label: each
    stage's thread-seconds, `<stage>_cpu` for each stage `timed()` books
    (none for one that `add()` alone books), and the watched threads'
    CPU clocks, read now."""
    snap = snapshot() if snap is None else snap
    rows = {s: d["seconds"] for s, d in snap.items()}
    rows.update((s + "_cpu", snap[s]["cpu"]) for s in TIMED)
    for row, clock in list(_watched.items()):
        try:
            rows[row] = time.clock_gettime(clock)
        except OSError:  # the thread has ended: no row
            pass
    return rows


def delta(before: dict, after: dict) -> dict[str, float]:
    """Per-stage thread-seconds between two snapshots."""
    return {s: after[s]["seconds"] - before[s]["seconds"] for s in STAGES}
