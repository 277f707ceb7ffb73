"""Per-stage wall-time accounting for the object data plane.

Every stage of the PUT/GET pipeline (stream read, etag folding, erasure
encode, bitrot hash, shard write, shard decode, response hand-off) folds
its elapsed seconds in here, so the remaining gap between codec speed and
client-visible throughput is attributable instead of argued about.
Exposed as `minio_dataplane_stage_seconds_total{stage=...}` by
server/metrics.py and consumed by bench.py's object-layer breakdown.

Stages overlap by design (the hasher folds batch N while the main thread
encodes N+1 and the pool writes N-1), so the per-stage sum may exceed the
pipeline's wall time — that is the point: a sum well above wall proves
overlap, a stage near wall names the bottleneck.
"""

from __future__ import annotations

import threading
import time

from minio_tpu.utils import tracing

# "fused_hash" books the frame-hash plane when MINIO_TPU_FUSED_HASH
# folds it into the encode program (erasure/coding.py): on the device
# path the bytes land here with ~zero seconds (the hash rides the encode
# launch — one pass is the point); on the host fallback it carries the
# tiled hash leg's real seconds so fused vs legacy "hash" stays
# attributable.
STAGES = ("read", "etag", "encode", "hash", "fused_hash", "write",
          "decode", "respond")

_lock = threading.Lock()
_seconds = {s: 0.0 for s in STAGES}
_bytes = {s: 0 for s in STAGES}


def add(stage: str, seconds: float, nbytes: int = 0) -> None:
    """Fold one timed span into a stage (thread-safe; stages are bumped
    from pool workers, hasher tasks and the main encode thread alike).

    When a request trace is ambient (utils/tracing.py rides the copied
    context into the same pool threads), the fold ALSO attributes to
    that trace — per-request read/etag/encode/hash/write/decode
    seconds, not just the global totals (ISSUE 12)."""
    with _lock:
        _seconds[stage] += seconds
        _bytes[stage] += nbytes
    tr = tracing.current_trace()
    if tr is not None:
        tr.add_stage(stage, seconds)


class timed:
    """`with timed("write", n): ...` — time a span into a stage."""

    __slots__ = ("stage", "nbytes", "_t0")

    def __init__(self, stage: str, nbytes: int = 0):
        self.stage = stage
        self.nbytes = nbytes

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        add(self.stage, time.perf_counter() - self._t0, self.nbytes)
        return False


def snapshot() -> dict[str, dict[str, float]]:
    """{stage: {"seconds": s, "bytes": n}} — copied under the lock so a
    metrics render never sees a half-updated row."""
    with _lock:
        return {s: {"seconds": _seconds[s], "bytes": _bytes[s]}
                for s in STAGES}


def delta(before: dict, after: dict) -> dict[str, float]:
    """Per-stage seconds between two snapshots (bench attribution)."""
    return {s: after[s]["seconds"] - before[s]["seconds"] for s in STAGES}
