"""Bandwidth-optimal repair: sub-shard Reed-Solomon reconstruction.

Heal today rebuilds a damaged shard by reading k FULL surviving shards
(Erasure.heal) — the right call for a wiped drive: every byte column of
plain RS is an independent (n, k) MDS codeword, so ANY exact rebuild of
a fully-lost shard must read >= k bytes per rebuilt byte.  Sub-k
"repair bandwidth" schemes either change the on-disk code (piggyback /
regenerating constructions) or ship GF(2) sub-symbols that only win for
n - k >= 16 — which no legal (k <= 16, m <= 8) geometry here reaches
("Practical Considerations in Repairing Reed-Solomon Codes", arxiv
2205.11015).  But the common heal trigger in a real fleet is NOT a
wiped drive: it is a shard with *partial* damage — bitrot in a few
frames, a torn tail from an interrupted write, latent sector errors.
For those, the bitrot frame hashes locate the damage exactly without
touching any survivor, and only the damaged block columns need the
k-wide read.

The subsystem is a planner + executor:

* ``plan_repair`` prices full-shard vs sub-shard repair from a residual
  map of the target's existing shard file (``scan_residual``: frame
  hashes only, streaming, constant memory), honors the
  ``MINIO_TPU_REPAIR_SCHEME`` operator override (``full`` keeps the
  legacy path selectable, ``subshard`` forces the ranged executor), and
  picks the k helper survivors, local drives first.

* ``repair_matrix`` builds the per-(helpers, lost) repair rows from the
  dual-codeword (syndrome/Lagrange) closed form — one O(k^2) row per
  lost shard instead of a k x k Gauss-Jordan inversion ("Efficient
  erasure decoding of Reed-Solomon codes", arxiv 0901.1886) — LRU-cached
  like the device codecs' reconstruct-matrix caches.

* ``execute_subshard`` makes one forward pass: it re-verifies the
  target's frames batch by batch (the residual map is a *pricing*
  input, never a correctness input), reads ONLY the damaged block
  columns from the helpers (ranged ``BitrotReader`` frame-group reads;
  remote shard streams re-issue their ranged RPC instead of draining,
  so survivors ship only the planned fraction), rebuilds them as
  batched GF(2^8) matmuls through the configured codec backend
  (single-chip / mesh via ``Erasure._device``, the cached dual-codeword
  row matmul on host), and restages a byte-identical shard file.  Any
  mid-repair failure — a helper or target dying, fresh corruption —
  raises ``SubshardAbort`` and the caller falls back to the full-shard
  decode, so heal always converges.

Byte accounting: ``CountingReader`` wraps every survivor reader in both
schemes and feeds ``repair_stats`` (surfaced as
``minio_repair_bytes_read_total{scheme=}`` and
``minio_repair_plans_total{scheme=}`` by server/metrics.py).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field

import numpy as np

from minio_tpu.ops import gf256, residency
from minio_tpu.utils import tracing
from . import bitrot
from . import coding as coding_mod

# ---------------------------------------------------------------- stats
# read by server/metrics.py

_stats_mu = threading.Lock()
repair_stats = {
    "full": {"plans": 0, "bytes_read": 0},
    "subshard": {"plans": 0, "bytes_read": 0},
    "fallbacks": 0,
    "target_scan_bytes": 0,
}


def _add_plan(scheme: str) -> None:
    with _stats_mu:
        repair_stats[scheme]["plans"] += 1


def add_read(scheme: str, nbytes: int) -> None:
    with _stats_mu:
        repair_stats[scheme]["bytes_read"] += nbytes


def _add_scan(nbytes: int) -> None:
    with _stats_mu:
        repair_stats["target_scan_bytes"] += nbytes


def note_fallback() -> None:
    with _stats_mu:
        repair_stats["fallbacks"] += 1
    tracing.event("repair.fallback")


def stats_snapshot() -> dict:
    with _stats_mu:
        return {
            "full": dict(repair_stats["full"]),
            "subshard": dict(repair_stats["subshard"]),
            "fallbacks": repair_stats["fallbacks"],
            "target_scan_bytes": repair_stats["target_scan_bytes"],
        }


def reset_stats() -> None:
    """Test/bench hook: zero the counters."""
    with _stats_mu:
        repair_stats["full"] = {"plans": 0, "bytes_read": 0}
        repair_stats["subshard"] = {"plans": 0, "bytes_read": 0}
        repair_stats["fallbacks"] = 0
        repair_stats["target_scan_bytes"] = 0


# ------------------------------------------------------------- controls

SCHEME_ENV = "MINIO_TPU_REPAIR_SCHEME"


def scheme_override() -> str:
    """Operator override: "" (auto) | "full" | "subshard"."""
    v = os.environ.get(SCHEME_ENV, "").strip().lower()
    return v if v in ("full", "subshard") else ""


def _max_subshard_frac() -> float:
    """Damaged-block fraction above which the ranged repair stops
    paying (its reads converge on the full-shard read while still
    paying the residual scan)."""
    try:
        return float(os.environ.get(
            "MINIO_TPU_REPAIR_SUBSHARD_MAX_FRAC", "0.9"))
    except ValueError:
        return 0.9


class SubshardAbort(Exception):
    """Sub-shard repair cannot complete (helper/target death, fresh
    corruption): the caller discards the partial staging and falls
    back to the full-shard decode."""


# -------------------------------------------- repair matrices (cached)
# The codec's systematic-Vandermonde code is the evaluation code
# {(f(0), ..., f(n-1)) : deg f < k} over GF(2^8) (gf256.coding_matrix is
# V @ inv(V_top), so codewords are evaluations of arbitrary degree-<k
# polynomials).  For any k+1 distinct points A, the Lagrange
# denominators u_i = 1 / prod_{l != i} (alpha_i - alpha_l) form a
# dual-code row supported exactly on A: sum_{i in A} u_i f(alpha_i) = 0.
# Rebuilding lost symbol j from helpers H (|H| = k) is therefore the
# single row  f(alpha_j) = sum_{i in H} (u_i / u_j) f(alpha_i)  — no
# k x k inversion, and identical to gf256.reconstruct_matrix's rows
# (pinned by tests/test_repair_diff.py and the sanitizer replay).

def _dual_coeffs(points: tuple[int, ...]) -> dict[int, int]:
    """Lagrange denominators u_i over the evaluation points alpha_i = i
    (GF(2^8) subtraction is XOR)."""
    u: dict[int, int] = {}
    for i in points:
        prod = 1
        for l in points:
            if l != i:
                prod = int(gf256.MUL_TABLE[prod, i ^ l])
        u[i] = gf256.gf_inv(prod)
    return u


def repair_matrix(k: int, m: int, helpers: tuple[int, ...],
                  lost: tuple[int, ...]) -> np.ndarray:
    """(len(lost), k) GF(2^8) matrix: lost_t = sum_i M[t, i] * helper_i.

    ``helpers`` are exactly k distinct surviving shard indices sorted
    ascending; ``lost`` the shard indices to rebuild (data or parity,
    disjoint from helpers).  Rows live in the shared signature-keyed
    matrix residency (ops/residency.py) — ONE LRU-bounded, hit/miss-
    counted cache with the device codecs' encode/reconstruct matrices,
    so steady-state heals (one drive down -> one signature) never
    rebuild rows on any call path.
    """
    helpers = tuple(helpers)
    lost = tuple(lost)
    if len(helpers) != k or len(set(helpers)) != k:
        raise ValueError(f"need exactly {k} distinct helpers")
    if set(helpers) & set(lost):
        raise ValueError("helpers and lost shards overlap")
    n = k + m
    if any(not 0 <= i < n for i in helpers + lost):
        raise ValueError("shard index out of range")

    def build() -> np.ndarray:
        mat = np.zeros((len(lost), k), dtype=np.uint8)
        for t, j in enumerate(lost):
            u = _dual_coeffs(helpers + (j,))
            uj_inv = gf256.gf_inv(u[j])
            for c, i in enumerate(helpers):
                mat[t, c] = gf256.MUL_TABLE[u[i], uj_inv]
        mat.setflags(write=False)
        return mat

    return residency.matrices.get(
        ("repair-host", k, m, helpers, lost), build)


# ------------------------------------------------------- residual scan


@dataclass
class ResidualMap:
    """Which blocks of a target's existing shard file still verify."""

    nblocks: int
    good: np.ndarray               # (nblocks,) bool
    scanned_bytes: int = 0

    @property
    def bad_fraction(self) -> float:
        if not self.nblocks:
            return 1.0
        return float((~self.good).sum()) / self.nblocks


def _block_groups(till: int, shard_size: int, group: int):
    """Yield (block0, nblocks, block_len) runs of uniform frame length
    covering logical bytes [0, till): full blocks in groups of up to
    ``group``, then the short tail block alone."""
    if till <= 0:
        return
    nfull = till // shard_size
    b = 0
    while b < nfull:
        g = min(group, nfull - b)
        yield b, g, shard_size
        b += g
    tail = till - nfull * shard_size
    if tail:
        yield nfull, 1, tail


def _read_full(stream, want: int) -> bytes:
    """Read up to ``want`` bytes; a short return means EOF or a drive
    error mid-read (callers treat what arrived as the usable prefix —
    scan_residual classifies its complete frames, the executor drops
    the stream for the rest of the pass)."""
    chunks = []
    got = 0
    while got < want:
        try:
            data = stream.read(want - got)
        except Exception:
            break
        if not data:
            break
        chunks.append(data)
        got += len(data)
    return b"".join(chunks)


def _verify_frames(arr: np.ndarray, hsize: int, algo: str) -> np.ndarray:
    """Per-row bool: does each [hash|block] frame's payload hash to its
    recorded hash?  One batched C call for the HighwayHash algorithms."""
    hashes = arr[:, :hsize]
    payload = arr[:, hsize:]
    if algo in ("highwayhash256S", "highwayhash256"):
        try:
            from minio_tpu.ops import host as hostops

            return (hostops.hh256_batch(payload) == hashes).all(axis=1)
        except RuntimeError:
            pass
    hash_fn, _ = bitrot.hasher_of(algo)
    return np.array(
        [hash_fn(payload[i].data) == hashes[i].tobytes()
         for i in range(arr.shape[0])], dtype=bool)


def scan_residual(stream, till: int, shard_size: int,
                  algo: str = bitrot.DEFAULT_ALGO,
                  group: int = 64) -> ResidualMap:
    """Planner pass over a target's EXISTING shard file: classify each
    block good/bad by its interleaved frame hash, streaming with
    constant memory.  Truncation and read errors mark the remaining
    blocks bad — a residual map can only under-claim.  The executor
    re-verifies every frame it reuses, so this is a *pricing* input,
    never a correctness input."""
    _, hsize = bitrot.hasher_of(algo)
    nblocks = -(-till // shard_size) if till > 0 else 0
    good = np.zeros(nblocks, dtype=bool)
    scanned = 0
    try:
        for b0, g, blen in _block_groups(till, shard_size, group):
            want = g * (hsize + blen)
            raw = _read_full(stream, want)
            scanned += len(raw)
            # classify every COMPLETE frame received even on a short
            # read: a torn tail must not condemn the group's good prefix
            # (that would price a near-full rebuild for a tail-truncated
            # shard file)
            gg = len(raw) // (hsize + blen)
            if gg:
                arr = np.frombuffer(
                    raw[: gg * (hsize + blen)], dtype=np.uint8
                ).reshape(gg, hsize + blen)
                good[b0:b0 + gg] = _verify_frames(arr, hsize, algo)
            if len(raw) != want:
                break  # truncated: the rest stays bad
    except Exception:
        pass  # drive error mid-scan: remaining blocks stay bad
    _add_scan(scanned)
    return ResidualMap(nblocks=nblocks, good=good, scanned_bytes=scanned)


# -------------------------------------------------------------- planner


@dataclass
class RepairPlan:
    scheme: str                      # "full" | "subshard"
    k: int
    m: int
    shard_size: int
    till: int                        # logical shard bytes per target
    algo: str
    lost: tuple[int, ...]
    helpers: tuple[int, ...]         # sorted ascending, exactly k
    bad_blocks: np.ndarray | None    # union bad mask over targets
    residuals: dict = field(default_factory=dict)
    est_bytes_full: int = 0          # frame bytes (hash interleave incl.)
    est_bytes_sub: int = 0
    forced: bool = False             # env override made the choice


def plan_repair(e, lost, survivors, part_size: int,
                residuals: dict[int, ResidualMap] | None = None,
                local: set[int] | None = None,
                algo: str = bitrot.DEFAULT_ALGO,
                override: str | None = None) -> RepairPlan:
    """Choose full-shard decode vs ranged sub-shard repair for one part.

    ``lost``: stale shard indices to rebuild; ``survivors``: healthy
    shard indices (>= k of them); ``residuals``: per-target
    ``scan_residual`` maps — targets without one (wiped drives, stale
    versions) force the full decode.  ``local`` marks shard indices
    whose drive is node-local: the planner prefers local helpers since
    ranged reads cost a re-issued RPC per run on remote drives.
    """
    lost = tuple(sorted(lost))
    residuals = residuals or {}
    till = e.shard_file_size(part_size)
    nblocks = -(-till // e.shard_size) if till > 0 else 0
    _, hsize = bitrot.hasher_of(algo)

    surv = [i for i in survivors if i not in lost]
    if local:
        surv.sort(key=lambda i: (0 if i in local else 1, i))
    helpers = tuple(sorted(surv[:e.k]))

    ov = scheme_override() if override is None else override
    lens = np.full(nblocks, e.shard_size, dtype=np.int64)
    if nblocks and till % e.shard_size:
        lens[-1] = till % e.shard_size
    est_full = e.k * (till + nblocks * hsize)

    eligible = (nblocks > 0 and len(helpers) == e.k
                and all(i in residuals for i in lost)
                and all(residuals[i].nblocks == nblocks for i in lost))
    bad = None
    est_sub = est_full
    if eligible:
        bad = np.zeros(nblocks, dtype=bool)
        for i in lost:
            bad |= ~residuals[i].good
        est_sub = int(e.k * ((lens[bad]).sum() + int(bad.sum()) * hsize))

    if ov == "full":
        scheme = "full"
    elif ov == "subshard":
        # forced: degenerate to an all-bad plan when no residual exists
        # (every block rebuilt from helpers — still byte-identical)
        scheme = "subshard"
        if bad is None:
            bad = np.ones(nblocks, dtype=bool)
            est_sub = est_full
    elif (eligible and bad is not None
            and float(bad.mean() if nblocks else 1.0) <= _max_subshard_frac()
            and est_sub < est_full):
        scheme = "subshard"
    else:
        scheme = "full"

    _add_plan(scheme)
    # trace mark: the planner's verdict with its pricing, so a heal
    # span shows WHY it read the bytes it read (ISSUE 12)
    tracing.event("repair.plan", scheme=scheme,
                  est_bytes_full=int(est_full),
                  est_bytes_sub=int(est_sub), forced=bool(ov))
    return RepairPlan(
        scheme=scheme, k=e.k, m=e.m, shard_size=e.shard_size, till=till,
        algo=algo, lost=lost, helpers=helpers,
        bad_blocks=bad if scheme == "subshard" else None,
        residuals=dict(residuals), est_bytes_full=est_full,
        est_bytes_sub=est_sub, forced=bool(ov))


# ------------------------------------------------------ byte accounting


class ByteCounter:
    """Tiny thread-safe accumulator: CountingReader accounting runs on
    the shard-io pool threads, where a bare `n += x` would drop
    updates."""

    __slots__ = ("n", "_mu")

    def __init__(self):
        self.n = 0
        self._mu = threading.Lock()

    def add(self, nbytes: int) -> None:
        with self._mu:
            self.n += nbytes


class CountingReader:
    """BitrotReader proxy accounting survivor frame bytes read (hash
    interleave included — the bytes a survivor actually ships).  Used
    by BOTH schemes so the full-vs-subshard comparison is honest even
    when the full path work-steals to spare drives."""

    def __init__(self, inner, algo: str, acct):
        self._inner = inner
        self._acct = acct
        self._hsize = bitrot.hasher_of(algo)[1]

    @property
    def shard_size(self) -> int:
        return self._inner.shard_size

    def read_blocks(self, offset: int, nblocks: int, block_len: int,
                    out=None):
        self._acct(nblocks * (self._hsize + block_len))
        return self._inner.read_blocks(offset, nblocks, block_len, out)

    def read_at(self, offset: int, length: int) -> bytes:
        if length > 0:
            nframes = -(-length // self._inner.shard_size)
            self._acct(length + nframes * self._hsize)
        return self._inner.read_at(offset, length)

    def read_at_ranges(self, runs, block_len: int):
        return {b0: self.read_blocks(b0 * self.shard_size, nb, block_len)
                for b0, nb in runs}

    def close(self) -> None:
        self._inner.close()


# ------------------------------------------------------------- executor


def _dispatch_raw(e, src: np.ndarray, helpers: tuple[int, ...],
                  lost: tuple[int, ...]) -> np.ndarray:
    """(B, k, L) helper columns -> (B, len(lost), L) rebuilt rows via
    the configured codec backend: mesh/device codecs for large batches
    (matrices device-resident via ops/residency.py), the cached
    dual-codeword row matmul on host — no per-dispatch Gauss-Jordan."""
    dev = e._dispatch_codec(src)
    if dev is not None:
        return coding_mod._on_device(
            dev, src, len(lost), lambda shards, **kw: dev.reconstruct(
                shards, helpers, lost, **kw))()
    mat = repair_matrix(e.k, e.m, helpers, lost)
    return e._host.matmul(mat, src)


def _dispatch(e, src: np.ndarray, helpers: tuple[int, ...],
              lost: tuple[int, ...]) -> np.ndarray:
    """Repair rebuild dispatch; with the request batcher gate on
    (MINIO_TPU_BATCHER, erasure/batcher.py) concurrent heals' rebuilds
    of one (helpers, lost) signature fuse into the same per-tick
    program as PUT/GET codec work — the third submitter feeding the one
    device pipeline (ISSUE 11)."""
    src = np.ascontiguousarray(src, dtype=np.uint8)
    helpers = tuple(helpers)
    lost = tuple(lost)

    def raw(cat: np.ndarray) -> np.ndarray:
        return _dispatch_raw(e, cat, helpers, lost)

    routed = e._via_batcher("repair", src, raw, (helpers, lost))
    if routed is not None:
        return routed()
    return raw(src)


def _runs_of(idxs: np.ndarray):
    """Contiguous runs of an ascending index array: (start, count)."""
    runs = []
    start = prev = int(idxs[0])
    for x in idxs[1:]:
        x = int(x)
        if x == prev + 1:
            prev = x
            continue
        runs.append((start, prev - start + 1))
        start = prev = x
    runs.append((start, prev - start + 1))
    return runs


def execute_subshard(e, plan: RepairPlan, readers: dict,
                     writers: dict, target_streams: dict,
                     on_scan=None) -> None:
    """One forward pass rebuilding ``plan.lost`` shards byte-identically.

    ``readers``: {shard_idx: BitrotReader-like} covering plan.helpers
    (CountingReader-wrapped by the caller).  ``writers``: {shard_idx:
    BitrotWriter} for the lost targets (staged tmp files).
    ``target_streams``: {shard_idx: raw stream of the target's existing
    shard file at offset 0}; targets absent here are rebuilt entirely
    from helpers.

    Per block group: read + re-verify the targets' existing frames,
    ranged-read ONLY the blocks bad on ANY target from the k helpers
    (one frame-group read per contiguous run per helper), rebuild them
    in one batched GF(2^8) dispatch, and write each target's frames in
    order (good payloads reused — the writer re-derives the identical
    hash — bad rows from the rebuild).  Raises SubshardAbort on any
    failure; the caller discards the staging and falls back to the
    full-shard decode.  ``on_scan`` additionally receives each
    target-stream read size (per-heal accounting on top of the global
    counters).
    """
    _, hsize = bitrot.hasher_of(plan.algo)
    S = e.shard_size
    lost = plan.lost
    helpers = plan.helpers
    alive = {i: target_streams.get(i) for i in lost}
    try:
        for b0, g, blen in _block_groups(
                plan.till, S, coding_mod.DEVICE_BATCH_BLOCKS):
            frames: dict[int, np.ndarray | None] = {}
            good: dict[int, np.ndarray] = {}
            for i in lost:
                st = alive.get(i)
                payload = None
                if st is not None:
                    try:
                        raw = _read_full(st, g * (hsize + blen))
                    except Exception:
                        raw = b""
                    _add_scan(len(raw))
                    if on_scan is not None:
                        on_scan(len(raw))
                    if len(raw) == g * (hsize + blen):
                        arr = np.frombuffer(raw, dtype=np.uint8).reshape(
                            g, hsize + blen)
                        payload = arr[:, hsize:]
                        good[i] = _verify_frames(arr, hsize, plan.algo)
                    else:
                        # short/failed target read: nothing further is
                        # reusable from this stream — close it now (the
                        # finally sweep only sees streams still alive)
                        try:
                            st.close()
                        except Exception:
                            pass
                        alive[i] = None
                frames[i] = payload
                if payload is None:
                    good[i] = np.zeros(g, dtype=bool)

            union_bad = np.zeros(g, dtype=bool)
            for i in lost:
                union_bad |= ~good[i]

            rebuilt = None
            pos_of: dict[int, int] = {}
            if union_bad.any():
                idxs = np.flatnonzero(union_bad)
                pos_of = {int(bi): p for p, bi in enumerate(idxs)}
                runs = [(b0 + r0, rg) for r0, rg in _runs_of(idxs)]
                by_helper: dict[int, dict[int, np.ndarray]] = {}
                for h in helpers:
                    r = readers.get(h)
                    if r is None:
                        raise SubshardAbort(f"helper {h} unavailable")
                    try:
                        by_helper[h] = r.read_at_ranges(runs, blen)
                    except Exception as ex:
                        raise SubshardAbort(
                            f"helper {h} failed mid-repair: {ex}")
                parts = [
                    np.stack([np.asarray(by_helper[h][a0])
                              for h in helpers], axis=1)  # (rg, k, blen)
                    for a0, _ in runs]
                src = parts[0] if len(parts) == 1 else np.concatenate(parts)
                try:
                    rebuilt = _dispatch(e, src, helpers, lost)
                except Exception as ex:
                    raise SubshardAbort(f"rebuild dispatch failed: {ex}")

            for t, i in enumerate(lost):
                out = np.empty((g, blen), dtype=np.uint8)
                gm = good[i]
                if gm.any():
                    out[gm] = frames[i][gm]
                badm = ~gm
                if badm.any():
                    rows = [pos_of[int(x)] for x in np.flatnonzero(badm)]
                    out[badm] = rebuilt[rows, t]
                w = writers[i]
                try:
                    wf = getattr(w, "write_frames", None)
                    if wf is not None:
                        wf(out)  # g > 1 implies blen == shard_size
                    else:
                        for bi in range(g):
                            w.write(out[bi])
                except Exception as ex:
                    raise SubshardAbort(f"target {i} write failed: {ex}")
    finally:
        for st in alive.values():
            if st is not None:
                try:
                    st.close()
                except Exception:
                    pass
