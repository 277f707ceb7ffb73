"""Replay driver for the sanitizer-instrumented native kernels.

Run inside a subprocess whose environment loads a sanitized build of
libminio_tpu_host (tests/test_sanitizers.py sets MINIO_TPU_NATIVE_LIB
to the `make asan`/`make ubsan`/`make tsan` artifact and LD_PRELOADs
the matching runtime).  NOT collected by pytest (no test_ functions) —
it is the workload, the assertions live in the parent test.

Modes:
  select    — replay the 512-case Select differential corpus
              (tests/select_corpus.py) through the native tier and
              compare byte-for-byte with the pure-Python row engine
  golden    — GF(2^8) encode/reconstruct golden vectors
              (cmd/erasure-coding.go self-test table) through the C
              matmul, plus the HighwayHash-256 reference self-test
  repair    — repair-kernel golden vectors (erasure/repair.py): the
              dual-codeword repair matrices applied through the C
              GF(2^8) matmul (2-D and batched 3-D) across geometries
              and multi-loss sets, pinned against
              gf256.reconstruct_matrix, plus the executor's strided
              frame-verify path over the batched HighwayHash kernel
  scanpool  — hammer the fused multi-threaded Select kernels (ScanPool
              in csrc/select_scan.cpp) from several Python threads at
              once: cross-thread block handoff under TSan

Exit codes: 0 ok, 1 divergence/failure, 3 native library unavailable
(parent skips).
"""

from __future__ import annotations

import io
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _recs(stream: bytes):
    from tests.select_corpus import canonical_records

    return canonical_records(stream)


def _run_select(expr, data, inp, out, tier):
    from minio_tpu import select as sel

    env = {}
    if tier == "row":
        env = {"MINIO_TPU_SELECT_COLUMNAR": "0",
               "MINIO_TPU_SELECT_BATCH": "0"}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        req = sel.SelectRequest(expr, inp, out)
        return b"".join(sel.run_select(req, io.BytesIO(data), len(data)))
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _require_native() -> None:
    from minio_tpu.ops import host
    from minio_tpu.select import native

    if native._load() is None:
        print("san_replay: native library failed to load "
              f"({host.lib_path()}); nothing to sanitize", file=sys.stderr)
        sys.exit(3)


def mode_select() -> None:
    from tests import select_corpus

    _require_native()
    n = bad = 0
    for family, seed, expr, data, inp, out in select_corpus.corpus():
        n += 1
        fast = _recs(_run_select(expr, data, inp, out, tier="native"))
        slow = _recs(_run_select(expr, data, inp, out, tier="row"))
        if fast != slow:
            bad += 1
            print(f"DIVERGENCE {family}/{seed}: {expr!r}",
                  file=sys.stderr)
    print(f"san_replay select: {n} cases, {bad} divergences")
    sys.exit(1 if bad else 0)


def mode_golden() -> None:
    import numpy as np
    import xxhash

    from minio_tpu.ops import gf256, host
    from tests.test_rs_golden import GOLDEN, TEST_DATA

    if not host.available():
        print("san_replay: host library unavailable", file=sys.stderr)
        sys.exit(3)
    failures = 0
    for (k, m), want in sorted(GOLDEN.items()):
        # shard like encode_data_np, but run the C matmul for parity
        data_shards = np.stack(gf256.encode_data_np(TEST_DATA, k, m)[:k])
        codec = host.HostRSCodec(k, m)
        parity = codec.encode(data_shards)
        h = xxhash.xxh64()
        for i, s in enumerate(list(data_shards) + list(parity)):
            h.update(bytes([i]))
            h.update(np.asarray(s, dtype=np.uint8).tobytes())
        if h.intdigest() != want:
            failures += 1
            print(f"RS golden mismatch for {k}+{m}", file=sys.stderr)
        # reconstruct shard 0 from the rest through the C matmul
        rebuilt = codec.reconstruct(
            np.stack(list(data_shards[1:]) + list(parity[:1])),
            list(range(1, k + 1)), [0])
        if not np.array_equal(rebuilt[0], data_shards[0]):
            failures += 1
            print(f"RS reconstruct mismatch for {k}+{m}", file=sys.stderr)

    # HighwayHash-256 reference self-test (cmd/bitrot.go:214)
    hh = host.HH256()
    msg, sum_ = b"", b""
    for _ in range(32):
        hh.reset()
        hh.update(msg)
        sum_ = hh.digest()
        msg += sum_
    want_hex = ("39c0407ed3f01b18d22c85db4aeff11e"
                "060ca5f43131b0126731ca197cd42313")
    if sum_.hex() != want_hex:
        failures += 1
        print("HighwayHash-256 self-test mismatch", file=sys.stderr)
    # batch entry point (hh256_batch walks a strided matrix)
    blocks = np.frombuffer(
        bytes(range(256)) * 32, dtype=np.uint8).reshape(16, 512)
    got = host.hh256_batch(blocks)
    for i in range(16):
        if bytes(got[i]) != host.hh256(blocks[i].tobytes()):
            failures += 1
            print(f"hh256_batch row {i} mismatch", file=sys.stderr)
            break
    print(f"san_replay golden: {len(GOLDEN)} EC configs, "
          f"{failures} failures")
    sys.exit(1 if failures else 0)


def mode_repair() -> None:
    import numpy as np

    from minio_tpu.erasure import bitrot, repair as repair_mod
    from minio_tpu.ops import gf256, host

    if not host.available():
        print("san_replay: host library unavailable", file=sys.stderr)
        sys.exit(3)
    failures = 0
    payload = bytes(range(256)) * 64  # 16 KiB, deterministic
    cases = 0
    for k in (2, 4, 8):
        for m in (1, 2, 4):
            shards = np.stack(gf256.encode_data_np(payload, k, m))
            codec = host.HostRSCodec(k, m)
            n = k + m
            loss_sets = [(0,), (n - 1,)]
            if m >= 2:
                loss_sets.append((1, n - 1))
            if m >= 4:
                loss_sets.append((0, 2, k, n - 1))
            for lost in loss_sets:
                surv = [i for i in range(n) if i not in lost]
                # two helper selections: data-heavy and parity-heavy
                for helpers in ({tuple(sorted(surv[:k])),
                                 tuple(sorted(surv[-k:]))}):
                    cases += 1
                    mat = repair_mod.repair_matrix(k, m, helpers, lost)
                    ref = gf256.reconstruct_matrix(k, m, helpers, lost)
                    if not np.array_equal(mat, ref):
                        failures += 1
                        print(f"repair_matrix != reconstruct_matrix "
                              f"{k}+{m} lost={lost} helpers={helpers}",
                              file=sys.stderr)
                    src = np.stack([shards[i] for i in helpers])
                    rebuilt = codec.matmul(mat, src)   # sanitized C matmul
                    want = np.stack([shards[i] for i in lost])
                    if not np.array_equal(rebuilt, want):
                        failures += 1
                        print(f"repair matmul mismatch {k}+{m} "
                              f"lost={lost} helpers={helpers}",
                              file=sys.stderr)
                    # batched 3-D dispatch (the executor's block-group
                    # shape): B block batches through the same matrix
                    cols = src.reshape(k, 8, -1).transpose(1, 0, 2)
                    got3 = codec.matmul(mat, np.ascontiguousarray(cols))
                    want3 = want.reshape(len(lost), 8, -1) \
                        .transpose(1, 0, 2)
                    if not np.array_equal(got3, want3):
                        failures += 1
                        print(f"batched repair matmul mismatch {k}+{m} "
                              f"lost={lost}", file=sys.stderr)

    # the executor's frame re-verify: strided [hash|payload] rows through
    # hh256_batch (a non-contiguous payload view is exactly what
    # _verify_frames hands the C kernel)
    algo = bitrot.DEFAULT_ALGO
    _, hsize = bitrot.hasher_of(algo)
    blen = 1024
    g = 32
    frames = np.zeros((g, hsize + blen), dtype=np.uint8)
    for i in range(g):
        block = bytes((i + j) & 0xFF for j in range(blen))
        frames[i, hsize:] = np.frombuffer(block, dtype=np.uint8)
        frames[i, :hsize] = np.frombuffer(
            bitrot.hasher_of(algo)[0](block), dtype=np.uint8)
    corrupt = [3, 17, 31]
    for i in corrupt:
        frames[i, hsize + 5] ^= 0xA5
    goodmask = repair_mod._verify_frames(frames, hsize, algo)
    want_mask = np.array([i not in corrupt for i in range(g)])
    if not np.array_equal(goodmask, want_mask):
        failures += 1
        print("frame re-verify mask mismatch", file=sys.stderr)

    print(f"san_replay repair: {cases} matrix cases, {failures} failures")
    sys.exit(1 if failures else 0)


def _tsan_report_paths() -> list:
    """TSan log files for THIS run, when TSAN_OPTIONS carries a
    log_path (reports go there instead of stderr)."""
    import glob

    for part in os.environ.get("TSAN_OPTIONS", "").replace(
            ",", ":").split(":"):
        if part.startswith("log_path="):
            base = part.split("=", 1)[1]
            return sorted(glob.glob(base + ".*"))
    return []


#: substrings attributing a sanitizer report block to OUR frames
_OUR_FRAMES = ("select_scan", "gf256_simd", "highwayhash",
               "minio_tpu_host")


def _check_tsan_reports() -> int:
    """Exit-code contribution for TSan runs: nonzero when any report
    block names our library/source.  CPython-internal reports are
    handled by csrc/tsan.supp (instrumented-CPython runs) or by the
    attribution here (plain runs) — either way a report in OUR frames
    is fatal, never noise.  Self-attribution needs TSAN_OPTIONS to
    carry log_path (reports on stderr are invisible to this process);
    without it, say so loudly — the caller must scan stderr itself
    (tests/test_sanitizers.py does both)."""
    if "log_path=" not in os.environ.get("TSAN_OPTIONS", ""):
        if "tsan" in os.environ.get("LD_PRELOAD", "") \
                or os.environ.get("MINIO_TPU_SAN", "") == "tsan":
            print("san_replay: no log_path in TSAN_OPTIONS — "
                  "self-attribution INACTIVE, reports go to stderr; "
                  "the caller must attribute them", file=sys.stderr)
        return 0
    ours = []
    for path in _tsan_report_paths():
        try:
            with open(path, encoding="utf-8", errors="replace") as f:
                text = f.read()
        except OSError:
            continue
        for block in text.split("WARNING: ThreadSanitizer")[1:]:
            if any(m in block for m in _OUR_FRAMES):
                ours.append(block[:2500])
    if ours:
        print("san_replay: ThreadSanitizer report attributed to our "
              f"frames ({len(ours)} block(s)):\n" + ours[0],
              file=sys.stderr)
        return 1
    return 0


def mode_scanpool() -> None:
    import threading

    _require_native()
    os.environ["MINIO_TPU_SELECT_THREADS"] = "4"
    # >= 1 MiB blocks engage the ScanPool's newline-split fan-out
    rows = "".join(f"r{i},{i % 997},{i % 97}\n" for i in range(120_000))
    data = ("a,b,c\n" + rows).encode()
    assert len(data) > (1 << 20)
    exprs = [
        "SELECT COUNT(*) FROM s3object WHERE b > 500",
        "SELECT COUNT(*), MIN(b), MAX(c) FROM s3object",
        "SELECT COUNT(*) FROM s3object WHERE a LIKE 'r1%'",
        "SELECT COUNT(*) FROM s3object WHERE b BETWEEN 10 AND 900",
    ]
    results: dict[int, object] = {}

    def worker(idx: int) -> None:
        try:
            for rep in range(3):
                expr = exprs[(idx + rep) % len(exprs)]
                out = _run_select(expr, data, {"CSV": {}}, {"CSV": {}},
                                  tier="native")
                results.setdefault(idx, []).append(len(out))
        except Exception as e:  # pragma: no cover - surfaced via exit code
            results[idx] = e

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    errs = [v for v in results.values() if isinstance(v, Exception)]
    if errs or len(results) != 6:
        print(f"san_replay scanpool: failures {errs}", file=sys.stderr)
        sys.exit(1)
    rc = _check_tsan_reports()
    print(f"san_replay scanpool: 6 threads x 3 scans ok"
          + ("" if rc == 0 else " — but TSan reported in our frames"))
    sys.exit(rc)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "select"
    {"select": mode_select,
     "golden": mode_golden,
     "repair": mode_repair,
     "scanpool": mode_scanpool}[mode]()
