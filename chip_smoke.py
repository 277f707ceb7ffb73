#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, through the chip.

Starts the real CLI (`python -m minio_tpu.server <root>/d{1...12}`) as
its one child with MINIO_TPU_ERASURE_BACKEND=tpu and every other gate at
its default (fsync on), then talks S3 to it over HTTP with the repo's
own SigV4 client: PUT large objects two at a time, PUT inline objects,
GET everything back, lose two data shards of one object and GET it
again (reconstruct on the device), lose two shards of another and heal
it through the admin API, PUT one object of a different batch shape.
One set of twelve drives is EC 8+4 with 1 MiB blocks and
HighwayHash256S frames — BASELINE.json config 2's geometry; its 10 GiB
object is cut to fit a run (`reduced` in the output).

What comes out is checked by the repo's own means: every object reads
back byte-identical (SHA-256), also after the shards were lost and after
the heal; and the server's own counters (minio_erasure_backend_bytes_
total, admin info's `erasure` block) must show the device coded the
large PUTs, the degraded GET and the heal, and nothing in the inline and
healthy-GET phases.  Anything else — a non-2xx, a mismatch, a server
that exits or leaves a traceback, no TPU — is exit code != 0 and no
result line.  A run that passed prints two lines: the report (one JSON
object; its timings are information for the next issue, not metrics),
and last the verdict, `{"ok": true, "device": {"platform", "kind",
"count"}}`, the device as the server's JAX reports it.

This process never imports JAX: the chip belongs to the server.
`--rehearse-cpu` debugs the script on a box without a chip (tiny sizes,
backend host, device checks skipped, output stamped as a rehearsal).
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import ctypes
import glob
import hashlib
import http.client
import importlib.metadata
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

from minio_tpu.storage import xlmeta
from minio_tpu.utils.s3client import S3Client, S3ClientError

HERE = os.path.dirname(os.path.abspath(__file__))
MIB = 1 << 20
DRIVES, K, M = 12, 8, 4     # one set of twelve -> EC 8+4
AK, SK = "smokeadmin", "smokesecret123"
BUCKET = "smoke"

REAL = {"large_n": 4, "large_size": 256 * MIB, "large_conc": 2,
        "small_n": 64, "small_size": 64 * 1024, "odd_size": 100 * MIB}
REHEARSAL = {"large_n": 4, "large_size": 3 * MIB, "large_conc": 2,
             "small_n": 8, "small_size": 64 * 1024, "odd_size": 2 * MIB}


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def body_of(seed: int, name: str, size: int) -> bytes:
    """`size` pseudo-random (incompressible) bytes, a function of the
    seed and the object's name; in pieces, since one randbytes call
    cannot make 256 MiB."""
    rng = random.Random(f"{seed}/{name}")
    piece = 16 * MIB
    return b"".join(rng.randbytes(min(piece, size - at))
                    for at in range(0, size, piece))


class Server:
    """The one child: the real CLI, its own process group."""

    def __init__(self, root: str, backend: str):
        self.root = root
        self.port = self._free_port()
        self.stderr_path = os.path.join(root, "server.stderr")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("MINIO_TPU_")}  # every gate at default
        env.update({"MINIO_TPU_ERASURE_BACKEND": backend,
                    "MINIO_ROOT_USER": AK, "MINIO_ROOT_PASSWORD": SK,
                    "PYTHONPATH": HERE})
        self._stderr = open(self.stderr_path, "wb")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "minio_tpu.server",
             f"{root}/d{{1...{DRIVES}}}",
             "--address", f"127.0.0.1:{self.port}"],
            env=env, cwd=HERE, stdout=subprocess.DEVNULL,
            stderr=self._stderr, start_new_session=True,
            preexec_fn=self._die_with_parent)

    @staticmethod
    def _die_with_parent() -> None:
        """In the child, before exec: SIGKILL it when this process dies,
        however it dies — a server that outlives a killed smoke would go
        on holding the chip."""
        pr_set_pdeathsig = 1
        ctypes.CDLL(None).prctl(pr_set_pdeathsig, signal.SIGKILL)

    @staticmethod
    def _free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    def stderr_text(self) -> str:
        with open(self.stderr_path, "rb") as f:
            return f.read().decode(errors="replace")

    def wait_live(self, timeout: float) -> float:
        """Seconds from spawn until /minio/health/live answers 200."""
        while time.perf_counter() - self.t_spawn < timeout:
            rc = self.proc.poll()
            check(rc is None, f"server exited with code {rc} before it "
                  f"served:\n{self.stderr_text()[-4000:]}")
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=2)
                conn.request("GET", "/minio/health/live")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return time.perf_counter() - self.t_spawn
            except OSError:
                pass
            time.sleep(0.25)
        raise SmokeFailure(f"server not live after {timeout:.0f} s:\n"
                           f"{self.stderr_text()[-4000:]}")

    def stop(self) -> None:
        """SIGTERM the group, then SIGKILL what is left of it."""
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait(timeout=30)
        self._stderr.close()


class Client(S3Client):
    """The package's own signed client (server/sigv4.sign_request under
    it), timing each request; any non-2xx fails the run."""

    def __init__(self, port: int):
        super().__init__(f"127.0.0.1:{port}", AK, SK, timeout=600.0)

    def request(self, method: str, path: str, body: bytes = b"",
                query: list | None = None):
        """One signed request -> (body, seconds)."""
        bucket, _, key = path.lstrip("/").partition("/")
        t0 = time.perf_counter()
        try:
            _, _, data = self._request(method, bucket, key, body=body,
                                       query=query, ok=range(200, 300))
        except S3ClientError as e:
            raise SmokeFailure(
                f"{method} {path} -> {e.status}: {e.body[:500]!r}") from e
        return data, time.perf_counter() - t0

    def erasure_info(self) -> dict:
        data, _ = self.request("GET", "/minio/admin/v3/info")
        return json.loads(data)["erasure"]

    def backend_bytes(self) -> dict:
        """minio_erasure_backend_bytes_total per backend label."""
        data, _ = self.request("GET", "/minio/v2/metrics/cluster")
        found = dict(re.findall(
            r'^minio_erasure_backend_bytes_total\{backend="(\w+)"\} (\d+)$',
            data.decode(), flags=re.M))
        check({"device", "host"} <= set(found),
              "metrics endpoint lacks minio_erasure_backend_bytes_total")
        return {k: int(v) for k, v in found.items()}


class Smoke:
    def __init__(self, args, root: str):
        self.args = args
        self.root = root
        self.sizes = REHEARSAL if args.rehearse_cpu else REAL
        self.sent: dict[str, tuple[str, int]] = {}  # key -> (sha256, size)
        self.phases: dict[str, dict] = {}
        self.server = Server(
            root, "host" if args.rehearse_cpu else "tpu")
        self.client = Client(self.server.port)

    # ------------------------------------------------------------ plumbing
    def put(self, key: str, size: int) -> float:
        body = body_of(self.args.seed, key, size)
        self.sent[key] = (hashlib.sha256(body).hexdigest(), size)
        _, seconds = self.client.request("PUT", f"/{BUCKET}/{key}", body)
        return seconds

    def get_and_compare(self, key: str) -> float:
        want_sha, want_size = self.sent[key]
        data, seconds = self.client.request("GET", f"/{BUCKET}/{key}")
        check(len(data) == want_size,
              f"GET {key}: {len(data)} bytes, sent {want_size}")
        check(hashlib.sha256(data).hexdigest() == want_sha,
              f"GET {key}: SHA-256 differs from what was sent")
        return seconds

    def phase(self, name: str, fn) -> dict:
        """Run one phase between two readings of the server's counters."""
        before_bytes = self.client.backend_bytes()
        before_info = self.client.erasure_info()
        t0 = time.perf_counter()
        out = fn()
        seconds = time.perf_counter() - t0
        after_bytes = self.client.backend_bytes()
        after_info = self.client.erasure_info()
        check(self.server.proc.poll() is None,
              f"server died during phase {name}")
        rec = {
            "seconds": round(seconds, 3),
            "bytes": out["bytes"],
            "requests": len(out["request_seconds"]),
            "slowest_request_seconds": round(
                max(out["request_seconds"]), 3),
            "backend_bytes": {
                b: after_bytes[b] - before_bytes[b] for b in after_bytes},
            "backend_dispatches": {
                b: after_info["dispatch"][b]["dispatches"]
                - before_info["dispatch"][b]["dispatches"]
                for b in after_info["dispatch"]},
            "peak_bytes_in_use": after_info.get("peakBytesInUse"),
        }
        rec.update(out.get("extra", {}))
        self.phases[name] = rec
        return rec

    def shard_drives(self, key: str, shards: tuple[int, ...]) -> list[str]:
        """Drive directories holding the given (1-based) shard indices of
        an object, read from each drive's own xl.meta."""
        out = {}
        for d in range(1, DRIVES + 1):
            drive = os.path.join(self.root, f"d{d}")
            with open(os.path.join(drive, BUCKET, key, "xl.meta"),
                      "rb") as f:
                fi = xlmeta.file_info_from_raw(f.read(), BUCKET, key)
            if fi.erasure.index in shards:
                out[fi.erasure.index] = drive
        check(sorted(out) == sorted(shards),
              f"{key}: shards {shards} not found on the drives ({out})")
        return [out[s] for s in shards]

    def part_files(self, drive: str, key: str) -> list[str]:
        return glob.glob(os.path.join(drive, BUCKET, key, "*", "part.*"))

    def lose_shards(self, key: str, shards: tuple[int, ...]) -> list[str]:
        """Delete the object's part files on the drives holding `shards`
        (data shards: a GET then has to reconstruct)."""
        drives = self.shard_drives(key, shards)
        for drive in drives:
            parts = self.part_files(drive, key)
            check(len(parts) == 1, f"{key}: expected one part file on "
                  f"{drive}, found {parts}")
            os.remove(parts[0])
        return drives

    def wait_shards_back(self, key: str, drives: list[str],
                         timeout: float) -> float:
        """Until each drive holds the object's part file again, at the
        size its siblings have (heal stages then renames into place)."""
        t0 = time.perf_counter()
        sibling = next(
            os.path.join(self.root, f"d{d}") for d in range(1, DRIVES + 1)
            if os.path.join(self.root, f"d{d}") not in drives)
        want = os.path.getsize(self.part_files(sibling, key)[0])
        while time.perf_counter() - t0 < timeout:
            sizes = [[os.path.getsize(p) for p in self.part_files(d, key)]
                     for d in drives]
            if all(s == [want] for s in sizes):
                return time.perf_counter() - t0
            time.sleep(0.2)
        raise SmokeFailure(
            f"{key}: shards not back on {drives} after {timeout:.0f} s")

    # -------------------------------------------------------------- phases
    def put_large(self) -> dict:
        s = self.sizes
        keys = [f"large-{i}" for i in range(s["large_n"])]
        with cf.ThreadPoolExecutor(s["large_conc"]) as pool:
            secs = list(pool.map(
                lambda k: self.put(k, s["large_size"]), keys))
        return {"bytes": s["large_n"] * s["large_size"],
                "request_seconds": secs}

    def put_inline(self) -> dict:
        s = self.sizes
        secs = [self.put(f"small-{i}", s["small_size"])
                for i in range(s["small_n"])]
        return {"bytes": s["small_n"] * s["small_size"],
                "request_seconds": secs}

    def get_all(self) -> dict:
        secs = [self.get_and_compare(k) for k in sorted(self.sent)]
        return {"bytes": sum(sz for _, sz in self.sent.values()),
                "request_seconds": secs}

    def degraded_get(self) -> dict:
        """Two data shards of large-0 gone: the GET reconstructs them,
        and the read path queues the heal that puts them back."""
        key = "large-0"
        drives = self.lose_shards(key, (1, 4))
        secs = [self.get_and_compare(key)]
        healed_after = self.wait_shards_back(key, drives, timeout=300)
        return {"bytes": self.sent[key][1], "request_seconds": secs,
                "extra": {"read_triggered_heal_seconds":
                          round(healed_after, 3)}}

    def heal(self) -> dict:
        """Two shards of large-1 gone and never read: only the admin
        heal sequence over the bucket brings them back."""
        key = "large-1"
        drives = self.lose_shards(key, (2, 7))
        path = f"/minio/admin/v3/heal/{BUCKET}"
        data, t_launch = self.client.request("POST", path)
        token = json.loads(data)["clientToken"]
        t0 = time.perf_counter()
        while True:
            data, _ = self.client.request(
                "POST", path, query=[("clientToken", token)])
            status = json.loads(data)
            if status["state"] != "running":
                break
            check(time.perf_counter() - t0 < 600, "heal never finished")
            time.sleep(0.2)
        check(status["state"] == "finished"
              and status["objectsFailed"] == 0, f"heal: {status}")
        sequence_seconds = time.perf_counter() - t0
        self.wait_shards_back(key, drives, timeout=5)
        secs = [t_launch, self.get_and_compare(key),
                self.get_and_compare("large-0")]
        return {"bytes": self.sent[key][1], "request_seconds": secs,
                "extra": {"heal_sequence_seconds":
                          round(sequence_seconds, 3),
                          "objects_healed": status["objectsHealed"],
                          "bytes_healed": status["bytesHealed"]}}

    def put_odd(self) -> dict:
        """A second batch shape: 100 MiB is 3 x 32 blocks + 4."""
        size = self.sizes["odd_size"]
        secs = [self.put("odd-0", size), self.get_and_compare("odd-0")]
        return {"bytes": size, "request_seconds": secs}

    # ----------------------------------------------------------------- run
    def run(self) -> dict:
        rehearsal = self.args.rehearse_cpu
        boot_seconds = self.server.wait_live(timeout=600)
        info = self.client.erasure_info()
        check(info["hostCodec"] == "native",
              "the server runs the numpy host codec: csrc/ did not build")
        if not rehearsal:
            check(info.get("platform") == "tpu",
                  f"server reports platform {info.get('platform')!r}")
            # every geometry these drives can write: 8+4, and 10+2 for
            # REDUCED_REDUNDANCY, whose shards are no multiple of the
            # kernel's tile and reach the device all the same
            geometry = info["boot"]["geometry"]
            check(geometry.get(f"{K}+{M}") == "device"
                  and set(geometry.values()) == {"device"},
                  f"a geometry does not resolve to the device: {geometry}")
        self.client.request("PUT", f"/{BUCKET}")

        large = self.sizes["large_size"]
        p = self.phase("put_large", self.put_large)
        self.expect(p, "put_large", device_at_least=p["bytes"], host=0)
        p = self.phase("put_inline", self.put_inline)
        self.expect(p, "put_inline", device=0)
        p = self.phase("get_all", self.get_all)
        self.expect(p, "get_all", device=0, host=0)
        p = self.phase("degraded_get", self.degraded_get)
        self.expect(p, "degraded_get", device_at_least=large, host=0)
        p = self.phase("heal", self.heal)
        self.expect(p, "heal", device_at_least=large, host=0)
        p = self.phase("put_odd", self.put_odd)
        self.expect(p, "put_odd", device_at_least=p["bytes"], host=0)

        info = self.client.erasure_info()
        self.server.stop()
        stderr = self.server.stderr_text()
        check("Traceback (most recent call last)" not in stderr,
              f"server stderr holds a traceback:\n{stderr[-4000:]}")
        cache_dir = info.get("compileCacheDir")
        out = {
            "ok": True,
            "platform": info.get("platform", "cpu"),
            "device_kind": info.get("deviceKind"),
            "device_count": info.get("deviceCount"),
        }
        if rehearsal:
            out["rehearsal"] = True
        out.update({
            "versions": {p: importlib.metadata.version(p)
                         for p in ("jax", "jaxlib", "libtpu")},
            "backend": info["backend"],
            "host_codec": info["hostCodec"],
            "geometry": info["boot"]["geometry"],
            "compile_cache": {
                "dir": cache_dir,
                "entries": len(os.listdir(cache_dir))
                if cache_dir and os.path.isdir(cache_dir) else 0},
            "boot": {"seconds_to_live": round(boot_seconds, 3),
                     "host_self_test_seconds":
                         info["boot"]["hostSelfTestSeconds"],
                     "jax_init_seconds": info["boot"]["jaxInitSeconds"],
                     "device_self_test_and_warmup_seconds":
                         info["boot"]["deviceSelfTestSeconds"]},
            "phases": self.phases,
            "peak_bytes_in_use": info.get("peakBytesInUse"),
            "seed": self.args.seed,
            "config": "BASELINE.json config 2: EC 8+4, 12 drives, 1 MiB "
                      "blocks, HighwayHash256S, fsync on",
            "reduced": [
                f"object size 10 GiB -> {self.sizes['large_n']} x "
                f"{self.sizes['large_size'] // MIB} MiB + one "
                f"{self.sizes['odd_size'] // MIB} MiB",
                "twelve drives are twelve directories of one file system"],
            "claim": None,
        })
        return out

    def expect(self, rec: dict, name: str, *, device: int | None = None,
               device_at_least: int | None = None,
               host: int | None = None) -> None:
        """Hold a phase's per-backend byte deltas to what the phase is:
        sizes here are whole blocks, so no tail block explains a host
        byte in a device phase.  A rehearsal has no device: the same
        bytes must then show on the host codec."""
        got = rec["backend_bytes"]
        if self.args.rehearse_cpu:
            check(got["device"] == 0, f"{name}: device bytes on the CPU")
            if device_at_least is not None:
                check(got["host"] >= device_at_least,
                      f"{name}: host coded {got['host']} bytes, "
                      f"expected >= {device_at_least}")
            return
        if device is not None:
            check(got["device"] == device,
                  f"{name}: device coded {got['device']} bytes, "
                  f"expected {device}")
        if device_at_least is not None:
            check(got["device"] >= device_at_least,
                  f"{name}: device coded {got['device']} bytes, "
                  f"expected >= {device_at_least}")
        if host is not None:
            check(got["host"] == host,
                  f"{name}: host codec coded {got['host']} bytes where "
                  f"the device should have ({got})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="object bodies are generated from it")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug the script without a chip: tiny sizes, "
                         "backend host, device checks skipped")
    ap.add_argument("--root", default=None,
                    help="directory for the drives (default: a fresh "
                         "temporary directory, removed afterwards)")
    args = ap.parse_args()
    root = args.root or tempfile.mkdtemp(prefix="chip-smoke-")
    os.makedirs(root, exist_ok=True)
    smoke = Smoke(args, root)
    try:
        result = smoke.run()
    except BaseException:
        smoke.server.stop()
        sys.stderr.write("---- server stderr (tail) ----\n"
                         + smoke.server.stderr_text()[-8000:] + "\n")
        raise
    finally:
        smoke.server.stop()
        if args.root is None:
            shutil.rmtree(root, ignore_errors=True)
    check("jax" not in sys.modules, "the smoke's parent imported JAX")
    print(json.dumps(result))
    # the verdict, last and alone: these keys and no others.  A rehearsal's
    # host-pinned server never initialised JAX, so it has no device to name
    print(json.dumps({"ok": True, "device": {
        "platform": result["platform"],
        "kind": result["device_kind"] or "none (host codec rehearsal)",
        "count": result["device_count"] or 0}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
