"""The system under test as one child process, and its counters' readers.

`Server` starts the real CLI (`python -m minio_tpu.server`), or for a
traced run the benchmark's launcher (`benchmark/serve.py`: the same
`main()` in-process with `jax.profiler` around the traced window), with
every `MINIO_TPU_*` gate at its default except the backend, which is
pinned to `tpu`: no chip means the server refuses to boot and the run
fails.  The child dies with its parent and is stopped, group and all,
before the run returns.  This process never imports JAX.
"""

from __future__ import annotations

import ctypes
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

from benchmark import s3
from benchmark.manifest import CHECKOUT


class RunFailure(Exception):
    """The run cannot give a result: no chip, a dead server, a bad cell."""


def _child_setup() -> None:
    """In the child, before exec: die with the parent (a server that
    outlives a killed run would go on holding the chip)."""
    pr_set_pdeathsig = 1
    ctypes.CDLL(None).prctl(pr_set_pdeathsig, signal.SIGKILL)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    def __init__(self, root: str, drives: int, backend: str, *,
                 launcher: list[str] | None = None,
                 extra_env: dict[str, str] | None = None):
        self.root = root
        self.port = free_port()
        self.stderr_path = os.path.join(root, "server.stderr")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("MINIO_TPU_")}
        env.update({"MINIO_TPU_ERASURE_BACKEND": backend,
                    "MINIO_ROOT_USER": s3.ACCESS_KEY,
                    "MINIO_ROOT_PASSWORD": s3.SECRET_KEY,
                    "PYTHONPATH": CHECKOUT})
        # the program keeps its compile cache at <checkout>/.jax_cache
        # unless this is set; say so explicitly so the path is fixed
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(CHECKOUT, ".jax_cache"))
        env.update(extra_env or {})
        argv = (launcher or [sys.executable, "-m", "minio_tpu.server"]) + [
            f"{root}/d{{1...{drives}}}",
            "--address", f"127.0.0.1:{self.port}"]
        self._stderr = open(self.stderr_path, "wb")
        self.t_spawn = time.perf_counter()
        self.proc = subprocess.Popen(
            argv, env=env, cwd=CHECKOUT, stdout=subprocess.DEVNULL,
            stderr=self._stderr, start_new_session=True,
            preexec_fn=_child_setup)
        self.conn = s3.Connection(self.port, timeout=60.0,
                                  signed_payload=True)

    def stderr_text(self) -> str:
        with open(self.stderr_path, "rb") as f:
            return f.read().decode(errors="replace")

    def stderr_size(self) -> int:
        return os.path.getsize(self.stderr_path)

    def check_alive(self, when: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RunFailure(f"server exited with code {rc} {when}:\n"
                             f"{self.stderr_text()[-4000:]}")

    def wait_live(self, timeout: float) -> None:
        while time.perf_counter() - self.t_spawn < timeout:
            self.check_alive("before it served")
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=2)
                conn.request("GET", "/minio/health/live")
                ok = conn.getresponse().status == 200
                conn.close()
                if ok:
                    return
            except OSError:
                pass
            time.sleep(0.1)
        raise RunFailure(f"server not live after {timeout:.0f} s:\n"
                         f"{self.stderr_text()[-4000:]}")

    def stop(self) -> None:
        self.conn.close()
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

    # ----------------------------------------------------------- readers
    def _get(self, path: str) -> bytes:
        status, data = self.conn.request("GET", path)
        if status != 200:
            raise RunFailure(f"GET {path} -> {status}: {data[:300]!r}")
        return data

    def erasure_info(self) -> dict:
        """Admin info's `erasure` block: backend, device, boot, dispatch."""
        return json.loads(self._get("/minio/admin/v3/info"))["erasure"]

    def counters(self) -> dict:
        """One scrape of the program's counters, as the metrics read them:
        {"bytes": {backend: n}, "dispatches": {backend: n},
         "stage_seconds": {stage: s}, "stage_bytes": {stage: n}}."""
        text = self._get("/minio/v2/metrics/cluster").decode()

        def rows(name: str, label: str) -> dict[str, float]:
            return {k: float(v) for k, v in re.findall(
                rf'^{name}\{{{label}="(\w+)"\}} ([0-9.e+-]+)$', text,
                flags=re.M)}

        out = {
            "bytes": rows("minio_erasure_backend_bytes_total", "backend"),
            "dispatches": rows("minio_erasure_backend_dispatches_total",
                               "backend"),
            "stage_seconds": rows("minio_dataplane_stage_seconds_total",
                                  "stage"),
            "stage_bytes": rows("minio_dataplane_stage_bytes_total",
                                "stage"),
        }
        if not {"device", "host"} <= set(out["bytes"]):
            raise RunFailure("the metrics endpoint lacks "
                             "minio_erasure_backend_bytes_total")
        return out

    def cpu_seconds(self) -> float:
        """utime + stime of the server's process group's processes, from
        /proc/<pid>/stat (the CLI is one process unless it pre-forks)."""
        ticks = os.sysconf("SC_CLK_TCK")
        total = 0.0
        for pid in os.listdir("/proc"):
            if not pid.isdigit():
                continue
            try:
                with open(f"/proc/{pid}/stat") as f:
                    fields = f.read().rsplit(") ", 1)[1].split()
            except (OSError, IndexError):
                continue
            if int(fields[2]) == self.proc.pid:  # pgrp
                total += (int(fields[11]) + int(fields[12])) / ticks
        return total
