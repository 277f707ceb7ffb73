"""The one general traffic generator: closed-loop S3 clients in worker
processes of their own, driven by a traffic file's parameters.

A traffic mix is data (`benchmark/traffic/<mix>.json`): clients, worker
processes, operation shares, object sizes, preload, stagger, warm-up.  Every
client owns its keys, so no client reads what another deleted, and every
seed gets the same multiset of operations and sizes in another order:
the seed permutes a fixed cycle and draws the keys, it never changes the
amount of work.  Bodies are a pure function of (seed, key), so any
process can regenerate what a key must hold.

Workers are spawned (not forked) and never import JAX.  All clocks are
`time.perf_counter()`, which on Linux is the machine-wide monotonic
clock, so the parent's window and the workers' stamps share a time base.
"""

from __future__ import annotations

import multiprocessing as mp
import threading
import time
import zlib

import numpy as np

from benchmark import s3

BUCKET = "bench"
OPS = ("GET", "STAT", "PUT", "DELETE")
CYCLE = 200  # operations in the fixed cycle a seed permutes


def body_of(seed: int, key: str, size: int) -> bytes:
    """`size` incompressible bytes, a function of the seed and the key."""
    rng = np.random.Generator(np.random.SFC64(
        np.random.SeedSequence([seed, zlib.crc32(key.encode()), size])))
    words = rng.integers(0, 2**64, size=-(-size // 8), dtype=np.uint64,
                         endpoint=False)
    return words.tobytes()[:size]


def size_grid(sizes: dict) -> list[int]:
    """The fixed set of object sizes of a mix: one size, or `count`
    sizes evenly spaced from `min` to `max`, whole KiB."""
    if "fixed" in sizes:
        return [int(sizes["fixed"])]
    lo, hi, n = int(sizes["min"]), int(sizes["max"]), int(sizes["count"])
    return [(lo + (hi - lo) * i // (n - 1)) // 1024 * 1024 for i in range(n)]


def op_cycle(shares: dict[str, int]) -> list[str]:
    """CYCLE operations holding exactly the mix's shares (percent)."""
    if sum(shares.values()) != 100 or set(shares) - set(OPS):
        raise ValueError(f"shares must be percent of {OPS}: {shares}")
    out: list[str] = []
    for op in OPS:
        out += [op] * (shares.get(op, 0) * CYCLE // 100)
    if len(out) != CYCLE:
        raise ValueError(f"shares do not divide a cycle of {CYCLE}: {shares}")
    return out


class Client:
    """One closed-loop client: a connection, its keys, its schedule."""

    def __init__(self, cid: int, seed: int, mix: dict, port: int):
        self.cid, self.seed, self.mix = cid, seed, mix
        self.conn = s3.Connection(port, timeout=float(mix["timeout_s"]))
        self.grid = size_grid(mix["sizes"])
        self.rng = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence([seed, cid, 1])))
        ops = op_cycle(mix["shares"])
        self.cycle = [ops[i] for i in self.rng.permutation(CYCLE)]
        self.put_sizes = [self.grid[i % len(self.grid)]
                          for i in self.rng.permutation(CYCLE)]
        self.at = 0          # position in the cycle
        self.serial = 0      # keys this client has created
        self.live: list[str] = []        # keys that must read back
        self.size: dict[str, int] = {}   # key -> bytes it holds
        self.kept: dict[str, bytes] = {}  # bodies small enough to keep
        self.deleted: list[str] = []
        self.buf = bytearray(max(self.grid))

    # ------------------------------------------------------------ bodies
    def expected(self, key: str) -> bytes:
        body = self.kept.get(key)
        return body if body is not None else body_of(
            self.seed, key, self.size[key])

    def _new_key(self, kind: str) -> str:
        self.serial += 1
        return f"{kind}/c{self.cid:03d}/{self.serial:06d}"

    # -------------------------------------------------------- operations
    def put(self, key: str, size: int, keep: bool) -> tuple[bool, int]:
        body = body_of(self.seed, key, size)
        self.size[key] = size
        status, _ = self.conn.request("PUT", f"/{BUCKET}/{key}", body=body)
        if status != 200:
            return False, 0
        self.live.append(key)
        if keep:
            self.kept[key] = body
        return True, size

    def get(self, key: str) -> tuple[bool, int, bool]:
        """-> (ok, bytes, identical).  The body is read into this
        client's buffer and compared with one memcmp."""
        want = self.expected(key)
        buf = self.buf if len(self.buf) == len(want) else bytearray(len(want))
        status, got = self.conn.request("GET", f"/{BUCKET}/{key}", into=buf)
        if status != 200:
            return False, 0, True
        same = got == len(want) and buf == want
        return True, (len(want) if same else 0), same

    def stat(self, key: str) -> tuple[bool, int, bool]:
        status, _ = self.conn.request("HEAD", f"/{BUCKET}/{key}")
        same = self.conn.last_length == str(self.size[key])
        return status == 200, 0, same or status != 200

    def delete(self, key: str) -> tuple[bool, int]:
        status, _ = self.conn.request("DELETE", f"/{BUCKET}/{key}")
        if status not in (200, 204):
            return False, 0
        self.live.remove(key)
        self.kept.pop(key, None)
        self.deleted.append(key)
        return True, 0

    def one(self) -> tuple:
        """The next operation of the cycle -> a request-log row
        (client, op, key, start, ack, bytes, ok, identical)."""
        i = self.at % CYCLE
        op = self.cycle[i]
        self.at += 1
        if op == "PUT":
            key = self._new_key("new")
        else:
            key = self.live[int(self.rng.integers(len(self.live)))]
        t0 = time.perf_counter()
        same = True
        try:
            if op == "PUT":
                ok, n = self.put(key, self.put_sizes[i],
                                 keep=bool(self.mix["keep_bodies"]))
            elif op == "GET":
                ok, n, same = self.get(key)
            elif op == "STAT":
                ok, n, same = self.stat(key)
            else:
                ok, n = self.delete(key)
        except Exception:  # a dead connection, a timeout: a failed request
            ok, n = False, 0
        return (self.cid, op, key, t0, time.perf_counter(), n, ok, same)

    # ------------------------------------------------------------ phases
    def preload(self, count: int) -> int:
        """PUT this client's share of the preload; -> failures."""
        failed = 0
        for i in range(count):
            key = self._new_key("pre")
            size = self.grid[(i + self.cid) % len(self.grid)]
            ok, _ = self.put(key, size, keep=True)
            failed += not ok
        return failed

    def stream(self, t_begin: float, t0: float,
               seconds: float) -> list[tuple]:
        """This client's closed loop, from its staggered start through the
        warm-up to the window's end; the request in flight then is
        finished, and not counted.  Rows carry their phase by the moment
        of the acknowledgement: 0 warm-up (before t0), 1 the window's own
        (in [t0, t0 + seconds)), 2 after it.  Every client sends from
        before t0 until after the window, so every request of phase 1
        ran under the full load."""
        rows, t_end = [], t0 + seconds
        start = t_begin + self.cid * float(self.mix["stagger_s"])
        while time.perf_counter() < start:
            time.sleep(0.0005)
        while time.perf_counter() < t_end:
            row = self.one()
            rows.append(row + (0 if row[4] < t0 else 1 if row[4] < t_end
                               else 2,))
        return rows

    def verify(self, keys: set[str]) -> dict[str, int]:
        """After the window: every key of `keys` (acknowledged PUTs of
        the window) that is still live reads back identical, and every
        key deleted in the window is gone."""
        out = {"readback_compared": 0, "readback_mismatch": 0,
               "deleted_checked": 0, "deleted_still_there": 0}
        for key in self.live:
            if key not in keys:
                continue
            out["readback_compared"] += 1
            try:
                ok, _, same = self.get(key)
            except Exception:
                ok, same = False, False
            out["readback_mismatch"] += not (ok and same)
        for key in self.deleted:
            out["deleted_checked"] += 1
            try:
                status, _ = self.conn.request("GET", f"/{BUCKET}/{key}")
            except Exception:
                status = 0
            out["deleted_still_there"] += status != 404
        return out


def _each(clients: list[Client], fn) -> list:
    """Run fn(client) on one thread per client; results in order."""
    out: list = [None] * len(clients)

    def run(i: int) -> None:
        try:
            out[i] = fn(clients[i])
        except BaseException as e:  # handed to the parent, which raises
            out[i] = e

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(clients))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in out:
        if isinstance(r, BaseException):
            raise r
    return out


def worker_main(conn, cids: list[int], seed: int, mix: dict,
                port: int) -> None:
    """A worker process: its clients, and the parent's commands."""
    clients = [Client(c, seed, mix, port) for c in cids]
    while True:
        cmd, *args = conn.recv()
        if cmd == "quit":
            break
        try:
            if cmd == "preload":
                out = sum(_each(clients, lambda c: c.preload(args[0])))
            elif cmd == "stream":
                cpu0, w0 = time.process_time(), time.perf_counter()
                rows = _each(clients, lambda c: c.stream(*args))
                cpu = time.process_time() - cpu0
                out = {"rows": [r for rs in rows for r in rs],
                       "cpu_share": cpu / (time.perf_counter() - w0)}
            elif cmd == "verify":
                out = _each(clients, lambda c: c.verify(args[0]))
            elif cmd == "sizes":
                out = {k: v for c in clients for k, v in c.size.items()}
            else:
                raise ValueError(cmd)
            conn.send(("ok", out))
        except BaseException as e:
            conn.send(("error", repr(e)))
    for c in clients:
        c.conn.close()


class LoadGenerator:
    """The parent's handle on the worker processes."""

    def __init__(self, seed: int, mix: dict, port: int):
        ctx = mp.get_context("spawn")
        n, procs = int(mix["clients"]), int(mix["processes"])
        self.workers = []
        for w in range(procs):
            cids = list(range(w, n, procs))
            parent, child = ctx.Pipe()
            p = ctx.Process(target=worker_main,
                            args=(child, cids, seed, mix, port),
                            daemon=True)
            p.start()
            child.close()
            self.workers.append((p, parent))

    def send(self, cmd: str, *args) -> None:
        for _, conn in self.workers:
            conn.send((cmd, *args))

    def call(self, cmd: str, *args) -> list:
        self.send(cmd, *args)
        return self.recv()

    def recv(self) -> list:
        out = []
        for p, conn in self.workers:
            status, val = conn.recv()
            if status != "ok":
                raise RuntimeError(f"load generator worker: {val}")
            out.append(val)
        return out

    def close(self) -> None:
        for p, conn in self.workers:
            try:
                conn.send(("quit",))
            except OSError:
                pass
        for p, conn in self.workers:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join(timeout=10)
            conn.close()
