"""Scenario replay engine (ISSUE 15).

``build_schedule`` turns a :class:`Scenario` into a deterministic
arrival schedule — a pure function of the scenario (seeded
``random.Random``, no wall clock): same seed, same Poisson arrival
times, same op/key/size sequence.  ``schedule_digest`` pins that
(a re-run must reproduce it bit-exact).

:class:`ScenarioEngine` replays a schedule against a REAL HTTP server:
one persistent SigV4-signing connection per simulated client, open-loop
pacing (a client sleeps until each request's scheduled offset; when the
server falls behind, requests queue on the connection and the attained
rate — recorded honestly — drops below the scheduled rate).  After the
replay the engine closes the loop through the server's own accounting:

* ``GET /minio/admin/v3/slo?window=<scenario>`` answers the per-class
  availability/p99 the scenario asserts (the server's ring-buffer
  histograms, not a client stopwatch);
* on ANY violation, ``GET /minio/admin/v3/trace/summary`` (the retained
  tail-capture store) attributes the violation to the dominant span
  stage — WHICH stage ate the p99, not just that it was eaten.

Chaos hooks (ChaosDisk faults, pool drain, worker kill) are armed by
name: the caller supplies ``{name: (start_fn, stop_fn)}`` — the hooks
need server internals the engine deliberately doesn't know about.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import random
import threading
import time
import urllib.parse

from minio_tpu.server import sigv4
#: nearest-rank quantile shared with the trace summary — one
#: definition, so client-side and trace-derived percentiles can't
#: silently diverge
from minio_tpu.utils.tracing import quantile as _pctl

#: ops the schedule can carry; "mpu" is one *logical* request that the
#: engine executes as create + parts + complete (all MULTIPART-class on
#: the server side, one latency sample on the client side)
OPS = ("get", "head", "put", "list", "delete", "mpu")


def _rng(sc, tag: str) -> random.Random:
    # string seeds hash deterministically across runs/platforms in
    # random.Random's version-2 seeding
    return random.Random(f"{sc.seed}:{tag}")


#: catalog memo — GET verification reads it per sample, inside the
#: latency-timed section, so rebuilding the seeded RNG draws per
#: request would both waste the shared box's CPU and inflate the
#: client-side latencies the per-bucket SLO clauses assert against
_catalog_cache: dict[tuple, dict] = {}


def catalog(sc) -> dict[str, dict[str, int]]:
    """bucket -> key -> size; the setup PUTs and GET verification both
    derive from this (bodies via :func:`body_bytes`).  Memoized on the
    fields that determine it."""
    key = (sc.seed, sc.buckets, sc.nobjects, sc.obj_bytes)
    got = _catalog_cache.get(key)
    if got is not None:
        return got
    out: dict[str, dict[str, int]] = {}
    for bucket in sc.buckets:
        rng = _rng(sc, f"catalog:{bucket}")
        lo, hi = sc.obj_bytes
        out[bucket] = {f"o{i:04d}": rng.randint(lo, hi)
                       for i in range(sc.nobjects)}
    if len(_catalog_cache) > 64:
        _catalog_cache.clear()
    _catalog_cache[key] = out
    return out


def body_bytes(sc, tag: str, size: int) -> bytes:
    return _rng(sc, f"body:{tag}").randbytes(size)


def _zipf_weights(n: int, s: float) -> list[float]:
    w = [1.0 / (i ** s) for i in range(1, n + 1)]
    tot = sum(w)
    return [x / tot for x in w]


def build_schedule(sc) -> list[dict]:
    """Deterministic arrival schedule: Poisson arrivals at ``sc.rate``
    over ``sc.duration_s``, ops drawn by weight, keys by shape (zipf
    over the catalog for reads, fresh ``w``-keys for writes, earlier
    ``w``-keys for deletes).  Pure function of the scenario."""
    rng = _rng(sc, "schedule")
    names = sorted(catalog(sc)[sc.buckets[0]])
    zw = _zipf_weights(len(names), sc.zipf_s)
    ops = [op for op, _ in sc.ops]
    weights = [w for _, w in sc.ops]
    # per-bucket workload override (ISSUE 18): one choices() draw per
    # request either way, so scenarios without bucket_ops keep their
    # exact RNG stream (and their pinned digests)
    bops = {b: ([o for o, _ in mix], [w for _, w in mix])
            for b, mix in (getattr(sc, "bucket_ops", None) or {}).items()}
    bops_post = {b: ([o for o, _ in mix], [w for _, w in mix])
                 for b, mix in (getattr(sc, "bucket_ops_post_flip",
                                        None) or {}).items()}
    bclients = getattr(sc, "bucket_clients", None) or {}
    quiet = list(sc.buckets[1:]) or list(sc.buckets)
    profile = getattr(sc, "rate_profile", ()) or ()
    flip_frac = getattr(sc, "mix_flip_at_frac", None)
    flip_at = None if flip_frac is None else flip_frac * sc.duration_s

    def rate_at(now: float) -> float:
        # piecewise regime-shift multiplier (ISSUE 18): still a pure
        # function of the scenario, so the digest pins the shift
        for lo, hi, mult in profile:
            if lo * sc.duration_s <= now < hi * sc.duration_s:
                return sc.rate * mult
        return sc.rate

    sched: list[dict] = []
    written: dict[str, list[str]] = {b: [] for b in sc.buckets}
    t = 0.0
    i = 0
    while True:
        t += rng.expovariate(rate_at(t))
        if t >= sc.duration_s:
            break
        if flip_at is not None and sc.hot_bucket_frac is not None:
            # tenant-mix flip: the hot role moves to buckets[1]; the
            # displaced bucket joins the quiet set.  Gated on the flip
            # field so pre-existing scenarios keep their exact RNG
            # stream (and therefore their pinned schedule digests).
            hot_i = 0 if t < flip_at else 1 % len(sc.buckets)
            others = [b for j, b in enumerate(sc.buckets)
                      if j != hot_i] or list(sc.buckets)
            bucket = sc.buckets[hot_i] \
                if rng.random() < sc.hot_bucket_frac \
                else others[rng.randrange(len(others))]
        elif sc.hot_bucket_frac is not None:
            bucket = sc.buckets[0] if rng.random() < sc.hot_bucket_frac \
                else quiet[rng.randrange(len(quiet))]
        else:
            bucket = sc.buckets[rng.randrange(len(sc.buckets))]
        cur = bops
        if bops_post and flip_at is not None and t >= flip_at \
                and bucket in bops_post:
            cur = bops_post  # the flood itself moved tenants
        b_ops, b_weights = cur.get(bucket, (ops, weights))
        op = rng.choices(b_ops, weights=b_weights)[0]
        ent = {"i": i, "t": round(t, 6), "client": i % sc.clients,
               "op": op, "bucket": bucket}
        span = bclients.get(bucket)
        if span is not None:
            # dedicated pool: the bucket's own clients, round-robin
            ent["client"] = span[0] + i % span[1]
        if op in ("get", "head"):
            ent["key"] = rng.choices(names, weights=zw)[0]
        elif op == "put":
            key = f"w{i:06d}"
            ent["key"] = key
            ent["size"] = rng.randint(*sc.put_bytes)
            written[bucket].append(key)
        elif op == "delete":
            prior = written[bucket]
            if prior:
                ent["key"] = prior[rng.randrange(len(prior))]
            else:
                # nothing written yet: a delete of a catalog key would
                # break later reads; deleting a never-written w-key is
                # the S3-idempotent 204
                ent["key"] = f"w-missing-{i:06d}"
        elif op == "list":
            # a tens-bucket of the o%04d catalog keys: "o003" matches
            # o0030..o0039 — every scheduled prefix walks real entries
            ent["prefix"] = \
                f"o{rng.randrange((sc.nobjects + 9) // 10):03d}"
            ent["max_keys"] = sc.list_max_keys
        elif op == "mpu":
            ent["key"] = f"mpu{i:06d}"
            ent["parts"] = sc.mpu_parts
            ent["part_size"] = sc.mpu_part_bytes
            ent["last_size"] = sc.mpu_last_bytes
        i += 1
        sched.append(ent)
    return sched


def schedule_digest(schedule: list[dict]) -> str:
    """The reproducibility pin of a scenario's schedule."""
    return hashlib.sha256(json.dumps(
        schedule, sort_keys=True, separators=(",", ":")).encode()
    ).hexdigest()




class _ClientConn:
    """One simulated client: persistent connection + SigV4 signing.
    Reconnects on transport failure (counted by the caller)."""

    def __init__(self, host: str, port: int, ak: str, sk: str,
                 timeout: float = 60.0):
        self.host, self.port = host, port
        self.ak, self.sk = ak, sk
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None

    def _connection(self) -> http.client.HTTPConnection:
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout)
        return self._conn

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str, query=(), data=b"",
                headers=None) -> tuple[int, bytes, dict]:
        query = list(query)
        headers = dict(headers or {})
        headers["host"] = f"{self.host}:{self.port}"
        signed = sigv4.sign_request(method, path, query, headers,
                                    data or b"", self.ak, self.sk)
        qs = "&".join(
            f"{urllib.parse.quote(k, safe='')}="
            f"{urllib.parse.quote(v, safe='')}" for k, v in query)
        url = urllib.parse.quote(path) + ("?" + qs if qs else "")
        try:
            conn = self._connection()
            conn.request(method, url, body=data or None, headers=signed)
            r = conn.getresponse()
            body = r.read()
            return r.status, body, dict(r.getheaders())
        except Exception:
            # drop the broken connection; the next request reconnects
            self.close()
            raise


class ScenarioEngine:
    """Replays scenarios against a live server and renders verdicts.

    ``chaos_hooks``: ``{name: (start_fn, stop_fn)}`` armed when a
    scenario names one.  ``slo_slot_s`` must match the server's
    ``MINIO_TPU_SLO_SLOT_S`` — the engine waits one slot after a replay
    so the scenario's slots are complete before it asks the server."""

    def __init__(self, host: str, port: int, access_key: str,
                 secret_key: str, chaos_hooks: dict | None = None,
                 slo_slot_s: float = 1.0, log=None):
        self.host, self.port = host, port
        self.ak, self.sk = access_key, secret_key
        self.chaos_hooks = chaos_hooks or {}
        self.slo_slot_s = slo_slot_s
        self._log = log or (lambda *_: None)

    # ------------------------------------------------------------ admin
    def _admin(self, method: str, path: str, query=(), data=b""):
        c = _ClientConn(self.host, self.port, self.ak, self.sk)
        try:
            return c.request(method, path, query, data)
        finally:
            c.close()

    def admin_json(self, method: str, path: str, query=(), data=b""):
        status, body, _ = self._admin(method, path, query, data)
        if status != 200:
            raise RuntimeError(
                f"{method} {path} -> {status}: {body[:200]!r}")
        return json.loads(body)

    # ------------------------------------------------------------ setup
    def setup(self, sc) -> None:
        """Buckets + catalog objects (idempotent: overwrites)."""
        c = _ClientConn(self.host, self.port, self.ak, self.sk)
        try:
            for bucket, keys in catalog(sc).items():
                status, _, _ = c.request("PUT", f"/{bucket}")
                if status not in (200, 409):
                    raise RuntimeError(
                        f"create bucket {bucket}: {status}")
                for key, size in keys.items():
                    body = body_bytes(sc, f"{bucket}/{key}", size)
                    status, _, _ = c.request(
                        "PUT", f"/{bucket}/{key}", data=body)
                    if status != 200:
                        raise RuntimeError(
                            f"seed {bucket}/{key}: {status}")
        finally:
            c.close()

    # ----------------------------------------------------------- replay
    def _execute(self, sc, conn: _ClientConn, ent: dict) -> dict:
        op = ent["op"]
        bucket = ent["bucket"]
        # synthesize request payloads BEFORE the latency clock starts:
        # seeded-RNG body generation is client-side work, not server
        # latency (same reasoning as the catalog memo)
        payload = None
        if op == "put":
            payload = body_bytes(sc, f"put:{ent['i']}", ent["size"])
        elif op == "mpu":
            payload = [body_bytes(
                sc, f"mpu:{ent['i']}:{pn}",
                ent["part_size"] if pn < ent["parts"]
                else ent["last_size"])
                for pn in range(1, ent["parts"] + 1)]
        t0 = time.perf_counter()
        status = 0
        err = ""
        try:
            if op in ("get", "head"):
                status, body, _ = conn.request(
                    "GET" if op == "get" else "HEAD",
                    f"/{bucket}/{ent['key']}")
                if op == "get" and status == 200:
                    want = catalog(sc)[bucket][ent["key"]]
                    if len(body) != want:
                        err = f"short body {len(body)} != {want}"
            elif op == "put":
                status, _, _ = conn.request(
                    "PUT", f"/{bucket}/{ent['key']}", data=payload)
            elif op == "delete":
                status, _, _ = conn.request(
                    "DELETE", f"/{bucket}/{ent['key']}")
            elif op == "list":
                status, _, _ = conn.request(
                    "GET", f"/{bucket}",
                    query=[("list-type", "2"),
                           ("prefix", ent["prefix"]),
                           ("max-keys", str(ent["max_keys"]))])
            elif op == "mpu":
                status = self._execute_mpu(conn, ent, payload)
        except Exception as e:  # transport failure
            status = -1
            err = repr(e)
        dur = time.perf_counter() - t0
        api_cls = {"get": "GET", "head": "GET", "put": "PUT",
                   "delete": "DELETE", "list": "LIST",
                   "mpu": "MULTIPART"}[op]
        return {"op": op, "cls": api_cls, "bucket": bucket,
                "status": status, "dur": dur, "err": err}

    def _execute_mpu(self, conn: _ClientConn, ent: dict,
                     parts: list[bytes]) -> int:
        key = ent["key"]
        path = f"/{ent['bucket']}/{key}"
        status, body, _ = conn.request("POST", path,
                                       query=[("uploads", "")])
        if status != 200:
            return status
        text = body.decode(errors="replace")
        lo = text.find("<UploadId>")
        hi = text.find("</UploadId>")
        if lo < 0 or hi < 0:
            return -1
        upload_id = text[lo + len("<UploadId>"):hi]

        def fail(st: int) -> int:
            # abort the dangling upload so a chaos-failed attempt
            # doesn't leak staged parts into the rest of the run
            try:
                conn.request("DELETE", path,
                             query=[("uploadId", upload_id)])
            except Exception:
                pass
            return st

        etags = []
        for pn, part in enumerate(parts, start=1):
            status, _, hdrs = conn.request(
                "PUT", path, data=part,
                query=[("partNumber", str(pn)),
                       ("uploadId", upload_id)])
            if status != 200:
                return fail(status)
            etags.append((pn, hdrs.get("ETag", hdrs.get("Etag", ""))))
        xml = "<CompleteMultipartUpload>" + "".join(
            f"<Part><PartNumber>{pn}</PartNumber><ETag>{etag}</ETag>"
            f"</Part>" for pn, etag in etags) \
            + "</CompleteMultipartUpload>"
        status, _, _ = conn.request(
            "POST", path, data=xml.encode(),
            query=[("uploadId", upload_id)])
        return fail(status) if status != 200 else status

    def replay(self, sc, schedule: list[dict]
               ) -> tuple[list[dict], float, float]:
        """Run the schedule with ``sc.clients`` threads; returns
        (samples, wall_seconds, replay_t0) — ``replay_t0`` is the
        perf-counter instant the clients were released, the anchor for
        the asserted SLO window.  Chaos (when named) is armed by a
        timer thread against the registered hook."""
        chaos = None
        if sc.chaos:
            chaos = self.chaos_hooks.get(sc.chaos)
            if chaos is None:
                # a silent no-op here would record a chaos "pass" in
                # which the fault never happened — the regression
                # surface would quietly stop testing fault tolerance.
                # Checked BEFORE any client thread starts, so nothing
                # is left parked on the barrier.
                raise ValueError(
                    f"scenario {sc.name!r} names chaos hook "
                    f"{sc.chaos!r} but no such hook is registered "
                    f"(have: {sorted(self.chaos_hooks)})")
        samples: list[list[dict]] = [[] for _ in range(sc.clients)]
        barrier = threading.Barrier(sc.clients + 1)
        per_client = [[e for e in schedule if e["client"] == idx]
                      for idx in range(sc.clients)]
        t_start = [0.0]

        def worker(idx: int) -> None:
            conn = _ClientConn(self.host, self.port, self.ak, self.sk)
            try:
                barrier.wait(30)
                base = t_start[0]
                for ent in per_client[idx]:
                    delay = base + ent["t"] - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    samples[idx].append(self._execute(sc, conn, ent))
            finally:
                conn.close()

        # lint: allow(budget-propagation): simulated CLIENTS — load generators outside the server's budget plane by definition
        threads = [threading.Thread(target=worker, args=(i,),
                                    name=f"sim-client-{i}", daemon=True)
                   for i in range(sc.clients)]
        for th in threads:
            th.start()
        stop_evt = threading.Event()
        chaos_thread = None
        if chaos is not None:
            start_fn, stop_fn = chaos

            def chaos_runner():
                if stop_evt.wait(sc.duration_s * sc.chaos_at_frac):
                    return
                self._log(f"  chaos[{sc.chaos}] armed")
                try:
                    start_fn()
                    stop_evt.wait(sc.duration_s * sc.chaos_dur_frac)
                finally:
                    stop_fn()
                    self._log(f"  chaos[{sc.chaos}] cleared")

            # lint: allow(budget-propagation): chaos timer for the scenario window, not request work
            chaos_thread = threading.Thread(
                target=chaos_runner, name="sim-chaos", daemon=True)
        t0 = time.perf_counter()
        t_start[0] = t0
        barrier.wait(30)
        if chaos_thread is not None:
            chaos_thread.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        stop_evt.set()
        if chaos_thread is not None:
            # the stop hook may poll server state to a terminal
            # condition (the drain hook waits out the decommission) —
            # give it real room; it is bounded by construction and the
            # verdict must reflect its outcome, not race past it
            chaos_thread.join(sc.duration_s + 180)
        return [s for per in samples for s in per], wall, t0

    # ---------------------------------------------------------- verdict
    @staticmethod
    def _aggregate(samples: list[dict], key) -> dict:
        groups: dict[str, dict] = {}
        for s in samples:
            k = key(s)
            d = groups.get(k)
            if d is None:
                d = groups[k] = {"count": 0, "errors": 0, "shed": 0,
                                 "durs": []}
            d["count"] += 1
            if s["status"] == 503:
                d["shed"] += 1
            elif s["status"] < 0 or s["status"] >= 500 or s["err"]:
                d["errors"] += 1
            d["durs"].append(s["dur"])
        out = {}
        for k, d in sorted(groups.items()):
            ds = sorted(d["durs"])
            out[k] = {
                "count": d["count"], "errors": d["errors"],
                "shed": d["shed"],
                "p50Ms": round(_pctl(ds, 0.50) * 1e3, 3),
                "p99Ms": round(_pctl(ds, 0.99) * 1e3, 3),
                "maxMs": round(ds[-1] * 1e3, 3),
            }
        return out

    def _attribute(self, since: float = 0.0) -> dict | None:
        """Dominant-stage attribution from the retained trace store:
        non-root span names ranked by total seconds (the root spans ARE
        the requests; the stages under them are where the time went).
        ``since`` (epoch) scopes the aggregate to this scenario's
        traces — the store spans the whole run, and an earlier
        scenario's 5 MiB part writes must not out-weigh the violating
        scenario's own stages."""
        try:
            doc = self.admin_json(
                "GET", "/minio/admin/v3/trace/summary",
                query=[("since", f"{since:.3f}")] if since else [])
        except Exception as e:
            return {"error": f"trace summary unavailable: {e!r}"}
        stages = {name: d for name, d in doc.get("spans", {}).items()
                  if not d.get("isRoot")}
        if not stages:
            return {"error": "no retained spans to attribute"}
        ranked = sorted(stages.items(), key=lambda kv: -kv[1]["totalS"])
        name, top = ranked[0]
        try:
            slow = self.admin_json("GET", "/minio/admin/v3/trace/slow",
                                   query=[("n", "50")])
            # scope to this scenario like the summary: the store spans
            # the whole run and a newest-first backfill would point
            # the investigator at another scenario's traces
            trace_ids = [t.get("traceId")
                         for t in slow.get("traces", [])
                         if t.get("start", 0.0) >= since][:5]
        except Exception:
            trace_ids = []
        return {
            "dominantStage": name,
            "totalS": top["totalS"], "count": top["count"],
            "p99Ms": top["p99Ms"],
            "top": [{"stage": n, "totalS": d["totalS"],
                     "p99Ms": d["p99Ms"]} for n, d in ranked[:3]],
            "slowTraceIds": trace_ids,
            "tracesAggregated": doc.get("traces", 0),
        }

    def run(self, sc) -> dict:
        """setup -> (qos apply) -> replay -> server-side SLO assertion
        -> (forensics on violation) -> scenario doc."""
        self._log(f"scenario {sc.name}: setup")
        self.setup(sc)
        schedule = build_schedule(sc)
        digest = schedule_digest(schedule)
        qos_applied = False
        try:
            if sc.qos is not None:
                self.admin_json("PUT", "/minio/admin/v3/qos",
                                data=json.dumps(sc.qos).encode())
                qos_applied = True
            # let the setup PUTs' slots close so the scenario window
            # below measures replay traffic, not catalog seeding: the
            # trailing window's FLOOR slot is included whole by
            # _Ring.agg_windows, so the gap must span two full slots
            time.sleep(self.slo_slot_s * 2.1)
            self._log(f"scenario {sc.name}: replaying "
                      f"{len(schedule)} requests over "
                      f"{sc.duration_s:g}s")
            replay_wall0 = time.time()
            samples, wall, replay_t0 = self.replay(sc, schedule)
        finally:
            if qos_applied:
                try:
                    self.admin_json("PUT", "/minio/admin/v3/qos",
                                    data=json.dumps(
                                        {"enable": False}).encode())
                except Exception as e:
                    # a failed revert must not mask the replay's own
                    # exception — but it must be LOUD: the shared
                    # server is left throttled for whatever runs next
                    self._log(f"scenario {sc.name}: QOS REVERT "
                              f"FAILED ({e!r}) — plane left enabled")
        # let the scenario's final slot close before asking the server
        time.sleep(self.slo_slot_s * 1.1)
        # the window is a TRAILING window anchored at query time, so it
        # must reach back to replay START — a chaos stop hook that
        # polled server state after the workers finished (the drain
        # hook) would otherwise push the replay's head out of the
        # asserted window
        window = (time.perf_counter() - replay_t0) + self.slo_slot_s
        server = self.admin_json("GET", "/minio/admin/v3/slo",
                                 query=[("window", f"{window:.3f}")])
        by_class = self._aggregate(samples, lambda s: s["cls"])
        by_bucket = self._aggregate(samples, lambda s: s["bucket"])
        total = len(samples)
        sheds = sum(1 for s in samples if s["status"] == 503)
        shed_fraction = sheds / total if total else 0.0

        violations: list[str] = []
        slo = sc.slo or {}
        if not server.get("enabled"):
            violations.append("slo-plane-disabled")
        for cls, targets in sorted((slo.get("classes") or {}).items()):
            srv = (server.get("classes") or {}).get(cls)
            win = (srv or {}).get("window") or {}
            if srv is None or not win.get("requests"):
                violations.append(f"{cls}:no-server-data")
                continue
            tgt_p99 = targets.get("p99_ms")
            if tgt_p99 is not None and win.get("p99Ms") is not None \
                    and win["p99Ms"] > tgt_p99:
                violations.append(
                    f"{cls}:latency p99 {win['p99Ms']}ms > "
                    f"{tgt_p99}ms")
            tgt_av = targets.get("availability")
            if tgt_av is not None and win.get("availability") is not None \
                    and win["availability"] < tgt_av:
                violations.append(
                    f"{cls}:availability {win['availability']} < "
                    f"{tgt_av}")
        max_shed = slo.get("shed_fraction_max")
        if max_shed is not None and shed_fraction > max_shed:
            violations.append(
                f"shed fraction {shed_fraction:.4f} > {max_shed}")
        for bucket, targets in sorted((slo.get("buckets") or {}).items()):
            b = by_bucket.get(bucket)
            if b is None:
                violations.append(f"bucket:{bucket}:no-traffic")
                continue
            tgt_p99 = targets.get("p99_ms")
            if tgt_p99 is not None and b["p99Ms"] > tgt_p99:
                violations.append(
                    f"bucket:{bucket}: p99 {b['p99Ms']}ms > "
                    f"{tgt_p99}ms")
            tgt_p50 = targets.get("p50_ms")
            if tgt_p50 is not None and b["p50Ms"] > tgt_p50:
                violations.append(
                    f"bucket:{bucket}: p50 {b['p50Ms']}ms > "
                    f"{tgt_p50}ms")
            shed_max = targets.get("shed_max")
            if shed_max is not None and b["shed"] > shed_max:
                violations.append(
                    f"bucket:{bucket}: {b['shed']} sheds > {shed_max}")
            shed_frac = targets.get("shed_frac_max")
            if shed_frac is not None and b["count"] \
                    and b["shed"] / b["count"] > shed_frac:
                violations.append(
                    f"bucket:{bucket}: shed fraction "
                    f"{b['shed'] / b['count']:.4f} > {shed_frac}")

        doc = {
            "name": sc.name,
            "description": sc.description,
            "seed": sc.seed,
            "durationS": sc.duration_s,
            "clients": sc.clients,
            "scheduledRate": sc.rate,
            "chaos": sc.chaos,
            "scheduleRequests": len(schedule),
            "scheduleSha256": digest,
            "wallS": round(wall, 3),
            "attainedReqPerS": round(total / wall, 3) if wall else 0.0,
            "shedFraction": round(shed_fraction, 6),
            "byClass": by_class,
            "byBucket": by_bucket if len(sc.buckets) > 1 else None,
            "serverSlo": {
                "enabled": server.get("enabled"),
                "windowS": window,
                "classes": {
                    cls: d.get("window")
                    for cls, d in (server.get("classes") or {}).items()},
                "burn": {
                    cls: d.get("burn")
                    for cls, d in (server.get("classes") or {}).items()},
                "tenants": server.get("tenants"),
            },
            "violations": violations,
            "verdict": "pass" if not violations else "fail",
            # 0.5s slack: a trace that began just before the replay
            # clock tick still belongs to this scenario
            "attribution": self._attribute(
                since=replay_wall0 - 0.5) if violations else None,
        }
        self._log(f"scenario {sc.name}: {doc['verdict']}"
                  + (f" ({violations})" if violations else ""))
        return doc

    def run_all(self, scenarios, capacity_probe: dict | None = None
                ) -> dict:
        results = [self.run(sc) for sc in scenarios]
        doc = {
            "schema": 1,
            "scenarios": results,
            "passCount": sum(1 for r in results
                             if r["verdict"] == "pass"),
            "failCount": sum(1 for r in results
                             if r["verdict"] == "fail"),
        }
        if capacity_probe:
            doc["capacityModel"] = self.capacity_model(
                results, capacity_probe)
        return doc

    @staticmethod
    def capacity_model(results: list[dict],
                       probe: dict) -> dict:
        """Fit of attained req/s against the box probes' effective
        cores (PR 8's ``_probe_effective_cores``): a deliberately
        simple linear model ``req/s ~= k * cores`` per scenario shape,
        so future PRs regress against a surface — 'zipf fan-in dropped
        from 41 to 28 req/s/core' — instead of anecdotes."""
        cores = max(float(probe.get("effectiveCores", 1.0)), 1e-6)
        points = [{"scenario": r["name"],
                   "attainedReqPerS": r["attainedReqPerS"],
                   "scheduledRate": r["scheduledRate"],
                   "chaos": r["chaos"],
                   "reqPerSPerCore": round(
                       r["attainedReqPerS"] / cores, 3)}
                  for r in results]
        clean = [p["reqPerSPerCore"] for p in points
                 if not p["chaos"]]
        return {
            "probe": probe,
            "points": points,
            "cleanReqPerSPerCore": {
                "max": max(clean) if clean else None,
                "min": min(clean) if clean else None,
            },
            "model": "req_per_s ≈ k × effective_cores; k per scenario "
                     "shape in points[].reqPerSPerCore (chaos "
                     "scenarios excluded from the clean envelope)",
        }
