"""Prometheus metrics + health endpoints.

Reference: cmd/metrics-v2.go (metric groups for capacity, drives, API
requests, heal, replication, scanner) served at
/minio/v2/metrics/{cluster,node}, and cmd/healthcheck-handler.go:36
(/minio/health/{live,ready,cluster} with quorum awareness).

Auth follows the reference default: metrics require an authenticated
admin principal (admin:Prometheus) unless MINIO_PROMETHEUS_AUTH_TYPE is
set to "public".  Health endpoints are always unauthenticated.
"""

from __future__ import annotations

import os
import time

from aiohttp import web

from minio_tpu.utils.prom import Registry, _fmt_labels
from .s3errors import S3Error

METRICS_PREFIX = "/minio/v2/metrics"
HEALTH_PREFIX = "/minio/health"

# request-duration buckets tuned for object storage (reference uses
# 8 buckets from 50ms..10s plus the Go client defaults)
API_BUCKETS = (.005, .025, .05, .1, .25, .5, 1, 2.5, 5, 10, 30)


class MetricsMixin:
    """Mixin for S3Server: registry, per-request recording, endpoints."""

    def init_metrics(self) -> None:
        r = Registry()
        self.metrics = r
        self._m_requests = r.counter(
            "minio_s3_requests_total",
            "Total S3 API requests", ("api",))
        self._m_errors = r.counter(
            "minio_s3_requests_errors_total",
            "S3 requests that returned an error", ("api",))
        self._m_4xx = r.counter(
            "minio_s3_requests_4xx_errors_total",
            "S3 requests with a 4xx response", ("api",))
        self._m_5xx = r.counter(
            "minio_s3_requests_5xx_errors_total",
            "S3 requests with a 5xx response", ("api",))
        self._m_ttfb = r.histogram(
            "minio_s3_ttfb_seconds",
            "Time to serve an S3 request", ("api",), buckets=API_BUCKETS)
        self._m_inflight = r.gauge(
            "minio_s3_requests_inflight_total",
            "Currently executing S3 requests")
        # admission control / deadline plane (reference requests_deadline,
        # cmd/handler-api.go:108)
        self._m_queue_wait = r.histogram(
            "minio_s3_queue_wait_seconds",
            "Admission queue wait before an API slot was granted",
            buckets=(.001, .005, .01, .05, .1, .25, .5, 1, 2.5, 5, 10))
        self._m_queue_waiting = r.gauge(
            "minio_s3_requests_waiting_total",
            "Requests currently waiting for an API slot")
        self._m_shed = r.counter(
            "minio_s3_requests_shed_total",
            "Requests shed with 503 SlowDown at admission")
        # hot-object serving tier (ISSUE 7): probable cache hits that
        # bypassed the saturated API lane via the dedicated hot lane —
        # RAM-served reads never queue behind drive-bound work
        self._m_hot_lane = r.counter(
            "minio_hotcache_lane_admissions_total",
            "Requests admitted through the hot-cache fast lane")
        self._m_rx = r.counter(
            "minio_s3_traffic_received_bytes",
            "Bytes received from S3 clients")
        self._m_tx = r.counter(
            "minio_s3_traffic_sent_bytes",
            "Bytes sent to S3 clients")
        self._m_uptime = r.gauge(
            "minio_node_uptime_seconds", "Server uptime")
        self._m_uptime.set_function(
            lambda: time.time() - self._start_time)

    # -- recording (called from the request funnel) --------------------------
    def record_api(self, api: str, status: int, dt: float,
                   rx: int = 0, tx: int = 0) -> None:
        self._m_requests.labels(api).inc()
        self._m_ttfb.labels(api).observe(dt)
        if status >= 500:
            self._m_5xx.labels(api).inc()
            self._m_errors.labels(api).inc()
        elif status >= 400:
            self._m_4xx.labels(api).inc()
            self._m_errors.labels(api).inc()
        if rx:
            self._m_rx.inc(rx)
        if tx:
            self._m_tx.inc(tx)

    # -- routes --------------------------------------------------------------
    def register_metrics_routes(self, app: web.Application) -> None:
        r = app.router
        r.add_get(f"{METRICS_PREFIX}/cluster", self.handle_metrics)
        r.add_get(f"{METRICS_PREFIX}/node", self.handle_metrics)
        r.add_get(f"{HEALTH_PREFIX}/live", self.handle_health_live)
        r.add_get(f"{HEALTH_PREFIX}/ready", self.handle_health_ready)
        r.add_get(f"{HEALTH_PREFIX}/cluster", self.handle_health_cluster)
        # reference also answers HEAD for the probes
        r.add_head(f"{HEALTH_PREFIX}/live", self.handle_health_live)
        r.add_head(f"{HEALTH_PREFIX}/ready", self.handle_health_ready)

    async def _metrics_auth(self, request: web.Request) -> None:
        if os.environ.get(
                "MINIO_PROMETHEUS_AUTH_TYPE", "").lower() == "public":
            return
        # same admin gate as every other admin op (incl. the service-
        # account/STS denial), action admin:Prometheus
        await self._admin_auth(request, await request.read(), "Prometheus")

    async def handle_metrics(self, request: web.Request) -> web.Response:
        try:
            await self._metrics_auth(request)
        except S3Error as e:
            return web.Response(status=e.status, text=e.code)
        text = await self._run(self._render_metrics)
        return web.Response(
            text=text, content_type="text/plain", charset="utf-8")

    def _render_metrics(self) -> str:
        """Registry counters + point-in-time cluster gauges."""
        lines = [self.metrics.render()]
        g = lines.append

        def gauge(name, help_, value, labels=""):
            g(f"# HELP {name} {help_}\n# TYPE {name} gauge\n"
              f"{name}{labels} {value}\n")

        # capacity + drive status (reference ClusterCapacity/ClusterDrive)
        try:
            si = self.api.storage_info()
            drives = [d for pool in si["pools"] for d in pool["disks"]]
            total = sum(d.get("total", 0) for d in drives)
            free = sum(d.get("free", 0) for d in drives)
            gauge("minio_cluster_capacity_raw_total_bytes",
                  "Total raw drive capacity", total)
            gauge("minio_cluster_capacity_raw_free_bytes",
                  "Free raw drive capacity", free)
            gauge("minio_cluster_drive_total", "Drives in the cluster",
                  len(drives))
            gauge("minio_cluster_drive_online_total", "Online drives",
                  sum(1 for d in drives if d.get("online")))
            gauge("minio_cluster_drive_offline_total", "Offline drives",
                  sum(1 for d in drives if not d.get("online")))
            # drive-health circuit breaker (reference drive offline
            # tracking, cmd/xl-storage-disk-id-check.go): open breakers
            # and lifetime trip/reconnect counters; fast-fail counts and
            # per-op latencies are storageinfo's (`health`, `opStats`)
            gauge("minio_cluster_drive_breaker_open_total",
                  "Drives with an open health circuit breaker",
                  sum(1 for d in drives
                      if (d.get("health") or {}).get("breakerOpen")))
            hl = []
            for name, help_, key in (
                    ("minio_drive_breaker_trips_total",
                     "Circuit-breaker trips per drive", "trips"),
                    ("minio_drive_reconnects_total",
                     "Probe-driven drive reconnects", "reconnects")):
                rows = [f"# HELP {name} {help_}", f"# TYPE {name} gauge"]
                any_ = False
                for d in drives:
                    h = d.get("health")
                    if h and h.get(key):
                        lbl = _fmt_labels(("drive",), (d["endpoint"],))
                        rows.append(f"{name}{lbl} {h[key]}")
                        any_ = True
                if any_:
                    hl.append("\n".join(rows) + "\n")
            for block in hl:
                g(block)
        except Exception:
            pass

        # erasure codec backend: which codec served PUT/GET/heal bytes
        # (the auto probe's verdict is admin info's, `erasure.deviceProbe`)
        try:
            from minio_tpu.erasure import coding as ec

            bl = ["# HELP minio_erasure_backend_dispatches_total Erasure "
                  "dispatches per codec backend",
                  "# TYPE minio_erasure_backend_dispatches_total gauge"]
            byl = ["# HELP minio_erasure_backend_bytes_total Erasure "
                   "bytes per codec backend",
                   "# TYPE minio_erasure_backend_bytes_total gauge"]
            for name, st in ec.backend_stats.items():
                lbl = _fmt_labels(("backend",), (name,))
                bl.append("minio_erasure_backend_dispatches_total"
                          f"{lbl} {st['dispatches']}")
                byl.append("minio_erasure_backend_bytes_total"
                           f"{lbl} {st['bytes']}")
            g("\n".join(bl) + "\n")
            g("\n".join(byl) + "\n")
            states = ec.geometry_states()
            if states:
                rl = ["# HELP minio_erasure_geometry_ready 1 once the "
                      "geometry's device programs are compiled and "
                      "self-tested, 0 while its dispatches are on the "
                      "host codec (warming, failed, no device)",
                      "# TYPE minio_erasure_geometry_ready gauge"]
                rl += ["minio_erasure_geometry_ready"
                       f"{_fmt_labels(('geometry',), (geom,))} "
                       f"{int(state == 'device')}"
                       for geom, state in states.items()]
                g("\n".join(rl) + "\n")
        except Exception:
            pass

        # object data-plane stage attribution (erasure/stagestats.py):
        # thread-seconds of work, bytes and wall time per pipeline
        # stage, so the codec-vs-client throughput gap is attributable.
        # Threads overlap inside a stage and stages overlap each other
        # (that is the pipeline working), so thread-seconds may exceed
        # request wall time; a stage whose WALL seconds near the
        # window's names the bottleneck.
        try:
            from minio_tpu.erasure import stagestats

            snap = stagestats.snapshot()
            srows = ["# HELP minio_dataplane_stage_seconds_total "
                     "Thread-seconds of work per object data-plane "
                     "pipeline stage; <stage>_cpu: of those, the "
                     "seconds on a CPU; loop_cpu: the event loop "
                     "thread's CPU seconds",
                     "# TYPE minio_dataplane_stage_seconds_total gauge"]
            brows = ["# HELP minio_dataplane_stage_bytes_total Bytes "
                     "processed per object data-plane pipeline stage",
                     "# TYPE minio_dataplane_stage_bytes_total gauge"]
            wrows = ["# HELP minio_dataplane_stage_wall_seconds_total "
                     "Seconds during which at least one thread was "
                     "inside the pipeline stage",
                     "# TYPE minio_dataplane_stage_wall_seconds_total "
                     "gauge"]
            srows += ["minio_dataplane_stage_seconds_total"
                      f"{_fmt_labels(('stage',), (row,))} "
                      f"{round(seconds, 6)}"
                      for row, seconds
                      in stagestats.seconds_rows(snap).items()]
            for stage, d in snap.items():
                lbl = _fmt_labels(("stage",), (stage,))
                brows.append("minio_dataplane_stage_bytes_total"
                             f"{lbl} {int(d['bytes'])}")
                wrows.append("minio_dataplane_stage_wall_seconds_total"
                             f"{lbl} {round(d['wall'], 6)}")
            g("\n".join(srows) + "\n")
            g("\n".join(brows) + "\n")
            g("\n".join(wrows) + "\n")
        except Exception:
            pass

        # S3 Select engine-tier counters: which tier answered queries
        # and how often the fast paths fell back or replayed blocks
        # (VERDICT r4 #1 done-condition: the eligibility cliff is
        # observable, not silent)
        try:
            import minio_tpu.select as sel_pkg
            from minio_tpu.select import batch as sel_batch
            from minio_tpu.select import columnar as sel_col
            from minio_tpu.select import native as sel_nat

            gauge("minio_select_native_queries_total",
                  "Select queries served by the native C++ scan tier",
                  sel_nat.stats["native"])
            gauge("minio_select_native_fallback_total",
                  "Select queries the native tier declined",
                  sel_nat.stats["fallback"])
            gauge("minio_select_native_replay_blocks_total",
                  "Blocks replayed through the row engine for exact "
                  "semantics", sel_nat.stats["replay_blocks"])
            gauge("minio_select_columnar_queries_total",
                  "Select queries served by the pyarrow columnar tier",
                  sel_col.stats["fast"])
            gauge("minio_select_batch_queries_total",
                  "Select queries served by the compiled row tier",
                  sel_batch.stats["batch"])
            gauge("minio_select_row_engine_queries_total",
                  "Select queries that fell through to the row engine",
                  sel_pkg.row_stats["queries"])
            # per-tier bytes scanned + the residual-replay fraction,
            # so the <5%-residual claim is measurable in production
            # (ISSUE 2: not just in bench)
            rows = ["# HELP minio_select_scanned_bytes_total Bytes "
                    "scanned per Select engine tier",
                    "# TYPE minio_select_scanned_bytes_total gauge"]
            for tier, nbytes in (
                    ("native", sel_nat.stats["bytes_scanned"]),
                    ("batch", sel_batch.stats["bytes"]),
                    ("row", sel_pkg.row_stats["bytes"])):
                rows.append("minio_select_scanned_bytes_total"
                            f'{{tier="{tier}"}} {nbytes}')
            g("\n".join(rows) + "\n")
            scanned = sel_nat.stats["bytes_scanned"]
            gauge("minio_select_native_replay_fraction",
                  "Fraction of native-tier bytes re-decided by the "
                  "Python replay (the residual exactness path)",
                  round(sel_nat.stats["bytes_replayed"] / scanned, 6)
                  if scanned else 0.0)
        except Exception:
            pass

        # repair planner/executor (erasure/repair.py): survivor bytes
        # read per scheme is THE heal-bandwidth signal — sub-shard
        # repair wins when its bytes_read stays well under full's for
        # the same healed objects; fallbacks count aborted ranged
        # repairs that converged via the full decode
        try:
            from minio_tpu.erasure import repair as repair_mod

            rsnap = repair_mod.stats_snapshot()
            rrows = ["# HELP minio_repair_bytes_read_total Survivor "
                     "frame bytes read per repair scheme",
                     "# TYPE minio_repair_bytes_read_total gauge"]
            prows = ["# HELP minio_repair_plans_total Repair planner "
                     "decisions per scheme",
                     "# TYPE minio_repair_plans_total gauge"]
            for scheme in ("full", "subshard"):
                lbl = _fmt_labels(("scheme",), (scheme,))
                rrows.append("minio_repair_bytes_read_total"
                             f"{lbl} {rsnap[scheme]['bytes_read']}")
                prows.append("minio_repair_plans_total"
                             f"{lbl} {rsnap[scheme]['plans']}")
            g("\n".join(rrows) + "\n")
            g("\n".join(prows) + "\n")
            gauge("minio_repair_fallbacks_total",
                  "Sub-shard repairs aborted mid-flight and converged "
                  "via the full-shard decode", rsnap["fallbacks"])
            gauge("minio_repair_target_scan_bytes_total",
                  "Target-shard bytes read by residual scans and "
                  "executor re-verification", rsnap["target_scan_bytes"])
        except Exception:
            pass

        # hot-object serving tier (serving/hotcache.py): hit/miss/fill
        # economics of the in-RAM tier — collapsed_reads counts GETs
        # that shared another request's single erasure read, and
        # invalidations counts choke-point drops (writes racing reads)
        hc = getattr(self, "hotcache", None)
        if hc is not None:
            hs = hc.stats()
            gauge("minio_hotcache_hits_total",
                  "GET/HEAD requests served from the hot-object tier",
                  hs["hits"])
            gauge("minio_hotcache_misses_total",
                  "Hot-tier lookups that fell through to the erasure "
                  "path", hs["misses"])
            gauge("minio_hotcache_fills_total",
                  "Completed back-end fill reads led by one request",
                  hs["fills"])
            gauge("minio_hotcache_collapsed_reads_total",
                  "GETs that streamed from another request's in-flight "
                  "fill instead of touching drives", hs["collapsed"])
            gauge("minio_hotcache_evictions_total",
                  "Entries evicted by the segmented-LRU byte budget",
                  hs["evictions"])
            gauge("minio_hotcache_invalidations_total",
                  "Choke-point invalidations (overwrite/copy/delete/"
                  "multipart/heal rewrites)", hs["invalidations"])
            gauge("minio_hotcache_bytes",
                  "Resident bytes in the hot-object tier", hs["bytes"])
            gauge("minio_hotcache_hit_ratio",
                  "Fraction of hot-tier lookups served from RAM",
                  hs["hitRatio"])

        # per-tenant QoS plane (server/qos.py, ISSUE 13): queue depth,
        # admissions, sheds, DRR rounds and metered bytes per tenant —
        # the noisy-neighbor forensics surface.  Rendered only while
        # the plane is on, so MINIO_TPU_QOS=0 stays metrics-identical
        # to the single-semaphore server.
        qos = getattr(self, "qos", None)
        if qos is not None:
            qs = qos.stats()
            gauge("minio_qos_deficit_rounds_total",
                  "DRR dispatch rotation rounds swept",
                  qs["deficitRounds"])
            per_tenant = [
                ("minio_qos_queue_length",
                 "Requests queued for admission per tenant",
                 "queueDepth"),
                ("minio_qos_inflight_count",
                 "Granted in-flight requests per tenant", "inflight"),
                ("minio_qos_admitted_total",
                 "Requests admitted per tenant", "admitted"),
                ("minio_qos_hot_lane_rejections_total",
                 "Hot-lane re-probe failures that fell back to the "
                 "QoS lane per tenant", "hotLaneRejections"),
            ]
            for name, help_, field in per_tenant:
                rows = [f"# HELP {name} {help_}", f"# TYPE {name} gauge"]
                for t, ts in sorted(qs["tenants"].items()):
                    lbl = _fmt_labels(("tenant",), (t,))
                    rows.append(f"{name}{lbl} {ts[field]}")
                g("\n".join(rows) + "\n")
            rows = ["# HELP minio_qos_shed_total Requests shed 503 per "
                    "tenant and reason (queue_full|deadline|hot_lane)",
                    "# TYPE minio_qos_shed_total gauge"]
            for t, ts in sorted(qs["tenants"].items()):
                # hot_lane: hot-lane claims refused at the tenant's cap
                # (the request fell back to normal QoS admission instead
                # of crowding hot_sem — the PR 13 carried leftover)
                for reason, field in (("queue_full", "shedQueueFull"),
                                      ("deadline", "shedDeadline"),
                                      ("hot_lane", "hotLaneCapped")):
                    lbl = _fmt_labels(("tenant", "reason"), (t, reason))
                    rows.append(f"minio_qos_shed_total{lbl} {ts[field]}")
            g("\n".join(rows) + "\n")
            rows = ["# HELP minio_qos_throttled_bytes_total Data-plane "
                    "bytes metered per tenant and direction (in=PUT "
                    "ingest, out=GET streaming)",
                    "# TYPE minio_qos_throttled_bytes_total gauge"]
            for t, ts in sorted(qs["tenants"].items()):
                for direction, field in (("in", "throttledInBytes"),
                                         ("out", "throttledOutBytes")):
                    lbl = _fmt_labels(("tenant", "direction"),
                                      (t, direction))
                    rows.append(
                        f"minio_qos_throttled_bytes_total{lbl} "
                        f"{ts[field]}")
            g("\n".join(rows) + "\n")

        # closed-loop SLO plane (server/slo.py, ISSUE 15): per-class
        # latency histograms over the slow window, objective-attainment
        # ratios (>= 1.0 means the objective is met) and multi-window
        # error-budget burn rates.  Rendered only while the plane is on
        # (MINIO_TPU_SLO), so the default server stays metrics-
        # identical to before.
        slo = getattr(self, "slo", None)
        # presence-guarded like the other conditional families: a
        # gate-on server that has recorded nothing emits none of them
        if slo is not None and (snap := slo.snapshot_for_metrics()):
            lat = ["# HELP minio_slo_latency_bucket Request latency "
                   "per SLO API class over the slow window "
                   "(cumulative, seconds)",
                   "# TYPE minio_slo_latency_bucket gauge"]
            for cls, d in snap.items():
                for le, cum in d["buckets"]:
                    lbl = _fmt_labels(("class", "le"), (cls, str(le)))
                    lat.append(f"minio_slo_latency_bucket{lbl} {cum}")
                lbl = _fmt_labels(("class", "le"), (cls, "+Inf"))
                lat.append(f"minio_slo_latency_bucket{lbl} "
                           f"{d['count']}")
            g("\n".join(lat) + "\n")
            rows = ["# HELP minio_slo_requests_count Requests recorded "
                    "per SLO API class over the slow window",
                    "# TYPE minio_slo_requests_count gauge"]
            srows = ["# HELP minio_slo_latency_sum_seconds Summed "
                     "request latency per SLO API class over the slow "
                     "window",
                     "# TYPE minio_slo_latency_sum_seconds gauge"]
            for cls, d in snap.items():
                lbl = _fmt_labels(("class",), (cls,))
                rows.append(f"minio_slo_requests_count{lbl} "
                            f"{d['count']}")
                srows.append(f"minio_slo_latency_sum_seconds{lbl} "
                             f"{d['sum']}")
            g("\n".join(rows) + "\n")
            g("\n".join(srows) + "\n")
            rows = ["# HELP minio_slo_objective_ratio Measured-vs-"
                    "objective attainment per class and objective "
                    "(>= 1.0 = meeting it)",
                    "# TYPE minio_slo_objective_ratio gauge"]
            any_ratio = False
            for cls, d in snap.items():
                for objective, ratio in sorted(d["ratios"].items()):
                    lbl = _fmt_labels(("class", "objective"),
                                      (cls, objective))
                    rows.append(
                        f"minio_slo_objective_ratio{lbl} {ratio}")
                    any_ratio = True
            if any_ratio:
                g("\n".join(rows) + "\n")
            rows = ["# HELP minio_slo_error_budget_burn Error-budget "
                    "burn rate per class and window (1.0 = spending "
                    "exactly the budget)",
                    "# TYPE minio_slo_error_budget_burn gauge"]
            any_burn = False
            for cls, d in snap.items():
                for win in ("fast", "slow"):
                    burn = d["burn"][win]
                    if burn is None:
                        continue
                    lbl = _fmt_labels(("class", "window"), (cls, win))
                    rows.append(
                        f"minio_slo_error_budget_burn{lbl} {burn}")
                    any_burn = True
            if any_burn:
                g("\n".join(rows) + "\n")

        # self-driving overload plane (server/controller.py, ISSUE 18):
        # tick/skip counters, per-action ladder depth and decision
        # counts, and the pool-add recommendation.  Rendered only while
        # the controller is on, so MINIO_TPU_CONTROLLER=0 stays
        # metrics-identical (pinned by tests/test_controller.py).
        ctrl = getattr(self, "controller", None)
        if ctrl is not None:
            cs = ctrl.stats()
            gauge("minio_controller_ticks_total",
                  "Controller sampling ticks since start", cs["ticks"])
            gauge("minio_controller_skipped_stale_total",
                  "Decisions refused because the snapshot went stale "
                  "between sample and act", cs["skippedStale"])
            gauge("minio_controller_pool_add_recommended",
                  "1 while the controller recommends adding a pool "
                  "(execution stays admin-gated)",
                  int(cs["poolAddRecommended"]))
            rows = ["# HELP minio_controller_active Intervention "
                    "ladder depth per action family",
                    "# TYPE minio_controller_active gauge"]
            arow = ["# HELP minio_controller_actions_total Controller "
                    "decisions per action family and direction",
                    "# TYPE minio_controller_actions_total gauge"]
            for name, a in sorted(cs["actions"].items()):
                lbl = _fmt_labels(("action",), (name,))
                rows.append(f"minio_controller_active{lbl} "
                            f"{a['depth']}")
                for direction, field in (("engage", "engagements"),
                                         ("revert", "reverts")):
                    lbl = _fmt_labels(("action", "direction"),
                                      (name, direction))
                    arow.append(f"minio_controller_actions_total{lbl} "
                                f"{a[field]}")
            g("\n".join(rows) + "\n")
            g("\n".join(arow) + "\n")

        # topology plane (ISSUE 14): pool drain/rebalance volume and
        # retry/fail classification plus site-resync push economics —
        # the drain-induced-load forensics surface next to the
        # decom/resync trace spans.  Rendered only when the deployment
        # has a multi-pool topology, a drain has run, or site peers
        # exist, so the single-pool no-decom server stays
        # metrics-identical to before.
        try:
            from minio_tpu.services import decom as decom_mod

            with decom_mod._stats_mu:
                tsnap = dict(decom_mod.stats)
            multi_pool = len(getattr(self.api, "pools", [])) > 1
            if multi_pool or any(tsnap.values()):
                gauge("minio_topology_drained_objects_total",
                      "Object versions moved out of draining/"
                      "rebalancing pools", tsnap["drained_objects"])
                gauge("minio_topology_drained_bytes_total",
                      "Logical bytes moved out of draining/"
                      "rebalancing pools", tsnap["drained_bytes"])
                gauge("minio_topology_drain_retries_total",
                      "Per-version move attempts retried "
                      "(retryable-classified failures)",
                      tsnap["retries"])
                rows = ["# HELP minio_topology_drain_failed_total "
                        "Version moves that exhausted retries, by "
                        "failure class",
                        "# TYPE minio_topology_drain_failed_total gauge"]
                for klass, key in (("retryable", "failed_retryable"),
                                   ("permanent", "failed_permanent")):
                    lbl = _fmt_labels(("class",), (klass,))
                    rows.append("minio_topology_drain_failed_total"
                                f"{lbl} {tsnap[key]}")
                g("\n".join(rows) + "\n")
                gauge("minio_topology_drain_skipped_stale_total",
                      "Stale source copies dropped because the "
                      "destination already held same-or-newer",
                      tsnap["skipped_stale"])
                gauge("minio_topology_drain_throttle_waits_total",
                      "Drain pauses deferring to foreground load "
                      "(brownout)", tsnap["throttle_waits"])
            if multi_pool and hasattr(self.api, "topology"):
                susp = self.api.topology.suspended()
                rows = ["# HELP minio_topology_pool_suspended 1 while "
                        "the pool is suspended from placement "
                        "(draining/decommissioned)",
                        "# TYPE minio_topology_pool_suspended gauge"]
                for i in range(len(self.api.pools)):
                    lbl = _fmt_labels(("pool",), (str(i),))
                    rows.append("minio_topology_pool_suspended"
                                f"{lbl} {1 if i in susp else 0}")
                g("\n".join(rows) + "\n")
        except Exception:
            pass
        try:
            site = getattr(self, "site", None)
            si = site.info() if site is not None else None
            if si and (si["peers"] or si["pushed"] or si["failed"]
                       or si["resyncs"]):
                gauge("minio_topology_resync_pushes_total",
                      "Site-replication docs queued by resync sweeps",
                      si["resyncPushed"])
                gauge("minio_topology_resync_skipped_total",
                      "Buckets the bloom change tracker proved clean "
                      "and resync skipped", si["resyncSkipped"])
                # push-level counters: ALL site pushes (mutation
                # propagation included), not just resync docs — named
                # accordingly so a resync alert cannot key on ordinary
                # peer-down mutation retries
                gauge("minio_topology_site_push_retries_total",
                      "Site-replication push attempts re-queued with "
                      "backoff (all pushes, resync included)",
                      si["retries"])
                gauge("minio_topology_site_push_failures_total",
                      "Site-replication pushes failed after all "
                      "retries (all pushes, resync included)",
                      si["failed"])
        except Exception:
            pass

        # geo-replication of object data (services/georep.py): push
        # economics, LWW conflict outcomes, and the per-peer breaker —
        # presence-guarded on the MINIO_TPU_GEOREP gate so a gated-off
        # server's scrape stays byte-identical to the seed
        try:
            georep = getattr(self, "georep", None)
            if georep is not None:
                from minio_tpu.services import georep as _georep

                with _georep._stats_mu:
                    gs = dict(_georep.stats)
                gauge("minio_georep_pushed_objects_total",
                      "Objects acked by a geo-replication peer",
                      gs["pushed_objects"])
                gauge("minio_georep_pushed_versions_total",
                      "Object versions acked by a geo-replication peer",
                      gs["pushed_versions"])
                gauge("minio_georep_pushed_bytes_total",
                      "Object payload bytes pushed to geo-replication "
                      "peers", gs["pushed_bytes"])
                gauge("minio_georep_applied_total",
                      "Incoming geo-replication versions applied "
                      "locally", gs["applied"])
                gauge("minio_georep_already_total",
                      "Incoming geo-replication versions already "
                      "present (idempotent re-push)", gs["already"])
                gauge("minio_georep_stale_dropped_total",
                      "Incoming versions dropped by last-writer-wins",
                      gs["stale_dropped"])
                gauge("minio_georep_failed_retryable_total",
                      "Push attempts that failed retryably and were "
                      "re-queued", gs["failed_retryable"])
                gauge("minio_georep_failed_permanent_total",
                      "Per-item pushes rejected permanently by a peer",
                      gs["failed_permanent"])
                gauge("minio_georep_breaker_opens_total",
                      "Times a per-peer geo-replication breaker "
                      "opened", gs["breaker_opens"])
                gauge("minio_georep_breaker_short_circuits_total",
                      "Sweeps skipped because a peer breaker was open",
                      gs["breaker_short_circuits"])
                gauge("minio_georep_sweeps_total",
                      "Geo-replication delta sweeps completed",
                      gs["sweeps"])
                gauge("minio_georep_lane_waits_total",
                      "Pushes delayed by the inter-site bandwidth "
                      "lane", gs["lane_waits"])
                brows = ["# HELP minio_georep_peer_breaker_open 1 "
                         "while the peer's push breaker is open",
                         "# TYPE minio_georep_peer_breaker_open gauge"]
                emit = False
                for name, br in list(georep._breakers.items()):
                    lbl = _fmt_labels(("peer",), (name,))
                    brows.append(
                        "minio_georep_peer_breaker_open"
                        f"{lbl} {1 if br.state() == 'open' else 0}")
                    emit = True
                if emit:
                    g("\n".join(brows) + "\n")
        except Exception:
            pass

        # metadata plane (storage/metajournal.py, ISSUE 17): commit-
        # journal batching economics (commits vs batches is THE
        # coalescing signal), rotation/replay volume and the sorted-
        # segment index footprint.  Presence-guarded on live journals,
        # so MINIO_TPU_META_JOURNAL=0 stays metrics-identical to the
        # per-commit-fsync server.
        try:
            from minio_tpu.storage import metajournal as _mj

            msnap = _mj.metrics_snapshot()
            if msnap:
                gauge("minio_meta_journals",
                      "Drives running a metadata commit journal",
                      msnap["journals"])
                gauge("minio_meta_journal_queue_length",
                      "Commits waiting for the next group flush across "
                      "drives", msnap["queue_depth"])
                gauge("minio_meta_journal_commits_total",
                      "xl.meta commits acknowledged through the "
                      "journal", msnap["commits"])
                gauge("minio_meta_journal_batches_total",
                      "Group-fsync flush batches (commits/batches = "
                      "mean coalescing factor)", msnap["batches"])
                gauge("minio_meta_journal_last_batch_size",
                      "Largest most-recent flush batch across drives",
                      msnap["last_batch"])
                gauge("minio_meta_journal_flush_seconds_total",
                      "Seconds spent in journal flushes (write + group "
                      "fsync + buffered applies)",
                      round(msnap["flush_seconds"], 6))
                gauge("minio_meta_journal_rotations_total",
                      "Journal rotations (in-place xl.meta syncs + "
                      "truncate)", msnap["rotations"])
                gauge("minio_meta_journal_replayed_total",
                      "Paths recovered by startup crash replay",
                      msnap["replayed"])
                gauge("minio_meta_journal_bytes",
                      "Bytes currently in journal files awaiting "
                      "rotation", msnap["journal_bytes"])
                gauge("minio_meta_index_segments_count",
                      "Sorted index segments on disk across drives",
                      msnap["segments"])
                gauge("minio_meta_index_spills_total",
                      "Memtable-to-segment spills", msnap["spills"])
                gauge("minio_meta_index_compaction_bytes_total",
                      "Bytes written by full-merge segment compaction",
                      msnap["compaction_bytes"])
        except Exception:
            pass

        # multi-process data plane (parallel/workers.py): job/commit
        # volume through the worker plane plus its supervision health —
        # workerDeaths counts in-flight-failing deaths, restarts counts
        # supervisor respawns (a climbing gap between the two means the
        # supervisor cannot keep workers alive)
        try:
            from minio_tpu.parallel import workers as _workers

            plane = _workers.get_plane(create=False)
            if plane is not None:
                ms = plane.stats()
                gauge("minio_mp_workers",
                      "I/O worker processes of the data plane",
                      ms["workers"])
                gauge("minio_mp_jobs_total",
                      "PUT data jobs dispatched to the worker plane",
                      ms["jobs"])
                gauge("minio_mp_commits_total",
                      "Node-batched commit rounds through the worker "
                      "plane", ms["commits"])
                gauge("minio_mp_job_failures_total",
                      "Worker-plane jobs that failed (died worker / "
                      "timeout)", ms["failures"])
                gauge("minio_mp_worker_deaths_total",
                      "Worker processes that died with jobs in flight",
                      ms["workerDeaths"])
                gauge("minio_mp_worker_restarts_total",
                      "Worker processes respawned by the supervisor",
                      ms["restarts"])
        except Exception:
            pass

        # device-resident erasure batcher (erasure/batcher.py, ISSUE
        # 11): cross-request codec coalescing economics — items vs
        # dispatches is THE batching signal (N same-tick submissions =
        # 1 fused program), shed/failed counters show deadline and
        # fault behavior, and the matrix-residency hit ratio shows
        # whether re-submitted geometries re-transfer their matrices
        try:
            from minio_tpu.erasure import batcher as batcher_mod

            bsnap = batcher_mod.stats_snapshot()
            if bsnap is not None:
                gauge("minio_batcher_ticks_total",
                      "Batcher tick windows flushed", bsnap["ticks"])
                gauge("minio_batcher_dispatches_total",
                      "Fused device/host programs dispatched by the "
                      "batcher", bsnap["dispatches"])
                gauge("minio_batcher_items_total",
                      "Codec work items submitted to the batcher",
                      bsnap["items"])
                gauge("minio_batcher_coalesced_items_total",
                      "Items that shared a fused dispatch with at "
                      "least one other item", bsnap["coalesced_items"])
                gauge("minio_batcher_batched_bytes_total",
                      "Payload bytes dispatched through fused batches",
                      bsnap["batched_bytes"])
                gauge("minio_batcher_shed_deadline_total",
                      "Items shed because their budget expired while "
                      "queued", bsnap["shed_deadline"])
                gauge("minio_batcher_failed_retryable_total",
                      "Items failed retryable back to the per-request "
                      "plane (tick-thread death, dispatch failure)",
                      bsnap["failed_retryable"])
                gauge("minio_batcher_deaths_total",
                      "Batcher tick-thread deaths", bsnap["deaths"])
                gauge("minio_batcher_queue_length",
                      "Items currently queued for the next tick",
                      bsnap["queue_depth"])
        except Exception:
            pass
        try:
            from minio_tpu.ops import residency as residency_mod

            msnap = residency_mod.matrices.stats()
            gauge("minio_erasure_matrix_residency_hits_total",
                  "Coding-matrix lookups served device/host-resident",
                  msnap["hits"])
            gauge("minio_erasure_matrix_residency_misses_total",
                  "Coding-matrix lookups that built (and transferred) "
                  "a matrix", msnap["misses"])
            gauge("minio_erasure_matrix_residency_evictions_total",
                  "Matrices evicted by the residency LRU bound",
                  msnap["evictions"])
            gauge("minio_erasure_matrix_residency_entries_count",
                  "Matrices currently resident", msnap["entries"])
        except Exception:
            pass

        # request tracing plane (utils/tracing.py, ISSUE 12): recording
        # volume, tail-capture economics and the bounded store's
        # honesty counters.  Rendered only while the plane is (or was)
        # on, so MINIO_TPU_TRACE=0 stays metrics-identical to the
        # pre-tracing server.
        try:
            from minio_tpu.utils import tracing

            if tracing.enabled() or tracing.stats["traces"]:
                ts = tracing.store.stats()
                gauge("minio_trace_traces_total",
                      "Traces recorded (one per request/heal sequence)",
                      tracing.stats["traces"])
                gauge("minio_trace_spans_total",
                      "Spans recorded across all traces",
                      tracing.stats["spans"])
                gauge("minio_trace_spans_dropped_total",
                      "Spans dropped by the per-trace span cap",
                      tracing.stats["spans_dropped"])
                gauge("minio_trace_captures_total",
                      "Traces retained by tail capture or head "
                      "sampling", ts["captures"])
                rows = ["# HELP minio_trace_capture_reason_total "
                        "Captured traces per retention reason",
                        "# TYPE minio_trace_capture_reason_total gauge"]
                for reason, n in sorted(ts["by_reason"].items()):
                    lbl = _fmt_labels(("reason",), (reason,))
                    rows.append(
                        f"minio_trace_capture_reason_total{lbl} {n}")
                g("\n".join(rows) + "\n")
                gauge("minio_trace_capture_evictions_total",
                      "Captured traces evicted by the store bound",
                      ts["evictions"])
                gauge("minio_trace_store_bytes",
                      "Approximate resident bytes of the trace store",
                      ts["bytes"])
                gauge("minio_trace_store_entries_count",
                      "Traces currently resident in the store",
                      ts["entries"])
        except Exception:
            pass

        # deadline/overload plane: hedged shard reads, abandoned
        # stragglers, RPC budget expiries, per-drive deadline timeouts
        try:
            from minio_tpu.distributed import rpc as rpc_mod
            from minio_tpu.erasure import objects as eobj

            gauge("minio_read_hedges_total",
                  "Shard reads steered away from a slow drive to a spare",
                  eobj.hedge_stats["hedged"])
            gauge("minio_read_stragglers_abandoned_total",
                  "Quorum fan-out stragglers abandoned after the grace "
                  "window", eobj.hedge_stats["abandoned"])
            gauge("minio_rpc_deadline_expired_total",
                  "RPC calls refused because the budget was already "
                  "spent (caller side)",
                  rpc_mod.deadline_stats["expired_local"])
            gauge("minio_rpc_deadline_rejected_total",
                  "RPC requests rejected expired-on-arrival (server "
                  "side)", rpc_mod.deadline_stats["expired_remote"])
        except Exception:
            pass
        try:
            # `drives` computed by the capacity block above; absent only
            # if storage_info failed there (then skip this block too)
            rows = ["# HELP minio_drive_deadline_timeouts_total Per-op "
                    "deadline-worker timeouts per drive",
                    "# TYPE minio_drive_deadline_timeouts_total gauge"]
            any_ = False
            for d in drives:
                h = d.get("health")
                if h and h.get("deadlineTimeouts"):
                    lbl = _fmt_labels(("drive",), (d["endpoint"],))
                    rows.append("minio_drive_deadline_timeouts_total"
                                f'{lbl} {h["deadlineTimeouts"]}')
                    any_ = True
            if any_:
                g("\n".join(rows) + "\n")
        except Exception:
            pass

        # usage from the scanner cache (reference BucketUsage group)
        svcs = getattr(self, "services", None)
        if svcs is not None:
            usage = svcs.scanner.usage
            gauge("minio_cluster_usage_total_bytes",
                  "Scanned object bytes", usage.total_size())
            gauge("minio_cluster_usage_object_total",
                  "Scanned object count", usage.total_objects())
            gauge("minio_cluster_bucket_total", "Buckets with usage data",
                  len(usage.buckets))
            # scanner data-usage detail per bucket (ISSUE 15 satellite;
            # reference cluster usage metrics): objects/bytes/versions/
            # delete-markers from the usage tree the scanner maintains
            # (services/usage_tree.py).  Presence-guarded: an idle
            # server with no scanned buckets emits none of these and
            # stays metrics-identical.  minio_usage_bytes supersedes
            # the old minio_bucket_usage_total_bytes (same label, same
            # value — one family, not two names that can drift).
            if usage.buckets:
                for name, help_, attr in (
                        ("minio_usage_objects",
                         "Scanned objects per bucket", "objects"),
                        ("minio_usage_bytes",
                         "Scanned logical bytes per bucket", "size"),
                        ("minio_usage_versions",
                         "Scanned object versions per bucket",
                         "versions"),
                        ("minio_usage_delete_markers",
                         "Scanned delete markers per bucket",
                         "delete_markers")):
                    rows = [f"# HELP {name} {help_}",
                            f"# TYPE {name} gauge"]
                    for b, u in sorted(usage.buckets.items()):
                        lbl = _fmt_labels(("bucket",), (b,))
                        rows.append(f"{name}{lbl} {getattr(u, attr)}")
                    g("\n".join(rows) + "\n")
            # heal/MRF (reference HealObjects group)
            ms = svcs.mrf.stats
            gauge("minio_heal_objects_healed_total",
                  "Objects healed by the MRF queue", ms.healed)
            gauge("minio_heal_objects_failed_total",
                  "Objects the MRF queue failed to heal", ms.failed)
            gauge("minio_heal_mrf_pending", "MRF queue depth", ms.pending)
            gauge("minio_heal_drive_resyncs_total",
                  "Drive reconnects that enqueued an MRF re-sync",
                  getattr(svcs, "drive_resyncs", 0))
            gauge("minio_heal_resync_objects_total",
                  "Objects enqueued for heal by drive re-syncs",
                  getattr(svcs, "resync_objects", 0))
            bo = getattr(svcs, "brownout", None)
            if bo is not None:
                bs = bo.stats()
                gauge("minio_brownout_engaged",
                      "1 while background services are browned out under "
                      "foreground overload", 1 if bs["engaged"] else 0)
                gauge("minio_brownout_engagements_total",
                      "Brownout engage transitions", bs["engagements"])
                gauge("minio_brownout_releases_total",
                      "Brownout release transitions", bs["releases"])
                gauge("minio_brownout_deferred_ops_total",
                      "Background operations deferred while browned out",
                      bs["deferrals"])
            if svcs.replication is not None:
                rs = svcs.replication.stats
                gauge("minio_replication_completed_total",
                      "Replication ops completed", rs.completed)
                gauge("minio_replication_failed_total",
                      "Replication ops failed", rs.failed)
                gauge("minio_replication_sent_bytes",
                      "Bytes replicated to targets", rs.bytes_replicated)
                gauge("minio_replication_proxied_requests_total",
                      "GET/HEAD requests proxied to replication targets",
                      rs.proxied)
                per_target = rs.targets_snapshot()
                if per_target:
                    per = [
                        ("minio_replication_target_completed_total",
                         "Replication ops completed per target",
                         "completed"),
                        ("minio_replication_target_failed_total",
                         "Replication ops failed per target", "failed"),
                        ("minio_replication_target_sent_bytes",
                         "Bytes replicated per target", "bytes_replicated"),
                        ("minio_replication_target_proxied_total",
                         "Requests proxied per target", "proxied"),
                    ]
                    for name, help_, attr in per:
                        rows = [f"# HELP {name} {help_}",
                                f"# TYPE {name} gauge"]
                        for arn, ts in sorted(per_target.items()):
                            lbl = _fmt_labels(("target",), (arn,))
                            rows.append(f"{name}{lbl} {getattr(ts, attr)}")
                        g("\n".join(rows) + "\n")
        # event notification backlog
        notifier = getattr(self, "notifier", None)
        if notifier is not None:
            pend = notifier.pending()
            gauge("minio_notify_target_queue_length",
                  "Undelivered events across targets",
                  sum(pend.values()))
        return "".join(lines)

    # -- health (always unauthenticated, reference
    #    cmd/healthcheck-handler.go) ----------------------------------------
    async def handle_health_live(self, request: web.Request) -> web.Response:
        return web.Response(status=200)

    async def handle_health_ready(self, request: web.Request) -> web.Response:
        ok = await self._run(self._cluster_healthy)
        return web.Response(status=200 if ok else 503,
                            headers={} if ok else
                            {"X-Minio-Error": "read quorum not available"})

    async def handle_health_cluster(self,
                                    request: web.Request) -> web.Response:
        ok = await self._run(self._cluster_healthy,
                             "maintenance" in request.rel_url.query)
        return web.Response(status=200 if ok else 503)

    def _cluster_healthy(self, maintenance: bool = False) -> bool:
        """Every erasure set must keep read quorum (one extra drive of
        headroom under ?maintenance).  Uses each set's ACTUAL configured
        parity and the drives' cached online state — no per-probe
        disk-info RPCs, so a hung peer can't stall the readiness probe
        (reference ClusterCheckHandler, cmd/healthcheck-handler.go:36)."""
        pools = getattr(self.api, "pools", None)
        if pools is None:
            return True
        for pool in pools:
            for es in getattr(pool, "sets", []):
                n = len(es.disks)
                online = sum(
                    1 for d in es.disks
                    if d is not None and d.is_online())
                need = n - es.default_parity + (1 if maintenance else 0)
                if online < max(need, 1):
                    return False
        return True
