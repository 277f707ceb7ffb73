"""Admin API plane: heal control, server/storage/data-usage info, user &
policy CRUD, top locks, service control.

Reference: cmd/admin-router.go:40 (route table), cmd/admin-handlers.go
(ServerInfoHandler, StorageInfoHandler, DataUsageInfoHandler),
cmd/admin-heal-ops.go:280 (LaunchNewHealSequence / status polling),
cmd/admin-handlers-users.go (user/policy CRUD).  Divergence from the
reference: madmin encrypts credential-bearing bodies with the admin
secret; here bodies are plain JSON over the SigV4-authenticated channel
(which the reference also relies on for integrity).

All admin requests must be SigV4-signed; the root account is always
allowed, other accounts need an IAM policy granting the `admin:<Op>`
action (reference cmd/admin-handler-utils.go checkAdminRequestAuth).
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import math
import os
import time

from aiohttp import web

from minio_tpu.storage import errors as st
from minio_tpu.storage.local import SYSTEM_VOL

from .s3errors import S3Error

ADMIN_PREFIX = "/minio/admin/v3"


def _finite_float(raw: str, name: str) -> float:
    """Parse a float query param, 400ing non-numbers AND non-finite
    values (``float('nan')`` parses happily but poisons downstream
    slot/clamp arithmetic — the QoS-admin NaN-proofing rule).  Range
    policy stays at the call site."""
    try:
        v = float(raw)
    except ValueError:
        v = float("nan")
    if not math.isfinite(v):
        raise S3Error("InvalidArgument",
                      f"{name} must be a finite number")
    return v


class AdminMixin:
    """Admin handlers; expects self.api, self.iam, self.services,
    self.locker, self.executor from S3Server."""

    def register_admin_routes(self, app: web.Application) -> None:
        r = app.router
        p = ADMIN_PREFIX
        wrap = self._admin_wrap
        r.add_get(f"{p}/info", wrap(self.admin_info, "ServerInfo"))
        r.add_get(f"{p}/storageinfo", wrap(self.admin_storage_info, "StorageInfo"))
        r.add_get(f"{p}/datausageinfo", wrap(self.admin_data_usage, "DataUsageInfo"))
        r.add_get(f"{p}/top/locks", wrap(self.admin_top_locks, "TopLocksAdmin"))
        r.add_post(f"{p}/service", wrap(self.admin_service, "ServiceRestart"))
        # heal: POST launches / polls / stops (reference HealHandler takes
        # bucket/prefix in the path and clientToken/forceStop in the query)
        for path in (f"{p}/heal/", f"{p}/heal/{{bucket}}",
                     f"{p}/heal/{{bucket}}/{{prefix:.*}}"):
            r.add_post(path, wrap(self.admin_heal, "Heal"))
        r.add_get(f"{p}/background-heal/status",
                  wrap(self.admin_bg_heal_status, "Heal"))
        # pool topology: status / decommission start / cancel (reference
        # cmd/admin-handlers-pools.go)
        r.add_get(f"{p}/pools/status",
                  wrap(self.admin_pools_status, "ServerInfo"))
        r.add_post(f"{p}/pools/decommission",
                   wrap(self.admin_pools_decommission, "DecommissionPool"))
        r.add_post(f"{p}/pools/cancel",
                   wrap(self.admin_pools_cancel, "DecommissionPool"))
        r.add_post(f"{p}/pools/add",
                   wrap(self.admin_pools_add, "DecommissionPool"))
        r.add_post(f"{p}/rebalance/start",
                   wrap(self.admin_rebalance_start, "RebalanceStart"))
        r.add_post(f"{p}/rebalance/stop",
                   wrap(self.admin_rebalance_stop, "RebalanceStop"))
        r.add_get(f"{p}/rebalance/status",
                  wrap(self.admin_rebalance_status, "RebalanceStatus"))
        # replication bandwidth report (reference
        # cmd/admin-handlers.go BandwidthMonitorHandler)
        r.add_get(f"{p}/bandwidth",
                  wrap(self.admin_bandwidth, "BandwidthMonitor"))
        # KMS plane (reference cmd/kms-handlers.go: KMSStatus,
        # KMSKeyStatus, KMSCreateKey)
        r.add_get(f"{p}/kms/status", wrap(self.admin_kms_status,
                                          "KMSStatus"))
        r.add_get(f"{p}/kms/key/status",
                  wrap(self.admin_kms_key_status, "KMSKeyStatus"))
        r.add_post(f"{p}/kms/key/create",
                   wrap(self.admin_kms_create_key, "KMSCreateKey"))
        # users / policies / groups / service accounts
        r.add_put(f"{p}/add-user", wrap(self.admin_add_user, "CreateUser"))
        r.add_delete(f"{p}/remove-user", wrap(self.admin_remove_user, "DeleteUser"))
        r.add_get(f"{p}/list-users", wrap(self.admin_list_users, "ListUsers"))
        r.add_put(f"{p}/set-user-status",
                  wrap(self.admin_set_user_status, "EnableUser"))
        r.add_put(f"{p}/add-canned-policy",
                  wrap(self.admin_add_policy, "CreatePolicy"))
        r.add_delete(f"{p}/remove-canned-policy",
                     wrap(self.admin_remove_policy, "DeletePolicy"))
        r.add_get(f"{p}/list-canned-policies",
                  wrap(self.admin_list_policies, "ListUserPolicies"))
        r.add_put(f"{p}/set-user-or-group-policy",
                  wrap(self.admin_set_policy_mapping, "AttachUserOrGroupPolicy"))
        r.add_put(f"{p}/update-group-members",
                  wrap(self.admin_update_group, "AddUserToGroup"))
        r.add_get(f"{p}/groups", wrap(self.admin_list_groups, "ListGroups"))
        r.add_put(f"{p}/add-service-account",
                  wrap(self.admin_add_service_account, "CreateServiceAccount"))
        # replication remote targets (reference cmd/admin-bucket-handlers.go
        # SetRemoteTargetHandler / ListRemoteTargetsHandler)
        r.add_put(f"{p}/set-remote-target",
                  wrap(self.admin_set_remote_target, "SetBucketTarget"))
        r.add_get(f"{p}/list-remote-targets",
                  wrap(self.admin_list_remote_targets, "GetBucketTarget"))
        r.add_delete(f"{p}/remove-remote-target",
                     wrap(self.admin_remove_remote_target, "SetBucketTarget"))
        r.add_put(f"{p}/replication-resync",
                  wrap(self.admin_replication_resync, "SetBucketTarget"))
        # observability: live trace + console log streams (reference
        # TraceHandler cmd/admin-handlers.go:1108, ConsoleLogHandler)
        r.add_get(f"{p}/trace", wrap(self.admin_trace, "ServerTrace"))
        # captured span trees: the tail-based slow/error store
        # (utils/tracing.py, ISSUE 12)
        r.add_get(f"{p}/trace/slow",
                  wrap(self.admin_trace_slow, "ServerTrace"))
        # aggregate per-stage timing over the retained trace store —
        # the simulator's (and a human's) "WHICH stage ate the p99"
        # answer without re-deriving timings by hand (ISSUE 15)
        r.add_get(f"{p}/trace/summary",
                  wrap(self.admin_trace_summary, "ServerTrace"))
        # live SLO objective status: per-class availability/latency vs
        # declarative objectives + error-budget burn (server/slo.py)
        r.add_get(f"{p}/slo", wrap(self.admin_slo, "ServerInfo"))
        r.add_get(f"{p}/log", wrap(self.admin_console_log, "ConsoleLog"))
        # on-demand cluster profiling (reference StartProfiling /
        # DownloadProfileData, cmd/peer-rest-client.go:469-490)
        r.add_post(f"{p}/profiling/start",
                   wrap(self.admin_profiling_start, "Profiling"))
        r.add_post(f"{p}/profiling/stop",
                   wrap(self.admin_profiling_stop, "Profiling"))
        # one-shot capture: start, sample for ?seconds=N, return the
        # collapsed-stack report in the same response (ISSUE 15 — the
        # two-call start/stop dance is for cluster-wide zips)
        r.add_post(f"{p}/profile",
                   wrap(self.admin_profile, "Profiling"))
        # speedtests (reference drive/object perf probes,
        # cmd/peer-rest-client.go:128 dperf + SpeedtestHandler)
        # write-heavy probes get their own action, NOT the read-only
        # ServerInfo gate (reference SpeedtestHandler admin action)
        r.add_post(f"{p}/speedtest/drive",
                   wrap(self.admin_drive_speedtest, "SpeedTest"))
        r.add_post(f"{p}/speedtest",
                   wrap(self.admin_object_speedtest, "SpeedTest"))
        # tiering (reference cmd/admin-handlers.go AddTierHandler /
        # ListTierHandler / RemoveTierHandler)
        r.add_put(f"{p}/tier", wrap(self.admin_add_tier, "SetTier"))
        r.add_get(f"{p}/tier", wrap(self.admin_list_tiers, "ListTier"))
        r.add_delete(f"{p}/tier", wrap(self.admin_remove_tier, "SetTier"))
        # site replication (reference cmd/site-replication.go admin
        # endpoints: SiteReplicationAdd / Info / Remove + the internal
        # apply channel pushes arrive on)
        r.add_post(f"{p}/site-replication/add",
                   wrap(self.admin_site_add, "SiteReplicationAdd"))
        r.add_get(f"{p}/site-replication/info",
                  wrap(self.admin_site_info, "SiteReplicationInfo"))
        r.add_post(f"{p}/site-replication/remove",
                   wrap(self.admin_site_remove, "SiteReplicationRemove"))
        r.add_post(f"{p}/site-replication/apply",
                   wrap(self.admin_site_apply, "SiteReplicationOperation"))
        r.add_post(f"{p}/site-replication/resync",
                   wrap(self.admin_site_resync, "SiteReplicationResync"))
        # geo-replication of object data (ISSUE 16, services/georep.py):
        # the apply channel peer pushes arrive on, live status, and the
        # per-peer cursor-reset resync — gated MINIO_TPU_GEOREP (status
        # answers {"enabled": false} when off, like /slo)
        r.add_post(f"{p}/georep/apply",
                   wrap(self.admin_georep_apply,
                        "SiteReplicationOperation"))
        r.add_get(f"{p}/georep/status",
                  wrap(self.admin_georep_status, "SiteReplicationInfo"))
        r.add_post(f"{p}/georep/resync",
                   wrap(self.admin_georep_resync,
                        "SiteReplicationResync"))
        # config KVS (reference cmd/admin-handlers-config-kv.go:
        # GetConfigKVHandler / SetConfigKVHandler / DelConfigKVHandler /
        # HelpConfigKVHandler)
        r.add_get(f"{p}/get-config", wrap(self.admin_get_config, "ConfigUpdate"))
        r.add_put(f"{p}/set-config-kv",
                  wrap(self.admin_set_config_kv, "ConfigUpdate"))
        r.add_delete(f"{p}/del-config-kv",
                     wrap(self.admin_del_config_kv, "ConfigUpdate"))
        r.add_get(f"{p}/help-config-kv",
                  wrap(self.admin_help_config, "ConfigUpdate"))
        # per-tenant QoS (ISSUE 13): read live tenant stats / set
        # weights, caps and bandwidth limits at runtime
        # (config-persisted through the dynamic `qos` subsystem)
        r.add_get(f"{p}/qos", wrap(self.admin_qos_get, "ServerInfo"))
        r.add_put(f"{p}/qos", wrap(self.admin_qos_set, "ConfigUpdate"))
        # SLO gate flip (ISSUE 16 satellite): PUT flips the plane live
        # like QoS; GET is registered with the SLO status route below
        r.add_put(f"{p}/slo", wrap(self.admin_slo_set, "ConfigUpdate"))
        # overload controller (ISSUE 18): live ladder/decision state;
        # the gate itself flips through the dynamic `controller`
        # config subsystem (set-config-kv controller enable=on)
        r.add_get(f"{p}/controller",
                  wrap(self.admin_controller, "ServerInfo"))

    # ---------------------------------------------------------------- auth
    #: admin ops whose duration is the CLIENT's choice (live follows,
    #: deliberate capture sleeps, measured probes) — recording them
    #: would poison the ADMIN latency objective with by-design walls
    _SLO_EXEMPT_OPS = frozenset(
        ("ServerTrace", "ConsoleLog", "Profiling", "SpeedTest"))

    def _admin_wrap(self, fn, op: str):
        async def handler(request: web.Request) -> web.StreamResponse:
            t0 = time.monotonic()
            status = 500
            # SLO plane captured at request start, like _handle: a
            # runtime gate flip mid-op records against the plane that
            # watched the op begin (ISSUE 16 satellite)
            slo = getattr(self, "slo", None)
            try:
                body = await request.read()
                await self._admin_auth(request, body, op)
                resp = await fn(request, body)
                status = resp.status
                return resp
            except asyncio.CancelledError:
                # client went away: same 499 carve-out as _handle —
                # neither a success nor server budget spend
                status = 499
                raise
            except S3Error as e:
                status = e.status
                return web.Response(
                    status=e.status,
                    body=json.dumps({"Code": e.code,
                                     "Message": e.message}).encode(),
                    content_type="application/json",
                )
            finally:
                # admin ops bypass _handle's funnel, so the SLO plane's
                # ADMIN class records here (server/slo.py, ISSUE 15);
                # slo.record itself skips 499
                if slo is not None and op not in self._SLO_EXEMPT_OPS:
                    slo.record(f"admin_{op}", status,
                               time.monotonic() - t0)
        return handler

    # ----------------------------------------------------- site replication
    async def admin_site_add(self, request: web.Request, body: bytes):
        from minio_tpu.services.site import SitePeer

        try:
            doc = json.loads(body)
            peers = [SitePeer.from_dict(p) for p in doc["peers"]]
        except (ValueError, KeyError, TypeError):
            raise S3Error("InvalidArgument",
                          'body must be {"peers": [{name, endpoint, '
                          'accessKey, secretKey}, ...]}')
        try:
            await self._run(self.site.add_peers, peers)
        except ValueError as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"status": "success",
                           "peers": [p.name for p in peers]})

    async def admin_site_info(self, request: web.Request, body: bytes):
        return self._json(self.site.info())

    async def admin_site_remove(self, request: web.Request, body: bytes):
        name = request.rel_url.query.get("name", "")
        if not name:
            raise S3Error("InvalidArgument", "name query param required")
        try:
            await self._run(self.site.remove_peer, name)
        except KeyError:
            raise S3Error("InvalidArgument", f"no such peer {name!r}")
        return self._json({})

    async def admin_site_apply(self, request: web.Request, body: bytes):
        """Receiving end of peer pushes: applies with propagation
        suppressed so mutations never loop between sites."""
        try:
            doc = json.loads(body)
        except ValueError:
            raise S3Error("InvalidArgument", "body must be JSON")
        try:
            await self._run(self.site.apply, doc)
        except ValueError as e:
            raise S3Error("InvalidArgument", str(e))
        except Exception as e:
            raise S3Error("InternalError", str(e))
        return self._json({})

    async def admin_site_resync(self, request: web.Request, body: bytes):
        """Re-push bucket state to one peer (reference `mc admin
        replicate resync`).  Uses the scanner's bloom change tracker to
        skip buckets that cannot have changed; ?full=true forces a
        complete walk."""
        name = request.rel_url.query.get("peer", "")
        if not name:
            raise S3Error("InvalidArgument", "peer query param required")
        full = request.rel_url.query.get("full", "").lower() \
            in ("1", "true", "yes")
        svcs = getattr(self, "services", None)
        tracker = getattr(svcs, "tracker", None) if svcs else None
        try:
            out = await self._run(self.site.resync, name, tracker, full)
        except KeyError:
            raise S3Error("InvalidArgument", f"no such peer {name!r}")
        return self._json(out)

    # ------------------------------------------- geo-replication (data)
    async def admin_georep_apply(self, request: web.Request,
                                 body: bytes):
        """Receiving end of object-data pushes (services/georep.py):
        applies version batches with propagation suppressed and
        answers per-item applied/already/stale results — the sender's
        ACK.  With the gate off the push bounces 503 (retryable at the
        sender: the peer may enable geo-replication later, and the
        sender's breaker owns the backoff meanwhile)."""
        georep = getattr(self, "georep", None)
        if georep is None:
            raise S3Error("SlowDown",
                          "geo-replication is disabled on this site "
                          "(MINIO_TPU_GEOREP)")
        try:
            doc = json.loads(body)
        except ValueError:
            raise S3Error("InvalidArgument", "body must be JSON")
        try:
            out = await self._run(georep.apply, doc)
        except ValueError as e:
            raise S3Error("InvalidArgument", str(e))
        except Exception as e:
            raise S3Error("InternalError", str(e))
        return self._json(out)

    async def admin_georep_status(self, request: web.Request,
                                  body: bytes):
        """Per-peer push-queue status: cursor, breaker state, worker
        liveness and process-lifetime totals.  ``{"enabled": false}``
        with the gate off (the /slo idiom — only this new endpoint
        admits the gate state)."""
        georep = getattr(self, "georep", None)
        if georep is None:
            return web.json_response({"enabled": False})
        return self._json(await self._run(georep.status))

    async def admin_georep_resync(self, request: web.Request,
                                  body: bytes):
        """Reset one peer's push cursor so the next sweep re-walks the
        namespace (idempotent re-pushes converge a peer that lost
        data); nudges this node's workers and broadcasts the nudge to
        cluster siblings."""
        georep = getattr(self, "georep", None)
        if georep is None:
            raise S3Error("InvalidArgument",
                          "geo-replication is disabled "
                          "(MINIO_TPU_GEOREP)")
        name = request.rel_url.query.get("peer", "")
        if not name:
            raise S3Error("InvalidArgument", "peer query param required")
        full = request.rel_url.query.get("full", "true").lower() \
            in ("1", "true", "yes")
        try:
            out = await self._run(georep.resync, name, full)
        except KeyError:
            raise S3Error("InvalidArgument", f"no such peer {name!r}")
        peers = getattr(self, "peers", None)
        if peers is not None and hasattr(peers, "georep_nudge"):
            peers.georep_nudge()
        return self._json(out)

    # ----------------------------------------------------------- speedtest
    @staticmethod
    def _int_q(request: web.Request, name: str, default: int,
               lo: int, hi: int) -> int:
        raw = request.rel_url.query.get(name, "")
        if not raw:
            return default
        try:
            v = int(raw)
        except ValueError:
            raise S3Error("AdminInvalidArgument",
                          f"{name} must be an integer")
        if not lo <= v <= hi:
            raise S3Error("AdminInvalidArgument",
                          f"{name} must be between {lo} and {hi}")
        return v

    async def admin_drive_speedtest(self, request: web.Request,
                                    body: bytes):
        """Sequential write+read throughput per LOCAL drive, O_DIRECT
        when the filesystem allows it so the page cache cannot inflate
        the numbers (reference dperf drive speedtest,
        cmd/peer-rest-client.go:128-380)."""
        from minio_tpu.distributed.peers import _probe_drive

        size = self._int_q(request, "size", 64 << 20, 1 << 20, 1 << 30)

        def run() -> list[dict]:
            out = []
            for pool in getattr(self.api, "pools", [self.api]):
                for d in pool.all_disks:
                    if d is None or not d.is_online():
                        continue
                    # unwrap the instrumentation to reach the drive root;
                    # remote drives have no local root and are skipped
                    # (each node probes its own drives)
                    inner = getattr(d, "_inner", d)
                    root = getattr(inner, "root", None)
                    if root is None:
                        continue
                    res = _probe_drive(d.endpoint(), root, size)
                    if "error" not in res:
                        res = {
                            "endpoint": res["endpoint"],
                            "writeMiBps": round(
                                res["write_gibs"] * 1024, 1),
                            "readMiBps": round(res["read_gibs"] * 1024, 1),
                            "bytes": res["bytes"],
                            "oDirect": res["o_direct"],
                        }
                    out.append(res)
            return out

        return self._json({"drives": await self._run(run)})

    async def admin_object_speedtest(self, request: web.Request,
                                     body: bytes):
        """PUT+GET throughput through the FULL object pipeline (erasure
        encode, bitrot, commit — reference objectSpeedTest)."""
        import io as _io
        import os

        from minio_tpu.erasure.objects import PutObjectOptions

        size = self._int_q(request, "size", 16 << 20, 1 << 10, 256 << 20)
        count = self._int_q(request, "count", 4, 1, 64)
        concurrent = self._int_q(request, "concurrent", 2, 1, 16)
        bucket = ".speedtest-" + os.urandom(4).hex()

        def run() -> dict:
            import concurrent.futures as cf

            self.api.make_bucket(bucket)
            data = os.urandom(size)
            try:
                t0 = time.monotonic()
                with cf.ThreadPoolExecutor(concurrent) as pool:
                    list(pool.map(
                        lambda i: self.api.put_object(
                            bucket, f"obj-{i}", _io.BytesIO(data), size,
                            PutObjectOptions()),
                        range(count)))
                put_s = time.monotonic() - t0

                def get_one(i):
                    _, stream = self.api.get_object(bucket, f"obj-{i}")
                    for _ in stream:
                        pass

                t0 = time.monotonic()
                with cf.ThreadPoolExecutor(concurrent) as pool:
                    list(pool.map(get_one, range(count)))
                get_s = time.monotonic() - t0
                total = size * count
                return {
                    "putMiBps": round(total / put_s / 2**20, 1),
                    "getMiBps": round(total / get_s / 2**20, 1),
                    "objectSize": size, "objects": count,
                    "concurrent": concurrent,
                }
            finally:
                try:
                    for i in range(count):
                        try:
                            self.api.delete_object(bucket, f"obj-{i}")
                        except Exception:
                            pass
                    self.api.delete_bucket(bucket, force=True)
                except Exception:
                    pass

        return self._json(await self._run(run))

    # ------------------------------------------------------------- tiering
    def _tier_mgr(self):
        services = self._services_or_503()
        if getattr(services, "tier", None) is None:
            raise S3Error("XMinioServerNotInitialized")
        return services.tier

    async def admin_add_tier(self, request: web.Request, body: bytes):
        from minio_tpu.services.tier import TierError

        try:
            doc = json.loads(body)
            name = doc.pop("name")
        except (ValueError, KeyError, TypeError, AttributeError):
            raise S3Error("InvalidArgument",
                          'body must be {"name": ..., "type": ..., ...}')
        try:
            await self._run(self._tier_mgr().add_tier, name, doc)
        except TierError as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({})

    async def admin_list_tiers(self, request: web.Request, body: bytes):
        mgr = self._tier_mgr()
        out = await self._run(mgr.list_tiers)
        return self._json({
            "tiers": out,
            "journalPending": mgr.journal.pending(),
            "transitioned": mgr.transitioned,
        })

    async def admin_remove_tier(self, request: web.Request, body: bytes):
        from minio_tpu.services.tier import TierError

        name = request.rel_url.query.get("name", "")
        if not name:
            raise S3Error("InvalidArgument", "name query param required")
        force = request.rel_url.query.get("force", "") in ("true", "1")
        try:
            await self._run(self._tier_mgr().remove_tier, name, force)
        except TierError as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({})

    # -------------------------------------------------------------- config
    async def admin_get_config(self, request: web.Request, body: bytes):
        """Effective merged config; secrets redacted like the reference
        (madmin redacts env-sensitive values on Get)."""
        cfg = await self._run(self.config.merged)
        for sub in cfg.values():
            for k in sub:
                if "secret" in k or "token" in k or "password" in k:
                    if sub[k]:
                        sub[k] = "*REDACTED*"
        return self._json(cfg)

    async def admin_set_config_kv(self, request: web.Request, body: bytes):
        from minio_tpu.config import ConfigError

        try:
            doc = json.loads(body)
            subsys = doc["subsys"]
            kvs = doc["kv"]
            if not isinstance(kvs, dict):
                raise ValueError("kv must be an object")
        except (ValueError, KeyError, TypeError):
            raise S3Error("InvalidArgument",
                          'body must be {"subsys": ..., "kv": {...}}')
        try:
            await self._run(self.config.set_kv, subsys, kvs)
        except ConfigError as e:
            raise S3Error("InvalidArgument", str(e))
        from minio_tpu.config import DYNAMIC

        return self._json({"restart": subsys not in DYNAMIC})

    # ------------------------------------------------------ per-tenant QoS
    async def admin_qos_get(self, request: web.Request, body: bytes):
        """Effective QoS state: gate, rule set, and per-tenant LIVE
        stats (queue depth, inflight, admissions, sheds, hot-lane
        folds, metered bytes, moving-average rates)."""
        qos = getattr(self, "qos", None)
        out = {"enabled": qos is not None}
        if qos is not None:
            out.update(qos.stats())
            out["rates"] = qos.rates()
        else:
            # plane off: still show what WOULD apply, so an operator
            # can stage rules before flipping the gate
            from .qos import QosPlane

            staged = QosPlane(self.max_concurrency)
            staged.load_config(self.config)
            out["defaults"] = staged.default_rule.to_dict()
            out["rules"] = {k: r.to_dict()
                            for k, r in staged.rules.items()}
        return self._json(out)

    async def admin_qos_set(self, request: web.Request, body: bytes):
        """Set tenant weights/caps/bandwidth (and optionally the gate)
        at runtime: persisted through the dynamic `qos` config
        subsystem, applied to the live plane without restart.  Partial
        bodies only touch the provided fields."""
        from minio_tpu.config import ConfigError

        try:
            doc = json.loads(body) if body else {}
            if not isinstance(doc, dict):
                raise ValueError("body must be a JSON object")
        except ValueError:
            raise S3Error("InvalidArgument", "malformed JSON body")
        kvs: dict[str, str] = {}
        if "enable" in doc:
            # strict bool: '"off"'/'"false"' strings are truthy in
            # Python and would silently flip the gate ON
            if not isinstance(doc["enable"], bool):
                raise S3Error("InvalidArgument",
                              "enable must be a JSON boolean")
            kvs["enable"] = "on" if doc["enable"] else "off"
        defaults = doc.get("defaults")
        if defaults is not None:
            if not isinstance(defaults, dict):
                raise S3Error("InvalidArgument",
                              "defaults must be an object")
            for field, key in (("weight", "default_weight"),
                               ("max_concurrency",
                                "default_max_concurrency"),
                               ("bandwidth", "default_bandwidth"),
                               ("hot_cap", "default_hot_cap")):
                if field in defaults:
                    v = defaults[field]
                    # bool is an int subclass (true would persist as
                    # the unparseable "True"), and json.loads accepts
                    # NaN/Infinity literals (a NaN weight starves the
                    # tenant: deficit arithmetic never reaches 1.0)
                    if isinstance(v, bool) \
                            or not isinstance(v, (int, float)) \
                            or not math.isfinite(v) or v < 0:
                        raise S3Error(
                            "InvalidArgument",
                            f"defaults.{field} must be a finite "
                            "number >= 0")
                    kvs[key] = str(v)
        if "max_queue" in doc:
            mq = doc["max_queue"]
            if mq == "auto":
                kvs["max_queue"] = "auto"
            elif isinstance(mq, int) and not isinstance(mq, bool) \
                    and mq > 0:
                kvs["max_queue"] = str(mq)
            else:
                raise S3Error("InvalidArgument",
                              'max_queue must be a positive integer '
                              'or "auto"')
        if "cost_unit" in doc:
            cu = doc["cost_unit"]
            # 0 is legal: flat unit pricing
            if isinstance(cu, int) and not isinstance(cu, bool) \
                    and cu >= 0:
                kvs["cost_unit"] = str(cu)
            else:
                raise S3Error("InvalidArgument",
                              "cost_unit must be an integer >= 0 "
                              "(bytes per deficit point; 0 = flat)")
        if "max_cost" in doc:
            mc = doc["max_cost"]
            if isinstance(mc, (int, float)) \
                    and not isinstance(mc, bool) \
                    and math.isfinite(mc) and mc >= 1:
                kvs["max_cost"] = str(mc)
            else:
                raise S3Error("InvalidArgument",
                              "max_cost must be a finite number >= 1")
        tenants = doc.get("tenants")
        if tenants is not None:
            if not isinstance(tenants, dict):
                raise S3Error("InvalidArgument",
                              "tenants must be an object")
            for key, rule in tenants.items():
                if not (key == "default" or key.startswith("bucket:")
                        or key.startswith("key:")):
                    raise S3Error(
                        "InvalidArgument",
                        f'tenant {key!r}: keys are "bucket:<name>", '
                        '"key:<access-key>" or "default"')
                if not isinstance(rule, dict):
                    raise S3Error("InvalidArgument",
                                  f"tenant {key!r} rule must be an "
                                  "object")
                for field in ("weight", "max_concurrency", "bandwidth",
                              "hot_cap"):
                    if field in rule and (
                            isinstance(rule[field], bool)
                            or not isinstance(rule[field], (int, float))
                            or not math.isfinite(rule[field])
                            or rule[field] < 0):
                        raise S3Error(
                            "InvalidArgument",
                            f"tenant {key!r}: {field} must be a "
                            "finite number >= 0")
                unknown = set(rule) - {"weight", "max_concurrency",
                                       "bandwidth", "hot_cap"}
                if unknown:
                    raise S3Error(
                        "InvalidArgument",
                        f"tenant {key!r}: unknown fields "
                        f"{sorted(unknown)}")
            kvs["tenants"] = json.dumps(tenants, sort_keys=True)
        if not kvs:
            raise S3Error("InvalidArgument",
                          "nothing to set: provide enable/defaults/"
                          "max_queue/cost_unit/max_cost/tenants")
        try:
            # set_kv persists to the drives and fires the dynamic
            # apply (S3Server._apply_qos_config) — live, no restart
            await self._run(self.config.set_kv, "qos", kvs)
        except ConfigError as e:
            raise S3Error("InvalidArgument", str(e))
        return await self.admin_qos_get(request, b"")

    async def admin_del_config_kv(self, request: web.Request, body: bytes):
        from minio_tpu.config import ConfigError

        subsys = request.rel_url.query.get("subsys", "")
        keys = [k for k in
                request.rel_url.query.get("keys", "").split(",") if k]
        if not subsys:
            raise S3Error("InvalidArgument", "subsys query param required")
        try:
            await self._run(self.config.del_kv, subsys, keys or None)
        except ConfigError as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({})

    async def admin_help_config(self, request: web.Request, body: bytes):
        from minio_tpu.config import ConfigError, ServerConfig

        subsys = request.rel_url.query.get("subsys", "") or None
        try:
            return self._json(ServerConfig.help(subsys))
        except ConfigError as e:
            raise S3Error("InvalidArgument", str(e))

    # -------------------------------------------------------- observability
    async def _stream_ndjson(self, request: web.Request, subscribe,
                             backlog=()) -> web.StreamResponse:
        """Shared NDJSON streamer: write `backlog`, then follow the
        subscription (created AFTER prepare so a failed handshake never
        leaks it) with idle keepalives.  Polls on the event loop — a
        follower must never park one of the shared executor's threads."""
        import asyncio

        resp = web.StreamResponse(
            status=200, headers={"Content-Type": "application/x-ndjson"})
        sub = None
        try:
            await resp.prepare(request)
            # snapshot the backlog BEFORE subscribing: an entry published
            # in between is dropped from the tail, never streamed twice
            items = backlog() if callable(backlog) else backlog
            sub = subscribe() if subscribe is not None else None
            for entry in items:
                await resp.write(json.dumps(entry).encode() + b"\n")
            idle = 0.0
            while sub is not None:
                entry = sub.get_nowait()
                if entry is None:
                    await asyncio.sleep(0.2)
                    idle += 0.2
                    if idle >= 1.0:
                        # keepalive so dead clients surface quickly
                        await resp.write(b"\n")
                        idle = 0.0
                    continue
                idle = 0.0
                await resp.write(json.dumps(entry).encode() + b"\n")
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        finally:
            if sub is not None:
                sub.close()
        return resp

    async def admin_trace(self, request: web.Request,
                          body: bytes) -> web.StreamResponse:
        """Long-poll NDJSON stream of per-request trace entries
        (reference TraceHandler, cmd/admin-handlers.go:1108; `mc admin
        trace` client).  ?err=true filters to error responses only.

        In distributed mode the stream is CLUSTER-wide: follower threads
        tail each peer's trace endpoint (?local=true) and merge entries
        into this response.  Peers are reached with this node's root
        credentials — bootstrap verification guarantees they match."""
        errs_only = request.rel_url.query.get("err", "") in ("true", "1")
        local_only = request.rel_url.query.get("local", "") in ("true", "1")
        flt = (lambda e: e.get("statusCode", 0) >= 400) if errs_only else None

        peers = [] if local_only else getattr(self, "peer_trace_addrs", [])
        stop = None
        if peers:
            import threading

            from minio_tpu.utils.deadline import service_thread

            stop = threading.Event()

        def subscribe():
            sub = self.trace.subscribe(filter_fn=flt)
            for addr in peers:
                service_thread(self._follow_peer_trace,
                               addr, sub, stop, errs_only,
                               name=f"trace-follow-{addr}")
            return sub

        try:
            return await self._stream_ndjson(request, subscribe)
        finally:
            if stop is not None:
                stop.set()

    def _follow_peer_trace(self, addr: str, sub, stop, errs_only: bool
                           ) -> None:
        """Pull one peer's trace entries into `sub`'s queue over the RPC
        plane (peer.trace_subscribe/poll, reference
        cmd/peer-rest-client.go:765 doTrace), reconnecting with backoff
        for as long as the client stream is open — a peer restart must
        not silently drop its traffic from an ongoing cluster trace."""
        import queue as queue_mod

        from minio_tpu.utils.logger import log

        client = getattr(self, "peer_clients", {}).get(addr)
        if client is None:
            return
        backoff = 1.0
        while not stop.is_set():
            sid = None
            try:
                sid = client.call("peer.trace_subscribe",
                                  {"err": errs_only})["id"]
                backoff = 1.0
                while not stop.is_set():
                    out = client.call("peer.trace_poll", {"id": sid})
                    if not out.get("ok"):
                        break  # subscription expired server-side
                    entries = out.get("entries", [])
                    for entry in entries:
                        entry.setdefault("node", addr)
                        try:
                            sub.q.put_nowait(entry)
                        except queue_mod.Full:
                            pass
                    if not entries and stop.wait(0.25):
                        break
            except Exception as e:
                log.warning("peer trace follower disconnected; retrying",
                            peer=addr, error=str(e))
            finally:
                if sid is not None:
                    try:
                        client.call("peer.trace_unsubscribe", {"id": sid})
                    except Exception:
                        pass
            if stop.wait(backoff):
                return
            backoff = min(backoff * 2, 15.0)

    async def admin_trace_slow(self, request: web.Request,
                               body: bytes) -> web.Response:
        """Captured span trees from the tail-based trace store
        (utils/tracing.py): every trace that ended in an error / 503
        shed, ran past MINIO_TPU_TRACE_SLOW_MS, or won the head-
        sampling draw.  ``?id=<traceId>`` fetches one trace (the id a
        user read off ``x-minio-tpu-trace-id``), ``?err=true`` filters
        to errors, ``?n=`` bounds the count (default 50)."""
        from minio_tpu.utils import tracing

        q = request.rel_url.query
        tid = q.get("id", "")
        if tid:
            doc = tracing.store.get(tid)
            if doc is None:
                raise S3Error("NoSuchKey", f"no captured trace {tid}")
            return web.json_response(tracing.span_tree(doc))
        try:
            n = max(1, min(1000, int(q.get("n", "50") or "50")))
        except ValueError:
            n = 50
        err_only = q.get("err", "") in ("true", "1")
        docs = tracing.store.snapshot(n=n, err_only=err_only)
        return web.json_response({
            "enabled": tracing.enabled(),
            "slowMs": tracing.slow_ms(),
            "store": tracing.store.stats(),
            "traces": [tracing.span_tree(d) for d in docs],
        })

    async def admin_trace_summary(self, request: web.Request,
                                  body: bytes) -> web.Response:
        """Per-stage latency aggregates over the retained trace store:
        span-name p50/p99/count/total plus the stagestats fold totals,
        and under ``dataplane`` the process-wide stage counters
        (thread-seconds, bytes, wall seconds) since boot.
        ``?n=`` bounds how many retained traces feed the aggregate
        (default: all); ``?since=<epoch-seconds>`` restricts to traces
        that STARTED at/after the instant (the simulator scopes a
        violation's attribution to its own scenario this way — the
        store spans the server's whole life).  This is the forensics
        surface the simulator (and a human chasing a p99) reads
        instead of re-deriving stage timings from counters."""
        from minio_tpu.erasure import stagestats
        from minio_tpu.utils import tracing

        q = request.rel_url.query
        try:
            n = max(1, min(10000, int(q.get("n", "10000") or "10000")))
        except ValueError:
            n = 10000
        since = 0.0
        raw = q.get("since", "")
        if raw:
            since = _finite_float(raw, "since")
            if since < 0:
                raise S3Error("InvalidArgument",
                              "since must be a non-negative epoch "
                              "seconds value")
        docs = tracing.store.snapshot(n=n)
        if since:
            docs = [d for d in docs if d.get("start", 0.0) >= since]
        out = tracing.summarize_stages(docs)
        # the process-wide stage counters beside the retained traces':
        # thread-seconds, bytes and wall time since boot, every request
        # in them, captured or not
        out["dataplane"] = {
            stage: {"seconds": round(d["seconds"], 6),
                    "bytes": int(d["bytes"]),
                    "wallSeconds": round(d["wall"], 6)}
            for stage, d in stagestats.snapshot().items()
            if d["seconds"] or d["bytes"]}
        out["enabled"] = tracing.enabled()
        out["store"] = tracing.store.stats()
        return web.json_response(out)

    async def admin_slo(self, request: web.Request,
                        body: bytes) -> web.Response:
        """Live SLO status (server/slo.py): per-class objective
        attainment, windowed p50/p99/availability and multi-window
        error-budget burn; per-tenant splits when the QoS plane is
        feeding tenant labels.  ``?window=<seconds>`` scopes the
        measured section (the simulator passes its scenario duration).
        With the plane off (MINIO_TPU_SLO unset) answers
        ``{"enabled": false}`` — the S3 and metrics surfaces stay
        byte-identical; only this new endpoint admits the gate state."""
        plane = getattr(self, "slo", None)
        if plane is None:
            return web.json_response({"enabled": False})
        q = request.rel_url.query
        window = None
        raw = q.get("window", "")
        if raw:
            window = _finite_float(raw, "window")
            if window <= 0:
                raise S3Error("InvalidArgument",
                              "window must be a positive number of "
                              "seconds")
        doc = await self._run(plane.status, window, True)
        return web.json_response(doc)

    async def admin_controller(self, request: web.Request,
                               body: bytes) -> web.Response:
        """Live overload-controller state (server/controller.py): per-
        action ladder depth, engagement/revert counts, stale-snapshot
        refusals and the pool-add recommendation.  With the gate off
        answers ``{"enabled": false}`` — the controller-off server
        stays byte-identical elsewhere."""
        ctrl = getattr(self, "controller", None)
        out = {"enabled": ctrl is not None}
        if ctrl is not None:
            out.update(ctrl.stats())
        return web.json_response(out)

    async def admin_slo_set(self, request: web.Request,
                            body: bytes) -> web.Response:
        """Flip the SLO gate at runtime (ISSUE 16 satellite): persisted
        through the dynamic `slo` config subsystem, applied live by
        S3Server._apply_slo_config — the QoS-gate idiom.  In-flight
        requests record against the plane captured at their start.
        Note MINIO_TPU_SLO env, when set, pins the gate and wins over
        this knob (gate_enabled precedence)."""
        from minio_tpu.config import ConfigError

        try:
            doc = json.loads(body) if body else {}
            if not isinstance(doc, dict):
                raise ValueError("body must be a JSON object")
        except ValueError:
            raise S3Error("InvalidArgument", "malformed JSON body")
        if "enable" not in doc:
            raise S3Error("InvalidArgument",
                          'nothing to set: provide {"enable": bool}')
        # strict bool: '"off"'/'"false"' strings are truthy in Python
        # and would silently flip the gate ON (the QoS-admin rule)
        if not isinstance(doc["enable"], bool):
            raise S3Error("InvalidArgument",
                          "enable must be a JSON boolean")
        kvs = {"enable": "on" if doc["enable"] else "off"}
        try:
            await self._run(self.config.set_kv, "slo", kvs)
        except ConfigError as e:
            raise S3Error("InvalidArgument", str(e))
        plane = getattr(self, "slo", None)
        return self._json({"enabled": plane is not None})

    async def admin_console_log(self, request: web.Request,
                                body: bytes) -> web.StreamResponse:
        """Recent console-log ring + live follow (reference
        ConsoleLogHandler, cmd/admin-handlers.go; cmd/consolelogger.go
        ring buffer)."""
        from minio_tpu.utils.logger import log as logger

        try:
            n = int(request.rel_url.query.get("limit", "100"))
        except ValueError:
            raise S3Error("InvalidArgument", "limit must be an integer")
        if n < 1:
            raise S3Error("InvalidArgument", "limit must be >= 1")
        follow = request.rel_url.query.get("follow", "") in ("true", "1")
        # backlog is snapshotted inside the streamer AFTER prepare but
        # BEFORE subscribing, so entries in between are dropped from the
        # tail rather than streamed twice
        return await self._stream_ndjson(
            request,
            (lambda: logger.pubsub.subscribe()) if follow else None,
            backlog=lambda: logger.recent(n))

    async def _admin_auth(self, request: web.Request, body: bytes,
                          op: str) -> None:
        if self._is_anonymous(request):
            raise S3Error("AccessDenied", "admin API requires signing")
        ctx = await self._auth(request, hashlib.sha256(body).hexdigest())
        if ctx.access_key == self.iam.root.access_key:
            return
        # service accounts / STS credentials never get admin access, even
        # when parented to root — a leaked app credential must not become
        # full admin (reference checkAdminRequestAuth denies svc/sts)
        ident = self.iam.users.get(ctx.access_key)
        if ident is None or ident.kind in ("svc", "sts"):
            raise S3Error("AccessDenied",
                          "admin API denied to service/STS credentials")
        if self.iam.evaluate(ctx.access_key, f"admin:{op}") != "allow":
            raise S3Error("AccessDenied", f"admin:{op} denied")

    # ----------------------------------------------------------- profiling
    def _profiler(self):
        """Per-server sampler (NOT a module singleton: in-process
        multi-node tests and embedded deployments need one per node)."""
        p = getattr(self, "_profiler_inst", None)
        if p is None:
            from minio_tpu.utils.profiling import Sampler

            p = self._profiler_inst = Sampler()
        return p

    async def admin_profile(self, request: web.Request, body: bytes):
        """One-shot sampled-stack capture: start the sampler, wait
        ``?seconds=N`` (default 5, clamped 0.1..60), stop, and return
        the collapsed-stack report directly (reference's admin
        profiling, minus the second round trip).  409 while a
        start/stop-managed capture is already running — a one-shot must
        not steal its samples."""
        seconds = min(60.0, max(0.1, _finite_float(
            request.rel_url.query.get("seconds", "5"), "seconds")))
        sampler = self._profiler()
        ok = await self._run(sampler.start)
        if not ok:
            return web.json_response(
                {"error": "a profiling capture is already running"},
                status=409)
        try:
            await asyncio.sleep(seconds)
        except BaseException:
            # client went away (or shutdown) mid-capture: stop the
            # sampler so the thread doesn't sample forever and future
            # captures aren't 409-blocked; the report is discarded.
            # Off-loop because stop() joins the sampler thread.
            # lint: allow(budget-propagation): cancellation cleanup must outlive the dead request
            self.executor.submit(sampler.stop)
            raise
        blob = await self._run(sampler.stop)
        return web.Response(body=blob, content_type="text/plain",
                            headers={"X-Minio-Profile-Seconds":
                                     f"{seconds:g}"})

    async def admin_profiling_start(self, request: web.Request, body: bytes):
        """Start the sampling profiler on this node and (unless
        ?local=true) every peer concurrently (reference StartProfiling
        fan-out)."""
        ptype = request.rel_url.query.get("profilerType", "cpu")
        if ptype not in ("cpu", ""):
            # Only the sampling CPU profiler exists; silently returning
            # CPU data under a mem/block/... name would be misleading.
            return web.json_response(
                {"error": f"unsupported profilerType {ptype!r} (cpu only)"},
                status=400)
        local_only = request.rel_url.query.get("local", "") in ("true", "1")
        ok = await self._run(self._profiler().start)
        me = getattr(self, "node_addr", "") or "local"
        results = [{"nodeName": me, "success": ok}]
        if not local_only:
            # peer fan-out over the RPC plane (peer.profiling_start,
            # reference cmd/peer-rest-client.go:469 StartProfiling)
            clients = getattr(self, "peer_clients", {})

            async def one(addr):
                try:
                    out = await self._run(
                        clients[addr].call, "peer.profiling_start", {})
                    return {"nodeName": addr,
                            "success": bool(out.get("success"))}
                except Exception as e:
                    return {"nodeName": addr, "success": False,
                            "error": str(e)}

            results += list(await asyncio.gather(*[
                one(a) for a in sorted(clients)
            ]))
        return self._json(results)

    async def admin_profiling_stop(self, request: web.Request, body: bytes):
        """Stop profiling and download the capture: raw collapsed-stack
        report with ?local=true, else a zip with one capture per node; a
        peer that cannot be reached contributes an ERROR entry so a
        partial capture is visibly partial (reference
        DownloadProfileData)."""
        local_only = request.rel_url.query.get("local", "") in ("true", "1")
        blob = await self._run(self._profiler().stop)
        if local_only:
            return web.Response(body=blob,
                                content_type="application/octet-stream")
        import io as iomod
        import zipfile

        # peer captures over the RPC plane (peer.profiling_stop,
        # reference cmd/peer-rest-client.go:481 DownloadProfileData)
        clients = getattr(self, "peer_clients", {})

        async def one(addr):
            try:
                out = await self._run(
                    clients[addr].call, "peer.profiling_stop", {})
                return addr, out.get("data", b""), None
            except Exception as e:
                return addr, None, str(e)

        peers = list(await asyncio.gather(*[
            one(a) for a in sorted(clients)
        ]))
        me = getattr(self, "node_addr", "") or "local"
        buf = iomod.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr(f"profile-{me.replace(':', '_')}-cpu.txt", blob)
            for addr, pb, err in peers:
                name = f"profile-{addr.replace(':', '_')}-cpu"
                if err is None:
                    z.writestr(f"{name}.txt", pb)
                else:
                    z.writestr(f"{name}.ERROR.txt", err)
        return web.Response(
            body=buf.getvalue(), content_type="application/zip",
            headers={"Content-Disposition":
                     'attachment; filename="profile.zip"'})

    def _json(self, obj, status: int = 200) -> web.Response:
        return web.Response(status=status, body=json.dumps(obj).encode(),
                            content_type="application/json")

    def _services_or_503(self):
        svcs = getattr(self, "services", None)
        if svcs is None:
            raise S3Error("XMinioServerNotInitialized",
                          "background services are not running")
        return svcs

    def _erasure_info(self) -> dict:
        """Erasure codec backend: configured backend, the device JAX
        found, per-backend dispatch/byte counters, auto-probe verdicts —
        so an operator can tell which codec their PUTs actually use."""
        from minio_tpu.erasure import coding as ec
        from minio_tpu.ops import device, host as host_codec

        backend = os.environ.get("MINIO_TPU_ERASURE_BACKEND", "auto")
        out = {
            "backend": backend,
            "hostCodec": "native" if host_codec.available() else "numpy",
            "dispatch": {k: dict(v)
                         for k, v in ec.backend_stats.items()},
            "deviceProbe": ec.probe_verdicts(),
        }
        if backend != "host":
            # a host-pinned process never initialises JAX: the chip
            # belongs to the one process that serves from it
            dev = device.info()
            out.update({
                "platform": dev.platform,
                "deviceKind": dev.kind,
                "deviceCount": dev.count,
                "peakBytesInUse": device.peak_bytes_in_use(),
                "compileCacheDir": device.compile_cache_dir(),
            })
        # what the CLI resolved at boot (per-geometry codec, self-test
        # and warm-up seconds); absent under an in-process harness
        boot = getattr(self, "erasure_boot", None)
        if boot is not None:
            # and what became of the geometries asked for since (a set
            # that lost drives writes at a raised parity): `warming`
            # while their dispatches are on the host codec, then
            # `device`, or `failed`
            out["boot"] = {**boot, "geometry": {
                **boot["geometry"], **ec.geometry_states()}}
        return out

    # ---------------------------------------------------------------- info
    async def admin_info(self, request: web.Request, body: bytes):
        si = await self._run(self.api.storage_info)
        drives = [d for pool in si["pools"] for d in pool["disks"]]
        info = {
            "mode": "online",
            "deploymentID": si["pools"][0].get("deployment_id", ""),
            "region": self.region,
            "uptimeSeconds": int(time.time() - self._start_time),
            "drives": {
                "total": len(drives),
                "online": sum(1 for d in drives if d.get("online")),
                "offline": sum(1 for d in drives if not d.get("online")),
                "healing": sum(1 for d in drives if d.get("healing")),
            },
            "pools": [{
                "sets": p["sets"], "drivesPerSet": p["drives_per_set"],
            } for p in si["pools"]],
        }
        svcs = getattr(self, "services", None)
        if svcs is not None:
            info["usage"] = svcs.scanner.data_usage_info()
            if svcs.replication is not None:
                # incl. per-target pending/failed/proxied counters
                # (reference madmin ReplicationInfo / bucket-targets state)
                info["replication"] = svcs.replication.stats.to_dict()
        # disk-cache stats when the API layer reads through an SSD cache
        # (reference madmin CacheStats via cacheObjects)
        from minio_tpu.gateway.cache import CacheLayer

        if isinstance(self.api, CacheLayer):
            info["cache"] = self.api.stats()
        # off the loop: the first caller may build the host library or
        # initialise JAX
        info["erasure"] = await self._run(self._erasure_info)
        # per-tenant QoS live stats (ISSUE 13): the health/admin view
        # of who is queued, admitted, shed and throttled right now
        qos = getattr(self, "qos", None)
        if qos is not None:
            info["qos"] = qos.stats()
        # per-server fan-in over the RPC plane (reference madmin
        # InfoMessage.Servers via peer-rest ServerInfo,
        # cmd/peer-rest-client.go:104); offline peers are reported as
        # such rather than failing the whole call
        peer_clients = getattr(self, "peer_clients", None)
        if peer_clients:
            me = getattr(self, "node_addr", "") or "local"
            servers = [{"endpoint": me, "state": "online",
                        "uptime": info["uptimeSeconds"]}]

            def probe(addr, client):
                try:
                    pi = client.call("peer.server_info", {})
                    return {"endpoint": addr, "state": "online",
                            "uptime": pi.get("uptime", 0),
                            "drives": len(pi.get("drives", [])),
                            "mem": pi.get("mem", {}),
                            "cpu": pi.get("cpu", {})}
                except Exception:
                    return {"endpoint": addr, "state": "offline"}

            probes = await asyncio.gather(*[
                self._run(probe, addr, c)
                for addr, c in sorted(peer_clients.items())
            ])
            info["servers"] = servers + list(probes)
        return self._json(info)

    async def admin_storage_info(self, request: web.Request, body: bytes):
        def gather():
            si = self.api.storage_info()
            # per-drive hardware identity + shared-mount sanity
            # (reference internal/smart + internal/mountinfo: admin
            # storage info shows device model/rotational and warns when
            # "drives" are really one filesystem)
            from minio_tpu.storage.driveinfo import (_mounts,
                                                     drive_hardware,
                                                     shared_mount_warnings)

            mounts = _mounts()  # parse /proc/self/mountinfo ONCE
            local_paths = []
            for pool in si.get("pools", []):
                for d in pool.get("disks", []):
                    ep = d.get("endpoint", "")
                    if ep and "//" not in ep and os.path.isdir(ep):
                        d["hardware"] = drive_hardware(ep, mounts)
                        local_paths.append(ep)
            warns = shared_mount_warnings(local_paths, mounts)
            if warns:
                si["warnings"] = warns
            return si

        return self._json(await self._run(gather))

    # ------------------------------------------------------------ pools
    def _decom_jobs(self) -> dict:
        jobs = getattr(self, "_decom_jobs_map", None)
        if jobs is None:
            jobs = self._decom_jobs_map = {}
        return jobs

    def _pool_idx(self, request) -> int:
        try:
            return int(request.rel_url.query.get("pool", ""))
        except ValueError:
            raise S3Error("AdminInvalidArgument",
                          "pool must be an integer index")

    async def admin_pools_status(self, request: web.Request, body: bytes):
        """Per-pool layout + decommission state (reference
        cmd/admin-handlers-pools.go StatusPool)."""
        from minio_tpu.services import decom as decom_mod

        if not hasattr(self.api, "pools"):
            raise S3Error("NotImplemented",
                          "pool topology does not apply to this backend")

        def run():
            out = []
            susp = self.api.topology.snapshot() \
                if hasattr(self.api, "topology") else {}
            for i, p in enumerate(self.api.pools):
                job = self._decom_jobs().get(i)
                state = (dict(job.state) if job is not None
                         else decom_mod.load_state(p))
                info = p.storage_info()
                out.append({
                    "pool": i,
                    "sets": info["sets"],
                    "drivesPerSet": info["drives_per_set"],
                    "decommission": state,
                    "draining": i in self.api._draining,
                    # suspended-from-placement reason ("" = in placement)
                    "suspended": susp.get(i, ""),
                })
            return out

        return self._json({"pools": await self._run(run)})

    async def admin_pools_decommission(self, request: web.Request,
                                       body: bytes):
        """Start draining one pool into the others (reference
        cmd/admin-handlers-pools.go StartDecommission)."""
        from minio_tpu.services.decom import PoolDecommission

        if not hasattr(self.api, "pools"):
            raise S3Error("NotImplemented",
                          "pool topology does not apply to this backend")
        idx = self._pool_idx(request)

        def run():
            jobs = self._decom_jobs()
            job = jobs.get(idx)
            if job is not None and job.state.get("state") == "draining":
                raise S3Error("AdminInvalidArgument",
                              f"pool {idx} is already draining")
            job = PoolDecommission(self.api, idx)
            # drain traffic defers to foreground load like every other
            # background plane (ISSUE 14: metered through the brownout
            # throttle)
            svcs = getattr(self, "services", None)
            if svcs is not None and getattr(svcs, "brownout", None) \
                    is not None:
                job.throttle = svcs.brownout.background_allowed
            job.start()
            jobs[idx] = job
            return dict(job.state)

        try:
            return self._json(await self._run(run))
        except st.InvalidArgument as e:
            raise S3Error("AdminInvalidArgument", str(e))

    async def admin_pools_cancel(self, request: web.Request, body: bytes):
        idx = self._pool_idx(request)

        def run():
            job = self._decom_jobs().get(idx)
            if job is None:
                raise S3Error("AdminInvalidArgument",
                              f"no decommission running for pool {idx}")
            job.cancel()
            return dict(job.state)

        return self._json(await self._run(run))

    async def admin_pools_add(self, request: web.Request, body: bytes):
        """Online pool expansion (ISSUE 14): grow the deployment with a
        new pool of local drives WITHOUT a restart — existing buckets
        are stamped onto it and placement starts routing new objects
        there immediately.  (The reference requires a restart with the
        new pool argument, cmd/erasure-server-pool.go; going past that
        is the point.)  Body: {"paths": ["/drive1", ...],
        "setSize": optional}."""
        if not hasattr(self.api, "pools"):
            raise S3Error("NotImplemented",
                          "pool topology does not apply to this backend")
        try:
            doc = json.loads(body)
            paths = doc["paths"]
            if not (isinstance(paths, list) and paths
                    and all(isinstance(x, str) and x for x in paths)):
                raise ValueError
            set_size = doc.get("setSize")
            if set_size is not None and (isinstance(set_size, bool)
                                         or not isinstance(set_size, int)
                                         or set_size <= 0):
                raise ValueError
        except (ValueError, KeyError, TypeError):
            raise S3Error("AdminInvalidArgument",
                          'body must be {"paths": ["/drive1", ...], '
                          '"setSize": optional int}')

        def run():
            from minio_tpu.erasure.sets import ErasureSets
            from minio_tpu.storage.local import LocalStorage

            try:
                es = ErasureSets([LocalStorage(p) for p in paths],
                                 set_size=set_size,
                                 pool_index=len(self.api.pools))
                idx = self.api.add_pool(es)
            except st.InvalidArgument as e:
                raise S3Error("AdminInvalidArgument", str(e))
            # the new pool's sets must feed the same choke points as
            # the boot-time ones (hot tier, metacache, bloom tracker,
            # MRF heal queue)
            rewire = getattr(self, "rewire_topology_hooks", None)
            if rewire is not None:
                rewire()
            return {"pool": idx, "sets": es.set_count,
                    "drivesPerSet": es.set_drive_count}

        return self._json(await self._run(run))

    async def admin_bandwidth(self, request: web.Request, body: bytes):
        """Cluster-wide replication bandwidth: this node's monitor plus
        every peer's over the RPC plane (reference
        BandwidthMonitorHandler + peer MonitorBandwidth)."""
        bucket = request.rel_url.query.get("bucket", "")
        svcs = getattr(self, "services", None)
        repl = getattr(svcs, "replication", None) if svcs else None
        me = getattr(self, "node_addr", "") or "local"
        out = {me: repl.bw_monitor.report(bucket) if repl else {}}
        clients = getattr(self, "peer_clients", {})

        def probe(addr, client):
            try:
                return addr, client.call("peer.bandwidth",
                                         {"bucket": bucket})["report"]
            except Exception as e:
                return addr, {"error": str(e)}

        for addr, report in await asyncio.gather(*[
            self._run(probe, a, c) for a, c in sorted(clients.items())
        ]):
            out[addr] = report
        return self._json(out)

    # ------------------------------------------------------------------ KMS
    def _kms_or_503(self):
        kms = getattr(self, "kms", None)
        if kms is None:
            raise S3Error("KMSNotConfigured", "no KMS is configured")
        return kms

    async def admin_kms_status(self, request: web.Request, body: bytes):
        """reference cmd/kms-handlers.go KMSStatusHandler."""
        kms = self._kms_or_503()
        return self._json({
            "name": type(kms).__name__,
            "defaultKeyID": getattr(kms, "key_id", ""),
            "endpoints": {getattr(kms, "endpoint", "local"): "online"},
        })

    async def admin_kms_key_status(self, request: web.Request, body: bytes):
        """Round-trip health check of one key: generate a data key under
        it and unseal the envelope (reference KMSKeyStatusHandler's
        encrypt/decrypt cycle)."""
        kms = self._kms_or_503()
        key_id = request.rel_url.query.get(
            "key-id", getattr(kms, "key_id", ""))
        out = {"keyId": key_id}

        def probe():
            pk, sealed = kms.generate_key("admin-kms-probe")
            got = kms.decrypt_key(sealed, "admin-kms-probe")
            return pk == got

        try:
            ok = await self._run(probe)
            out["encryptionErr" if not ok else "status"] = (
                "decrypted key differs" if not ok else "online")
        except Exception as e:
            out["encryptionErr"] = str(e)
        return self._json(out)

    async def admin_kms_create_key(self, request: web.Request, body: bytes):
        kms = self._kms_or_503()
        key_id = request.rel_url.query.get("key-id", "")
        if not key_id:
            raise S3Error("AdminInvalidArgument", "key-id is required")
        create = getattr(kms, "create_key", None)
        if create is None:
            raise S3Error("NotImplemented",
                          "the static local KMS cannot create keys "
                          "(configure a KES server)")
        from minio_tpu.crypto.kms import KMSError

        try:
            await self._run(create, key_id)
        except KMSError as e:
            raise S3Error("AdminInvalidArgument", str(e))
        return self._json({"keyId": key_id, "created": True})

    def _rebalance_job(self, create: bool = False):
        job = getattr(self, "_rebalance_inst", None)
        if job is None and create:
            from minio_tpu.services.decom import PoolRebalance

            job = self._rebalance_inst = PoolRebalance(self.api)
            svcs = getattr(self, "services", None)
            if svcs is not None and getattr(svcs, "brownout", None) \
                    is not None:
                job.throttle = svcs.brownout.background_allowed
        return job

    async def admin_rebalance_start(self, request: web.Request,
                                    body: bytes):
        """`mc admin rebalance start` (reference
        cmd/admin-handlers-pools.go RebalanceStart)."""
        if not hasattr(self.api, "pools") or len(self.api.pools) < 2:
            raise S3Error("AdminInvalidArgument",
                          "rebalance needs multiple pools")

        def run():
            job = self._rebalance_job(create=True)
            if job.state.get("state") == "running":
                raise S3Error("AdminInvalidArgument",
                              "rebalance already running")
            job.start()
            return job.status()

        return self._json(await self._run(run))

    async def admin_rebalance_stop(self, request: web.Request, body: bytes):
        job = self._rebalance_job()
        if job is None:
            raise S3Error("AdminInvalidArgument", "no rebalance started")
        await self._run(job.stop)
        return self._json(job.status())

    async def admin_rebalance_status(self, request: web.Request,
                                     body: bytes):
        job = self._rebalance_job()
        if job is None:
            if not hasattr(self.api, "pools") or len(self.api.pools) < 2:
                return self._json({"state": "none"})
            # no in-process job: instantiate one (its ctor reads the
            # quorum-persisted state of a previous process's run and
            # maps a dangling 'running' to 'interrupted') so the
            # response shape matches the live path
            job = await self._run(self._rebalance_job, True)
        return self._json(await self._run(job.status))

    async def admin_data_usage(self, request: web.Request, body: bytes):
        """Cluster usage; with ?bucket= (and optional ?prefix=) the
        hierarchical tree answers exact per-prefix usage with immediate
        children broken out (reference prefix usage over
        dataUsageCache, cmd/data-usage-cache.go)."""
        svcs = self._services_or_503()
        bucket = request.rel_url.query.get("bucket", "")
        if bucket:
            prefix = request.rel_url.query.get("prefix", "").strip("/")
            return self._json(
                svcs.scanner.usage_by_prefix(bucket, prefix))
        return self._json(svcs.scanner.data_usage_info())

    async def admin_top_locks(self, request: web.Request, body: bytes):
        locker = getattr(self, "locker", None)
        locks = locker.top_locks() if locker is not None else []
        return self._json({"locks": locks})

    async def admin_service(self, request: web.Request, body: bytes):
        action = request.rel_url.query.get("action", "")
        if action not in ("restart", "stop"):
            raise S3Error("InvalidArgument", f"unknown action {action!r}")
        # in-process server: acknowledge; the supervisor owns the lifecycle
        return self._json({"action": action, "accepted": True})

    # ---------------------------------------------------------------- heal
    async def admin_heal(self, request: web.Request, body: bytes):
        svcs = self._services_or_503()
        bucket = request.match_info.get("bucket", "")
        prefix = request.match_info.get("prefix", "")
        q = request.rel_url.query
        token = q.get("clientToken", "")
        if token:
            if q.get("forceStop") == "true":
                ok = svcs.heals.stop(token)
                return self._json({"stopped": bool(ok)})
            status = svcs.heals.get(token)
            if status is None:
                raise S3Error("InvalidArgument", "unknown heal token")
            return self._json(status.to_dict())
        deep = False
        if body:
            try:
                opts = json.loads(body)
                deep = bool(opts.get("scanMode") == 2 or opts.get("deep"))
            except ValueError:
                raise S3Error("InvalidArgument", "heal options must be JSON")
        status = await self._run(svcs.heals.launch, bucket, prefix, deep)
        return self._json({"clientToken": status.heal_id, "started": True})

    async def admin_bg_heal_status(self, request: web.Request, body: bytes):
        svcs = self._services_or_503()
        return self._json({
            "mrf": svcs.mrf.stats.to_dict(),
            "scanner": {
                "cycles": svcs.scanner.cycles,
                "last_update": svcs.scanner.usage.last_update,
            },
            "heals": svcs.heals.statuses(),
        })

    # ------------------------------------------------------- users/policies
    async def admin_add_user(self, request: web.Request, body: bytes):
        ak = request.rel_url.query.get("accessKey", "")
        if not ak:
            raise S3Error("InvalidArgument", "accessKey required")
        try:
            doc = json.loads(body)
            sk = doc["secretKey"]
        except (ValueError, KeyError):
            raise S3Error("InvalidArgument",
                          'body must be {"secretKey": ...}')
        policies = doc.get("policies", [])
        try:
            await self._run(self.iam.add_user, ak, sk, policies)
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"accessKey": ak})

    async def admin_remove_user(self, request: web.Request, body: bytes):
        ak = request.rel_url.query.get("accessKey", "")
        try:
            await self._run(self.iam.remove_user, ak)
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"removed": ak})

    async def admin_list_users(self, request: web.Request, body: bytes):
        return self._json({"users": await self._run(self.iam.list_users)})

    async def admin_set_user_status(self, request: web.Request, body: bytes):
        q = request.rel_url.query
        ak = q.get("accessKey", "")
        status = q.get("status", "")
        if status not in ("enabled", "disabled"):
            raise S3Error("InvalidArgument", "status must be enabled|disabled")
        try:
            await self._run(self.iam.set_user_status, ak,
                            status == "enabled")
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"accessKey": ak, "status": status})

    async def admin_add_policy(self, request: web.Request, body: bytes):
        name = request.rel_url.query.get("name", "")
        if not name:
            raise S3Error("InvalidArgument", "policy name required")
        try:
            await self._run(self.iam.set_policy, name, body)
        except Exception as e:
            raise S3Error("MalformedPolicy", str(e))
        return self._json({"policy": name})

    async def admin_remove_policy(self, request: web.Request, body: bytes):
        name = request.rel_url.query.get("name", "")
        try:
            await self._run(self.iam.delete_policy, name)
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"removed": name})

    async def admin_list_policies(self, request: web.Request, body: bytes):
        return self._json(
            {"policies": await self._run(self.iam.list_policies)})

    async def admin_set_policy_mapping(self, request: web.Request,
                                       body: bytes):
        q = request.rel_url.query
        names = [n for n in q.get("policyName", "").split(",") if n]
        target = q.get("userOrGroup", "")
        is_group = q.get("isGroup") == "true"
        try:
            if is_group:
                await self._run(self.iam.attach_group_policy, target, names)
            else:
                await self._run(self.iam.attach_policy, target, names)
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"userOrGroup": target, "policies": names})

    async def admin_update_group(self, request: web.Request, body: bytes):
        try:
            doc = json.loads(body)
            group = doc["group"]
            members = doc.get("members", [])
            remove = bool(doc.get("isRemove"))
        except (ValueError, KeyError):
            raise S3Error("InvalidArgument",
                          'body must be {"group":..., "members":[...]}')
        fn = (self.iam.remove_group_members if remove
              else self.iam.add_group_members)
        try:
            await self._run(fn, group, members)
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"group": group})

    async def admin_list_groups(self, request: web.Request, body: bytes):
        return self._json({"groups": await self._run(self.iam.list_groups)})

    async def admin_add_service_account(self, request: web.Request,
                                        body: bytes):
        try:
            doc = json.loads(body) if body else {}
        except ValueError:
            raise S3Error("InvalidArgument", "body must be JSON")
        parent = doc.get("targetUser", "")
        policy = doc.get("policy", "")
        if not parent:
            raise S3Error("InvalidArgument", "targetUser required")
        try:
            ident = await self._run(
                self.iam.create_service_account, parent, policy)
        except Exception as e:
            raise S3Error("InvalidArgument", str(e))
        return self._json({"accessKey": ident.access_key,
                           "secretKey": ident.secret_key})

    # ---------------------------------------------------- replication targets
    def _load_targets(self, bucket: str) -> list[dict]:
        from minio_tpu.services.replication import load_targets

        return [t.to_dict() for t in load_targets(self.meta, bucket)]

    async def admin_set_remote_target(self, request: web.Request, body: bytes):
        import uuid

        from minio_tpu.services.replication import ReplicationTarget

        bucket = request.rel_url.query.get("bucket", "")
        if not bucket:
            raise S3Error("InvalidArgument", "bucket query param required")
        try:
            doc = json.loads(body)
        except ValueError:
            raise S3Error("InvalidArgument", "body must be JSON")
        creds = doc.get("credentials") or {}
        # accept both the write key and the read key so a
        # list -> edit -> set round trip preserves the limit
        raw_bw = doc.get("bandwidth", doc.get("bandwidthLimit", 0)) or 0
        try:
            bw = int(raw_bw)
        except (TypeError, ValueError):
            raise S3Error("InvalidArgument",
                          "bandwidth must be an integer (bytes/sec)")
        tgt = ReplicationTarget(
            arn=doc.get("arn") or
            f"arn:minio:replication::{uuid.uuid4().hex[:12]}:"
            f"{doc.get('targetbucket', doc.get('bucket', ''))}",
            endpoint=doc.get("endpoint", ""),
            bucket=doc.get("targetbucket", doc.get("bucket", "")),
            access_key=doc.get("accessKey", creds.get("accessKey", "")),
            secret_key=doc.get("secretKey", creds.get("secretKey", "")),
            region=doc.get("region", "us-east-1"),
            bandwidth_limit=bw,
        )
        if not tgt.endpoint or not tgt.bucket:
            raise S3Error("InvalidArgument", "endpoint and targetbucket required")
        targets = [t for t in self._load_targets(bucket)
                   if t.get("arn") != tgt.arn]
        targets.append(tgt.to_dict())
        await self._run(self.meta.set_config, bucket, "replication_targets",
                        json.dumps(targets))
        return self._json({"arn": tgt.arn})

    async def admin_list_remote_targets(self, request: web.Request,
                                        body: bytes):
        bucket = request.rel_url.query.get("bucket", "")
        if not bucket:
            raise S3Error("InvalidArgument", "bucket query param required")
        targets = await self._run(self._load_targets, bucket)
        for t in targets:
            t.pop("secretKey", None)  # never return credentials
        return self._json(targets)

    async def admin_remove_remote_target(self, request: web.Request,
                                         body: bytes):
        bucket = request.rel_url.query.get("bucket", "")
        arn = request.rel_url.query.get("arn", "")
        if not bucket or not arn:
            raise S3Error("InvalidArgument", "bucket and arn required")
        targets = [t for t in await self._run(self._load_targets, bucket)
                   if t.get("arn") != arn]
        await self._run(self.meta.set_config, bucket, "replication_targets",
                        json.dumps(targets))
        return self._json({})

    async def admin_replication_resync(self, request: web.Request,
                                       body: bytes):
        """Re-enqueue every object of the bucket for replication
        (reference startReplicationResync)."""
        bucket = request.rel_url.query.get("bucket", "")
        if not bucket:
            raise S3Error("InvalidArgument", "bucket query param required")
        services = self._services_or_503()
        if services.replication is None:
            raise S3Error("XMinioServerNotInitialized")
        n = await self._run(services.replication.resync, bucket)
        return self._json({"enqueued": n})
