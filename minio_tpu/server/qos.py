"""Per-tenant QoS: weighted fair admission + bandwidth isolation.

The deadline/brownout plane (ISSUE 3) sheds *global* overload, but a
single hot bucket or access key could still monopolize the one API
semaphore and starve every quiet tenant — the reference stops at a
global per-node request cap (cmd/handler-api.go).  This plane replaces
that single semaphore with a **weighted deficit-round-robin scheduler**
(ISSUE 13):

* requests classify into tenants — an explicit ``key:<access-key>``
  rule wins over the request's bucket (``bucket:<name>``), and
  bucketless/anonymous requests ride the ``default`` class;
* each tenant owns a bounded FIFO queue (a FULL tenant queue sheds 503
  for THAT tenant while every other tenant keeps flowing), a deficit
  counter, an optional concurrency cap, and an optional data-plane
  bandwidth bucket (utils/bandwidth.py TokenBucket, generalized from
  the replication limiter);
* a fixed pool of global slots (api.requests_max, same sizing as the
  old semaphore) is granted by a DRR dispatch sweep that runs
  synchronously on every release.

The admit/release/reweight/shed protocol is specified first as an
executable model (analysis/concurrency/models/qos.py, per the PR 10
convention) and this implementation mirrors it action for action:
quantum tops up once per visit and only when the queue head is not yet
affordable, a drained queue forfeits its deficit, and a reweight clamps
stale credit.

Scheduler cost is weighted by ESTIMATED BYTES (ISSUE 14 satellite,
closing the PR 13 leftover): a request's admission spends
``clamp(ceil(content_length / cost_unit), 1, max_cost)`` deficit
instead of a flat 1, so one multipart PUT is priced honestly against N
small GETs.  Requests without a body (GETs — the response size is
unknown at admission) cost 1.  A top-up that does not yet afford a
heavy head still counts as sweep progress (the model's
save-up-not-progress mutation is the wedge this prevents: a request
costing more than its tenant's weight must be able to finish saving
across sweep rounds).  ``MINIO_TPU_QOS_COST_UNIT=0`` restores flat
unit pricing.

Threading: admission calls (try_admit / enqueue / abandon / release)
run on the aiohttp event loop, exactly like the semaphore they
replace.  ``_mu`` exists for the two cross-thread surfaces — admin
reconfigure (executor thread) and metrics scrapes — and is never held
across an await.

Knobs (env wins over the dynamic ``qos`` config subsystem):
``MINIO_TPU_QOS`` gates the plane (default 0: the legacy
single-semaphore path runs byte- and metrics-identical),
``MINIO_TPU_QOS_TENANTS`` (JSON rules), ``MINIO_TPU_QOS_MAX_QUEUE``,
``MINIO_TPU_QOS_DEFAULT_WEIGHT``, ``MINIO_TPU_QOS_DEFAULT_BANDWIDTH``,
``MINIO_TPU_QOS_DEFAULT_MAX_CONCURRENCY``,
``MINIO_TPU_QOS_COST_UNIT`` (bytes per deficit point, default 1 MiB;
0 = flat unit pricing), ``MINIO_TPU_QOS_MAX_COST`` (clamp, default 32).
"""

from __future__ import annotations

import asyncio
import json
import math
import os
import threading
import time
from collections import deque

from minio_tpu.utils.bandwidth import BandwidthMonitor, TokenBucket

#: idle tenant states (no queue, no inflight, no recent traffic) age
#: out so per-bucket auto-tenancy cannot grow the map unboundedly
IDLE_TTL_S = 900.0

#: weights below this are clamped: a zero/negative weight would starve
#: its own tenant by construction, which the no-starvation invariant
#: (models/qos.py) forbids for admitted rules
MIN_WEIGHT = 0.01

#: byte-cost pricing defaults: 1 deficit point per MiB of declared
#: body, clamped to [1, 32] so an attacker-sized Content-Length cannot
#: make its own tenant save forever (and bounds the sweep's save-up
#: rounds at max_cost / MIN_WEIGHT)
DEFAULT_COST_UNIT = 1 << 20
DEFAULT_MAX_COST = 32.0


class TenantQueueFull(Exception):
    """Arrival against a tenant queue standing at its bound — shed
    THIS tenant with 503 SlowDown; other tenants are unaffected."""


class TenantRule:
    """Admin-settable per-tenant parameters (a missing field falls back
    to the default class)."""

    __slots__ = ("weight", "max_concurrency", "bandwidth", "hot_cap")

    def __init__(self, weight: float = 1.0, max_concurrency: int = 0,
                 bandwidth: int = 0, hot_cap: int = 0):
        # NaN poisons the deficit arithmetic (deficit >= 1.0 is never
        # True — total tenant starvation from one config typo) and
        # int(inf) raises: non-finite values degrade to the neutral
        # defaults instead
        w = float(weight)
        if not math.isfinite(w):
            w = 1.0
        self.weight = max(w, MIN_WEIGHT)
        mc = float(max_concurrency)
        self.max_concurrency = max(int(mc), 0) if math.isfinite(mc) \
            else 0
        bw = float(bandwidth)
        self.bandwidth = max(int(bw), 0) if math.isfinite(bw) else 0
        # per-tenant hot-lane slot cap (ISSUE 18 satellite): 0 = fall
        # back to the plane-level hot_share bound
        hc = float(hot_cap)
        self.hot_cap = max(int(hc), 0) if math.isfinite(hc) else 0

    def to_dict(self) -> dict:
        return {"weight": self.weight,
                "max_concurrency": self.max_concurrency,
                "bandwidth": self.bandwidth,
                "hot_cap": self.hot_cap}

    @classmethod
    def from_dict(cls, doc: dict, default: "TenantRule") -> "TenantRule":
        return cls(
            weight=doc.get("weight", default.weight),
            max_concurrency=doc.get("max_concurrency",
                                    default.max_concurrency),
            bandwidth=doc.get("bandwidth", default.bandwidth),
            hot_cap=doc.get("hot_cap", default.hot_cap))


class _TenantState:
    """Scheduler-side view of one tenant: queue + deficit + counters."""

    __slots__ = ("key", "rule", "queue", "inflight", "deficit",
                 "admitted", "shed_full", "shed_deadline", "hot_admits",
                 "hot_rejects", "hot_inflight", "hot_capped",
                 "throttled_in", "throttled_out", "bw", "last_active")

    def __init__(self, key: str, rule: TenantRule):
        self.key = key
        self.rule = rule
        self.queue: deque = deque()   # asyncio futures, FIFO
        self.inflight = 0
        self.deficit = 0.0
        self.admitted = 0
        self.shed_full = 0
        self.shed_deadline = 0
        self.hot_admits = 0
        self.hot_rejects = 0
        self.hot_inflight = 0   # hot-lane slots this tenant HOLDS
        self.hot_capped = 0     # hot-lane claims refused at the cap
        self.throttled_in = 0
        self.throttled_out = 0
        self.bw = TokenBucket(rule.bandwidth) if rule.bandwidth > 0 \
            else None
        self.last_active = time.monotonic()

    def apply_rule(self, rule: TenantRule) -> None:
        """Admin reweight/recap/relimit, effective immediately: the
        deficit clamps to the new weight (models/qos.py
        reweight-keeps-stale-deficit) and the bandwidth bucket rebuilds
        only when the limit actually changed (an unchanged bucket keeps
        its debt so a reconfigure can't be used to reset pacing)."""
        old = self.rule
        self.rule = rule
        self.deficit = min(self.deficit, rule.weight)
        if rule.bandwidth != old.bandwidth or (
                self.bw is None and rule.bandwidth > 0):
            self.bw = TokenBucket(rule.bandwidth) \
                if rule.bandwidth > 0 else None

    def depth(self) -> int:
        return sum(1 for f in self.queue if not f.done())


class QosPlane:
    """The weighted-DRR admission scheduler + per-tenant bandwidth
    plane.  One instance per S3Server, replacing ``self.sem`` when
    MINIO_TPU_QOS is on."""

    def __init__(self, max_concurrency: int, *,
                 default_rule: TenantRule | None = None,
                 rules: dict[str, TenantRule] | None = None,
                 max_queue: int = 0,
                 cost_unit: int | None = None,
                 max_cost: float | None = None):
        self.max_concurrency = max(int(max_concurrency), 1)
        self.default_rule = default_rule or TenantRule()
        self.rules: dict[str, TenantRule] = dict(rules or {})
        # byte-cost pricing: bytes per deficit point (0 = flat unit
        # cost) and the [1, max_cost] clamp
        self.cost_unit = DEFAULT_COST_UNIT if cost_unit is None \
            else max(int(cost_unit), 0)
        self.max_cost = DEFAULT_MAX_COST if max_cost is None \
            else max(float(max_cost), 1.0)
        # per-tenant shed threshold; auto = 2x the slot pool (the old
        # plane queued unboundedly per-budget — the bound is what makes
        # one tenant's backlog finite)
        self.max_queue = int(max_queue) if max_queue > 0 \
            else max(16, 2 * self.max_concurrency)
        # per-tenant hot-lane cap (ISSUE 16 satellite): the hot lane
        # (app.hot_sem, sized max(max_concurrency, 4) * 2) is a SHARED
        # pool — without a per-tenant bound a hot-tenant flood of RAM
        # hits crowds the lane itself and other tenants' hits queue
        # behind drive-bound work.  Each tenant may hold at most
        # hot_share of the lane; at-cap claims fall through to normal
        # QoS admission (counted hotLaneCapped).
        self.hot_capacity = max(self.max_concurrency, 4) * 2
        self.hot_share = 0.5
        self.monitor = BandwidthMonitor()
        self._mu = threading.Lock()
        self._tenants: dict[str, _TenantState] = {}
        self._active = 0        # granted slots (== sum of inflight)
        self._queued = 0        # live waiters across ALL tenant queues:
        # maintained at the future lifecycle level (inc on enqueue, dec
        # exactly once at grant or pending-abandon) so the aggregate
        # brownout signal is O(1) per enqueue instead of a scan of
        # every tenant's queue under the lock
        self._rr = 0            # rotation origin for the dispatch sweep
        self._rounds = 0        # DRR rotation rounds swept
        self._external = 0      # slots held by the PREVIOUS plane's
        # in-flight requests at a runtime gate flip (seed_external)
        self._last_gc = time.monotonic()
        self._loop = None       # event loop, learned at first enqueue
        # generation counter, bumped on every reconfigure: the overload
        # controller (server/controller.py) pins the generation it
        # sampled and refuses to act when an admin write moved it —
        # the never-acts-on-a-stale-snapshot invariant, live
        self.reconfigures = 0

    # -- construction --------------------------------------------------------
    @staticmethod
    def gate_enabled(config=None, environ=None) -> bool:
        """MINIO_TPU_QOS env wins; else the ``qos.enable`` config key."""
        env = os.environ if environ is None else environ
        v = env.get("MINIO_TPU_QOS")
        if v is not None:
            return v.strip().lower() not in ("", "0", "off", "false", "no")
        if config is None:
            return False
        return config.get_bool("qos", "enable", False)

    @classmethod
    def from_config(cls, config, max_concurrency: int,
                    environ=None) -> "QosPlane | None":
        if not cls.gate_enabled(config, environ):
            return None
        plane = cls(max_concurrency)
        plane.load_config(config, environ)
        return plane

    @staticmethod
    def _parse_rules(raw: str, default: TenantRule) -> dict:
        """Tenant-rule JSON -> {key: TenantRule}; malformed input
        degrades to no rules (boot must not fail on a typo'd knob)."""
        try:
            doc = json.loads(raw or "{}")
            if not isinstance(doc, dict):
                return {}
            return {str(k): TenantRule.from_dict(v, default)
                    for k, v in doc.items() if isinstance(v, dict)}
        except (ValueError, TypeError):
            return {}

    def load_config(self, config, environ=None) -> None:
        """(Re)read weights/caps/limits from env + the ``qos`` config
        subsystem and apply them to live tenant states — the dynamic
        half of the admin surface (no restart)."""
        env = os.environ if environ is None else environ

        def knob(env_key: str, cfg_key: str) -> str:
            v = env.get(env_key)
            return v if v is not None else (
                config.get("qos", cfg_key) if config is not None else "")

        def num(text: str, fallback: float) -> float:
            try:
                return float(text)
            except (TypeError, ValueError):
                return fallback

        default = TenantRule(
            weight=num(knob("MINIO_TPU_QOS_DEFAULT_WEIGHT",
                            "default_weight"), 1.0),
            max_concurrency=int(num(
                knob("MINIO_TPU_QOS_DEFAULT_MAX_CONCURRENCY",
                     "default_max_concurrency"), 0)),
            bandwidth=int(num(knob("MINIO_TPU_QOS_DEFAULT_BANDWIDTH",
                                   "default_bandwidth"), 0)),
            hot_cap=int(num(knob("MINIO_TPU_QOS_DEFAULT_HOT_CAP",
                                 "default_hot_cap"), 0)))
        rules = self._parse_rules(
            knob("MINIO_TPU_QOS_TENANTS", "tenants"), default)
        mq_raw = knob("MINIO_TPU_QOS_MAX_QUEUE", "max_queue")
        max_queue = int(num(mq_raw, 0)) if mq_raw not in ("", "auto") \
            else 0
        cu_raw = knob("MINIO_TPU_QOS_COST_UNIT", "cost_unit")
        cost_unit = None if cu_raw in ("", None) \
            else max(int(num(cu_raw, DEFAULT_COST_UNIT)), 0)
        mc_raw = knob("MINIO_TPU_QOS_MAX_COST", "max_cost")
        max_cost = None if mc_raw in ("", None) \
            else max(num(mc_raw, DEFAULT_MAX_COST), 1.0)
        hs_raw = knob("MINIO_TPU_QOS_HOT_SHARE", "hot_share")
        hot_share = None if hs_raw in ("", None) \
            else min(max(num(hs_raw, 0.5), 0.01), 1.0)
        self.reconfigure(default_rule=default, rules=rules,
                         max_queue=max_queue, cost_unit=cost_unit,
                         max_cost=max_cost, hot_share=hot_share)

    def reconfigure(self, *, default_rule: TenantRule | None = None,
                    rules: dict[str, TenantRule] | None = None,
                    max_queue: int = 0,
                    cost_unit: int | None = None,
                    max_cost: float | None = None,
                    hot_share: float | None = None) -> None:
        """Apply a new rule set atomically; live tenant states pick up
        their new weight/cap/bandwidth immediately (deficit clamped)."""
        with self._mu:
            if default_rule is not None:
                self.default_rule = default_rule
            if rules is not None:
                self.rules = dict(rules)
            self.max_queue = int(max_queue) if max_queue > 0 \
                else max(16, 2 * self.max_concurrency)
            if cost_unit is not None:
                self.cost_unit = max(int(cost_unit), 0)
            if max_cost is not None and math.isfinite(float(max_cost)):
                self.max_cost = max(float(max_cost), 1.0)
            if hot_share is not None:
                self.hot_share = min(max(float(hot_share), 0.01), 1.0)
            for st in self._tenants.values():
                st.apply_rule(self.rules.get(st.key, self.default_rule))
            self.reconfigures += 1
            loop = self._loop
        # a raised cap/weight can make parked waiters eligible NOW:
        # kick a dispatch sweep on the event loop (reconfigure runs on
        # an executor thread and futures resolve only on the loop)
        if loop is not None and not loop.is_closed():
            try:
                loop.call_soon_threadsafe(self._dispatch_on_loop)
            except RuntimeError:
                pass  # loop shut down between the check and the call

    def _dispatch_on_loop(self) -> None:
        with self._mu:
            self._dispatch_locked()

    # -- classification ------------------------------------------------------
    @staticmethod
    def access_key_of(request) -> str:
        """CLAIMED access key, parsed cheaply pre-auth (classification
        must not cost a signature verification; weights are advisory
        scheduling state, and the signature still verifies in the
        handler)."""
        auth = request.headers.get("Authorization", "")
        if auth.startswith("AWS4-"):
            i = auth.find("Credential=")
            if i >= 0:
                cred = auth[i + len("Credential="):]
                return cred.split("/", 1)[0].split(",", 1)[0]
        elif auth.startswith("AWS "):
            return auth[4:].split(":", 1)[0]
        q = request.rel_url.query
        cred = q.get("X-Amz-Credential", "")
        if cred:
            return cred.split("/", 1)[0]
        return q.get("AWSAccessKeyId", "")

    def cost_of(self, request) -> float:
        """Admission cost of a request, weighted by its DECLARED body
        size: clamp(ceil(content_length / cost_unit), 1, max_cost).
        GETs (no body — the response size is unknown pre-admission) and
        sub-unit bodies cost 1; the clamp bounds both an attacker-sized
        Content-Length and the sweep's save-up rounds.  cost_unit=0
        restores flat unit pricing."""
        if self.cost_unit <= 0:
            return 1.0
        try:
            n = request.content_length or 0
        except (TypeError, ValueError):
            n = 0
        if n <= self.cost_unit:
            return 1.0
        return float(min(self.max_cost,
                         -(-int(n) // self.cost_unit)))

    def classify(self, request) -> str:
        """Tenant identity: explicit ``key:`` rule > the request's
        bucket (every bucket is its own tenant under the default class)
        > the ``default`` class for bucketless/anonymous requests."""
        ak = self.access_key_of(request)
        if ak:
            key = f"key:{ak}"
            if key in self.rules:
                return key
        bucket = request.match_info.get("bucket", "")
        if bucket:
            return f"bucket:{bucket}"
        return "default"

    # -- scheduler (event-loop callers) --------------------------------------
    def _state_locked(self, tenant: str) -> _TenantState:
        st = self._tenants.get(tenant)
        if st is None:
            st = _TenantState(tenant,
                              self.rules.get(tenant, self.default_rule))
            self._tenants[tenant] = st
        st.last_active = time.monotonic()
        return st

    def _prune_locked(self, st: _TenantState) -> None:
        """Drop abandoned (timed-out / disconnected) waiters from the
        queue front and release forfeited deficit when it empties.
        Lives on the plane (not the tenant state) because removing a
        future from a queue is the ONE place the aggregate _queued
        counter decrements — single-owner accounting, so a future
        cancelled by wait_for before abandon() runs still pairs its
        enqueue increment exactly once."""
        q = st.queue
        while q and q[0].done():
            q.popleft()
            self._queued -= 1
        if not q:
            st.deficit = 0.0

    @staticmethod
    def _under_cap(st: _TenantState) -> bool:
        cap = st.rule.max_concurrency
        return cap <= 0 or st.inflight < cap

    def try_admit(self, tenant: str, cost: float = 1.0) -> bool:
        """Fast path: a free slot, an under-cap tenant and an empty
        tenant queue admit without queueing (the model's direct-admit
        arrival; mirrors the old `not sem.locked()` branch so an idle
        server never counts spurious pressure).  Direct admits bypass
        the deficit (as modeled) — cost prices CONTENDED admissions,
        where fairness is decided."""
        with self._mu:
            self._gc_locked()
            st = self._state_locked(tenant)
            self._prune_locked(st)
            if self._active < self.max_concurrency \
                    and self._under_cap(st) and not st.queue:
                self._active += 1
                st.inflight += 1
                st.admitted += 1
                return True
            return False

    def enqueue(self, tenant: str, cost: float = 1.0):
        """Join the tenant's admission queue.  Returns (future,
        aggregate_depth) — the aggregate cross-tenant depth feeds
        brownout pressure.  Raises TenantQueueFull at the bound.  The
        byte-estimated cost rides the future itself; the dispatch sweep
        spends it from the tenant's deficit at admission."""
        loop = asyncio.get_running_loop()
        with self._mu:
            self._loop = loop
            st = self._state_locked(tenant)
            self._prune_locked(st)
            if st.depth() >= self.max_queue:
                st.shed_full += 1
                raise TenantQueueFull(tenant)
            fut = loop.create_future()
            fut._qos_cost = max(float(cost), 1.0)
            st.queue.append(fut)
            self._queued += 1
            depth = self._queued
        return fut, depth

    def abandon(self, tenant: str, fut, *, deadline: bool = False) -> None:
        """A queued waiter left (budget expiry / client disconnect):
        drop it and, when the queue empties, forfeit the deficit —
        exactly the model's budget-expires dequeue."""
        with self._mu:
            st = self._tenants.get(tenant)
            if st is None:
                return
            if not fut.done():
                fut.cancel()
            try:
                st.queue.remove(fut)
                self._queued -= 1  # single-owner: we removed it
            except ValueError:
                pass  # already popped (granted or pruned): counted there
            self._prune_locked(st)
            if deadline:
                st.shed_deadline += 1

    def release(self, tenant: str) -> None:
        """A granted request finished: free the slot and run the DRR
        dispatch sweep (the protocol's release action — skipping the
        sweep is the model's release-skips-dispatch mutation)."""
        with self._mu:
            st = self._tenants.get(tenant)
            if st is not None and st.inflight > 0:
                st.inflight -= 1
            self._active = max(0, self._active - 1)
            self._dispatch_locked()

    @staticmethod
    def _head_cost(st: _TenantState) -> float:
        """Cost of the tenant's queue head (1.0 for legacy futures)."""
        return getattr(st.queue[0], "_qos_cost", 1.0)

    def _dispatch_locked(self) -> None:
        """The DRR sweep over nonempty queues: quantum once per visit
        (only when the head is not yet affordable), spend the head's
        BYTE COST per admission, stop at the slot pool / tenant cap /
        drained queue / unaffordable head, forfeit deficit on empty.
        A top-up that does not yet afford a heavy head still counts as
        progress — a request costing more than its tenant's weight
        saves up across rounds instead of stranding (models/qos.py
        save-up-not-progress).  A round that admitted NOTHING (every
        servable tenant is saving) fast-forwards the remaining save-up
        rounds arithmetically — each saver gains k·weight where k is
        the fewest rounds until some head becomes affordable, exactly
        what k literal rounds would produce — so the sweep never spins
        cost/weight iterations under the plane mutex on the event loop
        (a hostile Content-Length with a tiny weight would otherwise
        stall the server).  Mirrors models/qos.py `_dispatch` (the
        fast-forward is state-identical to the model's literal
        rounds)."""
        progress = True
        while progress and self._active < self.max_concurrency:
            progress = False
            admitted_this_round = False
            savers: list[_TenantState] = []
            order = sorted(k for k, t in self._tenants.items() if t.queue)
            if not order:
                return
            self._rounds += 1
            n = len(order)
            start = self._rr % n
            for off in range(n):
                st = self._tenants[order[(start + off) % n]]
                self._prune_locked(st)
                if st.queue and self._active < self.max_concurrency \
                        and self._under_cap(st):
                    if st.deficit < self._head_cost(st):
                        st.deficit += st.rule.weight
                        progress = True  # saving toward a heavy head
                    while st.queue \
                            and self._active < self.max_concurrency \
                            and self._under_cap(st):
                        fut = st.queue[0]
                        if fut.done():
                            st.queue.popleft()
                            self._queued -= 1  # single-owner: removed
                            continue
                        cost = getattr(fut, "_qos_cost", 1.0)
                        if st.deficit < cost:
                            break  # keep saving next visit
                        st.queue.popleft()
                        self._queued -= 1  # single-owner: we removed it
                        st.deficit -= cost
                        st.inflight += 1
                        st.admitted += 1
                        self._active += 1
                        st.last_active = time.monotonic()
                        fut.set_result(True)
                        progress = True
                        admitted_this_round = True
                    if st.queue and self._under_cap(st) \
                            and st.deficit < self._head_cost(st):
                        savers.append(st)
                if not st.queue:
                    st.deficit = 0.0
            self._rr += 1
            if progress and not admitted_this_round and savers \
                    and self._active < self.max_concurrency:
                # fast-forward: k = rounds until the cheapest saver
                # affords; each saver gains exactly what k more literal
                # rounds would grant (growth stops at affordability, so
                # the deficit bound weight + cost - 1 is preserved)
                k = min(math.ceil(
                    (self._head_cost(st) - st.deficit) / st.rule.weight)
                    for st in savers)
                if k > 1:
                    for st in savers:
                        need = math.ceil(
                            (self._head_cost(st) - st.deficit)
                            / st.rule.weight)
                        st.deficit += min(k, need) * st.rule.weight

    def _gc_locked(self) -> None:
        """Age out idle auto-tenancy states (bounded map, bounded
        work: at most once per 60 s)."""
        now = time.monotonic()
        if now - self._last_gc < 60.0:
            return
        self._last_gc = now
        for key in [k for k, t in self._tenants.items()
                    if not t.queue and t.inflight == 0
                    and t.hot_inflight == 0
                    and now - t.last_active > IDLE_TTL_S]:
            del self._tenants[key]

    def seed_external(self, n: int) -> None:
        """Account for requests the PREVIOUS admission plane (the
        legacy semaphore) already has in flight when this plane takes
        over at a runtime gate flip: they hold real executor/IO
        capacity, so the pool starts with their slots granted —
        otherwise the flip would transiently admit up to 2x
        max_concurrency and break the executor-sizing invariant that
        keeps body-feed tasks schedulable."""
        with self._mu:
            n = max(0, int(n))
            self._external = n
            self._active += n

    def external_release(self) -> None:
        """A legacy-plane request finished while this plane is live:
        free its externally-seeded slot and run the dispatch sweep."""
        with self._mu:
            if self._external <= 0:
                return
            self._external -= 1
            self._active = max(0, self._active - 1)
            self._dispatch_locked()

    def saturated(self) -> bool:
        """True when every global slot is granted — the AGGREGATE
        overload signal: sheds fired while slots were still free are a
        tenant's private bound working and must not engage brownout."""
        with self._mu:
            return self._active >= self.max_concurrency

    # -- hot-lane accounting (ISSUE 13 satellite) ----------------------------
    def hot_cap(self) -> int:
        """Plane-level per-tenant hot-lane slot bound: hot_share of
        the lane (tenants without an explicit rule cap)."""
        return max(1, int(self.hot_capacity * self.hot_share))

    def hot_cap_of(self, st: "_TenantState") -> int:
        """Effective hot-lane bound for ONE tenant (ISSUE 18
        satellite): an explicit TenantRule.hot_cap wins (clamped to
        the lane size); 0 falls back to the uniform hot_share bound,
        so existing configs behave exactly as before."""
        if st.rule.hot_cap > 0:
            return min(st.rule.hot_cap, self.hot_capacity)
        return self.hot_cap()

    def hot_lane_try(self, tenant: str) -> bool:
        """Claim one per-tenant hot-lane slot (ISSUE 16 satellite).
        False when the tenant already holds its share of the lane —
        the request pays normal QoS admission instead, so one tenant's
        flood of RAM hits can never crowd `hot_sem` itself and starve
        other tenants' hits (counted hotLaneCapped)."""
        with self._mu:
            st = self._state_locked(tenant)
            if st.hot_inflight >= self.hot_cap_of(st):
                st.hot_capped += 1
                return False
            st.hot_inflight += 1
            return True

    def hot_lane_release(self, tenant: str) -> None:
        with self._mu:
            st = self._tenants.get(tenant)
            if st is not None and st.hot_inflight > 0:
                st.hot_inflight -= 1

    def note_hot_admit(self, tenant: str) -> None:
        with self._mu:
            self._state_locked(tenant).hot_admits += 1

    def note_hot_reject(self, tenant: str) -> None:
        """A probable hit failed its post-acquire re-probe and fell
        back to the API lane: folded into per-tenant stats so hit-ratio
        and shed counters stay honest under QoS."""
        with self._mu:
            self._state_locked(tenant).hot_rejects += 1

    # -- bandwidth (data-path metering) --------------------------------------
    def bw_wait(self, tenant: str, n: int, direction: str) -> float:
        """Charge `n` data-plane bytes to the tenant's bucket and
        return the pacing debt (0.0 when unlimited/inside burst); the
        async caller awaits asyncio.sleep on it.  Every metered chunk
        also feeds the per-tenant rate monitor."""
        if n <= 0:
            return 0.0
        with self._mu:
            st = self._state_locked(tenant)
            bw = st.bw
            if direction == "in":
                st.throttled_in += n
            else:
                st.throttled_out += n
        self.monitor.record(tenant, direction, n)
        return bw.debit(n) if bw is not None else 0.0

    # -- observability -------------------------------------------------------
    def stats(self) -> dict:
        """Per-tenant live stats + plane totals (metrics + admin)."""
        with self._mu:
            tenants = {}
            for key, st in self._tenants.items():
                tenants[key] = {
                    "weight": st.rule.weight,
                    "maxConcurrency": st.rule.max_concurrency,
                    "bandwidth": st.rule.bandwidth,
                    "hotCap": self.hot_cap_of(st),
                    "inflight": st.inflight,
                    "queueDepth": st.depth(),
                    "deficit": round(st.deficit, 6),
                    "admitted": st.admitted,
                    "shedQueueFull": st.shed_full,
                    "shedDeadline": st.shed_deadline,
                    "hotLaneAdmits": st.hot_admits,
                    "hotLaneRejections": st.hot_rejects,
                    "hotLaneInflight": st.hot_inflight,
                    "hotLaneCapped": st.hot_capped,
                    "throttledInBytes": st.throttled_in,
                    "throttledOutBytes": st.throttled_out,
                }
            return {
                "maxConcurrency": self.max_concurrency,
                "maxQueue": self.max_queue,
                "costUnit": self.cost_unit,
                "maxCost": self.max_cost,
                "hotCapPerTenant": self.hot_cap(),
                "active": self._active,
                "deficitRounds": self._rounds,
                "defaults": self.default_rule.to_dict(),
                "rules": {k: r.to_dict() for k, r in self.rules.items()},
                "tenants": tenants,
            }

    def rates(self) -> dict:
        """Per-tenant moving-average bytes/sec in/out (BandwidthMonitor
        generalized from replication targets to tenants)."""
        return self.monitor.report()
