"""Self-driving overload plane: an SLO burn-rate feedback controller
(ISSUE 18 tentpole).

The SLO plane (server/slo.py) computes Google-SRE multi-window error-
budget burn rates; the QoS plane (server/qos.py) takes live reconfig;
the brownout controller (services/brownout.py) can shed background
work; the erasure read fan-out hedges stragglers behind runtime-
mutable knobs (erasure/objects.py).  Until this module nothing
connected them — the observability plane was a dashboard with a human
on the knob.  The reference self-regulates the same surfaces from
in-process heuristics (adaptive API throttling in cmd/handler-api.go,
dynamic scanner/heal cycles); here the feedback signal is the burn
rate itself, so the loop answers regime shifts (flash crowds, tenant-
mix flips, stacked faults) a static config fails (no record from the
chip says so yet: ROADMAP D6).

Each tick the controller SAMPLES a snapshot (SLO status with the per-
tenant split, QoS stats, the QoS reconfigure generation), then DECIDES
per action ladder, with the protocol proven in
analysis/concurrency/models/controller.py:

* ``qos``      — a tenant whose traffic is burning ANOTHER tenant's
                 budget is reweighted/capped through the live QoS
                 reconfigure path (weight halved per rung, concurrency
                 and hot-lane caps tightened).  An admin PUT /qos
                 always wins: it moves the plane's generation counter,
                 which both voids the held snapshot (fresh-snapshot
                 invariant) and resets this ladder's bookkeeping so
                 the controller re-baselines on the admin's config.
* ``hedge``    — GET tail-latency burn widens read hedging
                 (erasure.objects.set_hedge_scale: shorter straggler
                 grace + lower slow-drive EWMA threshold), clamped so
                 no actuation can disable hedging or widen unbounded.
* ``brownout`` — fast-window burn on any class force-engages the
                 brownout (scanner/heal/MRF/decom/rebalance/georep all
                 poll background_allowed), freeing drive IOPs for the
                 foreground before the queue-depth heuristics see it.

A fourth output has no ladder: when the plane stays saturated while
burning, the controller RECOMMENDS a pool add (gauge + trace event,
derived from the same demand-vs-capacity shape the simulator's
capacity model fits).  Execution stays admin-gated — adding hardware
is an operator decision, the controller only says so out loud.

Every decision respects hysteresis (N consecutive over/under ticks),
a per-ladder cooldown, and a bounded ladder depth; a snapshot whose
world moved between sample and decide is refused and resampled.  Gate
``MINIO_TPU_CONTROLLER`` (env wins over ``controller.enable`` config,
runtime-flippable): default OFF, and off means byte- and metrics-
identical — no thread, no ``minio_controller_*`` families (pinned by
tests/test_controller.py).
"""

from __future__ import annotations

import os
import threading
import time

from minio_tpu.utils import tracing
from minio_tpu.utils.logger import log

from .qos import MIN_WEIGHT, TenantRule

_TRUTHY = ("1", "on", "true", "yes")

#: classes whose burn drives the background-shed and pool-add signals;
#: ADMIN/OTHER excluded — the controller must not brown out the
#: cluster because the admin API itself is slow
_DATA_CLASSES = ("GET", "PUT", "LIST", "DELETE", "MULTIPART")


class _Ladder:
    """One intervention ladder: the model's depth/streak/cooldown
    vector (models/controller.py), one per action family."""

    __slots__ = ("name", "depth", "streak_high", "streak_low",
                 "cooldown", "engagements", "reverts")

    def __init__(self, name: str):
        self.name = name
        self.depth = 0
        self.streak_high = 0
        self.streak_low = 0
        self.cooldown = 0
        self.engagements = 0
        self.reverts = 0


class OverloadController:
    """The feedback loop.  A single daemon thread ticks every
    ``tick_s``; every decision goes through one snapshot-validate-act
    pass per tick.  The clock is injectable so the unit matrix drives
    hysteresis/cooldown/staleness without sleeping."""

    def __init__(self, server, *, tick_s: float = 5.0,
                 burn_fast: float = 1.0, hysteresis: int = 2,
                 cooldown: int = 2, max_depth: int = 2,
                 clock=time.monotonic):
        self.server = server
        self.tick_s = max(float(tick_s), 0.05)
        self.burn_fast = max(float(burn_fast), 0.0)
        self.hysteresis = max(int(hysteresis), 1)
        self.cooldown = max(int(cooldown), 0)
        self.max_depth = max(int(max_depth), 1)
        self.clock = clock
        # a snapshot older than this at decide time is stale even if
        # no generation moved (the thread was wedged past its tick)
        self.stale_after_s = 2.0 * self.tick_s
        self._mu = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.ladders = {name: _Ladder(name)
                        for name in ("qos", "hedge", "brownout")}
        # qos-action bookkeeping: the admin rule set the intervention
        # is relative to, the tenant being tightened, and the plane
        # generation this controller last wrote/observed
        self._qos_baseline: dict[str, TenantRule] | None = None
        self._qos_offender: str | None = None
        self._expected_gen: int | None = None
        # pool-add recommendation (no ladder: it is advice, not an
        # actuation — execution stays admin-gated)
        self._sat_streak = 0
        self._calm_streak = 0
        self.pool_add_recommended = False
        self.pool_add_events = 0
        # counters (metrics + admin)
        self.ticks = 0
        self.skipped_stale = 0
        self.qos_admin_resets = 0
        self.offender_switches = 0

    # ------------------------------------------------------------- gate
    @staticmethod
    def gate_enabled(config=None, environ=None) -> bool:
        """MINIO_TPU_CONTROLLER env wins; else ``controller.enable`` —
        the env-over-config precedence every plane gate uses."""
        env = os.environ if environ is None else environ
        v = env.get("MINIO_TPU_CONTROLLER")
        if v is not None:
            return v.strip().lower() in _TRUTHY
        if config is None:
            return False
        return config.get_bool("controller", "enable", False)

    @classmethod
    def from_config(cls, server, config,
                    environ=None) -> "OverloadController | None":
        if not cls.gate_enabled(config, environ):
            return None
        env = os.environ if environ is None else environ

        def knob(env_key: str, cfg_key: str) -> str:
            v = env.get(env_key)
            if v is not None:
                return v
            return config.get("controller", cfg_key) \
                if config is not None else ""

        def num(text: str, fallback: float) -> float:
            try:
                return float(text)
            except (TypeError, ValueError):
                return fallback

        from minio_tpu.utils import deadline as deadline_mod

        tick_raw = knob("MINIO_TPU_CONTROLLER_TICK_S", "tick")
        try:
            tick = float(tick_raw)
        except (TypeError, ValueError):
            try:
                tick = deadline_mod.parse_duration(tick_raw) or 5.0
            except ValueError:
                tick = 5.0
        return cls(
            server,
            tick_s=tick,
            burn_fast=num(knob("MINIO_TPU_CONTROLLER_BURN_FAST",
                               "burn_fast"), 1.0),
            hysteresis=int(num(knob("MINIO_TPU_CONTROLLER_HYSTERESIS",
                                    "hysteresis"), 2)),
            cooldown=int(num(knob("MINIO_TPU_CONTROLLER_COOLDOWN",
                                  "cooldown"), 2)),
            max_depth=int(num(knob("MINIO_TPU_CONTROLLER_MAX_DEPTH",
                                   "max_depth"), 2)))

    # --------------------------------------------------------- lifecycle
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        from minio_tpu.utils.deadline import service_thread
        self._thread = service_thread(
            self._run, name="overload-controller")

    def close(self) -> None:
        """Stop the loop and STEP EVERY LADDER DOWN: the reverts-when-
        burn-subsides contract also covers the controller going away
        (gate flip, shutdown) — it must not leave a tenant throttled
        or a hedge widened with nobody watching the burn."""
        self._stop.set()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)
        self._thread = None
        self._stand_down()

    def _run(self) -> None:
        while not self._stop.wait(self.tick_s):
            try:
                self.tick()
            except Exception as e:  # the loop must survive any tick
                log.warning("controller tick failed", error=str(e))

    # ------------------------------------------------------------ sample
    def _sample(self) -> dict | None:
        """One consistent snapshot of the world the decide step reads.
        Returns None when the SLO plane is off — no burn signal means
        the controller stands down (fail-safe: never act blind)."""
        slo = getattr(self.server, "slo", None)
        if slo is None:
            self._stand_down()
            return None
        qos = getattr(self.server, "qos", None)
        gen = qos.reconfigures if qos is not None else None
        if qos is not None and self._expected_gen is not None \
                and gen != self._expected_gen:
            # an admin PUT /qos landed since our last write: the admin
            # owns the config now — drop the intervention bookkeeping
            # and re-baseline on their rules (no write: their config
            # IS the new ground truth)
            self._reset_qos_ladder()
            with self._mu:
                self.qos_admin_resets += 1
        self._expected_gen = gen
        return {
            "slo_plane": slo,
            "qos_plane": qos,
            "qos_gen": gen,
            # fast-window scoped: violations/ok must track the CURRENT
            # regime both ways — a slow-window view would keep a
            # recovered tenant looking burnt and block the revert rungs
            "status": slo.status(
                window_s=getattr(slo, "fast_s", None), tenants=True),
            "qos_stats": qos.stats() if qos is not None else None,
            "at": self.clock(),
        }

    def _fresh(self, snap: dict) -> bool:
        """The never-acts-on-a-stale-snapshot invariant, live: the
        planes sampled must still be the server's planes, the QoS
        generation must not have moved, and the snapshot must be
        younger than the staleness bound."""
        if self.clock() - snap["at"] > self.stale_after_s:
            return False
        if getattr(self.server, "slo", None) is not snap["slo_plane"]:
            return False
        qos = getattr(self.server, "qos", None)
        if qos is not snap["qos_plane"]:
            return False
        if qos is not None and qos.reconfigures != snap["qos_gen"]:
            return False
        return True

    # ------------------------------------------------------------ signals
    def _signals(self, snap: dict) -> dict:
        classes = snap["status"].get("classes", {})

        def fast(doc: dict) -> float:
            b = (doc.get("burn") or {}).get("fast")
            return b if b is not None else 0.0

        data = {c: d for c, d in classes.items() if c in _DATA_CLASSES}
        max_burn = max((fast(d) for d in data.values()), default=0.0)
        get_doc = classes.get("GET") or {}
        hedge_high = "latency" in (get_doc.get("violations") or ())
        burn_high = max_burn >= self.burn_fast and self.burn_fast > 0

        # offender/victim split for the qos ladder: the top-traffic
        # tenant is the offender only when a DIFFERENT tenant is
        # burning — its own sheds are its private bound working
        offender = None
        tenants = snap["status"].get("tenants") or {}
        if snap["qos_plane"] is not None and len(tenants) >= 2:
            agg = {}
            for t, cmap in tenants.items():
                reqs = sum((c.get("window") or {}).get("requests") or 0
                           for c in cmap.values())
                burn = max((fast(c) for c in cmap.values()),
                           default=0.0)
                bad = any(not c.get("ok", True) for c in cmap.values())
                agg[t] = (reqs, burn, bad)
            top = max(agg, key=lambda t: agg[t][0])
            victims = [t for t, (_, b, bad) in agg.items()
                       if t != top and (b >= self.burn_fast or bad)]
            if victims and agg[top][0] > 0:
                vmax = max(agg[v][0] for v in victims)
                if agg[top][0] >= 2 * max(vmax, 1):
                    offender = top
            if offender is None:
                # Request counts equalize under closed-loop saturation
                # (every pool attains only what the server releases),
                # so dominance must also be read in slot OCCUPANCY: by
                # Little's law a tenant's inflight count IS its slot-
                # seconds per second, and a PUT-heavy tenant camped on
                # the admission pool starves others without ever
                # out-requesting them.  A tenant already pinned under a
                # concurrency cap is excluded from the victim side:
                # burning at its own cap is that bound working, not
                # victimization — without this, a rescued quiet tenant
                # holding freed slots would read as the new offender.
                qstats = snap.get("qos_stats") or {}
                qten = qstats.get("tenants") or {}
                occ = {t: (qten.get(t) or {}).get("inflight") or 0
                       for t in agg}
                otop = max(occ, key=lambda t: occ[t], default=None)
                if otop is not None and occ[otop] > 0:
                    uncapped_victims = [
                        t for t, (_, b, bad) in agg.items()
                        if t != otop and (b >= self.burn_fast or bad)
                        and not (qten.get(t) or {}).get("maxConcurrency")]
                    half = max(2, (qstats.get("maxConcurrency") or 0) // 2)
                    vocc = max((occ[v] for v in uncapped_victims),
                               default=0)
                    if uncapped_victims and (
                            occ[otop] >= half
                            or occ[otop] >= 2 * max(vocc, 1)):
                        offender = otop
        return {
            "burn_high": burn_high,
            "hedge_high": hedge_high and burn_high,
            "qos_high": offender is not None and burn_high,
            "offender": offender,
            "max_burn": max_burn,
        }

    # ------------------------------------------------------------- decide
    def tick(self) -> None:
        snap = self._sample()
        with self._mu:
            self.ticks += 1
        if snap is None:
            return
        self.decide(snap)

    def decide(self, snap: dict) -> None:
        """Validate the snapshot, then run one ladder step per action.
        Split from tick() so the unit matrix can interleave an admin
        write between sample and decide."""
        if not self._fresh(snap):
            with self._mu:
                self.skipped_stale += 1
            return
        sig = self._signals(snap)
        decisions: list[tuple[str, str, int]] = []

        # ladder state flips under _mu (admin/status threads read it,
        # close() zeroes it); the actuations themselves run OUTSIDE
        # the lock — they touch other planes with their own locks
        def step(ladder: _Ladder, high: bool, engage, revert) -> None:
            with self._mu:
                pre_cd = ladder.cooldown
                if high:
                    ladder.streak_high = min(ladder.streak_high + 1,
                                             self.hysteresis)
                    ladder.streak_low = 0
                else:
                    ladder.streak_low = min(ladder.streak_low + 1,
                                            self.hysteresis)
                    ladder.streak_high = 0
                depth = ladder.depth
                do_engage = (high and pre_cd == 0
                             and ladder.streak_high >= self.hysteresis
                             and depth < self.max_depth)
                do_revert = ((not high) and pre_cd == 0
                             and ladder.streak_low >= self.hysteresis
                             and depth > 0)
            decided = False
            if do_engage:
                if engage(depth + 1):
                    with self._mu:
                        ladder.depth += 1
                        ladder.engagements += 1
                        ladder.cooldown = self.cooldown
                        ladder.streak_high = 0
                        new_depth = ladder.depth
                    decided = True
                    decisions.append((ladder.name, "engage", new_depth))
            elif do_revert:
                if revert(depth - 1):
                    with self._mu:
                        ladder.depth -= 1
                        ladder.reverts += 1
                        ladder.cooldown = self.cooldown
                        ladder.streak_low = 0
                        new_depth = ladder.depth
                    decided = True
                    decisions.append((ladder.name, "revert", new_depth))
            if not decided:
                with self._mu:
                    if ladder.cooldown > 0:
                        ladder.cooldown -= 1

        # tenant-mix flip: the ladder is engaged on tenant A but the
        # live offender is now tenant B (the regime shifted under us).
        # Move the WHOLE intervention to B at the current rung — one
        # reconfigure, still exactly one tenant tightened, still depth-
        # bounded — instead of deepening the cap on the wrong tenant.
        qlad = self.ladders["qos"]
        if qlad.depth > 0 and qlad.cooldown == 0 and sig["qos_high"] \
                and self._qos_offender is not None \
                and sig["offender"] != self._qos_offender:
            if self._qos_retarget(snap, sig["offender"], qlad.depth):
                with self._mu:
                    qlad.cooldown = self.cooldown
                decisions.append(("qos", "retarget", qlad.depth))
        step(qlad, sig["qos_high"],
             lambda d: self._qos_engage(snap, sig, d),
             lambda d: self._qos_revert(snap, d))
        step(self.ladders["hedge"], sig["hedge_high"],
             self._hedge_set, self._hedge_set)
        step(self.ladders["brownout"], sig["burn_high"],
             lambda d: self._brownout_set(True),
             lambda d: self._brownout_set(d > 0))
        self._pool_add_step(snap, sig)
        if decisions:
            root = tracing.start("controller.tick",
                                 maxBurnFast=round(sig["max_burn"], 3))
            token = tracing.install(root) if root is not None else None
            try:
                for name, direction, depth in decisions:
                    tracing.event(f"controller.{direction}",
                                  action=name, depth=depth)
                    log.info("controller action", action=name,
                             direction=direction, depth=depth)
            finally:
                if root is not None:
                    tracing.reset(token)
                    tracing.finish(root, status=200)

    # ----------------------------------------------------- qos actuation
    def _qos_rule_at(self, qos, depth: int) -> TenantRule:
        """The offender's rule at ladder depth `depth`, derived from
        the ADMIN baseline (never from our own previous write, so
        rungs do not compound into an unbounded intervention)."""
        base = (self._qos_baseline or {}).get(
            self._qos_offender, qos.default_rule)
        factor = 0.5 ** depth
        return TenantRule(
            weight=max(base.weight * factor, MIN_WEIGHT),
            max_concurrency=max(
                1, int((base.max_concurrency or qos.max_concurrency)
                       * factor)),
            bandwidth=base.bandwidth,
            hot_cap=max(1, int(qos.hot_capacity * factor * 0.5)))

    def _qos_engage(self, snap: dict, sig: dict, depth: int) -> bool:
        qos = snap["qos_plane"]
        if qos is None:
            return False
        if self._qos_offender is None:
            self._qos_offender = sig["offender"]
            self._qos_baseline = dict(qos.rules)
        if self._qos_offender is None:
            return False
        rules = dict(self._qos_baseline)
        rules[self._qos_offender] = self._qos_rule_at(qos, depth)
        qos.reconfigure(rules=rules, max_queue=qos.max_queue)
        self._expected_gen = qos.reconfigures
        return True

    def _qos_revert(self, snap: dict, depth: int) -> bool:
        qos = snap["qos_plane"]
        if qos is None or self._qos_offender is None:
            # nothing of ours is applied (admin reset or plane gone):
            # the rung unwinds as pure bookkeeping
            return True
        if depth <= 0:
            rules = dict(self._qos_baseline or {})
        else:
            rules = dict(self._qos_baseline or {})
            rules[self._qos_offender] = self._qos_rule_at(qos, depth)
        qos.reconfigure(rules=rules, max_queue=qos.max_queue)
        self._expected_gen = qos.reconfigures
        if depth <= 0:
            self._qos_offender = None
            self._qos_baseline = None
        return True

    def _qos_retarget(self, snap: dict, offender: str,
                      depth: int) -> bool:
        """Swap the tightened tenant: restore the old offender to its
        baseline rule and apply the same rung to the new one, in one
        reconfigure."""
        qos = snap["qos_plane"]
        if qos is None:
            return False
        self._qos_offender = offender
        rules = dict(self._qos_baseline or {})
        rules[offender] = self._qos_rule_at(qos, depth)
        qos.reconfigure(rules=rules, max_queue=qos.max_queue)
        self._expected_gen = qos.reconfigures
        with self._mu:
            self.offender_switches += 1
        return True

    def _reset_qos_ladder(self) -> None:
        with self._mu:
            ladder = self.ladders["qos"]
            ladder.depth = 0
            ladder.streak_high = 0
            ladder.streak_low = 0
            ladder.cooldown = 0
            self._qos_offender = None
            self._qos_baseline = None

    # --------------------------------------------------- hedge actuation
    def _hedge_set(self, depth: int) -> bool:
        from minio_tpu.erasure import objects as eobj

        eobj.set_hedge_scale(0.5 ** depth)
        return True

    # ------------------------------------------------ brownout actuation
    def _brownout_set(self, on: bool) -> bool:
        svcs = getattr(self.server, "services", None)
        bo = getattr(svcs, "brownout", None) if svcs is not None \
            else None
        if bo is None:
            return False
        bo.force(on)
        return True

    # ------------------------------------------- pool-add recommendation
    def _pool_add_step(self, snap: dict, sig: dict) -> None:
        qos = snap["qos_plane"]
        if qos is not None:
            saturated = qos.saturated()
        else:
            saturated = getattr(self.server, "_waiters", 0) > 0
        high = saturated and sig["burn_high"]
        with self._mu:
            if high:
                self._sat_streak = min(self._sat_streak + 1,
                                       self.hysteresis)
                self._calm_streak = 0
            else:
                self._calm_streak = min(self._calm_streak + 1,
                                        self.hysteresis)
                self._sat_streak = 0
            recommend = (high and self._sat_streak >= self.hysteresis
                         and not self.pool_add_recommended)
            calm = ((not high)
                    and self._calm_streak >= self.hysteresis)
            if recommend:
                # saturation + burn persisting through the hysteresis
                # window: admission capacity, not a transient, is the
                # bottleneck — the capacity-model shape (req/s ~ k x
                # cores; simulator/engine.py capacity_model) says more
                # hardware, and ONLY an admin may act on that
                self.pool_add_recommended = True
                self.pool_add_events += 1
            elif calm:
                self.pool_add_recommended = False
        if recommend:
            root = tracing.start("controller.pool_add",
                                 maxBurnFast=round(sig["max_burn"], 3))
            if root is not None:
                token = tracing.install(root)
                tracing.event("controller.pool_add_recommended")
                tracing.reset(token)
                tracing.finish(root, status=200)
            log.info("controller: pool add recommended "
                     "(saturated while burning; admin-gated)")

    # --------------------------------------------------------- stand-down
    def _stand_down(self) -> None:
        """Revert every live actuation and zero the ladders (SLO plane
        gone, gate flip, shutdown)."""
        qos = getattr(self.server, "qos", None)
        if self.ladders["qos"].depth > 0 and qos is not None \
                and self._qos_baseline is not None:
            try:
                qos.reconfigure(rules=dict(self._qos_baseline),
                                max_queue=qos.max_queue)
                self._expected_gen = qos.reconfigures
            except Exception:
                pass
        self._reset_qos_ladder()
        if self.ladders["hedge"].depth > 0:
            self._hedge_set(0)
        if self.ladders["brownout"].depth > 0:
            self._brownout_set(False)
        with self._mu:
            for ladder in self.ladders.values():
                ladder.depth = 0
                ladder.streak_high = 0
                ladder.streak_low = 0
                ladder.cooldown = 0
            self.pool_add_recommended = False
            self._sat_streak = 0
            self._calm_streak = 0

    # ------------------------------------------------------ observability
    def stats(self) -> dict:
        with self._mu:
            ticks = self.ticks
            skipped = self.skipped_stale
        return {
            "tickSeconds": self.tick_s,
            "burnFast": self.burn_fast,
            "hysteresis": self.hysteresis,
            "cooldown": self.cooldown,
            "maxDepth": self.max_depth,
            "ticks": ticks,
            "skippedStale": skipped,
            "qosAdminResets": self.qos_admin_resets,
            "offenderSwitches": self.offender_switches,
            "poolAddRecommended": self.pool_add_recommended,
            "poolAddEvents": self.pool_add_events,
            "offender": self._qos_offender,
            "actions": {
                name: {
                    "depth": ladder.depth,
                    "engagements": ladder.engagements,
                    "reverts": ladder.reverts,
                    "cooldown": ladder.cooldown,
                } for name, ladder in self.ladders.items()
            },
        }
