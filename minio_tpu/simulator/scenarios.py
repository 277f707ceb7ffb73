"""Declarative traffic scenarios (ISSUE 15).

A :class:`Scenario` is pure data: everything the engine needs to build
a deterministic arrival schedule (see ``engine.build_schedule`` — same
seed, same schedule, same request sequence) plus the SLOs the scenario
asserts after replay and the chaos hook it arms mid-run.

``builtin_scenarios()`` is the production mix: zipf read fan-in,
multipart ingest storm, list-heavy analytics, a multi-tenant QoS mix,
and two chaos variants (flaky-drive brownout, pool drain under live
traffic — the PR 14 harness shape).
Scenario SLO grammar::

    slo = {
      "classes": {"GET": {"p99_ms": 400, "availability": 0.995}},
      "shed_fraction_max": 0.05,          # client-side 503 fraction
      "buckets": {"simquiet": {"p99_ms": 800, "p50_ms": 200,
                               "shed_max": 0, "shed_frac_max": 0.1}},
    }

``classes`` asserts against the server's own accounting (the admin SLO
endpoint, windowed to the scenario); ``buckets`` asserts client-side
per-bucket latencies (the noisy-neighbor clause of the QoS mix).
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class Scenario:
    name: str
    seed: int
    duration_s: float
    clients: int
    rate: float                      # aggregate Poisson arrival rate, req/s
    ops: tuple                       # ((op, weight), ...); ops: get|head|
    #                                  put|list|delete|mpu
    buckets: tuple = ("sim",)
    #: fraction of requests aimed at buckets[0]; None = uniform.  The
    #: QoS mix points most traffic at the hot bucket/tenant.
    hot_bucket_frac: float | None = None
    nobjects: int = 48               # catalog keys per bucket (setup PUTs)
    obj_bytes: tuple = (4 << 10, 64 << 10)
    zipf_s: float = 1.1              # GET popularity skew
    put_bytes: tuple = (8 << 10, 96 << 10)
    mpu_parts: int = 2               # parts per multipart upload
    mpu_part_bytes: int = 5 << 20    # all-but-last part size (S3 minimum)
    mpu_last_bytes: int = 64 << 10
    list_max_keys: int = 100
    slo: dict = field(default_factory=dict)
    #: deterministic REGIME SHIFTS (ISSUE 18): piecewise arrival-rate
    #: multipliers ((start_frac, end_frac, mult), ...) applied inside
    #: build_schedule's Poisson loop — still a pure function of the
    #: scenario, so the schedule digest pins the shifted shape too
    rate_profile: tuple = ()
    #: tenant-mix flip: from this fraction of the run on, the hot role
    #: (hot_bucket_frac) moves from buckets[0] to buckets[1]; the
    #: displaced bucket joins the quiet set.  None = no flip.
    mix_flip_at_frac: float | None = None
    #: per-bucket op-mix override ``{bucket: ((op, weight), ...)}`` —
    #: tenants with different WORKLOADS (a PUT-flood offender vs a
    #: GET-only victim).  Buckets absent here draw from ``ops``.
    #: Gated: scenarios without it keep their exact RNG stream.
    bucket_ops: dict | None = None
    #: role swap riding ``mix_flip_at_frac``: from the flip on,
    #: buckets named here draw THIS mix instead of their
    #: ``bucket_ops`` one — the flood itself moves tenants, not just
    #: the arrival share.  Gated the same way.
    bucket_ops_post_flip: dict | None = None
    #: dedicated client pools ``{bucket: (first_client, n_clients)}``:
    #: that bucket's entries replay on their own closed-loop client
    #: span.  Without this, a stalled offender throttles the victim's
    #: OFFERED load too (every client serves every bucket, and a
    #: closed loop equalizes), hiding the very starvation a scenario
    #: wants to grade.  Buckets absent here keep the global
    #: ``i % clients`` assignment.  Gated: no extra RNG draws.
    bucket_clients: dict | None = None
    chaos: str | None = None         # engine chaos-hook name
    chaos_at_frac: float = 0.25      # hook start, fraction of duration
    chaos_dur_frac: float = 0.5      # hook length, fraction of duration
    qos: dict | None = None          # admin qos doc applied for the run
    description: str = ""


def builtin_scenarios(scale: float = 1.0) -> list[Scenario]:
    """The builtin set.  ``scale`` multiplies durations (rates are part
    of each scenario's identity and stay fixed) so a short tier can
    exercise the same shapes in less wall time; seeds are fixed, so a
    scenario's schedule digest is its reproducibility pin."""
    d = lambda s: max(3.0, s * scale)  # noqa: E731

    return [
        Scenario(
            name="zipf_read_fanin", seed=1501, duration_s=d(12),
            clients=8, rate=160.0, ops=(("get", 92), ("head", 8)),
            nobjects=64, zipf_s=1.1,
            slo={"classes": {
                "GET": {"p99_ms": 900.0, "availability": 0.999}},
                "shed_fraction_max": 0.01},
            description="million-user CDN shape: zipf(1.1) GET/HEAD "
                        "fan-in over a small hot set, served from the "
                        "hot tier"),
        Scenario(
            name="multipart_ingest_storm", seed=1502, duration_s=d(12),
            clients=6, rate=14.0,
            ops=(("mpu", 3), ("put", 9), ("get", 4)),
            nobjects=16, mpu_parts=2,
            slo={"classes": {
                "PUT": {"p99_ms": 4000.0, "availability": 0.995},
                "MULTIPART": {"p99_ms": 9000.0, "availability": 0.995}},
                "shed_fraction_max": 0.05},
            description="bulk ingest: multipart uploads (5MiB parts) "
                        "racing single PUTs and readbacks"),
        Scenario(
            name="list_heavy_analytics", seed=1503, duration_s=d(10),
            clients=6, rate=60.0,
            ops=(("list", 55), ("get", 35), ("head", 10)),
            nobjects=96,
            slo={"classes": {
                "LIST": {"p99_ms": 1500.0, "availability": 0.999},
                "GET": {"p99_ms": 1200.0, "availability": 0.999}},
                "shed_fraction_max": 0.02},
            description="analytics shape: namespace walks dominating, "
                        "point reads riding along"),
        Scenario(
            name="multi_tenant_qos_mix", seed=1504, duration_s=d(12),
            clients=10, rate=120.0,
            ops=(("get", 80), ("put", 15), ("list", 5)),
            buckets=("simhot", "simquiet"), hot_bucket_frac=0.9,
            nobjects=32,
            qos={"enable": True, "max_queue": 64, "tenants": {
                "bucket:simhot": {"weight": 1, "max_concurrency": 2},
                "bucket:simquiet": {"weight": 8}}},
            slo={"buckets": {
                "simquiet": {"p99_ms": 2500.0, "shed_max": 0}},
                # the hot tenant IS expected to shed under its cap;
                # only runaway collapse fails the scenario
                "shed_fraction_max": 0.75},
            description="noisy neighbor: 90% of arrivals hammer the "
                        "capped hot tenant; the quiet tenant must not "
                        "feel it (weighted DRR isolation)"),
        Scenario(
            name="chaos_disk_brownout", seed=1505, duration_s=d(14),
            clients=8, rate=80.0, ops=(("get", 90), ("put", 10)),
            nobjects=48, chaos="disk",
            chaos_at_frac=0.25, chaos_dur_frac=0.4,
            slo={"classes": {
                "GET": {"p99_ms": 2500.0, "availability": 0.995}},
                "shed_fraction_max": 0.05},
            description="two drives turn slow+flaky mid-run "
                        "(ChaosDisk); hedged reads + the breaker must "
                        "hold availability inside parity"),
        # MUST stay last: its drain decommissions pool 1 of bench_sim's
        # shared server for good (bench_sim asserts this ordering)
        Scenario(
            name="drain_under_traffic", seed=1506, duration_s=d(14),
            clients=8, rate=70.0, ops=(("get", 85), ("put", 15)),
            nobjects=48, chaos="drain",
            chaos_at_frac=0.2, chaos_dur_frac=1.0,
            slo={"classes": {
                "GET": {"p99_ms": 2500.0, "availability": 0.995},
                "PUT": {"p99_ms": 5000.0, "availability": 0.99}},
                "shed_fraction_max": 0.05},
            description="PR 14 harness shape: a pool decommission "
                        "starts mid-traffic; reads stay findable "
                        "mid-move, writes route to live pools"),
    ]


def controller_scenarios(scale: float = 1.0) -> list[Scenario]:
    """The regime-shift family (ISSUE 18): each scenario is replayed
    TWICE by its harness: once with the static config only
    (``MINIO_TPU_CONTROLLER=0``) and once with the overload controller
    on, against a deliberately scarce server (4 admission slots,
    600ms request deadline, hot cache off, a ~40ms floor on every
    drive op) so saturation is a property of the schedule, not of box
    noise.

    The starvation mechanism is SLOT-TIME, not grant share.  The DRR
    admission sweep is grant-fair: every backlogged tenant is visited
    each round, so a cost-1 victim cannot lose the weight game — but
    grants are not seconds.  A PUT costs ~10 serialized drive ops
    (xl.meta + shards + dirs) against a GET's ~2, so a PUT-flood
    tenant holds an admission slot ~4x longer per grant, the pool's
    RELEASE RATE collapses, and a GET victim whose demand exceeds
    release_rate/#backlogged starves into 600ms-deadline sheds — with
    the static config's weights (offender 16, victim 1) doing nothing
    to stop it.  The controller's rescue is the one actuator that
    prices slot-TIME: the offender's max_concurrency rung bounds how
    many slots its slow PUTs may occupy, restoring the release rate
    for everyone else.  The flooding tenant is EXPECTED to shed (its
    demand exceeds capacity by design; under the controller its own
    queue backs up even further), so the aggregate shed budgets are
    deliberately loose — victim isolation, not total shed volume, is
    what is being graded.

    Every scenario partitions its clients (``bucket_clients``): the
    victim drives the server from its OWN closed-loop pool.  With a
    shared pool a client stalled on a flooded request stops issuing
    victim requests too, the victim's offered load collapses in
    lockstep with the overload, and the grant-fair sweep trivially
    drains the shrunken victim backlog — the closed loop itself would
    hide the starvation from the verdict."""
    d = lambda s: max(3.0, s * scale)  # noqa: E731
    victim_ops = (("get", 100),)
    flood_ops = (("put", 70), ("get", 30))
    return [
        Scenario(
            name="flash_crowd", seed=1801, duration_s=d(15),
            clients=26, rate=16.0,
            ops=(("get", 100),),
            buckets=("flashhot", "flashquiet"), hot_bucket_frac=0.7,
            bucket_ops={"flashhot": flood_ops,
                        "flashquiet": victim_ops},
            bucket_clients={"flashhot": (0, 18),
                            "flashquiet": (18, 8)},
            nobjects=16, obj_bytes=(4 << 10, 32 << 10),
            put_bytes=(64 << 10, 256 << 10),
            rate_profile=((0.3, 1.0, 3.0),),
            qos={"enable": True, "max_queue": 64, "tenants": {
                "bucket:flashhot": {"weight": 16},
                "bucket:flashquiet": {"weight": 1}}},
            slo={"buckets": {
                "flashquiet": {"shed_frac_max": 0.25, "p50_ms": 520.0}},
                "shed_fraction_max": 0.9},
            description="flash crowd: arrivals triple from 30% of the "
                        "run on; the PUT-flood tenant's slow writes "
                        "hold the 4 admission slots and the GET "
                        "tenant starves unless the offender is "
                        "conc-capped"),
        Scenario(
            name="tenant_mix_flip", seed=1802, duration_s=d(14),
            clients=26, rate=42.0,
            ops=(("get", 100),),
            buckets=("mixa", "mixb", "mixquiet"), hot_bucket_frac=0.55,
            bucket_ops={"mixa": flood_ops, "mixb": victim_ops,
                        "mixquiet": victim_ops},
            bucket_ops_post_flip={"mixa": victim_ops,
                                  "mixb": flood_ops},
            bucket_clients={"mixa": (0, 9), "mixb": (9, 9),
                            "mixquiet": (18, 8)},
            nobjects=16, obj_bytes=(4 << 10, 32 << 10),
            put_bytes=(64 << 10, 256 << 10),
            mix_flip_at_frac=0.5,
            qos={"enable": True, "max_queue": 64, "tenants": {
                "bucket:mixa": {"weight": 16},
                "bucket:mixb": {"weight": 16},
                "bucket:mixquiet": {"weight": 1}}},
            slo={"buckets": {
                "mixquiet": {"shed_frac_max": 0.3, "p50_ms": 500.0}},
                "shed_fraction_max": 0.9},
            description="tenant-mix flip: the PUT flood moves from "
                        "tenant A to tenant B mid-run; a static cap "
                        "on A is useless after the flip — the "
                        "controller must re-identify the offender and "
                        "retarget its cap in one reconfigure"),
        Scenario(
            name="brownout_noisy_stacked", seed=1803,
            duration_s=d(14), clients=26, rate=42.0,
            ops=(("get", 100),),
            buckets=("stackhot", "stackquiet"), hot_bucket_frac=0.7,
            bucket_ops={"stackhot": flood_ops,
                        "stackquiet": victim_ops},
            bucket_clients={"stackhot": (0, 18),
                            "stackquiet": (18, 8)},
            nobjects=16, obj_bytes=(4 << 10, 32 << 10),
            put_bytes=(64 << 10, 256 << 10),
            chaos="disk", chaos_at_frac=0.3, chaos_dur_frac=0.5,
            qos={"enable": True, "max_queue": 64, "tenants": {
                "bucket:stackhot": {"weight": 16},
                "bucket:stackquiet": {"weight": 1}}},
            slo={"buckets": {
                # shed is the discriminator here: the victim's p50
                # rides the chaos disk's added latency, which the
                # controller can route around (hedge) but not remove —
                # the p50 clause is a deadline bound, not the grade
                "stackquiet": {"shed_frac_max": 0.4, "p50_ms": 650.0}},
                "shed_fraction_max": 0.9},
            description="stacked faults: a PUT flood saturates "
                        "admission while one drive turns slow+flaky "
                        "mid-run; the controller stacks the QoS cap, "
                        "wider read hedging, and a forced background "
                        "brownout"),
    ]


def georep_scenarios(scale: float = 1.0) -> list[Scenario]:
    """The multi-region family (ISSUE 16): replayed against the
    PRIMARY of a two-cluster pair with ``MINIO_TPU_GEOREP=1`` and a
    joined site peer.  The engine grades the primary-facing SLO (the
    whole point of the async push queue is that the client never waits
    on the WAN); cross-site convergence and read-your-writes are graded
    AFTER replay by the harness polling the secondary for byte-identity.

    Each scenario owns its bucket so convergence checks can't bleed
    across scenarios.  Chaos hooks the harness must register:

    * ``peer_kill`` — close the secondary mid-push, restart it at the
      SAME port (the breaker must open, then the retried sweeps must
      converge against the restarted peer);
    * ``worker_kill`` — SIGKILL one mp I/O worker of the primary
      (``MINIO_TPU_WORKERS>=1``); the plane supervisor respawns it and
      in-flight PUTs surface as honest errors inside the availability
      budget.
    """
    d = lambda s: max(3.0, s * scale)  # noqa: E731

    return [
        Scenario(
            name="replication_burst", seed=1601, duration_s=d(10),
            clients=6, rate=50.0,
            ops=(("put", 55), ("get", 38), ("delete", 7)),
            buckets=("grburst",), nobjects=32,
            put_bytes=(8 << 10, 64 << 10),
            slo={"classes": {
                "PUT": {"p99_ms": 4000.0, "availability": 0.995},
                "GET": {"p99_ms": 1500.0, "availability": 0.995}},
                "shed_fraction_max": 0.05},
            description="write burst while the push queue drains to "
                        "the peer: primary-facing PUT latency must not "
                        "absorb the WAN (async replication), deletes "
                        "replicate as versioned markers"),
        Scenario(
            name="peer_kill_mid_push", seed=1602, duration_s=d(12),
            clients=6, rate=45.0,
            ops=(("put", 50), ("get", 50)),
            buckets=("grpeer",), nobjects=32,
            chaos="peer_kill", chaos_at_frac=0.25, chaos_dur_frac=0.4,
            slo={"classes": {
                "PUT": {"p99_ms": 4000.0, "availability": 0.995},
                "GET": {"p99_ms": 1500.0, "availability": 0.995}},
                "shed_fraction_max": 0.05},
            description="secondary killed mid-push and restarted at "
                        "the same address: breaker opens, primary SLO "
                        "holds, retried sweeps converge after restart"),
        Scenario(
            name="worker_kill", seed=1603, duration_s=d(12),
            clients=6, rate=45.0,
            ops=(("put", 45), ("get", 55)),
            buckets=("grwork",), nobjects=32,
            # PUT bodies must clear the 128 KiB inline bound: inline
            # objects never reach the mp worker plane, and a kill that
            # can't hit an in-flight job tests nothing
            put_bytes=(160 << 10, 256 << 10),
            chaos="worker_kill", chaos_at_frac=0.3, chaos_dur_frac=0.3,
            # the PUT budget PRICES the designed fault: a SIGKILL
            # deterministically fails the in-flight jobs of the dead
            # worker until the supervisor respawns it (~2-3% of this
            # schedule's PUTs on the shared container); 0.95 passes
            # that baseline while still failing a supervisor that
            # cannot keep workers alive
            slo={"classes": {
                "PUT": {"p99_ms": 5000.0, "availability": 0.95},
                "GET": {"p99_ms": 2000.0, "availability": 0.99}},
                "shed_fraction_max": 0.05},
            description="one mp I/O worker of the primary SIGKILLed "
                        "mid-run; the plane supervisor respawns it, "
                        "the kill window's in-flight PUTs fit the "
                        "availability budget, replication still "
                        "converges"),
        Scenario(
            name="read_your_writes_across_sites", seed=1604,
            duration_s=d(10), clients=4, rate=30.0,
            ops=(("put", 60), ("get", 40)),
            buckets=("grryw",), nobjects=24,
            slo={"classes": {
                "PUT": {"p99_ms": 4000.0, "availability": 0.995},
                "GET": {"p99_ms": 1500.0, "availability": 0.995}},
                "shed_fraction_max": 0.02},
            description="every acknowledged write must become readable "
                        "BYTE-IDENTICAL on the secondary: the harness "
                        "polls the peer after replay and records the "
                        "convergence lag next to this verdict"),
    ]


def smoke_scenario() -> Scenario:
    """Tier-1 sized: a few seconds against a real server, generous
    budgets (CI boxes are noisy — this pins the loop closes, not that
    CI is fast)."""
    return Scenario(
        name="smoke_zipf_read", seed=7701, duration_s=3.0, clients=4,
        rate=40.0, ops=(("get", 80), ("put", 12), ("list", 8)),
        nobjects=12, obj_bytes=(2 << 10, 8 << 10),
        put_bytes=(2 << 10, 8 << 10),
        slo={"classes": {
            "GET": {"p99_ms": 15000.0, "availability": 0.98}},
            "shed_fraction_max": 0.2},
        description="tier-1 smoke: tiny zipf mix, generous budgets")
