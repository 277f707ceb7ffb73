"""ISSUE 26: the stage timer as the one leaf-span primitive
(erasure/stagestats.py): counters, the profiler's trace, the request
tree.  ISSUE 37: a stage's seconds on a CPU beside its seconds on the
clock, and the places where a request waits between the stages.

One PUT and one degraded GET of a 16 MiB object on a 2+2 set run once
under `jax.profiler` (host codec); a second, small stream runs through
the Pallas kernel in interpret mode, the dispatch path of the chip.  The
tests then read the `.xplane.pb` the way benchmark/trace.py does, the
counters' deltas and the captured request traces.  CPU only: no number
here is a device number.
"""

import concurrent.futures as cf
import io
import os
import re
import shutil
import sys
import threading
import time
import types

import numpy as np
import pytest

from benchmark import serve
from benchmark import trace as bench_trace
from minio_tpu.erasure import bitrot, coding, stagestats
from minio_tpu.erasure.objects import ErasureObjects
from minio_tpu.ops import host
from minio_tpu.storage.instrumented import instrument
from minio_tpu.storage.local import LocalStorage
from minio_tpu.utils import tracing
from tests import device_codec

# leaves a PUT + degraded GET reach on the host codec, and the three more
# of a device dispatch
HOST_PUT = ("read", "etag", "host_codec", "hash", "write", "commit",
            "ns_lock", "write_wait", "open", "close")
HOST_GET = ("meta_read", "ns_lock", "open", "read_wait", "shard_read",
            "verify", "assemble", "host_codec", "respond")
DEVICE = ("h2d", "launch", "fetch")
# and the one more of a GET that is served: its body written to the
# connection's socket by the executor thread that pulled it (ISSUE 36)
SERVED = ("send",)
# the hops between threads and the handler's whole time (ISSUE 37):
# counters only
HOPS = ("exec_wait", "loop_wait", "pool_wait")
# what an inline request's `request` is split into
# (benchmark/metrics/unstaged_ms_per_op.json subtracts the same)
INLINE = ("admit", "auth", "exec_wait", "loop_wait", "meta_read", "commit",
          "ns_lock")
OBJECT_BYTES = 16 << 20
SECONDS_ROW = re.compile(
    r'minio_dataplane_stage_seconds_total\{stage="(\w+)"\} (\S+)')


def _delta(before: dict, after: dict) -> dict:
    return {s: {k: after[s][k] - before[s][k] for k in after[s]}
            for s in stagestats.STAGES}


def _device_stream(tmp_path) -> dict:
    """Two full 2+2 blocks encoded and, with a data shard away, decoded
    through PallasRSCodec in interpret mode -> the GET's stage deltas."""
    from minio_tpu.ops import rs_pallas

    k, m, bs = 2, 2, 1 << 20
    device_codec.plant(k, m, rs_pallas.PallasRSCodec(k, m, interpret=True))
    try:
        e = coding.Erasure(k, m, bs, backend="tpu")
        size = 2 * bs
        data = np.random.default_rng(3).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        paths = [tmp_path / f"shard{i}" for i in range(k + m)]
        writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size)
                   for p in paths]
        e.encode_stream(io.BytesIO(data), writers, size, k + 1)
        for w in writers:
            w.close()
        readers = [None if i == 0 else bitrot.BitrotReader(
            open(paths[i], "rb"), e.shard_file_size(size), e.shard_size)
            for i in range(k + m)]
        out = io.BytesIO()
        before = stagestats.snapshot()
        e.decode_stream(out, readers, 0, size, size)
        after = stagestats.snapshot()
        for r in readers:
            if r is not None:
                r.close()
        assert out.getvalue() == data
        return _delta(before, after)
    finally:
        device_codec.unplant(k, m)


def _captured(api: str, holding: str) -> dict:
    """The captured request trace of `api` that holds the stage."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        docs = [d for d in tracing.store.snapshot()
                if d["name"] == api and holding in d["stages"]]
        if docs:
            return docs[0]
        time.sleep(0.02)
    raise AssertionError(f"no {api} trace with {holding} was captured")


def _served(root) -> types.SimpleNamespace:
    """One object of two full blocks and a tail, and one inline object,
    PUT and fetched over HTTP -> the captured request traces of the
    large GET and the inline pair, and the seconds family's rows in a
    scrape before and a scrape after them."""
    from tests.s3_harness import S3TestServer

    rng = np.random.default_rng(36)
    body = rng.integers(0, 256, (2 << 20) + 36, dtype=np.uint8).tobytes()
    inline = rng.integers(0, 256, 37 << 10, dtype=np.uint8).tobytes()
    srv = S3TestServer(str(root))

    def scrape() -> dict:
        text = srv.request("GET", "/minio/v2/metrics/cluster").body
        return {row: float(v)
                for row, v in SECONDS_ROW.findall(text.decode())}

    try:
        assert srv.request("PUT", "/bkt").status == 200
        before = scrape()
        assert srv.request("PUT", "/bkt/served", data=body).status == 200
        tracing.store.clear()
        assert srv.request("GET", "/bkt/served").body == body
        served = _captured("get_object", "respond")
        tracing.store.clear()
        assert srv.request("PUT", "/bkt/inline", data=inline).status == 200
        assert srv.request("GET", "/bkt/inline").body == inline
        return types.SimpleNamespace(
            doc=served, inline_put=_captured("put_object", "request"),
            inline_get=_captured("get_object", "request"),
            before=before, after=scrape())
    finally:
        srv.close()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The run itself, once -> what the tests read."""
    import jax

    tmp = tmp_path_factory.mktemp("stagespans")
    mp = pytest.MonkeyPatch()
    mp.setenv("MINIO_TPU_ERASURE_BACKEND", "host")
    mp.setenv("MINIO_TPU_TRACE", "1")
    mp.setenv("MINIO_TPU_TRACE_SLOW_MS", "0")
    # the CPU clock around every interval, not one in sixteen: a few
    # requests have to show their CPU seconds
    mp.setattr(stagestats, "CPU_EVERY", 1)
    disks = instrument([LocalStorage(str(tmp / f"d{i}")) for i in range(4)])
    for d in disks:
        d.make_volume("bkt")
    api = ErasureObjects(disks)
    data = np.random.default_rng(26).integers(
        0, 256, OBJECT_BYTES, dtype=np.uint8).tobytes()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        root = tracing.begin_request("put_object")
        api.put_object("bkt", "obj", io.BytesIO(data), len(data))
        put_doc = tracing.end_request(root)
        # drives 2 and 4 hold one data and one parity shard of a 2+2
        # object whatever its rotation (benchmark/selfcheck.py)
        for i in (1, 3):
            shutil.rmtree(os.path.join(str(tmp), f"d{i}", "bkt", "obj"))
        before = stagestats.snapshot()
        root = tracing.begin_request("get_object")
        _, stream = api.get_object("bkt", "obj")
        body = b"".join(stream)
        get_doc = tracing.end_request(root)
        get_stages = _delta(before, stagestats.snapshot())
        device_stages = _device_stream(tmp)
        served = _served(tmp / "served")
    finally:
        jax.profiler.stop_trace()
        mp.undo()
    assert body == data
    events = bench_trace.load_events(
        bench_trace.find_xplane(str(tmp / "trace")))
    return types.SimpleNamespace(
        host_spans={name for name, _, _ in events["host"]},
        put_doc=put_doc, get_doc=get_doc, served_doc=served.doc,
        served=served,
        get_stages=get_stages, device_stages=device_stages,
        total=stagestats.snapshot())


@pytest.mark.parametrize(
    "stage", sorted(set(HOST_PUT + HOST_GET + DEVICE + SERVED)))
def test_leaf_lies_in_the_profile_and_counts(traced, stage):
    assert f"dp.{stage}" in traced.host_spans
    row = traced.total[stage]
    assert row["seconds"] > 0
    # the clock of the union runs only while a thread is inside
    assert 0 < row["wall"] <= row["seconds"] + 1e-9


@pytest.mark.parametrize(
    "stage", sorted(stagestats.PARENTS) + ["compile"] + list(HOPS))
def test_parent_compile_and_hop_write_no_span(traced, stage):
    """A span around other spans would take every idle gap's name; a
    span named compile would count as a compilation; a hop between two
    threads lies on neither's line."""
    assert f"dp.{stage}" not in traced.host_spans
    assert stage in stagestats.STAGES  # a counter all the same
    if stage != "compile":
        assert traced.total[stage]["seconds"] > 0


def test_decode_is_booked_though_it_has_no_span(traced):
    assert traced.get_stages["decode"]["seconds"] > 0
    assert traced.total["encode"]["seconds"] > 0


def test_span_names_honour_the_benchmarks_readers(traced):
    ours = {n for n in traced.host_spans if n.startswith(("dp.", "drive."))}
    assert {"drive.read_version", "drive.rename_data",
            "drive.read_file_stream"} <= ours
    for name in ours:
        assert not bench_trace.COMPILE_SPAN.search(name), name
        assert name != serve.MARK
        assert len(name) <= 80 and " = " not in name
    # the scrape of benchmark/server.py reads stage labels as \w+
    rows = stagestats.seconds_rows()
    assert set(stagestats.STAGES) < set(rows)
    assert all(row.isidentifier() for row in rows)


@pytest.mark.parametrize("path,leaves", [
    ("get_stages", ("read_wait", "assemble", "host_codec")),
    ("device_stages", ("read_wait", "assemble", "h2d", "launch", "fetch")),
])
def test_owning_threads_leaves_close_on_decode(traced, path, leaves):
    """On the stream's own thread the leaves do not nest and leave
    little of `decode` unnamed (a loose limit: no test of the box)."""
    stages = getattr(traced, path)
    decode = stages["decode"]["seconds"]
    named = sum(stages[s]["seconds"] for s in leaves)
    assert all(stages[s]["seconds"] > 0 for s in leaves)
    assert 0.8 * decode <= named <= decode
    if path == "device_stages":
        assert stages["host_codec"]["seconds"] == 0


@pytest.mark.parametrize("doc,leaves", [
    ("put_doc", HOST_PUT), ("get_doc", HOST_GET),
    # over HTTP the handler's one fan-out opens the object: no
    # host_codec on a healthy set, and the body's `send`
    ("served_doc", ("meta_read", "read_wait", "shard_read", "verify",
                    "assemble", "respond") + SERVED)])
def test_captured_request_holds_leaf_spans(traced, doc, leaves):
    # a degraded GET's staged reads check their frames inside one native
    # call a shard (erasure/bitrot.py _read_native): the hash's seconds
    # are booked as `verify`, inside `shard_read`'s span, with none of
    # their own
    native = doc == "get_doc" and host.available()
    doc = getattr(traced, doc)
    names = {s["name"] for s in doc["spans"]}
    spans = set(leaves) - {"verify"} if native else set(leaves)
    assert {f"dp.{s}" for s in spans} <= names
    if native:
        assert "dp.verify" not in names
    assert not {"dp.decode", "dp.encode"} & names
    # per-request seconds stay, parents among them
    assert set(leaves) <= set(doc["stages"])
    assert {"encode", "decode"} & set(doc["stages"])
    if "send" in leaves:
        # a worker wrote every byte: one piece, the group with the
        # partial block riding in it
        sends = [s for s in doc["spans"] if s["name"] == "dp.send"]
        assert len(sends) == 1
    # the trace's start on the profiler's clock places its spans there
    assert 0 < doc["startMonotonic"] <= time.perf_counter()
    (root,) = [s for s in doc["spans"] if s["parent"] is None]
    for s in doc["spans"]:
        if s["name"].startswith("dp."):
            assert s["parent"] == root["id"]
            assert 0 <= s["t0"] <= root["dur"] + 1e-3


def test_wall_time_union_under_threads(monkeypatch):
    """More threads than cores inside one stage: the union never passes
    the thread-seconds, counts an overlap once and leaves nobody inside."""
    monkeypatch.setattr(stagestats, "CPU_EVERY", 1)
    before = stagestats.snapshot()["verify"]
    start = threading.Barrier(8)

    def work():
        start.wait(timeout=10)
        for _ in range(200):
            with stagestats.timed("verify", 3):
                pass
        with stagestats.timed("verify", 3):
            time.sleep(0.02)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work) for _ in range(8)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        elapsed = time.perf_counter() - t0
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    after = stagestats.snapshot()["verify"]
    assert stagestats._inside["verify"] == 0
    assert after["bytes"] - before["bytes"] == 8 * 201 * 3
    seconds = after["seconds"] - before["seconds"]
    wall = after["wall"] - before["wall"]
    assert seconds >= 8 * 0.02
    assert 0.02 <= wall <= min(seconds, elapsed) + 1e-9
    # no interval's CPU seconds lost or counted twice: each is read
    # inside its wall interval
    assert 0 < after["cpu"] - before["cpu"] <= seconds


def test_compile_seconds_are_a_counter():
    import jax
    import jax.numpy as jnp

    from minio_tpu.server.__main__ import _count_compile_seconds

    before = stagestats.snapshot()["compile"]
    _count_compile_seconds()
    jax.jit(lambda x: x * 26 + 1)(jnp.arange(26)).block_until_ready()
    after = stagestats.snapshot()["compile"]
    assert after["seconds"] > before["seconds"]
    assert after["wall"] == before["wall"]


def test_send_is_a_leaf_beside_respond():
    """`respond` is the decode thread's put into the sink's queue;
    the response is `send` (ISSUE 36)."""
    assert "send" in stagestats.STAGES and "send" not in stagestats.PARENTS
    assert stagestats._SPAN_NAMES["send"] == "dp.send"


def test_scrape_shows_the_wall_family(traced):
    from minio_tpu.server.metrics import MetricsMixin

    class _Reg:
        def render(self):
            return ""

    text = MetricsMixin._render_metrics(
        types.SimpleNamespace(metrics=_Reg(), api=None))
    for family in ("seconds", "bytes", "wall_seconds"):
        for stage in ("read_wait", "fetch", "commit", "admit", "compile",
                      "send"):
            assert (f'minio_dataplane_stage_{family}_total'
                    f'{{stage="{stage}"}} ') in text


def _spin(cpu_seconds: float) -> None:
    end = time.thread_time() + cpu_seconds
    while time.thread_time() < end:
        pass


@pytest.mark.parametrize("inside,least,most", [
    # a loop that never leaves the CPU of its own accord: all of its
    # CPU seconds, which are no more than its seconds on the clock (how
    # many more those are is the box's other processes taking turns)
    (_spin, 0.19, None),
    # a sleep books next to none
    (time.sleep, 0.0, 0.02),
])
def test_cpu_seconds_beside_wall_seconds(monkeypatch, inside, least, most):
    monkeypatch.setattr(stagestats, "CPU_EVERY", 1)
    before = stagestats.snapshot()["verify"]
    with stagestats.timed("verify"):
        inside(0.2)
    after = stagestats.snapshot()["verify"]
    seconds = after["seconds"] - before["seconds"]
    cpu = after["cpu"] - before["cpu"]
    assert seconds >= 0.2
    assert least <= cpu <= (seconds + 1e-3 if most is None else most)


def test_cpu_seconds_are_an_estimate_from_one_interval_in_sixteen():
    """800 intervals of half a millisecond on a CPU: about fifty are
    read, each counts sixteen-fold, and the sum is near the 0.4 s (four
    standard deviations of the draw either way)."""
    assert stagestats.CPU_EVERY == 16
    before = stagestats.snapshot()["pad"]["cpu"]
    for _ in range(800):
        with stagestats.timed("pad"):
            _spin(0.0005)
    cpu = stagestats.snapshot()["pad"]["cpu"] - before
    assert 0.15 <= cpu <= 0.8


def test_cpu_rows_only_where_threads_are_inside(traced):
    """`<stage>_cpu` for every stage timed() books, the parents among
    them; none for a stage that add() alone books."""
    rows = set(traced.served.after)
    assert {s + "_cpu" for s in stagestats.TIMED} <= rows
    assert not {s + "_cpu" for s in stagestats.ADD_ONLY} & rows
    assert set(stagestats.TIMED) | stagestats.ADD_ONLY \
        == set(stagestats.STAGES)
    assert {"encode", "decode"} <= set(stagestats.TIMED)
    assert len(stagestats.TIMED) == 24
    # and the one watched thread's, while that thread lives
    assert rows - set(stagestats.seconds_rows()) <= {"loop_cpu"} < rows
    snap = stagestats.snapshot()
    assert all(("cpu" in snap[s]) == (s in stagestats.TIMED) for s in snap)


def test_scrape_holds_the_waits_and_the_loops_cpu(traced):
    before, after = traced.served.before, traced.served.after
    for row in HOPS + ("ns_lock", "open", "write_wait", "request",
                       "meta_read_cpu", "commit_cpu", "send_cpu"):
        assert after[row] > before[row], row
    # the event loop's thread answered five requests between the scrapes
    assert after["loop_cpu"] > before["loop_cpu"] > 0
    # a served request cannot have waited longer than it took
    assert after["exec_wait"] - before["exec_wait"] \
        < after["request"] - before["request"]


def test_pool_wait_is_the_queue_for_a_pool_thread(monkeypatch):
    pool = cf.ThreadPoolExecutor(max_workers=1)
    monkeypatch.setattr(coding, "_shared_pool", pool)
    try:
        before = stagestats.snapshot()["pool_wait"]["seconds"]
        release = threading.Event()
        held = coding.io_submit(release.wait, 10)
        queued = coding.io_submit(lambda a, b: a + b, 3, 4)
        time.sleep(0.1)
        release.set()
        assert queued.result(10) == 7 and held.result(10)
        waited = stagestats.snapshot()["pool_wait"]["seconds"] - before
    finally:
        pool.shutdown()
    assert 0.1 <= waited < 5


def test_ns_lock_is_the_locks_wait():
    from minio_tpu.erasure.objects import NamespaceLock

    ns = NamespaceLock()
    before = stagestats.snapshot()["ns_lock"]
    got = threading.Event()

    def reader():
        with ns.read("bkt/obj"):
            got.set()

    parked = threading.Thread(target=reader)
    with ns.write("bkt/obj"):
        parked.start()
        deadline = time.monotonic() + 10
        while not stagestats._inside["ns_lock"] \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        time.sleep(0.1)
        assert not got.is_set()
    parked.join(timeout=10)
    assert got.is_set()
    after = stagestats.snapshot()["ns_lock"]
    seconds = after["seconds"] - before["seconds"]
    assert 0.1 <= seconds < 15
    # parked, not spinning
    assert after["cpu"] - before["cpu"] < 0.5 * seconds


@pytest.mark.parametrize("doc", ["inline_put", "inline_get"])
def test_inline_request_closes_on_its_stages(traced, doc):
    """`request` is the handler's whole time, so what the handler, its
    hops and the object layer name of an inline request is no more than
    it, and no small part of it (loose: no test of the box)."""
    stages = getattr(traced.served, doc)["stages"]
    assert all(s in stages for s in ("request", "exec_wait", "loop_wait",
                                     "ns_lock", "admit", "auth"))
    named = sum(stages.get(s, 0.0) for s in INLINE)
    assert 0.1 * stages["request"] <= named <= stages["request"]
    assert ("commit" if doc == "inline_put" else "meta_read") in stages
