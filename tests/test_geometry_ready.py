"""A geometry's device programs are never compiled on a request's thread
(ISSUE 35).

The single-chip codec of a geometry is ready once its device self-test
has passed (`coding._DeviceCodec.self_test`: every program a dispatch
can run, compiled and compared with the oracle).  Boot does that for the
geometries a healthy set writes.  Any other — a PUT to sixteen drives
with two away is written at the upgraded parity 10+6 — is coded by the
host codec, byte for byte the same, while one background thread runs
the self-test; then its dispatches go to the device.  Interpret mode
stands in for the chip, `ops/gf256.py` is the oracle, blocks are 64 KiB
(a shard: 6,554 bytes at 10+6, 5,958 at 11+5, 5,042 at 13+3: k divides
no block and no shard is a multiple of the kernel's tile).
"""

import io
import sys
import threading
import time
import types

import numpy as np
import pytest

from minio_tpu import selftest
from minio_tpu.erasure import coding, stagestats
from minio_tpu.erasure.coding import Erasure
from minio_tpu.erasure.objects import ErasureObjects, PutObjectOptions
from minio_tpu.storage.local import LocalStorage
from tests import device_codec
from tests.device_codec import Seen as _Seen
from tests.device_codec import bytes_of as _bytes_of
from tests.device_codec import oracle_parity as _oracle_parity

BS = 1 << 16
# what sixteen drives write with two, one and (at REDUCED_REDUNDANCY) one
# drive away
GEOMETRIES = [(10, 6), (11, 5), (13, 3)]
DC = coding._DeviceCodec
_ids = "{0[0]}+{0[1]}".format


class _Compiles:
    """Every XLA compilation of this process, with the thread it ran on
    (as tests/test_batch_sizes.py counts them)."""

    threads: list = []
    listening = False

    @classmethod
    def listen(cls):
        import jax.monitoring

        def fold(event: str, _seconds: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                cls.threads.append(threading.current_thread().name)

        if not cls.listening:
            jax.monitoring.register_event_duration_secs_listener(fold)
            cls.listening = True


def _on_a_chip(mp):
    """The engine believes a TPU is attached, and its self-test runs at
    the tests' block size (a 1 MiB block is minutes of interpret mode)."""
    mp.setattr(coding.device, "info", lambda: types.SimpleNamespace(
        platform="tpu", kind="interpret mode", count=1))
    real = selftest.device_self_test
    mp.setattr(selftest, "device_self_test",
               lambda k, m, _block_size: real(k, m, BS))


def _join_warmer(timeout=300.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        warmer = DC._warmer
        if warmer is None:
            return
        warmer.join(timeout=1.0)
    raise AssertionError("the warm-up thread did not end")


def _batch(g, k, s, seed):
    return np.random.default_rng(seed).integers(
        0, 256, size=(g, k, s), dtype=np.uint8)


class _Warmed:
    """One geometry taken from unknown to ready as a serving node takes
    it: a first dispatch finds no programs, is coded on the host and
    asks; the warm-up thread self-tests; the next dispatch is the
    device's.  What was seen on the way is kept for the tests."""

    def __init__(self, k, m):
        from minio_tpu.ops import rs_pallas

        self.k, self.m = k, m
        self.mp = pytest.MonkeyPatch()
        _on_a_chip(self.mp)
        _Compiles.listen()
        device_codec.unplant(k, m)
        self.codec = _Seen(rs_pallas.PallasRSCodec(k, m, interpret=True))
        DC._cache[(k, m)] = (self.codec, None)
        self.e = e = Erasure(k, m, BS, backend="tpu")
        batch = _batch(10, k, e.shard_size, 35)
        self.want = _oracle_parity(batch, m)
        me = threading.current_thread().name

        n0 = len(_Compiles.threads)
        warm0, host0 = _bytes_of("warming"), \
            coding.backend_stats["host"]["bytes"]
        self.cold = e._encode_shards(batch)
        self.cold_state = coding.geometry_states().get(f"{k}+{m}")
        self.cold_shapes = list(self.codec.shapes)
        self.cold_warming = _bytes_of("warming") - warm0
        self.cold_host = coding.backend_stats["host"]["bytes"] - host0
        self.cold_compiles_here = _Compiles.threads[n0:].count(me)

        _join_warmer()
        self.warm_threads = set(_Compiles.threads[n0:])
        self.warm_state = coding.geometry_states().get(f"{k}+{m}")
        del self.codec.shapes[:]
        n1 = len(_Compiles.threads)
        warm0, dev0 = _bytes_of("warming"), \
            coding.backend_stats["device"]["bytes"]
        self.warm = e._encode_shards(batch)
        self.warm_shapes = list(self.codec.shapes)
        self.warm_warming = _bytes_of("warming") - warm0
        self.warm_device = coding.backend_stats["device"]["bytes"] - dev0
        self.nbytes = batch.nbytes
        self.compiles_since = n1

    def close(self):
        device_codec.unplant(self.k, self.m)
        self.mp.undo()


@pytest.fixture(scope="module", params=GEOMETRIES, ids=_ids)
def warmed(request):
    w = _Warmed(*request.param)
    yield w
    w.close()


@pytest.fixture
def clean_engine():
    """The engine's knowledge of geometries as the test found it."""
    saved = [dict(d) for d in (DC._cache, DC._ready, DC._state)]
    yield
    _join_warmer()
    for d, was in zip((DC._cache, DC._ready, DC._state), saved):
        d.clear()
        d.update(was)


def test_not_ready_is_the_hosts_and_books_warming(warmed):
    """The first dispatch of a geometry nobody compiled: the host codec's
    bytes, equal to the oracle's, counted as the host's and as `warming`;
    the device codec was not called, the calling thread compiled
    nothing, and admin info says `warming`."""
    np.testing.assert_array_equal(warmed.cold, warmed.want)
    assert warmed.cold_shapes == []
    assert warmed.cold_host == warmed.nbytes
    assert warmed.cold_warming == warmed.nbytes
    assert warmed.cold_compiles_here == 0
    assert warmed.cold_state == "warming"


def test_ready_after_the_warm_up_is_the_devices_and_books_none(warmed):
    """Once the background self-test has passed the same dispatch goes
    to the device, carried at a compiled size, equal to the oracle;
    `warming` books nothing; what compiled, compiled on the warm-up
    thread alone."""
    np.testing.assert_array_equal(warmed.warm, warmed.want)
    assert warmed.warm_state == "device"
    assert warmed.warm_shapes == [(coding.carrier_blocks(10), 10)]
    assert warmed.warm_device == warmed.nbytes
    assert warmed.warm_warming == 0
    assert warmed.warm_threads <= {"codec-warm"}
    assert DC.ready(warmed.k, warmed.m) is warmed.codec


@pytest.mark.parametrize("what", ["encode", "reconstruct"])
@pytest.mark.parametrize("g", [1, 10, 16, 32])
def test_a_warmed_geometry_codes_as_the_oracle(warmed, g, what):
    """g blocks through the engine's own entries, both directions and
    1..m rows: the oracle's bytes from a program the warm-up compiled
    (nothing compiles on this thread), at a compiled batch size."""
    e, k, m = warmed.e, warmed.k, warmed.m
    s = e.shard_size
    size = coding.carrier_blocks(g)
    batch = _batch(g, k, s, g * 131 + k)
    parity = _oracle_parity(batch, m)
    del warmed.codec.shapes[:]
    if what == "encode":
        np.testing.assert_array_equal(e._encode_shards(batch), parity)
        np.testing.assert_array_equal(e._encode_shards_async(batch)(), parity)
        np.testing.assert_array_equal(e._host.encode(batch), parity)
        dispatches = 2
    else:
        full = np.concatenate([batch, parity], axis=1)
        for lost in range(1, m + 1):
            wanted = tuple(range(lost))
            avail = tuple(range(lost, lost + k))
            src = np.ascontiguousarray(full[:, lost:lost + k])
            got = e._reconstruct_shards(src, avail, wanted)
            np.testing.assert_array_equal(got, batch[:, :lost])
            np.testing.assert_array_equal(
                e._host.reconstruct(src, avail, wanted), got)
        dispatches = m
    assert warmed.codec.shapes == [
        (size, None if size == g else g)] * dispatches
    assert _Compiles.threads[warmed.compiles_since:] == []


def test_a_ten_block_put_and_degraded_get_after_the_warm_up(warmed,
                                                           tmp_path):
    """A whole object through the streams at the warmed geometry: the
    PUT's one encode dispatch and the degraded GET's one reconstruct
    dispatch are the device's, and the body comes back."""
    from minio_tpu.erasure import bitrot

    e, k, m = warmed.e, warmed.k, warmed.m
    size = 10 * BS
    body = np.random.default_rng(k).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    paths = [tmp_path / f"shard{i}" for i in range(k + m)]
    writers = [bitrot.BitrotWriter(open(p, "wb"), e.shard_size)
               for p in paths]
    del warmed.codec.shapes[:]
    n, failed = e.encode_stream(io.BytesIO(body), writers, size, k)
    assert n == size and not failed
    for w in writers:
        w.close()
    till = e.shard_file_size(size)
    readers = [None if i in (0, k + 1) else bitrot.BitrotReader(
        open(p, "rb"), till, e.shard_size) for i, p in enumerate(paths)]
    out = io.BytesIO()
    assert e.decode_stream(out, readers, 0, size, size) == size
    assert out.getvalue() == body
    assert warmed.codec.shapes == [(coding.carrier_blocks(10), 10)] * 2
    assert _Compiles.threads[warmed.compiles_since:] == []


def test_a_failed_background_self_test_leaves_the_geometry_on_the_host(
        clean_engine, monkeypatch):
    """A device codec that computes wrong parity: its self-test fails on
    the warm-up thread, which logs it and ends; the node goes on serving
    the geometry from the host codec, and says `failed`."""
    from minio_tpu.ops import rs_pallas

    k, m = 9, 7
    _on_a_chip(monkeypatch)
    good = rs_pallas.PallasRSCodec(k, m, interpret=True)

    class Wrong:
        backend = "device"
        calls = 0

        def encode(self, batch, blocks=None):
            Wrong.calls += 1
            out = np.array(good.encode(batch))
            out[-1, -1, -1] ^= 1
            return out

        reconstruct = good.reconstruct

    device_codec.unplant(k, m)
    DC._cache[(k, m)] = (Wrong(), None)
    e = Erasure(k, m, BS, backend="tpu")
    batch = _batch(3, k, e.shard_size, 97)
    want = _oracle_parity(batch, m)
    np.testing.assert_array_equal(e._encode_shards(batch), want)
    _join_warmer()
    assert Wrong.calls == 1  # the self-test's first encode, and no more
    assert coding.geometry_states()[f"{k}+{m}"] == "failed"
    assert DC.ready(k, m) is None
    warm0 = _bytes_of("warming")
    np.testing.assert_array_equal(e._encode_shards(batch), want)
    assert _bytes_of("warming") - warm0 == batch.nbytes
    assert Wrong.calls == 1 and DC._warmer is None  # never asked for again


def test_many_threads_ask_and_one_self_test_runs(clean_engine, monkeypatch):
    """More askers than cores, switching every few instructions: the
    geometry's self-test runs once, on one thread, and every asker is
    answered by the host codec until it has passed."""
    k, m = 7, 5
    monkeypatch.setattr(coding.device, "info", lambda: types.SimpleNamespace(
        platform="tpu", kind="interpret mode", count=1))
    ran = []

    def slow_self_test(k_, m_, _block_size):
        ran.append(threading.current_thread().name)
        time.sleep(0.05)
        return 0.05

    monkeypatch.setattr(selftest, "device_self_test", slow_self_test)
    device_codec.unplant(k, m)
    codec = object()
    DC._cache[(k, m)] = (codec, None)
    answers, go = [], threading.Event()

    def ask():
        go.wait(5)
        for _ in range(200):
            answers.append(DC.ready(k, m))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        askers = [threading.Thread(target=ask) for _ in range(32)]
        for t in askers:
            t.start()
        go.set()
        for t in askers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in askers)
    finally:
        sys.setswitchinterval(was)
    _join_warmer(30)
    assert ran == ["codec-warm"]
    assert len(answers) == 32 * 200
    assert set(answers) <= {None, codec}
    assert DC.ready(k, m) is codec
    assert coding.geometry_states()[f"{k}+{m}"] == "device"


def test_without_a_tpu_nothing_is_asked_for(clean_engine, monkeypatch):
    """Backend auto on a box with no TPU: the host codec for good, no
    thread, no `warming`."""
    k, m = 6, 3
    monkeypatch.setattr(coding.device, "info", lambda: types.SimpleNamespace(
        platform="cpu", kind="cpu", count=1))
    device_codec.unplant(k, m)
    e = Erasure(k, m, BS, backend="auto")
    warm0 = _bytes_of("warming")
    assert e._device(64 << 20, e.shard_size, dispatch=True) is None
    assert DC._warmer is None
    assert coding.geometry_states()[f"{k}+{m}"] == "host"
    assert _bytes_of("warming") == warm0


def test_compile_wait_is_what_compiled_outside_a_self_test():
    """The `jax.monitoring` listener books every compilation as `compile`
    and, unless the compiling thread is inside a device self-test, as
    `compile_wait` too; both are exported from boot at 0."""
    import jax.monitoring

    from minio_tpu.server.__main__ import _count_compile_seconds

    event = "/jax/core/compile/backend_compile_duration"
    for stage in ("compile_wait", "warming"):
        assert set(stagestats.snapshot()[stage]) == {"seconds", "bytes",
                                                    "wall"}
    _count_compile_seconds()

    def seconds():
        snap = stagestats.snapshot()
        return snap["compile"]["seconds"], snap["compile_wait"]["seconds"]

    c0, w0 = seconds()
    jax.monitoring.record_event_duration_secs(event, 1.25)
    c1, w1 = seconds()
    assert c1 - c0 >= 1.25 and w1 - w0 == pytest.approx(c1 - c0)
    DC._warming.on = True
    try:
        assert DC.self_testing()
        jax.monitoring.record_event_duration_secs(event, 2.5)
    finally:
        DC._warming.on = False
    c2, w2 = seconds()
    assert c2 - c1 >= 2.5 and w2 == w1
    assert not DC.self_testing()


# -- the set says which geometry its next PUT writes -------------------------

def _set_of_sixteen(tmp_path):
    disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(1, 17)]
    for d in disks:
        d.make_volume("bkt")
    return ErasureObjects(disks)


def test_the_set_asks_when_its_count_of_online_drives_changes(
        tmp_path, monkeypatch):
    """Sixteen drives at EC:4.  Healthy, a PUT asks for nothing.  With
    drives 1 and 7 gone a GET asks for nothing either (a node that only
    reads never writes the raised parity); the first PUT's pass over the
    drives asks for the geometries a PUT of either storage class is now
    written at, 10+6 and (REDUCED_REDUNDANCY, 14+2 raised by two) 12+4,
    once; the PUT is written at 10+6 on the fourteen drives that are
    there and reads back."""
    import shutil

    asked = []
    monkeypatch.setattr(
        Erasure, "warm", lambda self: asked.append((self.k, self.m)))
    es = _set_of_sixteen(tmp_path)
    body = np.random.default_rng(16).integers(
        0, 256, 3 * (1 << 20), dtype=np.uint8).tobytes()
    es.put_object("bkt", "healthy", io.BytesIO(body), len(body))
    assert asked == []
    for d in (1, 7):
        shutil.rmtree(tmp_path / f"d{d}")
    _, stream = es.get_object("bkt", "healthy")
    assert b"".join(stream) == body
    assert asked == []
    info = es.put_object("bkt", "degraded", io.BytesIO(body), len(body))
    assert info.size == len(body)
    assert asked == [(10, 6), (12, 4)]
    es.put_object("bkt", "degraded2", io.BytesIO(body), len(body))
    assert asked == [(10, 6), (12, 4)]  # the same count: not again
    fi, _, _ = es._quorum_info("bkt", "degraded")
    assert (fi.erasure.data_blocks, fi.erasure.parity_blocks) == (10, 6)
    parts = list(tmp_path.glob("d*/bkt/degraded/*/part.1"))
    assert len(parts) == 14
    assert {p.stat().st_size for p in parts} == {3 * (104858 + 32)}
    _, stream = es.get_object("bkt", "degraded")
    assert b"".join(stream) == body
    assert not (tmp_path / "d1").exists() and not (tmp_path / "d7").exists()


@pytest.mark.parametrize("n,parity,offline,want", [
    (16, 4, 0, (12, 4)), (16, 4, 1, (11, 5)), (16, 4, 2, (10, 6)),
    (16, 4, 4, (8, 8)), (16, 4, 6, (8, 8)), (16, 2, 1, (13, 3)),
    (12, 4, 2, (6, 6)), (4, 2, 1, (2, 2)),
])
def test_write_geometry_is_the_reference_s(tmp_path, n, parity, offline, want):
    """The program's parity upgrade against the plain reference's
    (benchmark/reference/parity_upgrade.py, cmd/erasure-object.go:770-805)."""
    from benchmark.reference import parity_upgrade

    es = ErasureObjects([None] * n, default_parity=parity)
    assert es._write_geometry(parity, offline) == want
    assert parity_upgrade.upgraded(n, parity, offline) == want
    assert es._parity_for(PutObjectOptions()) == parity
