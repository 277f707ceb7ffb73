"""chip_smoke.py on a box without a chip, and the rules it relies on.

The rehearsal drives the whole script — real CLI child, signed HTTP,
lost shards, read-triggered and admin heal, the counters it reads — at a
tiny size on the host codec.  Without the rehearsal switch and without a
chip the script must fail: a CPU run may never pass for a chip run.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from minio_tpu.erasure.coding import Erasure
from minio_tpu.ops import device
from minio_tpu.storage import errors

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(tmp_path, *flags):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, SMOKE, "--root", str(tmp_path / "drives"), *flags],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=280)


def test_rehearsal_passes_and_says_so(tmp_path):
    proc = _run_smoke(tmp_path, "--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-3000:]
    report, verdict = map(json.loads, proc.stdout.strip().splitlines())
    # the last line is the verdict: these keys and no others
    assert list(verdict) == ["ok", "device"] and verdict["ok"] is True
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    out = report
    assert out["ok"] is True and out["rehearsal"] is True
    assert out["platform"] == "cpu"
    assert out["host_codec"] == "native"
    assert list(out)[-1] == "claim" and out["claim"] is None
    phases = out["phases"]
    assert list(phases) == ["put_large", "put_inline", "get_all",
                            "degraded_get", "heal", "put_odd"]
    # the counters the chip run is judged by move as the phases say:
    # large PUTs and both repairs code bytes, healthy GETs code none
    assert phases["put_large"]["backend_bytes"]["host"] \
        >= phases["put_large"]["bytes"]
    assert phases["get_all"]["backend_bytes"] == {
        "host": 0, "device": 0, "mesh": 0}
    assert phases["degraded_get"]["backend_bytes"]["host"] > 0
    assert phases["heal"]["backend_bytes"]["host"] > 0
    assert all(p["backend_bytes"]["device"] == 0 for p in phases.values())


def test_without_a_chip_it_fails(tmp_path):
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "a failed smoke printed a result"
    assert "needs a TPU" in proc.stderr


def test_backend_tpu_without_a_tpu_raises():
    assert device.info().platform == "cpu"
    with pytest.raises(device.BackendUnavailable, match="needs a TPU"):
        Erasure(8, 4, backend="tpu")
    with pytest.raises(errors.InvalidArgument, match="unknown erasure"):
        Erasure(8, 4, backend="TPU")


def test_only_full_width_shards_leave_the_host(monkeypatch):
    """The single-chip path takes a geometry's full-width shards only: a
    64 KiB inline object (shard 8192, tileable) and a tail block stay on
    the host codec whatever the backend.  The rule is by shard length,
    not by geometry: 12+4, 14+2 and 10+2, whose full shards are no
    multiple of the kernel's tile, go to the device like 8+4."""
    from minio_tpu.erasure import coding

    class Fake:
        backend = "device"

    monkeypatch.setitem(coding._DeviceCodec._cache, (8, 4), (Fake(), True))
    monkeypatch.setitem(coding._DeviceCodec._ready, (8, 4), Fake())
    e = Erasure(8, 4, backend="tpu")
    assert e._device(32 << 20, e.shard_size) is not None
    assert e._device(64 << 10, 8192) is None
    assert e._device(512 << 10, 65536) is None
    assert coding.steady_state_backend(4, 2) == "host"  # auto, no TPU
    for k, m in ((12, 4), (14, 2), (10, 2)):
        monkeypatch.setitem(coding._DeviceCodec._cache, (k, m), (Fake(), True))
        monkeypatch.setitem(coding._DeviceCodec._ready, (k, m), Fake())
        odd = Erasure(k, m, backend="tpu")
        assert odd.shard_size % 8192 != 0
        assert odd._device(32 << 20, odd.shard_size) is not None
        assert odd._device(odd.shard_size // 2 * k, odd.shard_size // 2) is None
        monkeypatch.setenv("MINIO_TPU_ERASURE_BACKEND", "tpu")
        assert coding.steady_state_backend(k, m) == "device"
        monkeypatch.delenv("MINIO_TPU_ERASURE_BACKEND")


def test_compile_cache_dir_from_outside_is_the_one_used(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set from outside wins and a compile
    lands in it; unset, the cache is <checkout>/.jax_cache."""
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from minio_tpu.ops import device\n"
        "d = device.enable_compile_cache()\n"
        "assert d == device.compile_cache_dir()\n"
        "if os.environ.get('SMOKE_COMPILE'):\n"
        "    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
        "print(d)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    outside = tmp_path / "cache"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path),
        env=dict(env, JAX_COMPILATION_CACHE_DIR=str(outside),
                 SMOKE_COMPILE="1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(outside)
    assert os.listdir(outside), "nothing was cached in the directory"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=str(tmp_path), env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == os.path.join(REPO, ".jax_cache")


def test_native_library_name_follows_its_sources(tmp_path, monkeypatch):
    """The host library's file name is a content hash of csrc/: a changed
    source is a different file, so a stale build is never opened."""
    import shutil

    from minio_tpu.ops import host

    built = host.lib_path()
    assert built and os.path.exists(built) and host.available()
    assert os.path.basename(built) == host._lib_name()
    copy = tmp_path / "csrc"
    shutil.copytree(host._CSRC, copy,
                    ignore=shutil.ignore_patterns("*.so", "*.tmp"))
    monkeypatch.setattr(host, "_CSRC", str(copy))
    assert host._lib_name() == os.path.basename(built)
    with open(copy / "gf256_simd.cpp", "a") as f:
        f.write("\n// changed\n")
    assert host._lib_name() != os.path.basename(built)


def test_device_self_test_catches_wrong_bytes(monkeypatch):
    """The boot self-test of the device codec: right bytes pass, a codec
    that computes wrong parity is fatal (interpret mode stands in for the
    chip here; on the chip the server runs it at boot)."""
    from minio_tpu import selftest
    from minio_tpu.erasure import coding
    from minio_tpu.ops import rs_pallas

    good = rs_pallas.PallasRSCodec(4, 2, interpret=True)
    monkeypatch.setattr(coding, "DEVICE_BATCH_SIZES", (2,))
    monkeypatch.setitem(coding._DeviceCodec._cache, (4, 2), (good, None))
    assert selftest.device_self_test(4, 2, 64 << 10) > 0

    class Wrong:
        def encode(self, batch):
            out = np.array(good.encode(batch))
            out[-1, -1, -1] ^= 1
            return out

        reconstruct = good.reconstruct

    monkeypatch.setitem(coding._DeviceCodec._cache, (4, 2), (Wrong(), None))
    with pytest.raises(selftest.SelfTestError, match="gf256 oracle"):
        selftest.device_self_test(4, 2, 64 << 10)
