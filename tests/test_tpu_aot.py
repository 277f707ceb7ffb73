"""Every device program the repo has, AOT-compiled for the TPU v5e.

No chip is needed: the installed libtpu describes a v5e topology
(`jax.experimental.topologies`) and compiles for it, so a kernel that
Mosaic or XLA:TPU refuses is caught on the CPU box, before chip time is
spent on it.  Compiling is not running — results on the chip are
compared with the oracle by the server's device self-test and by
chip_smoke.py.

The compiles run in a child process (`python tests/test_tpu_aot.py`):
libtpu keeps its log file open for the life of the process that loaded
it, which the session's fd-leak check would rightly call a leak.  The
child prints one JSON object, {program: temp bytes | "error: ..."}.
"""

import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

from minio_tpu.erasure.coding import DEVICE_BATCH_BLOCKS, DEVICE_BATCH_SIZES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK = 1 << 20
B = DEVICE_BATCH_BLOCKS  # the steady-state batch

# What the chip runs (ISSUE 32): a dispatch of any number of blocks is
# carried at one of coding.DEVICE_BATCH_SIZES, so for every geometry a
# cell of the benchmark has, at each of those sizes, the encode program
# and the reconstruct programs of 1..m rows: what boot's
# device_self_test compiles, and nothing a request can reach besides.
SERVED = [(2, 2), (8, 4), (12, 4)]
# What a set writes once drives are away (ISSUE 35): sixteen drives at
# EC:4 with two gone raise a PUT's parity to 10+6, 104,858-byte shards
# (12.8 tiles) that no boot compiles.  The warm-up thread compiles the
# same list for it (coding._DeviceCodec.self_test); here the chip's
# compiler sees it first.  Its text is pinned nowhere: no cell that
# exists ran it before.
WARMED = [(10, 6)]


def _served(k, m, b):
    return [f"pallas_encode_{k}+{m}_B{b}"] + [
        f"pallas_reconstruct_{k}+{m}_r{r}_B{b}" for r in range(1, m + 1)]


PROGRAMS = [
    "pallas_encode_4+2_B32",
    "pallas_encode_words_8+4_B32",
    "gf_bitmatmul_8+4_B32",
] + [name for k, m in SERVED + WARMED for b in DEVICE_BATCH_SIZES
     for name in _served(k, m, b)]

# The lowered text of the byte entry at B = 32, as the parent of ISSUE 32
# lowered it (sha256, 16 hex digits; the Mosaic kernel's serialised body
# left out: it holds the checkout's path, and HEAD_SHA pins its source).
# The four 64 MiB cells of the benchmark run these programs: a change to
# how short batches are carried must leave them letter for letter.
TEXT_AT_32 = {
    "pallas_encode_4+2_B32": "cb45b7231160ec4a",
    "pallas_encode_2+2_B32": "8a198b5eecb263f2",
    "pallas_reconstruct_2+2_r1_B32": "d23263dca6c010a2",
    "pallas_reconstruct_2+2_r2_B32": "8a198b5eecb263f2",
    "pallas_encode_8+4_B32": "e835931734fbf03d",
    "pallas_reconstruct_8+4_r1_B32": "0060bae2fe6b1eba",
    "pallas_reconstruct_8+4_r2_B32": "f57ff20bb149f637",
    "pallas_reconstruct_8+4_r3_B32": "b2d7b4c7c33e1ebf",
    "pallas_reconstruct_8+4_r4_B32": "e835931734fbf03d",
    "pallas_encode_12+4_B32": "ca57d01216887b59",
    "pallas_reconstruct_12+4_r1_B32": "5eefedbbcfe9c0d5",
    "pallas_reconstruct_12+4_r2_B32": "8dd48636121ee919",
    "pallas_reconstruct_12+4_r3_B32": "132eae00e4ee9ceb",
    "pallas_reconstruct_12+4_r4_B32": "ca57d01216887b59",
}
# ops/rs_pallas.py down to the codec class: the kernel, its two jit
# entries and the lines they stand on, which the persistent compile
# cache's key follows (ROADMAP D12).  New code goes below.
HEAD_SHA = "f606886f1ae000d2"


def _masked(text: str) -> str:
    return hashlib.sha256(re.sub(
        r'backend_config = "[^"]*"', "", text).encode()).hexdigest()[:16]


def _head_sha() -> str:
    with open(os.path.join(REPO, "minio_tpu", "ops", "rs_pallas.py")) as f:
        head = f.read().split("\nclass PallasRSCodec", 1)[0]
    return hashlib.sha256(head.encode()).hexdigest()[:16]


def _compile_all() -> dict:
    """Child side: lower + compile each program for one v5e device."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from minio_tpu.ops import rs_pallas, rs_tpu

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    sharding = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def mat(r, k):
        return spec((r * 8, k * 8), jnp.int8)

    def shards(k, b=B):
        return spec((b, k, -(-BLOCK // k)), jnp.uint8)

    words = spec((B, 8, BLOCK // 8 // 4), jnp.int32)
    code = rs_pallas._coding_call_bytes
    programs = {
        "pallas_encode_4+2_B32": (code, (mat(2, 4), shards(4))),
        "pallas_encode_words_8+4_B32":
            (rs_pallas._coding_call, (mat(4, 8), words)),
        "gf_bitmatmul_8+4_B32":
            (rs_tpu.gf_bitmatmul, (mat(4, 8), shards(8))),
    }
    for k, m in SERVED + WARMED:
        for b in DEVICE_BATCH_SIZES:
            rows = [m] + list(range(1, m + 1))  # encode, then r1..rm
            programs.update({
                name: (code, (mat(r, k), shards(k, b)))
                for name, r in zip(_served(k, m, b), rows)})
    assert sorted(programs) == sorted(PROGRAMS)
    out = {"device_kind": topo.devices[0].device_kind, "text": {}}
    temps = {}  # encode and the m-row reconstruct are one program
    for name, (fn, args) in programs.items():
        try:
            lowered = fn.lower(*args)
            if name in TEXT_AT_32:
                out["text"][name] = _masked(lowered.as_text())
            key = (fn, tuple(a.shape for a in args))
            if key not in temps:
                temps[key] = lowered.compile().memory_analysis() \
                    .temp_size_in_bytes
            out[name] = temps[key]
        except Exception as e:  # reported per program; the parent fails it
            out[name] = f"error: {type(e).__name__}: {e}"[:2000]
    return out


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    # a guard against a hung compiler, not a speed limit: the child
    # compiles every program one after another on one core, 7.6 min
    # alone on a shared 8-vCPU box
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900)
    # no skip: a box whose libtpu cannot describe a v5e cannot vouch for
    # the kernels, and that must be seen
    assert proc.returncode == 0, (
        "AOT compile child failed (v5e topology unavailable?):\n"
        + proc.stderr[-3000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_topology_is_v5e(compiled):
    assert compiled["device_kind"] == "TPU v5 lite"


@pytest.mark.parametrize("name", PROGRAMS)
def test_compiles_for_v5e(compiled, name):
    temp = compiled[name]
    assert isinstance(temp, int), f"{name} did not compile: {temp}"
    print(f"{name}: temp_size_in_bytes={temp}")


@pytest.mark.parametrize("name", sorted(TEXT_AT_32))
def test_full_batch_lowers_to_the_parents_text(compiled, name):
    assert compiled["text"][name] == TEXT_AT_32[name]


def test_every_served_full_batch_has_its_text_pinned(compiled):
    assert sorted(compiled["text"]) == sorted(TEXT_AT_32)
    assert sorted(TEXT_AT_32) == sorted(
        ["pallas_encode_4+2_B32"]
        + [n for k, m in SERVED for n in _served(k, m, 32)])


def test_kernel_source_stands_where_it_stood():
    assert _head_sha() == HEAD_SHA, (
        "ops/rs_pallas.py changed above the codec class: every checkout "
        "recompiles (D12) and the 64 MiB cells run another program")


if __name__ == "__main__":
    print(json.dumps(_compile_all()))
