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

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BLOCK = 1 << 20
B = 32  # coding.DEVICE_BATCH_BLOCKS: the steady-state batch

PROGRAMS = [
    "pallas_encode_8+4_B32",
    "pallas_encode_4+2_B32",
    "pallas_encode_words_8+4_B32",
    "pallas_reconstruct_8+4_r1_B32",
    "pallas_reconstruct_8+4_r3_B32",
    "pallas_reconstruct_8+4_r2_B32",
    "gf_bitmatmul_8+4_B32",
    # EC 2+2: 512 KiB shards; a degraded GET rebuilds one row, a heal two
    "pallas_encode_2+2_B32",
    "pallas_reconstruct_2+2_r1_B32",
    "pallas_reconstruct_2+2_r2_B32",
    # EC 12+4: K no power of two in the kernel's unpack, shards of
    # 87,382 bytes widened to whole tiles inside the program
    "pallas_encode_12+4_B32",
    "pallas_reconstruct_12+4_r1_B32",
    "pallas_reconstruct_12+4_r2_B32",
]


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

    def shards(k):
        return spec((B, k, -(-BLOCK // k)), jnp.uint8)

    words = spec((B, 8, BLOCK // 8 // 4), jnp.int32)
    programs = {
        "pallas_encode_8+4_B32":
            (rs_pallas._coding_call_bytes, (mat(4, 8), shards(8))),
        "pallas_encode_4+2_B32":
            (rs_pallas._coding_call_bytes, (mat(2, 4), shards(4))),
        "pallas_encode_words_8+4_B32":
            (rs_pallas._coding_call, (mat(4, 8), words)),
        "pallas_reconstruct_8+4_r1_B32":
            (rs_pallas._coding_call_bytes, (mat(1, 8), shards(8))),
        "pallas_reconstruct_8+4_r3_B32":
            (rs_pallas._coding_call_bytes, (mat(3, 8), shards(8))),
        "pallas_reconstruct_8+4_r2_B32":
            (rs_pallas._coding_call_bytes, (mat(2, 8), shards(8))),
        "gf_bitmatmul_8+4_B32":
            (rs_tpu.gf_bitmatmul, (mat(4, 8), shards(8))),
        "pallas_encode_2+2_B32":
            (rs_pallas._coding_call_bytes, (mat(2, 2), shards(2))),
        "pallas_reconstruct_2+2_r1_B32":
            (rs_pallas._coding_call_bytes, (mat(1, 2), shards(2))),
        "pallas_reconstruct_2+2_r2_B32":
            (rs_pallas._coding_call_bytes, (mat(2, 2), shards(2))),
        "pallas_encode_12+4_B32":
            (rs_pallas._coding_call_bytes, (mat(4, 12), shards(12))),
        "pallas_reconstruct_12+4_r1_B32":
            (rs_pallas._coding_call_bytes, (mat(1, 12), shards(12))),
        "pallas_reconstruct_12+4_r2_B32":
            (rs_pallas._coding_call_bytes, (mat(2, 12), shards(12))),
    }
    assert sorted(programs) == sorted(PROGRAMS)
    out = {"device_kind": topo.devices[0].device_kind}
    for name, (fn, args) in programs.items():
        try:
            compiled = fn.lower(*args).compile()
            out[name] = compiled.memory_analysis().temp_size_in_bytes
        except Exception as e:  # reported per program; the parent fails it
            out[name] = f"error: {type(e).__name__}: {e}"[:2000]
    return out


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=280)
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


if __name__ == "__main__":
    print(json.dumps(_compile_all()))
