"""Multi-device sharded erasure pipeline over a jax.sharding.Mesh.

The reference's scale-out story is goroutine fan-out per drive plus REST
RPC between nodes (SURVEY.md §2.4/§2.5).  The TPU-native equivalent maps
the two hot axes onto a device mesh:

- ``blocks`` axis — data parallelism over independent 1 MiB erasure
  blocks (the streaming pipeline's batch dimension; MinIO analogue:
  concurrent objects/parts).
- ``shards`` axis — tensor parallelism over the K data shards: each
  device holds K/n_shards source shards, computes a *partial* GF(2)
  popcount for every parity bit from its local columns of the coding
  matrix, and a ``psum`` over the shards axis completes the GF(2^8)
  dot product (mod-2 of the summed counts).  This is the collective
  replacement for MinIO's parallelWriter shard fan-out
  (cmd/erasure-encode.go:36): parity emerges from an ICI all-reduce
  instead of N goroutines.

Everything compiles under one jit with static shapes; the same code runs
on a virtual CPU mesh (tests) and a real TPU slice.
"""

from __future__ import annotations

import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

shard_map = jax.shard_map

from minio_tpu.ops import residency, rs_tpu


def make_mesh(n_devices: int | None = None, *, blocks: int | None = None):
    """Build a (blocks, shards) mesh over the first n_devices devices."""
    devs = jax.devices()[: n_devices or len(jax.devices())]
    n = len(devs)
    if blocks is None:
        blocks = 2 if n % 2 == 0 and n > 1 else 1
    shards = n // blocks
    if blocks * shards != n:
        raise ValueError(f"cannot factor {n} devices into ({blocks}, ...)")
    return Mesh(np.asarray(devs).reshape(blocks, shards), ("blocks", "shards"))


def _partial_counts(mat_local: jax.Array, shards_local: jax.Array) -> jax.Array:
    """Local contribution to parity-bit popcounts: (B, R8, S) int32."""
    bits = rs_tpu._unpack_bits(shards_local)  # (B, K8/d, S)
    counts = jax.lax.dot_general(
        mat_local, bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (R8, B, S)
    return jnp.moveaxis(counts, 1, 0)


def sharded_coding_fn(mesh: Mesh):
    """Jitted distributed GF(2^8) coding matmul over the mesh.

    f(mat_bits (R8, K8) int8, batch (B, K, S) uint8) -> (B, R, S) uint8
    with B sharded over ``blocks`` and K over ``shards``; each device
    computes partial parity-bit popcounts from its local shard columns
    and a psum over ``shards`` (mod 2) completes the GF(2) dot — the
    collective replacement for the reference's per-drive goroutine
    fan-out (cmd/erasure-encode.go:36).
    """
    def local(mat_cols, shards_local):
        counts = _partial_counts(mat_cols, shards_local)
        total = jax.lax.psum(counts, "shards")
        return rs_tpu._pack_bits(total & 1)

    shmapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, "shards"), P("blocks", "shards", None)),
        out_specs=P("blocks", None, None),
    )
    return jax.jit(shmapped)


def sharded_encode_fn(mesh: Mesh, k: int, m: int):
    """Return a jitted distributed encode: (B, K, S) uint8 -> (B, M, S)."""
    mat = jnp.asarray(rs_tpu.encode_bits_matrix(k, m))  # (M8, K8)
    return partial(sharded_coding_fn(mesh), mat)


# The set-major tick-batch ordering that makes the `blocks` axis
# sharding below a sharding BY ERASURE SET lives with its caller:
# erasure/batcher.py::set_major_order (jax-free, so the host-only path
# never imports this module mid-tick).


# Collective-launch serialization: two threads launching collective
# programs concurrently can interleave their per-device enqueues in
# different orders — device A runs thread 1's psum while device B runs
# thread 2's, and both wait forever on their missing partners (observed
# as a hard wedge on a 4-virtual-chip (2,2) mesh).  One
# launch at a time keeps every device's queue in program order.
# MODULE-level on purpose: codec instances are cached per (k, m)
# geometry, so a per-instance lock would still let an 8+4 and a 4+2
# launch race onto the same devices.  The ISSUE 11 request batcher
# sidesteps the hazard by construction (single tick thread); this lock
# keeps the PER-REQUEST mesh plane safe too.
_LAUNCH_MU = threading.Lock()


class MeshRSCodec:
    """Production multi-device codec with the host/Pallas codec surface.

    Selected by the streaming erasure engine via
    MINIO_TPU_ERASURE_BACKEND=mesh (coding.Erasure._device): (B, K, S)
    batches from the object layer's PutObject/heal paths are sharded over
    the (blocks, shards) device mesh, so encode parity and heal
    reconstruction emerge from ICI collectives instead of one chip.
    Requires K to divide over the ``shards`` axis; batches are padded up
    to the ``blocks`` axis size.
    """

    backend = "mesh"  # explicit dispatch-stats bucket (ADVICE r5)

    def __init__(self, k: int, m: int, mesh: Mesh | None = None):
        if mesh is None:
            mesh = make_mesh()
        self.k, self.m, self.mesh = k, m, mesh
        self.n_bl = mesh.shape["blocks"]
        self.n_sh = mesh.shape["shards"]
        if k % self.n_sh != 0:
            raise ValueError(
                f"k={k} does not divide over the {self.n_sh}-way shards axis"
            )
        self._fn = sharded_coding_fn(mesh)
        # matrices live in the shared signature-keyed residency
        # (ops/residency.py): re-instantiating a codec or reaching the
        # same signature from a different call path (encode vs heal vs
        # repair) never re-transfers a matrix to the devices, and the
        # combinatorial churn of degraded-read signatures stays
        # LRU-bounded (VERDICT r5 weak #5) with hit/miss counters
        self._enc = residency.matrices.get(
            ("mesh-enc", k, m),
            lambda: jnp.asarray(rs_tpu.encode_bits_matrix(k, m)))
        self.dispatches = 0  # observability: mesh dispatch count
        from jax.sharding import NamedSharding

        self._in_sharding = NamedSharding(mesh, P("blocks", "shards", None))

    def _run(self, mat: jax.Array, batch) -> jax.Array:
        batch = np.asarray(batch, dtype=np.uint8)
        b = batch.shape[0]
        pad = (-b) % self.n_bl
        if pad:
            batch = np.concatenate(
                [batch, np.zeros((pad,) + batch.shape[1:], np.uint8)]
            )
        with _LAUNCH_MU:
            # see _LAUNCH_MU: concurrent collective launches can
            # cross-interleave per-device queues and deadlock
            dev = jax.device_put(batch, self._in_sharding)
            out = self._fn(mat, dev)
            self.dispatches += 1
        return out[:b] if pad else out

    def encode(self, data_shards) -> jax.Array:
        """(B, K, S) uint8 -> (B, M, S) parity."""
        return self._run(self._enc, data_shards)

    def reconstruct(self, src_shards, available, wanted) -> jax.Array:
        """(B, K, S) surviving shards -> (B, len(wanted), S)."""
        sig = (tuple(available), tuple(wanted))
        mat = residency.matrices.get(
            ("mesh-rec", self.k, self.m) + sig,
            lambda: jnp.asarray(
                rs_tpu.reconstruct_bits_matrix(self.k, self.m, *sig)))
        return self._run(mat, src_shards)


def sharded_pipeline_step(mesh: Mesh, k: int, m: int, heal_wanted=(0,)):
    """Full distributed erasure 'training step' for dry-run validation.

    One step = encode all blocks (TP psum over shards axis) -> simulate a
    degraded read missing `heal_wanted` -> reconstruct them (second
    collective matmul) -> return max |rebuilt - original| per block so the
    step has a scalar 'loss' observable (0 when the pipeline is correct).
    """
    n = k + m
    coding = sharded_coding_fn(mesh)
    enc_mat = jnp.asarray(rs_tpu.encode_bits_matrix(k, m))
    # degraded read: reconstruct from the first k surviving shards
    avail = tuple(i for i in range(n) if i not in heal_wanted)[:k]
    rec_mat = jnp.asarray(
        rs_tpu.reconstruct_bits_matrix(k, m, avail, tuple(heal_wanted))
    )
    srcs = avail

    @jax.jit
    def step(data_shards):
        parity = coding(enc_mat, data_shards)  # (B, M, S)
        full = jnp.concatenate([data_shards, parity], axis=1)
        src = full[:, list(srcs), :]  # first-k surviving shards
        rebuilt = coding(rec_mat, src)  # (B, len(wanted), S)
        orig = full[:, list(heal_wanted), :]
        loss = jnp.max(
            jnp.abs(rebuilt.astype(jnp.int32) - orig.astype(jnp.int32))
        )
        return parity, rebuilt, loss

    return step


def reshard_blocks_to_shards(mesh: Mesh):
    """All-to-all layout transpose over ICI: block-sharded rows become
    shard-sharded columns.

    The storage analogue of sequence-parallel all-to-all (DeepSpeed-
    Ulysses style): after a distributed encode each device holds ALL
    shard columns of ITS blocks; the drive-write phase wants each device
    to hold ONE shard column of ALL blocks (so every device streams one
    complete per-drive shard file).  One `lax.all_to_all` over the
    blocks axis performs the exchange entirely on interconnect.

    In:  (B, N, S) laid out P("blocks", "shards", None)
         (per-device: a block-row slice of every shard column it owns)
    Out: (B, N, S) laid out P(None, ("shards", "blocks"), None)
         (per-device: ALL blocks of a narrower shard-column range — the
         complete per-drive streams).  Requires the per-device shard
         width N/ns to be divisible by the blocks axis size.
    """
    def local(x):  # x: (B/nb, N/ns, S)
        return jax.lax.all_to_all(
            x, "blocks", split_axis=1, concat_axis=0, tiled=True)

    return shard_map(
        local, mesh=mesh,
        in_specs=P("blocks", "shards", None),
        out_specs=P(None, ("shards", "blocks"), None),
    )


def ring_rotate_shards(mesh: Mesh, shift: int = 1):
    """Ring `ppermute` over the shards axis: every device hands its
    shard slice to its ring neighbor.

    The storage analogue of ring attention's neighbor exchange: when a
    device's drive drops out of a write set, shard responsibility
    rotates around the ICI ring instead of rerouting through a host.
    """
    ns = mesh.shape["shards"]
    perm = [(i, (i + shift) % ns) for i in range(ns)]

    def local(x):
        return jax.lax.ppermute(x, "shards", perm)

    return shard_map(
        local, mesh=mesh,
        in_specs=P("blocks", "shards", None),
        out_specs=P("blocks", "shards", None),
    )
