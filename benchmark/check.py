"""What the timed path left on the drives, against the plain reference.

For a sample of the objects the window (or, for a read cell, the
preload the window read from) wrote, drawn from the seed: where each
shard must be (the reference's `hashOrder`), that all k+m drives hold
one, that every data shard is the body's split, every parity shard the
reference Reed-Solomon code of it (benchmark/reference/gf256.py), and
every frame's 32-byte prefix the reference HighwayHash-256 of the frame
(benchmark/reference/highwayhash.py).  Exact comparisons: each limit
is 0.  Nothing here imports the program or reads anything it computed
other than the files under test.
"""

from __future__ import annotations

import glob
import os
import zlib

import numpy as np

from benchmark.loadgen import BUCKET, body_of
from benchmark.reference import gf256, highwayhash

HASH = 32


def hash_order(key: str, n: int) -> list[int]:
    """1-based shard index held by drive 1..n (cmd/erasure-metadata-
    utils.go hashOrder: a rotation by the key's CRC-32)."""
    start = (zlib.crc32(key.encode()) & 0xFFFFFFFF) % n
    return [1 + ((start + i) % n) for i in range(1, n + 1)]


def expected_shards(body: bytes, k: int, m: int,
                    block: int) -> list[list[np.ndarray]]:
    """What each of the k+m shard files must hold without its hash
    prefixes: a list over shards of a list over blocks of uint8 rows
    (the whole blocks coded together, then the shorter tail block)."""
    data = np.frombuffer(body, dtype=np.uint8)
    out: list[list[np.ndarray]] = [[] for _ in range(k + m)]
    nfull, tail = divmod(len(body), block)
    if nfull:
        shard = -(-block // k)
        blocks = data[:nfull * block].reshape(nfull, block)
        if shard * k != block:
            blocks = np.pad(blocks, ((0, 0), (0, shard * k - block)))
        split = blocks.reshape(nfull, k, shard).transpose(1, 0, 2)
        flat = np.ascontiguousarray(split).reshape(k, nfull * shard)
        parity = gf256.encode(flat, m).reshape(m, nfull, shard)
        for i in range(k):
            out[i] += list(split[i])
        for j in range(m):
            out[k + j] += list(parity[j])
    if tail:
        shard = -(-tail // k)
        last = np.zeros(shard * k, dtype=np.uint8)
        last[:tail] = data[nfull * block:]
        split = last.reshape(k, shard)
        parity = gf256.encode(split, m)
        for i in range(k):
            out[i].append(split[i])
        for j in range(m):
            out[k + j].append(parity[j])
    return out


def check_object(drive_dirs: list[str], key: str, body: bytes, k: int,
                 m: int, block: int) -> dict[str, int]:
    """Counts for one object; all 0 when the drives hold what the
    reference says they must."""
    out = {"shards_missing": 0, "shard_mismatch": 0, "frame_hash_mismatch": 0}
    want = expected_shards(body, k, m, block)
    order = hash_order(key, k + m)
    rows, digests = [], []
    for drive, shard_index in zip(drive_dirs, order):
        parts = glob.glob(os.path.join(
            glob.escape(os.path.join(drive, BUCKET, key)), "*", "part.1"))
        if len(parts) != 1:
            out["shards_missing"] += 1
            continue
        with open(parts[0], "rb") as f:
            raw = np.frombuffer(f.read(), dtype=np.uint8)
        blocks = want[shard_index - 1]
        if raw.size != sum(HASH + b.size for b in blocks):
            out["shard_mismatch"] += 1
            continue
        at, same = 0, True
        for b in blocks:
            digests.append(raw[at:at + HASH])
            frame = raw[at + HASH:at + HASH + b.size]
            rows.append(frame)
            same = same and np.array_equal(frame, b)
            at += HASH + b.size
        out["shard_mismatch"] += not same
    # frames of one length hash together, vectorised over rows
    by_len: dict[int, list[int]] = {}
    for i, r in enumerate(rows):
        by_len.setdefault(r.size, []).append(i)
    for idx in by_len.values():
        got = highwayhash.hh256_rows(np.stack([rows[i] for i in idx]))
        for j, i in enumerate(idx):
            out["frame_hash_mismatch"] += not np.array_equal(
                got[j], digests[i])
    return out


def check_sample(drive_dirs: list[str], sizes: dict[str, int], seed: int,
                 k: int, m: int, block: int, inline_below: int,
                 sample: int) -> dict[str, int]:
    """`sample` objects drawn from the seed, the largest among them.
    Objects below the inline threshold hold no shard files (their
    shards ride inside xl.meta) and are not drawn."""
    out = {"objects_on_disk_compared": 0, "shards_missing": 0,
           "shard_mismatch": 0, "frame_hash_mismatch": 0}
    keys = sorted(key for key, n in sizes.items() if n >= inline_below)
    if not keys or sample < 1:
        return out
    rng = np.random.Generator(np.random.PCG64(
        np.random.SeedSequence([seed, 99])))
    largest = max(keys, key=lambda key: (sizes[key], key))
    rest = [key for key in keys if key != largest]
    drawn = [largest] + [rest[i] for i in rng.permutation(len(rest))
                         [:max(0, sample - 1)]]
    for key in drawn:
        res = check_object(drive_dirs, key, body_of(seed, key, sizes[key]),
                           k, m, block)
        out["objects_on_disk_compared"] += 1
        for name, n in res.items():
            out[name] += n
    return out
