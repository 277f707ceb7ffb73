"""Pallas fused codec vs the golden-pinned numpy codec (interpret mode on CPU)."""

import numpy as np
import pytest

from minio_tpu.ops import gf256, rs_pallas

S = 8192  # minimum aligned shard size (4 * _TILE_WORDS)


def _rand(b, k, s, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=(b, k, s), dtype=np.uint8)


@pytest.mark.parametrize("k,m", [(2, 2), (4, 2), (8, 4)])
def test_pallas_encode_matches_numpy(k, m):
    shards = _rand(2, k, S)
    codec = rs_pallas.PallasRSCodec(k, m, interpret=True)
    got = np.asarray(codec.encode(shards))
    for b in range(2):
        np.testing.assert_array_equal(got[b], gf256.encode_np(shards[b], m))


def test_pallas_encode_words_matches_bytes():
    k, m = 4, 2
    shards = _rand(1, k, S, seed=3)
    codec = rs_pallas.PallasRSCodec(k, m, interpret=True)
    words = np.ascontiguousarray(shards).view(np.int32).reshape(1, k, S // 4)
    got_w = np.asarray(codec.encode_words(words)).view(np.uint8).reshape(1, m, S)
    got_b = np.asarray(codec.encode(shards))
    np.testing.assert_array_equal(got_w, got_b)


def test_pallas_reconstruct():
    k, m = 8, 4
    data = _rand(2, k, S, seed=5)
    codec = rs_pallas.PallasRSCodec(k, m, interpret=True)
    full = np.asarray(codec.encode_blocks(data))
    kill = (0, 3, 8, 11)
    avail = tuple(i for i in range(k + m) if i not in kill)
    src = full[:, list(avail[:k]), :]
    reb = np.asarray(codec.reconstruct(src, avail, kill))
    for j, idx in enumerate(kill):
        np.testing.assert_array_equal(reb[:, j], full[:, idx], err_msg=f"shard {idx}")


def test_pallas_does_not_interpret_unasked():
    """Without interpret=True the codec is the Mosaic kernel or nothing:
    on a box with no TPU it must raise, never quietly run interpreted
    under a device codec's name."""
    codec = rs_pallas.PallasRSCodec(4, 2)
    assert codec._interpret is False
    with pytest.raises(ValueError, match="interpret mode"):
        np.asarray(codec.encode(_rand(1, 4, S)))


def test_pallas_codes_a_shard_shorter_than_a_tile():
    """Any shard length reaches the kernel: the dispatch program widens
    the batch to whole tiles and cuts the made rows back."""
    codec = rs_pallas.PallasRSCodec(4, 2, interpret=True)
    shards = _rand(1, 4, 1000)
    got = np.asarray(codec.encode(shards))
    np.testing.assert_array_equal(got[0], gf256.encode_np(shards[0], 2))
    with pytest.raises(ValueError):  # the word entry still wants tiles
        codec.encode_words(np.zeros((1, 4, 250), np.int32))


# a full 1 MiB block's shard at the geometries a stock sixteen- or
# twelve-drive node writes: no multiple of the 8,192-byte tile, nor of 4
ODD = [(12, 4, 87382), (14, 2, 74899), (10, 2, 104858)]


@pytest.mark.parametrize("k,m,s", ODD)
def test_pallas_encode_at_odd_shard_lengths(k, m, s):
    assert s == -(-(1 << 20) // k) and s % 8192 and s % 4
    shards = _rand(2, k, s, seed=k)
    # the last real column next to the first added one must not mix
    shards[:, :, -1] = 0xFF
    codec = rs_pallas.PallasRSCodec(k, m, interpret=True)
    got = np.asarray(codec.encode(shards))
    assert got.shape == (2, m, s)
    for b in range(2):
        np.testing.assert_array_equal(got[b], gf256.encode_np(shards[b], m))


@pytest.mark.parametrize("k,m,s,r", [
    (k, m, s, r) for k, m, s in ODD for r in (1, 2, 4) if r <= m])
def test_pallas_reconstruct_at_odd_shard_lengths(k, m, s, r):
    data = _rand(2, k, s, seed=100 + k + r)
    full = np.concatenate(
        [data, np.stack([gf256.encode_np(d, m) for d in data])], axis=1)
    # lose the first data shard and, from r = 2 on, parity and more data
    kill = (0, k, 3, k + 2)[:r]
    avail = tuple(i for i in range(k + m) if i not in kill)[:k]
    codec = rs_pallas.PallasRSCodec(k, m, interpret=True)
    reb = np.asarray(codec.reconstruct(
        np.ascontiguousarray(full[:, list(avail)]), avail, kill))
    assert reb.shape == (2, r, s)
    for j, idx in enumerate(kill):
        np.testing.assert_array_equal(reb[:, j], full[:, idx],
                                      err_msg=f"shard {idx}")


def test_odd_shard_length_books_pad_bytes_and_no_seconds():
    from minio_tpu.erasure import stagestats

    k, m, s = 12, 4, 87382
    codec = rs_pallas.PallasRSCodec(k, m, interpret=True)
    before = stagestats.snapshot()["pad"]
    codec.encode(_rand(2, k, s))
    codec.encode(_rand(2, k, 8192))  # whole tiles: nothing to book
    after = stagestats.snapshot()["pad"]
    assert after["seconds"] == before["seconds"]
    assert after["bytes"] - before["bytes"] == 2 * (k * 90112 + m * s)
