"""The compacted grower's leaf-id replay kernel (ops/leafid_pallas.py).

On the CPU the kernel runs under the Pallas interpreter, against the
per-split XLA update it replaces on a TPU (``grower_unified
._split_leaf_ids``, applied once a split in order) and a NumPy replay,
element for element.  A whole tree grown with the kernel against one
grown with the per-split update is ``tests/test_leafcompact.py``'s; the
chip's own compile of it inside a tree's program is
``tests/test_tpu_compile_wide.py``'s.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.io.binning import PackSpec
from lightgbm_tpu.models import grower_unified
from lightgbm_tpu.ops import leafid_pallas

L = 255


def _records(bins, splits, seed, thresholds):
    """A tree's split records and its NumPy leaf ids: node n splits a
    leaf that holds rows (leaf 0 to n), on a random column, at a
    threshold drawn from ``thresholds``; records past ``splits`` are
    valid-looking splits that must not be applied."""
    rng = np.random.RandomState(seed)
    F, N = bins.shape
    ids = np.zeros(N, np.int32)
    leaf = np.zeros(L - 1, np.int32)
    row = rng.randint(0, F, L - 1).astype(np.int32)
    row[0] = F - 1
    thr = rng.choice(thresholds, L - 1).astype(np.int32)
    for n in range(L - 1):
        held = np.unique(ids)
        leaf[n] = rng.choice(held) if n < splits else rng.randint(0, n + 1)
        if n < splits:
            go = (ids == leaf[n]) & (bins[row[n]].astype(np.int32) > thr[n])
            ids[go] = n + 1
    return leaf, row, thr, ids


def _xla(bins, splits, leaf, row, thr):
    ids = jnp.zeros((bins.shape[1],), jnp.int32)
    for n in range(splits):
        ids = grower_unified._split_leaf_ids(ids, bins, row[n], leaf[n],
                                             thr[n], n + 1)
    return np.asarray(ids)


@pytest.fixture
def small_chunks(monkeypatch):
    """1,024 rows a chunk: a test's few thousand rows make several, the
    last one ragged."""
    monkeypatch.setattr(leafid_pallas, "MAX_ROWS", 1024)


@pytest.mark.parametrize("by_view", [True, False])
@pytest.mark.parametrize("F,N,splits,thresholds,packed", [
    # 220 columns (not whole 32-row tiles of the 8-bit table, a block of
    # 224), 2,500 rows: two whole chunks and a tail of 452, no whole
    # number of 128
    (220, 2500, L - 1, "spread", False),
    (28, 3000, L - 1, "spread", False),
    # a tree that stopped early: the records past its splits stay put
    (220, 2500, 40, "spread", False),
    # the split's column through a packing's canonical -> stored map
    (28, 2500, L - 1, "spread", True),
    # the thresholds at both ends of the codes
    (220, 2500, L - 1, "ends", False),
])
def test_kernel_equals_per_split_update(monkeypatch, small_chunks, F, N,
                                        splits, thresholds, packed, by_view):
    """Both ways of reading a split's words (``WORDS_BY_VIEW``: the view of
    the block's ref, or the bitcast of the loaded rows that jax 0.9's TPU
    interpreter can run) against the per-split update and NumPy."""
    monkeypatch.setattr(leafid_pallas, "WORDS_BY_VIEW", by_view)
    fp, rows, chunks = leafid_pallas.replay_grid(F, N)
    assert (rows, chunks) == (1024, -(-N // 1024))
    assert fp == F + (-F) % 8
    rng = np.random.RandomState(F + N + splits)
    bins = rng.randint(0, 256, (F, N)).astype(np.uint8)
    assert (bins >= 128).any()
    thr_set = {"spread": np.concatenate(([0, 254], rng.randint(0, 255, 30))),
               "ends": np.array([0, 254])}[thresholds]
    leaf, feature, thr, want = _records(bins, splits, seed=F + splits,
                                        thresholds=thr_set)
    if packed:
        # the records hold canonical columns; the table stores them in the
        # packing's order, so the replay reads row c2p[feature]
        perm = tuple(np.random.RandomState(3).permutation(F).tolist())
        packing = PackSpec(widths=(64, 255), counts=(10, F - 10), perm=perm)
        stored = np.empty_like(bins)
        stored[np.asarray(packing.c2p)] = bins
        row = np.asarray(grower_unified.partition_feature(
            packing, jnp.asarray(feature)))
        assert not np.array_equal(row, feature)
        bins = stored
    else:
        row = feature
    args = [jnp.asarray(a) for a in (leaf, row, thr)]
    xla = _xla(jnp.asarray(bins), splits, *args)
    got = np.asarray(leafid_pallas.replay_leaf_ids(
        jnp.asarray(bins), jnp.asarray(splits + 1), *args, interpret=True))
    assert got.dtype == np.int32 and got.shape == (N,)
    np.testing.assert_array_equal(xla, want)
    np.testing.assert_array_equal(got, want)
    # the case says something: rows went right at many splits, at both
    # ends of the codes where those are the thresholds
    right = np.unique(got[got > 0])
    if thresholds == "ends":
        assert {0, 254} <= set(thr[right - 1])
    else:
        assert len(right) > min(splits, 30) - 1


def test_grid_follows_the_table():
    grid = leafid_pallas.replay_grid
    # the ranking cell's table: 16,384 rows a chunk of 224 columns
    assert grid(220, 7_325_625) == (224, 16384, 448)
    # the wide table: the block inside BLOCK_BYTES
    assert grid(2000, 400_000) == (2000, 3072, 131)
    assert 2 * 2000 * 3072 < 16 << 20
    # a small table: one chunk of its rows in whole 1,024s
    assert grid(13, 1000) == (16, 1024, 1)
