"""The level histogram's one formulation (``ops/trees._hist_gemm``: a one-hot
GEMM over row blocks, on every backend) against sums written out here with
``np.add.at``, and the forest / boosted fits that stand on it against the
plain reference of the trees cell (``benchmarks/references/tabular_trees.py``:
one tree and one level at a time, every histogram product exact), which
shares no code with the program.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.references import tabular_trees as ref  # noqa: E402
from transmogrifai_tpu.ops import trees as Tr  # noqa: E402

BINS = 16


def _fixture(seed=0, n=400, d=6):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(np.float32)
    Xb, _ = Tr.quantize(X, BINS)
    return Xb, y, rng


@pytest.mark.parametrize("layout", ["shared", "per_tree", "compacted"])
def test_block_gemm_equals_add_at_histogram(layout):
    """Three row blocks of 160 rows (80 of padding, in no slot), 3 trees, 4
    slots of which the histogram collects a tree's own 2, rows resting at -1:
    every (tree, slot, channel, feature, bin) sum against ``np.add.at``; the
    GEMM's sums come back [T, channel, bin, slot, feature]."""
    Xb, y, rng = _fixture()
    n, d = Xb.shape
    T, nb, bn, c1 = 3, 3, 160, 2
    w = rng.poisson(1.0, size=(T, n)).astype(np.float32)
    slot = rng.integers(-1, 4, size=(T, n)).astype(np.int32)
    hist_slot = np.asarray([[0, 1], [2, 3], [1, 3]], np.int32)
    kept = np.sort(rng.permuted(np.tile(np.arange(d), (T, 1)), axis=1)[:, :4],
                   axis=1)
    if layout == "shared":
        gh = np.stack([-y, np.ones(n, np.float32)], axis=-1)       # [n, c1]
        gh_t = np.broadcast_to(gh, (T, n, c1))
    else:  # every tree its own gradients, as a boosting step has them
        p = 1.0 / (1.0 + np.exp(-rng.normal(size=(T, n)))).astype(np.float32)
        gh_t = gh = np.stack([p - y, p * (1 - p)], axis=-1).astype(np.float32)
    cols = kept if layout == "compacted" else np.tile(np.arange(d), (T, 1))

    want = np.zeros((T, 2, c1, cols.shape[1], BINS), np.float64)
    for t in range(T):
        for s in range(2):
            rows = np.flatnonzero(slot[t] == hist_slot[t, s])
            for j, col in enumerate(cols[t]):
                for ch in range(c1):
                    np.add.at(want[t, s, ch, j], Xb[rows, col],
                              np.float64(w[t, rows] * gh_t[t, rows, ch]))

    def blocks(a, axis, fill=0):
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, nb * bn - n)
        a = np.pad(a, widths, constant_values=fill)
        a = a.reshape(a.shape[:axis] + (nb, bn) + a.shape[axis + 1:])
        return jnp.asarray(np.moveaxis(a, axis, 0))

    Xk = blocks(Xb.T[kept], 2) if layout == "compacted" \
        else blocks(Xb.astype(np.int32), 0)
    got = Tr._hist_gemm(Xk, blocks(gh, gh.ndim - 2), blocks(w, 1),
                        blocks(slot, 1, fill=-1), jnp.asarray(hist_slot),
                        BINS, per_tree=layout != "shared")
    assert got.shape == (T, c1, BINS, 2, cols.shape[1])
    got = np.asarray(got).transpose(0, 3, 1, 4, 2)
    assert np.abs(want).sum() > 100
    if layout == "shared":  # sums of small integers: exact
        assert np.array_equal(np.asarray(got), want.astype(np.float32))
    else:
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def _reference_rows(Xb, kept, g, h, w, depth, frontier, mcw, lam):
    """The reference tree's prediction for every training row."""
    p = {"n_bins": BINS, "max_depth": depth, "max_frontier": frontier,
         "reg_lambda": lam, "gamma": 0.0, "min_child_weight": mcw,
         "min_info_gain": 0.0}
    value, _, _ = ref.grow_tree(*ref.kept_onehot(np.asarray(Xb, np.int32),
                                                 kept, BINS),
                                jnp.asarray(g), jnp.asarray(h), jnp.asarray(w),
                                p, False)
    return np.asarray(value)


def test_forest_chunked_equals_the_plain_reference():
    """8 trees in 2 chunks on their kept features (3 of 6), depth 4: every
    tree's prediction for every training row, tree by tree."""
    Xb, y, rng = _fixture(seed=3)
    n, d = Xb.shape
    T = 8
    kb, kf = Tr.rng_keys(3)
    wt = np.asarray(Tr.bootstrap_weights(kb, n, T))
    kept = np.asarray(Tr.kept_features(kf, d, T, 0.5))
    forest = Tr.fit_forest_chunked(
        jnp.asarray(Xb), jnp.asarray(-y[:, None]), jnp.ones(n),
        jnp.asarray(wt), jnp.asarray(kept), jnp.full(T, 5.0),
        max_depth=4, n_bins=BINS, chunk=4, frontier=16)
    assert (np.asarray(forest.split_feat) >= 0).sum() > 3 * T
    got = np.asarray(jax.vmap(
        lambda t: Tr.predict_tree(jnp.asarray(Xb), t, 4))(forest))[:, :, 0]
    for t in range(T):
        want = _reference_rows(Xb, kept[t], -y, np.ones(n, np.float32), wt[t],
                               4, 16, 5.0, 1e-6)
        np.testing.assert_allclose(got[t], want, atol=1e-6)


def test_gbt_equals_the_plain_reference():
    """6 rounds of depth 3, logistic loss: the final margins."""
    Xb, y, rng = _fixture(seed=5)
    n, d = Xb.shape
    R, eta = 6, 0.3
    _, F = Tr.fit_gbt(jnp.asarray(Xb), jnp.asarray(y), jnp.ones(n),
                      jnp.ones((R, n)), jnp.ones((R, d)), loss="logistic",
                      n_rounds=R, max_depth=3, n_bins=BINS, frontier=8,
                      eta=eta)
    want = np.zeros(n, np.float32)
    for _ in range(R):
        p = 1.0 / (1.0 + np.exp(-want, dtype=np.float32))
        want = want + np.float32(eta) * _reference_rows(
            Xb, np.arange(d), p - y, np.maximum(p * (1 - p), 1e-6),
            np.ones(n, np.float32), 3, 8, 1.0, 1.0)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(F)[:, 0], want, atol=1e-5)
