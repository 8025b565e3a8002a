"""The level grower's bins-major level tensors (``ops/trees._grow_level_batch``:
[T, c+1, B, slots, d], left children first, running sums plane by plane,
the best split as best bin per feature then best feature) against a plain
level written here the way the formulas read: one tree at a time, sums by
``np.add.at`` on [slots, c, d, B], ``np.cumsum`` over the bins, one flat
arg-max over (feature, bin), siblings built directly (no subtraction), pool
and beam rules as ``ops/trees`` documents them.  Trees equal node for node.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import trees as Tr

N, DEPTH, FRONTIER = 300, 5, 8           # levels 3 and 4 are a gain-ranked beam
LAM, GAMMA, MCW, MIG = 1e-3, 0.01, 2.0, 1e-3


def plain_tree(Xb, g, h, w, cols, B):
    """(nodes i32[P, 4], leaf_val f64[P, c]) of one tree on columns ``cols``
    (original indices, ascending): the level rules of ``ops/trees`` in
    numpy, float64, histograms [m, c, k, B] with the bins minor."""
    n, c = g.shape
    M, L = FRONTIER, FRONTIER.bit_length() - 1
    P = Tr._pool_size(DEPTH, M)
    nodes = np.tile(np.asarray([-1, 0, 0, 0], np.int32), (P, 1))
    leaf = np.zeros((P, c))
    gw, hw = g * w[:, None], h * w
    leaf[0] = -gw.sum(0) / (hw.sum() + LAM)
    slot = np.zeros(n, np.int64)
    n_active = 1
    Xk = Xb[:, cols].astype(np.int64)
    for t in range(DEPTH):
        m = min(1 << t, M)
        next_cap = min(2 * m, M)
        base = (1 << t) - 1 if t < L else M - 1 + (t - L) * M
        free = (1 << (t + 1)) - 1 if t < L else base + M
        G = np.zeros((m, c, len(cols), B))
        H = np.zeros((m, len(cols), B))
        rows = np.flatnonzero(slot >= 0)
        for j in range(len(cols)):
            np.add.at(H, (slot[rows], j, Xk[rows, j]), hw[rows])
            for ch in range(c):
                np.add.at(G, (slot[rows], ch, j, Xk[rows, j]), gw[rows, ch])
        GT, HT = G[:, :, 0, :].sum(-1), H[:, 0, :].sum(-1)
        GL, HL = np.cumsum(G, -1), np.cumsum(H, -1)
        GR, HR = GT[:, :, None, None] - GL, HT[:, None, None] - HL
        gain = (GL ** 2).sum(1) / (HL + LAM) + (GR ** 2).sum(1) / (HR + LAM) \
            - ((GT ** 2).sum(1) / (HT + LAM))[:, None, None]
        ok = (HL >= MCW) & (HR >= MCW) & (np.arange(B) < B - 1)
        flat = np.where(ok, gain, -np.inf).reshape(m, -1)
        best = flat.argmax(1)                 # the first of equals
        best_gain = flat.max(1)
        bf, bb = best // B, best % B
        split = (best_gain > GAMMA) & (best_gain >= MIG * HT) \
            & (np.arange(m) < n_active)
        if next_cap < 2 * m:                  # the beam, ties to the lower slot
            order = np.argsort(np.where(split, -best_gain, np.inf),
                               kind="stable")
            rank = np.empty(m, np.int64)
            rank[order] = np.arange(m)
            split &= rank < next_cap // 2
        child = (np.cumsum(split) - 1) * 2
        for s in np.flatnonzero(split):
            GLb, HLb = GL[s, :, bf[s], bb[s]], HL[s, bf[s], bb[s]]
            nodes[base + s] = (cols[bf[s]], bb[s], free + child[s],
                               free + child[s] + 1)
            leaf[free + child[s]] = -GLb / (HLb + LAM)
            leaf[free + child[s] + 1] = -(GT[s] - GLb) / (HT[s] - HLb + LAM)
        live = slot >= 0
        s = np.maximum(slot, 0)
        moves = live & split[s]
        right = Xk[np.arange(n), bf[s]] > bb[s]
        slot = np.where(moves, child[s] + right, -1)
        n_active = 2 * int(split.sum())
    return nodes, leaf


def _table(seed, d, B, c):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(N, d)).astype(np.float32)
    score = X[:, 0] - X[:, 1] * (X[:, 2] > 0) + 0.5 * rng.normal(size=N)
    y = np.digitize(score, np.quantile(score, np.arange(1, c + 1) / (c + 1))
                    ) if c > 1 else (score > 0).astype(np.int64)
    Xb = np.asarray(Tr.quantize(X, B)[0])
    return Xb, y, rng


def _gradients(layout, y, c, T, rng):
    """(g [T, n, c], h [T, n]) as float32, per tree; a forest's are shared."""
    if layout != "boost":       # gini / variance trees: integers, h = 1
        g = -np.eye(c + 1, dtype=np.float32)[y][:, :c] if c > 1 \
            else -y[:, None].astype(np.float32)
        return np.broadcast_to(g, (T,) + g.shape), np.ones((T, N), np.float32)
    p = 1.0 / (1.0 + np.exp(-rng.normal(size=(T, N, c))))
    g = (p - (np.eye(c + 1)[y][:, :c] if c > 1 else y[:, None])
         ).astype(np.float32)
    return g, np.maximum(p * (1 - p), 1e-6).mean(-1).astype(np.float32)


def _program(Xb, g, h, w, feat_t, layout, B, subtract, monkeypatch):
    """``grow_forest`` traced anew (the subtraction flag is read at trace)."""
    monkeypatch.setenv("TMOG_HIST_SUBTRACT", subtract)
    T = w.shape[0]
    hyper = dict(reg_lambda_t=jnp.full(T, LAM), gamma_t=jnp.full(T, GAMMA),
                 mcw_t=jnp.full(T, MCW), mig_t=jnp.full(T, MIG))
    if layout == "boost":
        gh_t = jnp.asarray(np.concatenate([g, h[..., None]], -1))
        fn = lambda: Tr.grow_forest(jnp.asarray(Xb), None, None, jnp.asarray(w),
                                    jnp.asarray(feat_t), DEPTH, B, FRONTIER,
                                    gh_t=gh_t, **hyper)
    else:
        fn = lambda: Tr.grow_forest(jnp.asarray(Xb), jnp.asarray(g[0]),
                                    jnp.asarray(h[0]), jnp.asarray(w),
                                    jnp.asarray(feat_t), DEPTH, B, FRONTIER,
                                    **hyper)
    return jax.tree.map(np.asarray, jax.jit(fn)())


def _assert_trees_equal(tree, Xb, g, h, w, cols_t, B):
    for t, cols in enumerate(cols_t):
        nodes, leaf = plain_tree(Xb, g[t].astype(np.float64),
                                 h[t].astype(np.float64),
                                 w[t].astype(np.float64), np.asarray(cols), B)
        assert (nodes[:, 0] >= 0).sum() >= 4            # a real tree
        got = np.stack([tree.split_feat[t], tree.split_bin[t], tree.left[t],
                        tree.right[t]], axis=-1)
        assert np.array_equal(got, nodes), (t, np.flatnonzero(
            (got != nodes).any(-1)))
        # float32 sums (and parent - light, whose rounding does not shrink with
        # the child) against float64: a few 1e-6, of the value where it is large
        np.testing.assert_allclose(tree.leaf_val[t], leaf, atol=4e-6, rtol=2e-6)


@pytest.mark.parametrize("c", [1, 3])
@pytest.mark.parametrize("subtract", ["1", "0"])
@pytest.mark.parametrize("layout", ["mask", "kept", "boost"])
def test_level_equals_the_plain_level(monkeypatch, layout, subtract, c):
    """Shared-mask forest, compacted forest (k < d) and per-tree boosting
    gradients, with and without sibling subtraction, one channel and three:
    d * B = 128 here."""
    d, B, T = 8, 16, 3
    Xb, y, rng = _table(11 + c, d, B, c)
    g, h = _gradients(layout, y, c, T, rng)
    w = rng.poisson(1.0, size=(T, N)).astype(np.float32) if layout != "boost" \
        else (rng.random((T, N)) < 0.8).astype(np.float32)
    if layout == "kept":
        feat_t = np.sort(rng.permuted(np.tile(np.arange(d), (T, 1)),
                                      axis=1)[:, :5], axis=1).astype(np.int32)
        cols_t = list(feat_t)
    else:
        feat_t = np.ones((T, d), np.float32)
        feat_t[np.arange(T), rng.integers(0, d, T)] = 0.0     # one masked a tree
        cols_t = [np.flatnonzero(row) for row in feat_t]
    tree = _program(Xb, g, h, w, feat_t, layout, B, subtract, monkeypatch)
    _assert_trees_equal(tree, Xb, g, h, w, cols_t, B)


def test_fused_width_no_multiple_of_128(monkeypatch):
    """d * B = 7 * 12 = 84: no axis of the level is a lane multiple."""
    d, B, T = 7, 12, 2
    Xb, y, rng = _table(5, d, B, 1)
    g, h = _gradients("boost", y, 1, T, rng)
    w = np.ones((T, N), np.float32)
    tree = _program(Xb, g, h, w, np.ones((T, d), np.float32), "boost", B,
                    "1", monkeypatch)
    _assert_trees_equal(tree, Xb, g, h, w, [np.arange(d)] * T, B)


def test_identical_columns_tie_to_the_lower_feature(monkeypatch):
    """Column 4 is a copy of column 1: every gain of one is the other's, bit
    for bit, and no node may split on 4."""
    d, B, T = 8, 16, 2
    Xb, y, rng = _table(23, d, B, 1)
    Xb = Xb.copy()
    Xb[:, 4] = Xb[:, 1]
    Xb[:, 0] = Xb[:, 6]        # the strongest column, doubled as well
    g, h = _gradients("boost", y, 1, T, rng)
    w = np.ones((T, N), np.float32)
    tree = _program(Xb, g, h, w, np.ones((T, d), np.float32), "boost", B,
                    "1", monkeypatch)
    used = set(tree.split_feat[tree.split_feat >= 0].tolist())
    assert {0, 1} & used and not {4, 6} & used, used
    _assert_trees_equal(tree, Xb, g, h, w, [np.arange(d)] * T, B)


@pytest.mark.parametrize("subtract", ["1", "0"])
def test_empty_bins_tie_to_the_lower_bin(monkeypatch, subtract):
    """Every column uses bins 0, 3, 4, 9 and 13 of 16 alone, so a split's gain
    repeats over the empty bins above it (float gradients: running sums that
    are not plane-by-plane adds break these ties by their rounding).  The
    lower bin wins: every split bin is one of the five."""
    d, B, T = 6, 16, 2
    rng = np.random.default_rng(3)
    used_bins = np.asarray([0, 3, 4, 9, 13])
    raw = rng.integers(0, 5, size=(N, d))
    Xb = used_bins[raw].astype(np.int8)
    y = ((raw[:, 0] >= 2) ^ (raw[:, 1] >= 3) ^ (rng.random(N) < 0.1)
         ).astype(np.int64)
    g, h = _gradients("boost", y, 1, T, rng)
    w = np.ones((T, N), np.float32)
    tree = _program(Xb, g, h, w, np.ones((T, d), np.float32), "boost", B,
                    subtract, monkeypatch)
    bins = tree.split_bin[tree.split_feat >= 0]
    assert len(bins) >= 8 and set(bins.tolist()) <= set(used_bins.tolist())
    _assert_trees_equal(tree, Xb, g, h, w, [np.arange(d)] * T, B)
