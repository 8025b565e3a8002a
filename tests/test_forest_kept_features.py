"""A forest grown on its kept features alone (``ops/trees.grow_forest`` handed
``kept_features``' index table: the compacted layout, a tree-batched GEMM k
wide) against the same forest grown full width under the draw's masks (the
flat GEMM, d wide): the same trees, node for node, with original feature
indices in every record.  A forest's histograms are sums of integers (0/-1
gradients, unit hessians, Poisson weights), so the leaves are compared bit for
bit.  Then the sizes that follow from the kept width (``forest_chunk_size``,
``hist_blocks``) and the sweep's ``tree_kept_levels`` counter."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import trees as Tr

N, D, BINS, T, FRAC = 421, 12, 16, 4, 0.4


def _table(classes: int):
    rng = np.random.default_rng(11)
    X = rng.normal(size=(N, D)).astype(np.float32)
    z = X[:, 0] - X[:, 1] + 0.6 * X[:, 4] * (X[:, 7] > 0) + 0.5 * rng.normal(size=N)
    y = (z > 0).astype(np.int32) + (z > 1).astype(np.int32) * (classes > 2)
    Xb, _ = Tr.quantize(X, BINS)
    g = -y[:, None].astype(np.float32) if classes == 2 \
        else -np.eye(classes, dtype=np.float32)[y]
    return jnp.asarray(Xb), jnp.asarray(g)


def _draws():
    kb, kf = Tr.rng_keys(11)
    return Tr.bootstrap_weights(kb, N, T), Tr.feature_masks(kf, D, T, FRAC), \
        Tr.kept_features(kf, D, T, FRAC)


def _grow(Xb, g, w, feat, depth: int, frontier: int):
    # a new jit a call: traced again, so TMOG_HIST_SUBTRACT and a patched
    # ``hist_blocks`` apply, and compiled whole (eager growth dispatches, and
    # compiles, every op of every level by itself)
    tree, node = jax.jit(lambda xb, gg, ww, ft: Tr.grow_forest(
        xb, gg, jnp.ones(N), ww, ft, depth, BINS, frontier,
        reg_lambda_t=jnp.full(T, 1e-6), gamma_t=jnp.zeros(T),
        mcw_t=jnp.full(T, 4.0), mig_t=jnp.full(T, 1e-3),
        return_row_node=True))(Xb, g, w, feat)
    return jax.tree.map(np.asarray, tree), np.asarray(node)


@pytest.mark.parametrize("subtract", ["0", "1"])
@pytest.mark.parametrize("blocks", ["one_block", "four_blocks"])
@pytest.mark.parametrize("classes", [2, 3])
@pytest.mark.parametrize("depth,frontier", [(3, 8), (6, 64), (6, 8)],
                         ids=["depth3", "depth6", "beam"])
def test_compacted_growth_equals_masked_full_width(monkeypatch, depth, frontier,
                                                   classes, blocks, subtract):
    monkeypatch.setenv("TMOG_HIST_SUBTRACT", subtract)
    Xb, g = _table(classes)
    w, masks, kept = _draws()
    assert kept.shape == (T, 5)
    want, want_node = _grow(Xb, g, w, masks, depth, frontier)
    if blocks == "four_blocks":  # 512 > 421: the last block is mostly padding
        monkeypatch.setattr(Tr, "hist_blocks", lambda n, lhs, rhs: (4, 128))
    got, got_node = _grow(Xb, g, w, kept, depth, frontier)
    assert (want.split_feat >= 0).sum() > 2 * T  # real trees were grown
    for field in ("split_feat", "split_bin", "left", "right", "leaf_val"):
        assert np.array_equal(getattr(want, field), getattr(got, field)), field
    assert np.array_equal(want_node, got_node)
    # the records hold original indices, and only those the tree kept
    kept = np.asarray(kept)
    for t in range(T):
        used = got.split_feat[t][got.split_feat[t] >= 0]
        assert set(used) <= set(kept[t])


def test_tie_of_two_identical_kept_columns_goes_to_the_lower_index():
    Xb, g = _table(2)
    Xb = Xb.at[:, 9].set(Xb[:, 0])      # column 9 is column 0 again
    w, _, _ = _draws()
    kept = jnp.asarray([[0, 3, 9], [0, 9, 11], [0, 1, 9], [0, 5, 9]], jnp.int32)
    tree, _ = _grow(Xb, g, w, kept, 4, 16)
    assert (tree.split_feat[:, 0] == 0).all()       # the strongest column
    assert (tree.split_feat == 0).sum() > T and not (tree.split_feat == 9).any()


def test_the_draw_keeps_exactly_k_and_the_lower_index_of_a_tie(monkeypatch):
    """Two uniform draws that tie at the k-th place: the mask keeps k, not
    k + 1, and of the tied pair the lower feature."""
    r = jnp.asarray([[0.5, 0.2, 0.7, 0.2, 0.9, 0.1]])
    monkeypatch.setattr(jax.random, "uniform", lambda key, shape: r)
    key = jax.random.PRNGKey(0)
    assert np.asarray(Tr.feature_masks(key, 6, 1, 2 / 6)).tolist() \
        == [[0, 1, 0, 0, 0, 1]]
    assert np.asarray(Tr.kept_features(key, 6, 1, 2 / 6)).tolist() == [[1, 5]]


@pytest.mark.parametrize("seed,d,trees", [(42, 760, 50), (7, 20, 300)])
def test_index_table_is_the_plain_draw_ascending(seed, d, trees):
    """``kept_features`` of the program's draw against the rule written out
    (the k smallest of one uniform per feature): the set a plain reference
    reads from the same key, in ascending order."""
    frac = np.sqrt(d) / d
    k = Tr.n_kept(d, frac)
    _, kf = Tr.rng_keys(seed)
    kept = np.asarray(Tr.kept_features(kf, d, trees, frac))
    r = np.asarray(jax.random.uniform(kf, (trees, d)))
    plain = r <= np.sort(r, axis=1)[:, k - 1:k]
    assert kept.shape == (trees, k) and (plain.sum(axis=1) == k).all()
    for t in range(trees):
        assert np.array_equal(kept[t], np.flatnonzero(plain[t]))


def _gathers_of_the_binned_matrix(width: int) -> int:
    """Gathers in the lowered growth that read the binned matrix (either way
    up), when every tree is handed ``width`` feature indices."""
    Xb, g = _table(2)
    w, _, _ = _draws()
    feat = jnp.tile(jnp.arange(width, dtype=jnp.int32), (T, 1))
    ones = jnp.ones(T)
    closed = jax.make_jaxpr(lambda xb, idx: Tr.grow_forest(
        xb, g, jnp.ones(N), w, idx, 3, BINS, 8, reg_lambda_t=ones,
        gamma_t=ones, mcw_t=ones, mig_t=ones))(Xb, feat)

    def count(jaxpr) -> int:
        here = sum(e.primitive.name == "gather"
                   and sorted(e.invars[0].aval.shape) == sorted(Xb.shape)
                   for e in jaxpr.eqns)
        return here + sum(count(sub) for e in jaxpr.eqns
                          for sub in jax.core.jaxprs_in_params(e.params))

    return count(closed.jaxpr)


def test_every_feature_kept_lowers_with_no_gather():
    """k == d is the full-width program, on the CPU too: nothing gathers from
    the binned matrix.  k < d gathers each tree's columns once, outside the
    levels."""
    assert _gathers_of_the_binned_matrix(D) == 0
    assert _gathers_of_the_binned_matrix(5) == 1


def test_chunks_are_sized_from_the_kept_width():
    """The trees cell's depth-12 forests (frontier 256, 32 bins, 32,768 x 760,
    28 kept): 17 trees a chunk full width, >= 300 on the kept features."""
    shape = dict(max_depth=12, n_bins=32, d=760, c=1, frontier=256, n_rows=32768)
    assert Tr.forest_chunk_size(**shape) == 17
    assert Tr.forest_chunk_size(**shape, n_kept=760) == 17
    chunk = Tr.forest_chunk_size(**shape, n_kept=28)
    assert 300 <= chunk < 900
    assert Tr.balanced_chunk(900, chunk) == 300       # 3 chunks, not 53
    assert Tr.forest_chunk_size(**dict(shape, max_depth=6, frontier=64),
                                n_kept=28) >= 900     # depth 6: one chunk


def test_row_blocks_of_the_tree_batched_gemm_fit_their_budget(monkeypatch):
    """``grow_forest`` hands ``hist_blocks`` operand sizes that hold the tree
    axis on both sides, T * m * c1 and T * k * B; at the cell's sizes a
    block's operands stay within a quarter of the chunk budget."""
    seen = []
    whole = Tr.hist_blocks
    monkeypatch.setattr(Tr, "hist_blocks",
                        lambda n, lhs, rhs: seen.append((n, lhs, rhs)) or whole(n, lhs, rhs))
    Xb, g = _table(2)
    w, _, kept = _draws()
    _grow(Xb, g, w, kept, 6, 8)        # light half of 8 slots, 2 channels
    assert seen == [(N, T * 4 * 2, T * 5 * BINS)]
    for trees, m in ((300, 128), (900, 16), (900, 2)):
        lhs, rhs = trees * m * 2, trees * 28 * 32
        nb, bn = whole(32768, lhs, rhs)
        assert nb > 1 and bn % 128 == 0 and (nb - 1) * bn < 32768 <= nb * bn
        assert 4 * bn * (lhs + rhs) <= Tr._CHUNK_BUDGET_BYTES / 4


# ---------------------------------------------------------------------------
# the sweep's counter
# ---------------------------------------------------------------------------
def _plan(space, n: int = 240, d: int = 16):
    from transmogrifai_tpu.evaluators.classification import (
        OpBinaryClassificationEvaluator)
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan

    rng = np.random.default_rng(5)
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] + X[:, 3] + 0.5 * rng.normal(size=n) > 0).astype(np.float32)
    fold = rng.permutation(n) % 3
    train_w = np.stack([fold != f for f in range(3)]).astype(np.float32)
    plan = build_sweep_plan(space, X, y, train_w,
                            OpBinaryClassificationEvaluator())
    assert plan is not None
    return plan, train_w


def test_default_grid_counts_its_forest_levels_as_kept():
    """LR 8 + RF 18 + XGB 2 at 3 folds: 900 forest trees of each of depth 3,
    6, 12 on their kept features, boosting's 12,000 levels full width."""
    from transmogrifai_tpu.impl.selector.defaults import default_binary_space
    from transmogrifai_tpu.ops import sweep

    plan, _ = _plan(default_binary_space())
    levels = sweep._spec_tree_levels(plan.spec, 3)
    assert levels["tree_level_builds"] == 30_900
    assert levels["tree_kept_levels"] == 900 * (3 + 6 + 12) == 18_900
    # the leaves its 2,700 forest and 1,200 boosted trees hand the trees
    # cell's 32,768 rows, one channel each (PERF.md, PR 34)
    assert sweep._spec_tree_levels(plan.spec, 3, 32768)["tree_leaf_reads"] \
        == 88_473_600 + 39_321_600


@pytest.mark.parametrize("strategy,kept", [("auto", 3 * 2 * 3 * (2 + 3)), ("all", 0)])
def test_run_stats_counts_kept_levels_of_a_launch(strategy, kept):
    """A launch through the fused sweep: two candidates of
    3 trees, depth 2 and 3, 3 folds — every level kept with sqrt(d) features
    a tree, none with all of them; the scores are finite either way."""
    from transmogrifai_tpu.impl.classification.trees import (
        OpRandomForestClassifier)
    from transmogrifai_tpu.ops import sweep

    rf = OpRandomForestClassifier(num_trees=3, feature_subset_strategy=strategy)
    grid = [{"max_depth": 2, "min_instances_per_node": 1},
            {"max_depth": 2, "min_instances_per_node": 5},
            {"max_depth": 3, "min_instances_per_node": 1},
            {"max_depth": 3, "min_instances_per_node": 5}]
    plan, train_w = _plan([(rf, grid)])
    sweep.reset_run_stats()
    out = np.asarray(sweep.run_sweep(plan.spec, plan.X, tuple(plan.xbs), plan.y,
                                     train_w, 1.0 - train_w, plan.blob))
    assert np.isfinite(out).all()
    stats = sweep.run_stats()
    assert stats["tree_level_builds"] == 3 * 2 * 3 * (2 + 3)
    assert stats["tree_kept_levels"] == kept
