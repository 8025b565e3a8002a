"""A forest group's score is the same whatever its chunk: the chunk map adds
up its own trees' leaves (``ops/sweep._forest_group_scores``), whole forests
at a time where the plan's chunk holds one, equal parts of one where it does
not — each forest filled up to whole parts with zero-weight trees — and
``ops/trees.balanced_chunk`` with the forest as its ``group`` cuts such
chunks for every tree count, a prime one too.  And what a chunk reads is
right: the leaves it selects at ``row_node`` (``ops/trees.read_leaves``) are
the estimator path's pointer walk over the training rows, at one channel and
at ten, and ``run_stats()`` counts them."""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import Evaluators
from transmogrifai_tpu.impl.classification.trees import OpRandomForestClassifier
from transmogrifai_tpu.impl.trees_common import tree_from_params
from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
from transmogrifai_tpu.ops import sweep, trees as Tr

FOLDS = 3


@pytest.mark.parametrize("n_forests, n_trees, chunk_max, want", [
    (18, 50, 381, 300),    # the trees cell's depth 12: 3 chunks of 6 forests
    (18, 50, 1028, 900),   # its depth 6: one chunk
    (18, 50, 79, 50),      # 10 classes, depth 12: a forest a chunk
    (18, 50, 263, 250),    # 10 classes, depth 6: 4 chunks of 5 (two padded)
    (18, 50, 49, 25),      # less than a forest: its even parts
    (18, 50, 9, 9),        # six parts of 9: the forest filled up to 54 trees
    (18, 53, 10, 9),       # a prime forest: six parts of 9, not 53 of one
    (18, 101, 10, 10),     # eleven parts of 10 (110 trees)
    (18, 50, 1, 1), (18, 7, 6, 4), (1, 20, 1000, 20)])
def test_chunks_never_straddle_a_forest(n_forests, n_trees, chunk_max, want):
    chunk = Tr.balanced_chunk(n_forests * n_trees, chunk_max, group=n_trees)
    assert chunk == want and chunk <= max(chunk_max, 1)
    assert chunk % n_trees == 0 or chunk < n_trees
    # no group: the cut there always was
    assert Tr.balanced_chunk(900, 381) == 300
    assert Tr.balanced_chunk(900, 79) == 75


@pytest.fixture(scope="module", params=[2, 4], ids=["one-channel", "four-classes"])
def plan(request):
    return _plan(request.param, 10)


def _plan(k, n_trees, spread=1.0):
    rng = np.random.default_rng(33)
    n, d = 300, 12
    X = np.round(rng.normal(size=(n, d)), 2).astype(np.float32)
    y = np.clip(np.round(spread * (X[:, 0] + X[:, 1]) + k / 2 - 0.5),
                0, k - 1).astype(np.float32)
    fold = rng.permutation(n) % FOLDS
    train_w = np.stack([fold != f for f in range(FOLDS)]).astype(np.float32)
    grid = [{"max_depth": 3, "min_instances_per_node": m} for m in (1, 10)]
    ev = (Evaluators.BinaryClassification.auPR() if k == 2
          else Evaluators.MultiClassification.error())
    p = build_sweep_plan([(OpRandomForestClassifier(num_trees=n_trees), grid)],
                         X, y, train_w, ev)
    assert p is not None
    (frag,) = p.spec[1]
    assert frag[0] == "forest" and frag[1] == (1 if k == 2 else k)
    return p, train_w, frag


def _scores(plan, train_w, frag, chunk):
    (group,) = frag[2]
    group = group[:11] + (chunk,) + group[12:]
    return np.asarray(sweep._forest_group_scores(
        group, tuple(plan.xbs), plan.y, train_w, plan.blob, frag[1]))


@pytest.mark.parametrize("chunk", [20, 5, 4, 3, 1])
def test_group_scores_do_not_depend_on_the_chunk(plan, chunk):
    """60 trees (3 folds x 2 candidates x 10): two forests a chunk, half a
    forest, parts that do not divide it (4 and 3: each forest filled up to 12
    trees), single trees."""
    p, train_w, frag = plan
    whole = _scores(p, train_w, frag, FOLDS * 2 * 10)
    assert whole.shape == (FOLDS, 2, 300, frag[1])
    np.testing.assert_allclose(_scores(p, train_w, frag, chunk), whole,
                               rtol=0, atol=2e-7)


@pytest.mark.parametrize("chunk", [16, 3])
def test_a_prime_forest_is_the_mean_of_its_own_trees(chunk):
    """7 trees a forest, 4 classes: two forests a chunk (16: 14 trees of it)
    and parts of 3 (each forest filled up to 9) read what single trees read,
    and that is the mean of 7 trees' class distributions: rows sum to 1."""
    p, train_w, frag = _plan(4, 7)
    (group,) = frag[2]
    assert group[11] == 42          # the plan's own cut: all six forests
    single = _scores(p, train_w, frag, 1)
    np.testing.assert_allclose(single.sum(axis=-1), 1.0, rtol=0, atol=1e-5)
    np.testing.assert_allclose(_scores(p, train_w, frag, chunk), single,
                               rtol=0, atol=2e-7)


@pytest.mark.parametrize("k", [2, 10], ids=["one-channel", "ten-classes"])
def test_group_scores_are_the_estimators_walk_and_the_reads_are_counted(k):
    """A fold's candidate fitted alone (``fit_arrays``) and walked over the
    training rows (``predict_forest``) against the fused group's [F, Gc, n, c]
    block: the same draws, the same trees, the leaves read by selection where
    the walk gathers them; and a launch of the plan counts trees x rows x
    channels leaf reads."""
    p, train_w, frag = _plan(k, 10, spread=k / 4)
    c = frag[1]
    assert len(np.unique(p.y)) == k
    (group,) = frag[2]
    fused = _scores(p, train_w, frag, group[11])
    assert fused.shape == (FOLDS, 2, 300, c)
    X, y = np.asarray(p.X), np.asarray(p.y)
    for f in range(FOLDS):
        for ci, mcw in enumerate((1, 10)):
            cand = OpRandomForestClassifier(
                num_trees=10, max_depth=3, min_instances_per_node=mcw)
            params = cand.fit_arrays(X, y, w=train_w[f])
            walk = np.asarray(Tr.predict_forest(
                Tr.bin_with_edges(X, params["edges"]), tree_from_params(params), 3))
            # a binary forest's one channel is p(1): the estimator stores [1-p, p]
            np.testing.assert_allclose(fused[f, ci], walk[:, -c:], rtol=0, atol=1e-6)
    sweep.reset_run_stats()
    sweep.run_sweep(p.spec, p.X, tuple(p.xbs), p.y, train_w, 1.0 - train_w, p.blob)
    assert sweep.run_stats()["tree_leaf_reads"] == FOLDS * 2 * 10 * 300 * c
