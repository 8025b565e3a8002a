"""One tree grower (``ops/trees.grow_forest`` / ``_grow_level_batch``) on every
backend: a single tree is its chunk of one, the program a CPU lowers forms
its level sums by the one-hot GEMM and by nothing else (what
``tests/test_tpu_compile.py`` reads in the program lowered for a described
v5e), and an index table that keeps every feature is the full-width
program."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import trees as Tr

N, D, BINS, DEPTH, FRONTIER = 389, 10, 16, 5, 8     # levels 3-4 are a beam


def _table():
    rng = np.random.default_rng(31)
    X = rng.normal(size=(N, D)).astype(np.float32)
    y = (X[:, 0] - X[:, 3] * (X[:, 5] > 0) + 0.5 * rng.normal(size=N) > 0
         ).astype(np.float32)
    kb, _ = Tr.rng_keys(31)
    w = Tr.bootstrap_weights(kb, N, 1)[0]
    return jnp.asarray(Tr.quantize(X, BINS)[0]), jnp.asarray(y), w, rng


def _gradients(kind: str, y, rng):
    if kind == "forest":
        return -y[:, None], jnp.ones(N)
    p = jax.nn.sigmoid(jnp.asarray(rng.normal(size=N), jnp.float32))
    return (p - y)[:, None], jnp.maximum(p * (1 - p), 1e-6)


@pytest.mark.parametrize("feat", ["mask", "kept"])
@pytest.mark.parametrize("gradients", ["forest", "boosted"])
def test_grow_tree_is_grow_forest_of_one_tree(gradients, feat):
    """Bit for bit, nodes, leaves and each row's resting node, with a feature
    mask and with a kept-feature index table (k < d: the compacted layout)."""
    Xb, y, w, rng = _table()
    g, h = _gradients(gradients, y, rng)
    ft = jnp.asarray([1, 1, 0, 1, 0, 1, 1, 0, 1, 0], jnp.float32) \
        if feat == "mask" else jnp.asarray([0, 1, 3, 5, 6, 8], jnp.int32)
    hyper = dict(reg_lambda=1e-3, gamma=0.01, min_child_weight=3.0,
                 min_info_gain=1e-3)
    tree, node = jax.jit(lambda: Tr.grow_tree(
        Xb, g, h, w, ft, DEPTH, BINS, FRONTIER, return_row_node=True,
        **hyper))()
    forest, nodes = jax.jit(lambda: Tr.grow_forest(
        Xb, g, h, w[None], ft[None], DEPTH, BINS, FRONTIER,
        reg_lambda_t=jnp.full(1, 1e-3), gamma_t=jnp.full(1, 0.01),
        mcw_t=jnp.full(1, 3.0), mig_t=jnp.full(1, 1e-3),
        return_row_node=True))()
    assert tree.split_feat.shape == forest.split_feat.shape[1:]
    assert (np.asarray(tree.split_feat) >= 0).sum() > 6
    assert set(np.asarray(tree.split_feat).tolist()) <= {-1, 0, 1, 3, 5, 6, 8}
    for one, of_one in zip(tree + (node,), forest + (nodes,)):
        assert np.array_equal(np.asarray(one), np.asarray(of_one)[0])
    # without the row nodes it is the tree alone
    alone = jax.jit(lambda: Tr.grow_tree(Xb, g, h, w, ft, DEPTH, BINS,
                                         FRONTIER, **hyper))()
    assert np.array_equal(np.asarray(alone.leaf_val), np.asarray(tree.leaf_val))


def _eqns(jaxpr):
    """Every equation of a traced program, loop and scan bodies included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _row_ops(fn, *args):
    """Of the program ``fn`` traces on this backend: (GEMMs that contract
    over the N rows, scatters, gathers that read an operand with a row
    axis)."""
    gemms = scatters = gathers = 0
    for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        name = e.primitive.name
        shape = e.invars[0].aval.shape if e.invars else ()
        if name == "dot_general":
            (lhs_c, _), _ = e.params["dimension_numbers"]
            gemms += [shape[a] for a in lhs_c] == [N]
        elif name.startswith("scatter"):
            # the root's leaf value going into the pool [T, P, c] is the one
            # scatter there is; a segment_sum would be a scatter-add over rows
            scatters += not (name == "scatter" and N not in shape)
        elif name == "gather":
            gathers += N in shape
    return gemms, scatters, gathers


LEVEL_GEMMS = 3 + 1     # widths 1, 2, 4 unrolled + the loop body at 8 slots


@pytest.mark.parametrize("layout", ["shared", "per_tree", "compacted"])
def test_cpu_program_sums_by_the_level_gemm_alone(layout):
    """What a CPU traces is what ``tests/test_tpu_compile.py`` reads in the
    program lowered for a described v5e: no scatter over the rows, one GEMM
    over the rows a level, and of gathers only the compacted layout's one
    read of each tree's kept columns, outside the levels."""
    Xb, y, w, rng = _table()
    T = 3
    g, h = _gradients("boosted", y, rng)
    gh_t = jnp.broadcast_to(jnp.concatenate([g, h[:, None]], 1), (T, N, 2))
    ft = jnp.tile(jnp.asarray([0, 1, 3, 5, 6, 8], jnp.int32), (T, 1)) \
        if layout == "compacted" else jnp.ones((T, D), jnp.float32)
    ones = jnp.ones(T)

    def grow(xb, wt):
        shared = layout != "per_tree"
        return Tr.grow_forest(
            xb, g if shared else None, h if shared else None, wt, ft, DEPTH,
            BINS, FRONTIER, reg_lambda_t=ones, gamma_t=0 * ones, mcw_t=ones,
            mig_t=0 * ones, gh_t=None if shared else gh_t)

    assert jax.default_backend() == "cpu"
    assert _row_ops(grow, Xb, jnp.tile(w, (T, 1))) \
        == (LEVEL_GEMMS, 0, int(layout == "compacted"))


def test_index_table_of_every_feature_is_the_full_width_program():
    """k == d: the levels are d wide over the shared matrix, as under a mask
    of ones — the same trees bit for bit, and nothing gathered."""
    Xb, y, w, rng = _table()
    T = 3
    g, h = _gradients("forest", y, rng)
    wt = jnp.tile(w, (T, 1)) * jnp.arange(1, T + 1)[:, None]
    ones = jnp.ones(T)

    def grow(ft):
        return Tr.grow_forest(Xb, g, h, wt, ft, DEPTH, BINS, FRONTIER,
                              reg_lambda_t=1e-6 * ones, gamma_t=0 * ones,
                              mcw_t=4 * ones, mig_t=0 * ones)

    table = jnp.tile(jnp.arange(D, dtype=jnp.int32), (T, 1))
    mask = jnp.ones((T, D), jnp.float32)
    for a, b in zip(jax.jit(grow)(table), jax.jit(grow)(mask)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert _row_ops(grow, table) == _row_ops(grow, mask) == (LEVEL_GEMMS, 0, 0)
