"""``ops/trees.read_leaves``: a grown tree's per-row leaf values by selection
(a one-hot contraction over a node's lane, a select-sum over its block of 128)
equal ``take_along_axis`` value for value, at every pool size the default
grids grow (depth 0, 3, 6, boosted 10, 12), channel count and tree count, on
a row count that is no multiple of the row block — and what it does with a
table that is not finite, which a selection cannot hide (0 * NaN)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.ops import trees as Tr

N = 1000


def gathered(leaf_val, row_node):
    """The read this replaced: [T, c, n]."""
    c = leaf_val.shape[2]
    return np.take_along_axis(np.asarray(leaf_val),
                              np.asarray(row_node)[:, :, None].repeat(c, 2),
                              axis=1).transpose(0, 2, 1)


def table(T, P, c, seed=0):
    rng = np.random.default_rng(seed)
    # leaves as a grower writes them: full float32 mantissas, both signs
    leaf_val = (rng.normal(size=(T, P, c)) * 10.0 ** rng.integers(
        -6, 4, size=(T, P, 1))).astype(np.float32)
    row_node = rng.integers(0, P, size=(T, N)).astype(np.int32)
    return leaf_val, row_node


@pytest.mark.parametrize("T", [1, 7])
@pytest.mark.parametrize("c", [1, 3, 10])
@pytest.mark.parametrize("P", [1, 15, 127, 1023, 1535])
def test_selection_equals_the_gather(P, c, T):
    leaf_val, row_node = table(T, P, c, seed=P + c + T)
    got = np.asarray(jax.jit(Tr.read_leaves)(leaf_val, row_node))
    assert got.shape == (T, c, N) and got.dtype == np.float32
    assert (got == gathered(leaf_val, row_node)).all()


def test_several_row_blocks_of_a_ragged_row_count(monkeypatch):
    """A budget that cuts 1,000 rows into blocks of 128: the last block is
    moved back to end at the last row and writes its shared rows again."""
    monkeypatch.setattr(Tr, "_CHUNK_BUDGET_BYTES", 4 * 4 * 7 * (128 + 120) * 128)
    leaf_val, row_node = table(7, 1535, 10)
    assert Tr.hist_blocks(N, 7 * 128, 7 * 120) == (8, 128)
    got = np.asarray(Tr.read_leaves(leaf_val, row_node))
    assert (got == gathered(leaf_val, row_node)).all()


def test_dead_slots_parked_rows_and_a_padding_tree():
    """A table as ``grow_forest`` leaves it: dead slots hold 0.0 (some -0.0),
    every row of one tree rests at the root, and a zero-weight padding tree
    has a pool of zeros."""
    leaf_val, row_node = table(4, 127, 3)
    leaf_val[:, 40:90] = 0.0
    leaf_val[0, 60:70] = -0.0
    row_node[1] = 0                       # a stump: all rows parked at node 0
    leaf_val[3] = 0.0                     # the padding tree
    got = np.asarray(Tr.read_leaves(leaf_val, row_node))
    assert (got == gathered(leaf_val, row_node)).all()
    assert (got[1] == leaf_val[1, 0][:, None]).all()
    assert (got[3] == 0.0).all()


def test_grown_trees_read_what_a_walk_predicts():
    """End to end on grown trees, a zero-weight one among them: the table
    ``grow_forest`` hands back is finite everywhere (reg_lambda 0: a tree
    without weight would read 0 / 0 at its root) and the selection at
    ``row_node`` is the pointer walk's prediction on the training rows."""
    rng = np.random.default_rng(3)
    n, d, T = 600, 6, 3
    Xb = rng.integers(0, 8, size=(n, d)).astype(np.int32)
    y = (Xb[:, 0] + Xb[:, 1] > 7).astype(np.float32)
    g = -jax.nn.one_hot(y.astype(np.int32), 2, dtype=jnp.float32)
    w = rng.poisson(1.0, size=(T, n)).astype(np.float32)
    w[2] = 0.0
    zeros = jnp.zeros(T, jnp.float32)
    tree, row_node = Tr.grow_forest(
        jnp.asarray(Xb), g, jnp.ones(n), jnp.asarray(w),
        jnp.ones((T, d), jnp.float32), 4, 8, 16, reg_lambda_t=zeros,
        gamma_t=zeros, mcw_t=jnp.ones(T), mig_t=zeros, return_row_node=True)
    assert np.isfinite(np.asarray(tree.leaf_val)).all()
    got = np.asarray(Tr.read_leaves(tree.leaf_val, row_node))
    walk = np.asarray(jax.vmap(lambda t: Tr.predict_tree(Xb, t, 4))(tree))
    assert (got == walk.transpose(0, 2, 1)).all()
    assert (got[2] == 0.0).all()


def test_a_nan_in_an_unread_slot_is_loud_not_hidden():
    """What the helper documents of a table that is not finite: the NaN is
    in no row's node, a gather would never see it, and the selection hands
    it to every row whose node shares its block of 128 and its channel —
    and to no other: a wrong finite number is never made of it."""
    leaf_val, row_node = table(2, 300, 3)
    row_node[row_node == 130] = 131
    leaf_val[0, 130, 1] = np.nan          # block 1, lane 2, channel 1, tree 0
    got = np.asarray(Tr.read_leaves(leaf_val, row_node))
    want = gathered(leaf_val, row_node)
    assert np.isfinite(want).all()
    poisoned = np.zeros(got.shape, bool)
    poisoned[0, 1] = (row_node[0] // 128) == 1
    assert poisoned.any() and np.isnan(got[poisoned]).all()
    assert (got[~poisoned] == want[~poisoned]).all()
