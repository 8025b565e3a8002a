"""The row-blocked level-histogram build of ``ops/trees.grow_forest`` against
the whole one-hot GEMM (one block): the same trees, for a row count that is no
multiple of the block, in both gradient layouts (shared: forests; per tree:
boosting), with and without sibling subtraction.  The blocks only cut the
contraction; padding rows sit in no slot."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from transmogrifai_tpu.ops import trees as Tr

N, D, BINS, T, DEPTH, FRONTIER = 421, 6, 16, 5, 5, 8


def _data():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(N, D)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.5 * rng.normal(size=N) > 0).astype(np.float32)
    Xb, _ = Tr.quantize(X, BINS)
    kb, kf = Tr.rng_keys(7)
    w = Tr.bootstrap_weights(kb, N, T)
    fm = Tr.feature_masks(kf, D, T, 0.7)
    return jnp.asarray(Xb), jnp.asarray(y), w, fm, rng


def _grow(layout: str):
    Xb, y, w, fm, rng = _data()
    hyper = dict(reg_lambda_t=jnp.full(T, 1e-3), gamma_t=jnp.zeros(T),
                 mcw_t=jnp.full(T, 4.0), mig_t=jnp.zeros(T))
    if layout == "shared":
        g, h, gh_t = -y[:, None], jnp.ones(N), None
    else:  # every tree its own gradients, as a boosting step has them
        p = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(T, N)), jnp.float32))
        g = h = None
        gh_t = jnp.stack([p - y[None, :], jnp.maximum(p * (1 - p), 1e-6)], -1)
    # a new jit a call: traced again, so the flag and the patched
    # ``hist_blocks`` apply, and compiled whole
    tree, row_node = jax.jit(lambda: Tr.grow_forest(
        Xb, g, h, w, fm, DEPTH, BINS, FRONTIER, return_row_node=True,
        gh_t=gh_t, **hyper))()
    return jax.tree.map(np.asarray, tree), np.asarray(row_node)


@pytest.mark.parametrize("subtract", ["0", "1"])
@pytest.mark.parametrize("layout", ["shared", "per_tree"])
def test_blocked_build_equals_whole_gemm(monkeypatch, layout, subtract):
    monkeypatch.setenv("TMOG_HIST_SUBTRACT", subtract)
    assert Tr.hist_blocks(N, T * FRONTIER, 2 * D * BINS) == (1, N)
    whole, whole_nodes = _grow(layout)
    # 4 blocks of 128 rows: 512 > 421, so the last block is mostly padding
    monkeypatch.setattr(Tr, "hist_blocks", lambda n, lhs, rhs: (4, 128))
    blocked, blocked_nodes = _grow(layout)
    assert (whole.split_feat >= 0).sum() > 3 * T  # real trees were grown
    assert np.array_equal(whole.split_feat, blocked.split_feat)
    assert np.array_equal(whole.split_bin, blocked.split_bin)
    assert np.array_equal(whole.left, blocked.left)
    assert np.array_equal(whole_nodes, blocked_nodes)
    np.testing.assert_allclose(whole.leaf_val, blocked.leaf_val, atol=1e-5)


def test_block_length_follows_the_shapes():
    """One block while the operands fit their share of the chunk budget;
    past it equal blocks, a multiple of 128 rows, that cover every row."""
    assert Tr.hist_blocks(891, 900 * 8, 2 * 20 * 32) == (1, 891)
    for n in (10_279, 32_768, 180_224):
        nb, bn = Tr.hist_blocks(n, 17 * 128, 2 * 760 * 32)
        assert nb > 1 and bn % 128 == 0 and (nb - 1) * bn < n <= nb * bn
        assert 4 * bn * (17 * 128 + 2 * 760 * 32) <= Tr._CHUNK_BUDGET_BYTES / 4
