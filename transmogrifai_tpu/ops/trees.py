"""Histogram-based decision-tree / forest / boosting kernels — pure XLA.

The reference gets trees from Spark MLlib (RandomForest/GBT/DecisionTree)
and the XGBoost C++ core over JNI (`build.gradle:90`,
core/.../impl/classification/OpXGBoostClassifier.scala:47).  On TPU the
idiomatic formulation is the *histogram method* with static shapes and no
per-row control flow (SURVEY §7 "Trees/GBT/XGBoost on TPU"):

- features are pre-quantized to ``n_bins`` integer bins (subsampled quantile
  sketch — XGBoost's approx sketch analog; Spark's maxBins),
- a tree grows breadth-first over a BOUNDED FRONTIER of ``M`` node slots:
  early levels are unrolled at their exact widths (1, 2, 4, ... nodes), deep
  levels run in ONE ``lax.fori_loop`` body with a fixed ``M``-slot frontier —
  so compile cost is independent of depth and per-level memory/compute is
  capped at ``M * d * B`` instead of ``2^depth * d * B``,
- per level the (slot, feature, bin) gradient histograms are built as a
  one-hot GEMM accumulated over row blocks (``grow_forest`` /
  ``_grow_level_batch``: one program for every row count and backend, so
  the program the tests run is the one the chip runs); the best split per
  slot is running sums over the bins and an arg-max, on level tensors that
  keep their bins a MAJOR axis ([T, c+1, B, slots, features]: never the 32
  bins on the TPU's 128 lanes),
- rows carry a frontier-slot id; the level update is a small GEMM or a
  select, and a compare, per row block,
- second-order (g, h) statistics make the same builder serve XGBoost-style
  boosting (Newton leaves), RF regression (g = -y: variance gain, mean
  leaves), and RF classification (g = -onehot(y): gini-equivalent gain,
  class-distribution leaves),
- a forest grows its trees together, a chunk at a time (``grow_forest``;
  ``grow_tree`` is its chunk of one), each tree on its own kept features
  alone where it keeps fewer than all (``kept_features``: the level tensors
  are k wide, not d); boosting is ``lax.scan`` over rounds, each round a
  forest of its candidates' trees — a whole RF trains as ONE XLA launch and
  boosting compiles to a single fixed-trip loop.

Speeds: ``PERF.md`` (PRs 29, 30, 32: the default selector grid on 32,768 x
760 rows, TPU v5e).  A comment here that gives none says "not measured".

Frontier exactness: depth-wise growth is EXACT whenever every level has at
most ``M // 2`` valid splits.  A valid split needs hessian weight
``>= min_child_weight`` in each child, so at most ``H_total / (2 * mcw)``
nodes per level can split — ``frontier_cap`` sizes ``M`` from that bound.
When data is so large that the bound exceeds ``max_frontier``, growth becomes
a gain-ranked beam (LightGBM max-leaves analog) — the standard bounded-width
compromise, documented here rather than hidden.

Trees are stored as flat pointer arrays: ``split_feat`` (-1 = leaf),
``split_bin``, ``left``/``right`` child pool indices, ``leaf_val[pool, c]``
— pytree-friendly and trivially serializable.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from ..parallel.mesh import mesh_psum, record_trace_event
from ..utils import devcache


class Tree(NamedTuple):
    """One tree as a flat node pool; leading axes may batch trees/rounds."""

    split_feat: jax.Array  # i32[P]  (-1 => leaf)
    split_bin: jax.Array   # i32[P]  (go right if bin > split_bin)
    left: jax.Array        # i32[P]  pool index of left child
    right: jax.Array       # i32[P]  pool index of right child
    leaf_val: jax.Array    # f32[P, c]


# ---------------------------------------------------------------------------
# Quantization — subsampled quantile sketch (XGBoost approx / Spark maxBins)
# ---------------------------------------------------------------------------
_SKETCH_ROWS = 1 << 18  # 262144 — plenty for <=256 quantile edges


def _bin_dtype(n_bins: int):
    """Narrowest dtype holding every bin id in [0, n_bins).

    int8 tops out at +127, so it is safe through ``n_bins == 128`` (ids
    0..127) and must promote to int32 beyond — at exactly 128 the old
    ``<= 127`` boundary promoted a bin matrix that still fit, and one bin
    more would have overflowed int8 had the comparison been ``< 256``-style
    sloppy.  Regression-pinned at 127/128/255/256 in
    tests/test_trees_binning.py."""
    if n_bins < 2:
        raise ValueError(f"n_bins must be >= 2 (one split edge), got {n_bins}")
    return np.int8 if n_bins <= 128 else np.int32


@jax.jit
def _bin_chunk(X, edges):
    """i32[n, d]: per-feature searchsorted (left) — log2(B) compare steps."""
    return jax.vmap(lambda e, x: jnp.searchsorted(e, x, side="left"),
                    in_axes=(0, 1), out_axes=1)(edges, X)


def sketch_edges(X: np.ndarray, n_bins: int, seed: int = 0) -> np.ndarray:
    """Quantile split candidates f32[d, n_bins-1] from a row subsample."""
    X = np.asarray(X, np.float32)
    n = X.shape[0]
    if n > _SKETCH_ROWS:
        idx = np.random.default_rng(seed).choice(n, _SKETCH_ROWS, replace=False)
        X = X[idx]
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(X, qs, axis=0).T.astype(np.float32)  # [d, n_bins-1]


def bin_with_edges(X: np.ndarray, edges: np.ndarray,
                   chunk: int = 1 << 20) -> np.ndarray:
    """Apply fitted edges (vectorized on device, row-chunked for huge n).

    Bin b holds values in (edges[b-1], edges[b]]; value <= edges[0] is bin 0;
    value > edges[-1] is the last bin.
    """
    X = devcache.as_f32(X)  # a jax.Array is binned where it lies
    n = X.shape[0]
    n_bins = edges.shape[1] + 1
    dt = _bin_dtype(n_bins)
    ed = jnp.asarray(edges)
    if n <= chunk:
        return np.asarray(_bin_chunk(devcache.put(X, tag="bin"), ed)).astype(dt)
    out = np.empty(X.shape, dt)
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        out[lo:hi] = np.asarray(_bin_chunk(devcache.put(X[lo:hi], tag="bin"), ed)).astype(dt)
    return out


def quantize(X: np.ndarray, n_bins: int = 32,
             seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Equi-depth binning: (X_binned int8/i32[n, d], edges f32[d, n_bins-1])."""
    edges = sketch_edges(X, n_bins, seed=seed)
    return bin_with_edges(X, edges), edges


# ---------------------------------------------------------------------------
# Frontier sizing
# ---------------------------------------------------------------------------
def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 1).bit_length()


def frontier_cap(n: int, max_depth: int, min_child_weight: float = 1.0,
                 h_max: float = 1.0, max_frontier: int = 512,
                 total_weight: float = None) -> int:
    """Frontier slots M for ``grow_forest`` (static; power of two).

    At most ``H_total / (2 * mcw)`` nodes can validly split per level
    (children need hessian weight >= mcw each), so a frontier of
    ``H_total / mcw`` slots loses nothing.  ``h_max`` bounds one row's
    hessian per unit weight (1 for variance/gini trees, 0.25 for
    logistic/softmax).  ``total_weight`` is the actual row-weight sum (max
    over the tree batch) — callers that know their weights (bootstrap,
    DataBalancer up-weighting) MUST pass it; the 1.25*n fallback only covers
    unweighted rows plus mild Poisson-bootstrap inflation.  Beyond
    ``max_frontier`` growth is a gain-ranked beam (see module docstring).
    """
    if max_depth <= 1:
        return 2
    tw = 1.25 * n if total_weight is None else float(total_weight)
    exact = int(np.ceil(h_max * tw / max(min_child_weight, 1e-3)))
    # 2^max_depth (not 2^(max_depth-1)): the last split level's children must
    # all fit the next frontier, else the beam silently halves the deepest
    # level; when this term binds the tree is fully unrolled and exact.
    m = min(1 << max_depth, max(exact, 2), max_frontier, _next_pow2(n))
    return max(_next_pow2(m) if m & (m - 1) else m, 2)


def _pool_size(max_depth: int, frontier: int) -> int:
    """Node-pool capacity: exact heap for unrolled levels + M per loop level.

    Pool layout is STATIC: level t < log2(M) occupies [2^t - 1, 2^(t+1) - 1);
    loop level t >= log2(M) occupies [M - 1 + (t - L)*M, ...+M).  Every level
    claims its full block whether or not all slots split — offsets are then
    independent of the tree, so the batched node/leaf writes stay single
    vectorized ops over the tree axis instead of serializing per tree.
    """
    if max_depth <= 0:
        return 1
    L = frontier.bit_length() - 1  # log2(M)
    u = min(max_depth, L)
    return (1 << (u + 1)) - 1 + max(max_depth - L, 0) * frontier


def frontier_is_exact(n: int, max_depth: int, min_child_weight: float,
                      h_max: float, frontier: int,
                      total_weight: float = None) -> bool:
    """True when ``frontier`` provably cannot overflow (no beam truncation):
    a level's children are bounded by H_total / mcw <= h_max*sum(w) / mcw,
    so a frontier at least that wide (or fully unrolled) never ranks splits.
    The exact-cap fast path then replaces the gain-rank argsorts with a
    trivial count clamp.  ``total_weight`` must be the ACTUAL max weight sum
    over the tree batch when weights can exceed 1 per row (Poisson
    bootstrap, DataBalancer ~n/(1-p)); the 1.25*n fallback is only safe for
    near-unit weights."""
    tw = 1.25 * n if total_weight is None else float(total_weight)
    exact = int(np.ceil(h_max * tw / max(min_child_weight, 1e-3)))
    return frontier >= min(1 << max_depth, exact)


# ---------------------------------------------------------------------------
# Tree growth
# ---------------------------------------------------------------------------
def _hist_subtract() -> bool:
    """Parent-minus-child histogram subtraction (the XGBoost/LightGBM trick).

    Each split level builds per-bin G/H histograms only for the LIGHTER
    child (by hessian weight) of every sibling pair and derives the heavy
    sibling as ``parent_hist - light_hist`` from parent histograms carried
    level to level — halving the dominant histogram-build cost and, on
    row-sharded launches, the psum payload (the subtraction happens AFTER
    the data-axis psum on already-global stats).  Not bitwise-identical to
    the direct build (f32 ``parent - light`` rounds differently than
    summing the heavy rows), so near-tied splits can flip; parity is pinned
    at the sweep-metric level in tests/test_hist_subtract_parity.py.
    TMOG_HIST_SUBTRACT=0/1 forces either way (default on).
    """
    import os

    force = os.environ.get("TMOG_HIST_SUBTRACT")
    if force is not None and force != "":
        return force == "1"
    return True


#: precision of the level grower's selections by matmul (a 0/1 selector
#: against histogram sums, split statistics or leaf values): the TPU's default
#: rounds the selected float32 numbers to bfloat16 — a parent histogram of
#: thousands of rows, a leaf's p(1) — which the chip runs of PR 29 read as
#: forest fold AuPRs up to 5.3e-3 off the plain reference (PERF.md).  Exact
#: here; the level-histogram GEMM itself keeps the default (hist_blocks).
_EXACT = lax.Precision.HIGHEST

#: bytes one chunk of trees may hold in level tensors (``forest_chunk_size``)
#: and, a quarter of it, one row block of the level GEMM's operands
_CHUNK_BUDGET_BYTES = 3e9


def hist_blocks(n: int, lhs_rows: int, rhs_cols: int) -> Tuple[int, int]:
    """(row blocks, rows a block) of the level-histogram GEMM.

    The GEMM contracts over the n rows; its operands ([lhs_rows, n] weighted
    slot one-hot, [n, rhs_cols] bin one-hot) are only ever made one row block
    at a time, inside the scan that accumulates the [lhs_rows, rhs_cols]
    histogram.  Where every tree has a bin one-hot of its own (its kept
    columns: ``_grow_level_batch``'s compacted layout) ``rhs_cols`` counts
    the trees too, T * k * B.  A block's operands get a quarter of the chunk
    budget; blocks are equal and a multiple of 128 rows, and the rows are
    padded up to their sum with rows that sit in no slot.  A table that fits
    one block (Titanic) is one block of exactly n rows: the whole GEMM."""
    cap = int(_CHUNK_BUDGET_BYTES / 4 / (4 * (lhs_rows + rhs_cols))) // 128 * 128
    nb = -(-n // max(cap, 128))
    if nb == 1:
        return 1, n
    rows = -(-n // nb)
    return nb, -(-rows // 128) * 128


def bin_onehot(Xb, n_bins: int) -> jax.Array:
    """Gradient-FREE histogram RHS of one row block: [n, B*d] with entry
    (r, b*d + j) = 1[bin(r, j) == b] — bin-major, so the level's sums come
    out with the features minor (``_hist_gemm``).  Per-tree gradients
    (boosting) ride the LHS of the level GEMM against it."""
    n, d = Xb.shape
    hit = Xb.astype(jnp.int32)[:, None, :] == jnp.arange(n_bins)[:, None]
    return hit.astype(jnp.float32).reshape(n, -1)


def grad_onehot(Xb, gh, n_bins: int) -> jax.Array:
    """Shared RHS of the level-histogram GEMM for one row block:
    [n, c1*B*d] where entry (r, c*B*d + b*d + j) = gh[r, c] * 1[bin(r, j)
    == b], contracted against the weighted slot one-hot — row weights live
    on the slot side, so this tensor is shared by every tree of a forest."""
    n, d = Xb.shape
    # one select, not a product with a stored one-hot: the [n, B, d] one-hot
    # would be written and read once more than this tensor is
    hit = Xb.astype(jnp.int32)[:, None, None, :] \
        == jnp.arange(n_bins)[:, None]
    og = jnp.where(hit, gh.astype(jnp.float32)[:, :, None, None], 0)  # [n,c1,B,d]
    return og.reshape(n, -1)


def predict_tree(Xb, tree: Tree, max_depth: int) -> jax.Array:
    """f32[n, c] — pointer walk for ``max_depth`` steps; rows rest at leaves."""
    Xb = Xb.astype(jnp.int32)
    n = Xb.shape[0]
    node0 = jnp.zeros((n,), jnp.int32)

    def step(_, node):
        nf = tree.split_feat[node]
        nb = tree.split_bin[node]
        row_bin = jnp.take_along_axis(Xb, jnp.maximum(nf, 0)[:, None], axis=1)[:, 0]
        child = jnp.where(row_bin > nb, tree.right[node], tree.left[node])
        return jnp.where(nf >= 0, child, node)

    node = lax.fori_loop(0, max_depth, step, node0) if max_depth > 0 else node0
    return tree.leaf_val[node]


def read_leaves(leaf_val, row_node) -> jax.Array:
    """f32[T, c, n]: ``leaf_val[t, row_node[t, i], :]`` for every tree and
    training row, rows minor — what ``grow_forest``'s ``row_node`` is for
    (growth routes every row, so a tree's prediction on its training rows
    needs no pointer walk).  Read by SELECTION, not by gather: a per-element
    gather runs at ~1e8 elements a second on the TPU, and the default grids
    ask for 1e8-1e9 of them a fit (PERF.md, PR 34).

    A node index is (block of ``lanes`` <= 128 nodes, lane).  The row's lane
    picks its entry of every block at once — ONE one-hot contraction,
    [T, blocks * c, lanes] x [T, lanes, rows], a single K tile whatever the
    pool size P — and a select-sum over the blocks (``route_block``'s
    ``pick``) keeps the row's own.  One term of each sum is non-zero and the
    contraction is ``_EXACT``, so the float32 leaf comes back bit for bit
    (-0.0 as +0.0).  The one-hot exists a row block at a time
    (``hist_blocks``: its own quarter of the chunk budget, so
    ``forest_chunk_size`` does not count it), and each block's values land
    in the output where they belong; the last block is moved back to end at
    row n, so nothing is padded or sliced and the rows it shares with the
    block before it are written twice with the same numbers.

    The table must be finite: 0 * NaN poisons a selection.  ``grow_forest``
    keeps it so (dead slots and unclaimed pool entries hold 0.0, a root
    without weight reads 0.0).  A non-finite entry is not hidden and never
    turned into a wrong number: every row of that tree whose node lies in
    the entry's block reads NaN in the entry's channel, whether or not the
    entry is the row's own, and every other read is exact."""
    T, P, c = leaf_val.shape
    n = row_node.shape[1]
    lanes = min(P, 128)
    nblk = -(-P // lanes)
    table = jnp.pad(leaf_val, ((0, 0), (0, nblk * lanes - P), (0, 0)))
    table = table.reshape(T, nblk, lanes, c).transpose(0, 1, 3, 2) \
        .reshape(T, nblk * c, lanes)
    nb, bn = hist_blocks(n, T * lanes, T * nblk * c)
    iota_l = jnp.arange(lanes)[None, :, None]
    iota_b = jnp.arange(nblk)[None, :, None, None]

    def block(i, out):
        start = jnp.minimum(i * bn, n - bn)
        node = lax.dynamic_slice_in_dim(row_node, start, bn, axis=1)  # [T, bn]
        lane_hot = (node % lanes)[:, None, :] == iota_l        # [T, lanes, bn]
        part = jnp.einsum("tql,tlr->tqr", table,
                          lane_hot.astype(jnp.float32), precision=_EXACT)
        mine = (node // lanes)[:, None, None, :] == iota_b  # [T, nblk, 1, bn]
        vals = jnp.where(mine, part.reshape(T, nblk, c, bn), 0.0).sum(axis=1)
        return lax.dynamic_update_slice_in_dim(out, vals, start, axis=2)

    with jax.named_scope("trees.leaves"):
        return lax.fori_loop(0, nb, block, jnp.zeros((T, c, n), jnp.float32))


# ---------------------------------------------------------------------------
# The level grower — the whole tree chunk in ONE GEMM per level
#
# A note from before the first chip run (not chip evidence by PERF.md's
# rule, not measured again): the per-tree contraction ([m, n] @ [n, c1*d*B]
# batched over the trees) lowered far worse than the SAME reduction
# flattened to a single [T*m, n] @ [n, c1*d*B] GEMM.  So every tree is grown
# as one of a chunk with an explicit tree axis (a single tree is a chunk of
# one, ``grow_tree``; a boosting round a chunk of its candidates' trees): slot
# one-hots are built [T, m, rows] (slot axis ahead of rows: no transpose
# before the flatten) and every level runs one flat GEMM — accumulated over
# row blocks (``hist_blocks``), so its cost in memory does not grow with n.
# The flat GEMM needs one RHS for all trees.  A forest whose trees keep k < d
# features of their own has none, and pays d / k times the contraction to
# pretend it has: there the level is a tree-batched GEMM over each tree's own
# kept columns (chip numbers: PERF.md, PR 30).
# ---------------------------------------------------------------------------
def _hist_gemm(Xk, ghk, wk, slot_k, hist_slot, n_bins: int, per_tree: bool):
    """The ONE place a level's sums are formed, on every backend: the
    weighted g and h of each tree's rows by (channel, bin, slot, feature),
    f32[T, c1, B, mh, d], as a one-hot GEMM accumulated over the row blocks
    (operands as ``_grow_level_batch`` describes them; ``hist_slot``
    i32[T, mh] names the frontier slot each histogram row collects).

    Why that order.  On the TPU's (8, 128) tiles a level tensor with its 32
    bins minor is stored four times its size, and every copy in or out of
    that form moves the padding too: 5.4 GB a beam level of the trees cell,
    most of the 23 ms it spent outside this GEMM (PERF.md, PR 32).  With the
    bins a major axis the tiled pair is (slot, feature) or (feature, slot),
    whichever XLA picks, both dense, and the running sums over the bins are
    adds of whole planes.  So the LHS rows run (tree, channel, slot) — g and
    h on planes of their own — and the transpose below is the one time the
    level's sums change layout.  The shared matrix's one-hot columns run
    bin-major (b*d + j), so that transpose never sees a 32-wide minor axis;
    a compacted tree's columns stay feature-major (j*B + b), where XLA fuses
    the bin compare into the tree-batched GEMM (bin-major there cost a
    forest chunk 0.2 s of 1.07 on the chip) and k*B is lane-dense as it is.
    """
    compact = Xk.ndim == 4
    bn, d = (Xk.shape[3], Xk.shape[2]) if compact else Xk.shape[1:]
    T, mh = hist_slot.shape
    c1 = ghk.shape[-1]
    B = n_bins
    on_lhs = compact or per_tree          # where the gradients ride

    def hist_block(acc, xs):
        xb, ghb, wb, sb = xs
        # weighted slot one-hot of the block, slot axis BEFORE rows:
        # flattening needs no transpose
        Sw = (sb[:, None, :] == hist_slot[:, :, None]).astype(jnp.float32) \
            * wb[:, None, :]                                        # [T, mh, bn]
        if on_lhs:   # LHS rows (tree, channel, slot): a plane a channel
            ghb = ghb.transpose(0, 2, 1) if per_tree else ghb.T[None]
            lhs = ghb[:, :, None, :] * Sw[:, None, :, :]     # [T, c1, mh, bn]
        if compact:
            rhs = (xb[:, :, None, :] == jnp.arange(B, dtype=xb.dtype)[
                None, None, :, None]).astype(jnp.float32).reshape(
                    T, d * B, bn)
            return acc + lax.dot_general(
                lhs.reshape(T, -1, bn), rhs, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32), None  # [T, c1*mh, d*B]
        if per_tree:
            rhs = bin_onehot(xb, B)                                 # [bn, B*d]
            lhs = lhs.reshape(-1, bn)
        else:
            rhs = grad_onehot(xb, ghb, B)                        # [bn, c1*B*d]
            lhs = Sw.reshape(-1, bn)
        return acc + lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32), None

    gemm = (T, c1 * mh, d * B) if compact else \
        (T * c1 * mh, B * d) if per_tree else (T * mh, c1 * B * d)
    GH, _ = lax.scan(hist_block, jnp.zeros(gemm, jnp.float32),
                     (Xk, ghk, wk, slot_k))
    if compact:
        return GH.reshape(T, c1, mh, d, B).transpose(0, 1, 4, 2, 3)
    if per_tree:
        return GH.reshape(T, c1, mh, B, d).transpose(0, 1, 3, 2, 4)
    # shared gradients ride the RHS: the channel comes out beside the bins
    return GH.reshape(T, mh, c1, B, d).transpose(0, 2, 3, 1, 4)


def _grow_level_batch(Xk, ghk, wk, feat_t, nodes, leaf_val, slot_base,
                      next_free, n_active, slot_k, node_k, m: int,
                      next_cap: int, n_bins: int, reg_lambda_t, gamma_t,
                      mcw_t, mig_t, exact_cap: bool, per_tree: bool,
                      axis_name: Optional[str] = None,
                      pair_light=None, pair_hist=None,
                      want_pairs: bool = False):
    """One breadth-first level over an ``m``-slot frontier, for T trees.

    SCATTER/GATHER-FREE by design: XLA TPU lowers batched scatters and
    per-element gathers to near-serial loops, so the (slot, feature, bin)
    sums are a one-hot GEMM, every per-row lookup of per-slot data is a
    select against the row's slot, node records land with ONE
    ``dynamic_update_slice`` per level (the frontier occupies the static
    pool block ``[slot_base, slot_base + m)`` — see ``_pool_size``; offsets
    are tree-independent so the write stays one vectorized op), children
    pack into ``[next_free, next_free + 2k)`` via tiny selection matmuls (no
    argsort), and the next frontier needs no materialized map — slot j of
    the next level IS pool id ``next_free + j``.  ``m`` and ``next_cap`` are
    static; when ``next_cap < 2*m`` the level keeps only the top
    ``next_cap // 2`` splits by gain — unless ``exact_cap`` says the
    frontier provably cannot overflow, where a count clamp replaces the
    sorts.  A node's leaf value is written once, when the node is created
    (root at init).

    Everything that has a row axis arrives cut into ``nb`` row blocks of
    ``bn`` rows (``grow_forest`` cuts them once, ``hist_blocks`` sizes
    them): wk f32[nb, T, bn], slot_k / node_k i32[nb, T, bn] (each row's
    frontier slot, -1 = resting or padding, and its pool node, so boosting
    reads final leaf values without a predict walk), ``ghk`` f32[nb, bn, c1]
    where every tree sees the same g/h (forests) or, ``per_tree``,
    f32[nb, T, bn, c1] (boosting: each batch element has its own margins F).
    Per tree: nodes i32[T, P, 4] (feat, bin, left, right), leaf_val
    f32[T, P, c], n_active i32[T] (the live width of the frontier),
    hyperparameters f32[T].

    Histogram subtraction (``_hist_subtract``): with ``pair_hist``
    f32[T, c+1, B, m/2, d] (the parent slots' histograms, packed at sibling-
    pair positions by the PREVIOUS level) and ``pair_light`` f32[T, m/2]
    (1.0 = the lighter child sits in the even/left slot), histograms are
    built only for the light child of each pair; the heavy sibling is
    ``parent - light`` AFTER the data-axis psum.  ``want_pairs`` appends
    (pair_light', pair_hist') for the NEXT level to the return tuple.

    The binned matrix sets the width every level tensor has:

    - SHARED, Xk int[nb, bn, d]: all d features, ``feat_t`` f32[T, d] masks
      the ones a tree may split on (None: all).  Shared gradients ride the
      RHS (``grad_onehot`` of the block) against the weighted slot one-hot
      [T*m, bn]; per-tree gradients ride the LHS ([T*m*c1, bn]) against the
      block's gradient-free ``bin_onehot``: one flat GEMM either way.
    - COMPACTED, Xk int[nb, T, k, bn]: each tree's k kept columns, rows
      minor, ``feat_t`` i32[T, k] their original indices, ascending.  One
      tree-batched GEMM, [T, m*c1, bn] x [T, k*B, bn]: gradients ride the
      LHS whoever owns them, the RHS is the bin one-hot of the tree's own
      columns.  Histograms, split scan, carried pair histograms and routing
      are k wide; the arg-max walks (kept column, bin) in the original
      order, so ties still go to the lower feature and bin, and node
      records hold ``feat_t``'s original index.

    Every layout accumulates block by block, and every level tensor —
    histograms, running sums, gains, the carried pair histograms — is
    [T, c+1, B, slots, d]: bins major, never minor (``_hist_gemm``).  Three
    named scopes split the level in a profiler trace: ``trees.hist`` (the
    block scan), ``trees.split`` (running sums, gain, arg-max, beam ranking,
    node records), ``trees.route`` (the second block scan: each row's next
    slot and node).
    """
    B = n_bins
    compact = Xk.ndim == 4
    # d: the width the level is built at (all features, or the k kept)
    nb, bn, d = (Xk.shape[0], Xk.shape[3], Xk.shape[2]) if compact \
        else Xk.shape
    T = wk.shape[1]
    c1 = ghk.shape[-1]
    c = c1 - 1
    iota_m = jnp.arange(m)
    in_use = iota_m[None, :] < n_active[:, None]                    # [T, m]
    subtract = pair_hist is not None
    pairs = m // 2

    # The level's tensors list a frontier's slots LEFT CHILDREN FIRST: row
    # side * pairs + p of their slot axis is slot 2p + side.  The two halves
    # of a subtracted level (each pair's left child, each pair's right child)
    # are then whole blocks of that axis, laid end to end — nothing is
    # interleaved along a tiled axis.  What a level reduces to per slot is
    # small ([T, m] or [T, c, m]), and is put in slot order as soon as it
    # exists: pool ids, beam ranks and routing never see the row order.
    def to_slots(a):                      # [.., m rows] -> [.., m slots]
        return a if m == 1 else jnp.swapaxes(
            a.reshape(a.shape[:-1] + (2, pairs)), -1, -2).reshape(a.shape)

    def to_rows(a):                       # [.., m slots] -> [.., m rows]
        return a if m == 1 else jnp.swapaxes(
            a.reshape(a.shape[:-1] + (pairs, 2)), -1, -2).reshape(a.shape)

    if subtract:
        # histogram subtraction: the level GEMM's LHS covers only the LIGHT
        # child of each sibling pair (half the slot rows); the heavy sibling
        # is parent - light after the data-axis psum
        hist_slot = (2 * jnp.arange(pairs)[None, :]
                     + (pair_light < 0.5).astype(jnp.int32))        # [T, mh]
        record_trace_event("hist_subtracted", "mm_batch",
                           2 * T * pairs * nb * bn * c1 * d * B)
    else:
        hist_slot = jnp.broadcast_to(to_rows(iota_m)[None, :], (T, m))
    with jax.named_scope("trees.hist"):
        GH = _hist_gemm(Xk, ghk, wk, slot_k, hist_slot, B, per_tree)
        # row-sharded launch: local-rows histograms psum to the GLOBAL
        # per-bin stats, so every shard picks identical splits (distributed-
        # XGBoost histogram aggregation); row routing below stays local.
        # Subtracted levels psum only the light half of the payload; parents
        # are already post-psum globals from the prior level.
        GH = mesh_psum(GH, axis_name)                    # [T, c1, B, mh, d]
        if subtract:
            GH_h = pair_hist - GH
            lp = (pair_light > 0.5)[:, None, None, :, None]
            GH = jnp.concatenate([jnp.where(lp, GH, GH_h),
                                  jnp.where(lp, GH_h, GH)], axis=3)
    with jax.named_scope("trees.split"):
        # running sums over the bins, g and h planes together, one bin after
        # the other: B adds of whole [m, d] planes, a scan along the major
        # axis.  Written as the recurrence, not ``cumsum``: S[b] IS
        # S[b-1] + GH[b], so a bin that is empty in a node ties with the bin
        # below it exactly and the lower bin wins (a log-step scan adds in
        # another order and breaks such ties by its rounding); and XLA leaves
        # the planes where they lie, where its ``reduce-window`` took the
        # level's tensors through relayout copies in and out (PERF.md, PR 32)
        def add_plane(run, plane):
            run = run + plane
            return run, run

        _, S = lax.scan(add_plane, jnp.zeros_like(GH[:, :, 0]),
                        jnp.moveaxis(GH, 2, 0))
        S = jnp.moveaxis(S, 0, 2)                        # [T, c1, B, m, d]
        GL, HL = S[:, :c], S[:, c]              # [T,c,B,m,d], [T,B,m,d]
        # a node's totals: feature 0's bins
        tot = GH[..., 0].sum(axis=2)            # [T, c1, m]
        GT, HT = tot[:, :c], tot[:, c]
        GR = GT[:, :, None, :, None] - GL
        HR = HT[:, None, :, None] - HL

        lam = reg_lambda_t[:, None]

        def score(Gp, Hp):
            return (Gp * Gp).sum(axis=1) / (Hp + lam[..., None, None])

        gain = score(GL, HL) + score(GR, HR) \
            - ((GT * GT).sum(axis=1) / (HT + lam))[:, None, :, None]
        mcw = mcw_t[:, None, None, None]
        valid = (HL >= mcw) & (HR >= mcw) \
            & (jnp.arange(B)[:, None, None] < B - 1)
        if not compact and feat_t is not None:
            valid &= feat_t[:, None, None, :] > 0.0
        gain = jnp.where(valid, gain, -jnp.inf)                 # [T,B,m,d]
        # the best split of a slot, ties to the lower feature and then the
        # lower bin: each feature's best bin first (the first of equals),
        # then the best feature (the first of equals)
        bin_of = jnp.argmax(gain, axis=1)                       # [T, m, d]
        gain_of = jnp.max(gain, axis=1)
        bf = jnp.argmax(gain_of, axis=-1).astype(jnp.int32)     # [T, m]
        at_bf = bf[:, :, None] == jnp.arange(d)                 # [T, m, d]
        bb = jnp.where(at_bf, bin_of, 0).sum(axis=-1).astype(jnp.int32)
        # the winning split's running sums: one term a sum, so exact
        hit = at_bf[:, None, :, :] & (bb[:, None, :, None]
                                      == jnp.arange(B)[:, None, None])
        S_best = jnp.where(hit[:, None], S, 0.0).sum(axis=(2, 4))  # [T,c1,m]
        GL_best, HL_best = S_best[:, :c], S_best[:, c]
        # slot order from here on
        best_gain, bf, bb, GT, HT, GL_best, HL_best = map(
            to_slots, (jnp.max(gain_of, axis=-1), bf, bb, GT, HT, GL_best,
                       HL_best))
        # Spark minInfoGain parity: our gain is the total-sum-of-squares
        # drop, which equals node_weight * Spark's per-row impurity decrease
        # for both gini (g=-onehot) and variance (g=-y) trees — so the
        # per-row threshold scales by the node's hessian total
        # (DefaultSelectorParams.MinInfoGain).
        do_split = (best_gain > gamma_t[:, None]) \
            & (best_gain >= mig_t[:, None] * HT) & in_use
        half = next_cap // 2
        if next_cap < 2 * m and not exact_cap:
            key = jnp.where(do_split, -best_gain, jnp.inf)
            rank = jnp.argsort(jnp.argsort(key, axis=1), axis=1)
            do_split &= rank < half
            k = jnp.cumsum(do_split.astype(jnp.int32), axis=1)
        else:
            k = jnp.cumsum(do_split.astype(jnp.int32), axis=1)
            if next_cap < 2 * m:
                do_split &= k <= half
                k = jnp.minimum(k, half)
        n_split = k[:, -1]
        child_idx = (k - 1) * 2
        left_pool = next_free + child_idx
        right_pool = left_pool + 1
        # a compacted tree records the ORIGINAL index of its kept column
        feat = bf if not compact else jnp.where(
            bf[:, :, None] == jnp.arange(d), feat_t[:, None, :], 0).sum(-1)
        rec = jnp.stack([jnp.where(do_split, feat, -1),
                         jnp.where(do_split, bb, 0),
                         jnp.where(do_split, left_pool, 0),
                         jnp.where(do_split, right_pool, 0)], axis=-1)
        nodes = lax.dynamic_update_slice(nodes, rec, (0, slot_base, 0))
        GR_best = GT - GL_best
        HR_best = HT - HL_best
        # dead slots have HL_best = 0; with reg_lambda = 0 the ratio is 0/0 =
        # NaN and 0 * NaN would poison the child-packing matmul below
        lval = jnp.where(do_split[:, None, :],
                         -GL_best / (HL_best + lam)[:, None, :], 0.0)
        rval = jnp.where(do_split[:, None, :],
                         -GR_best / (HR_best + lam)[:, None, :], 0.0)
        iota_cap = jnp.arange(next_cap)
        pos_l = jnp.where(do_split, child_idx, -1)
        pos_r = jnp.where(do_split, child_idx + 1, -1)
        L_eq = (iota_cap[None, :, None]
                == pos_l[:, None, :]).astype(leaf_val.dtype)
        R_eq = (iota_cap[None, :, None]
                == pos_r[:, None, :]).astype(leaf_val.dtype)
        child_vals = jnp.einsum("tpm,tcm->tpc", L_eq, lval, precision=_EXACT) \
            + jnp.einsum("tpm,tcm->tpc", R_eq, rval,
                         precision=_EXACT)                # [T, next_cap, c]
        leaf_val = lax.dynamic_update_slice(leaf_val, child_vals,
                                            (0, next_free, 0))
        if want_pairs:
            # parent histograms for the NEXT level's sibling pairs: slot s's
            # (post-psum, post-reassembly) planes packed at pair child_idx / 2
            # by every other row of the child-packing selector L_eq; the
            # light-left flag comes from the winning split's child hessians
            P_pair = L_eq[:, 0::2, :]                # [T, next_cap // 2, m]
            new_pair_hist = jnp.einsum(
                "tpm,tcbmd->tcbpd", to_rows(P_pair), GH,
                precision=_EXACT)            # [T, c1, B, next_cap // 2, d]
            new_pair_light = jnp.einsum(
                "tpm,tm->tp", P_pair, (HL_best <= HR_best).astype(jnp.float32))
    # route rows, a row block at a time.  Each row needs its slot's
    # (do_split, split bin, child slot) and its own bin of the slot's split
    # feature; per-element gathers serialize on the TPU, so the bin comes
    # from ONE flat GEMM per block, one-hot(split feature) [T*m, d] against
    # the block's bins [bn, d] -> [T, m, bn], and the row's slot picks its
    # entry.  Every number is a small integer: exact in one bf16 pass up to
    # 256 bins.  A compacted tree has its own k columns: the row's slot picks
    # the split column, and a select over the k picks its bin (integers).
    if not compact:
        feat_sel = jax.nn.one_hot(bf, d, dtype=jnp.float32).reshape(T * m, d)

    def route_block(_, xs):
        xb, sb, nk = xs
        S = sb[:, None, :] == iota_m[None, :, None]                # [T, m, bn]

        def pick(per_slot):                                     # -> [T, bn]
            return jnp.where(S, per_slot[:, :, None], 0).sum(axis=1)

        splits_here = pick(do_split.astype(jnp.int32)) > 0
        if compact:
            row_bin = jnp.where(
                jnp.arange(d)[None, :, None] == pick(bf)[:, None, :],
                xb.astype(jnp.int32), 0).sum(axis=1)
            go_right = (row_bin > pick(bb)).astype(jnp.int32)
        else:
            slot_bin = lax.dot_general(
                feat_sel, xb.astype(jnp.float32),
                (((1,), (1,)), ((), ()))).reshape(T, m, bn)
            go_right = (jnp.where(S, slot_bin, 0.0).sum(axis=1)
                        > pick(bb).astype(jnp.float32)).astype(jnp.int32)
        child = pick(child_idx) + go_right
        return None, (jnp.where(splits_here, child, -1),
                      jnp.where(splits_here, next_free + child, nk))

    with jax.named_scope("trees.route"):
        _, (slot_k, node_k) = lax.scan(route_block, None, (Xk, slot_k, node_k))
    if want_pairs:
        return (nodes, leaf_val, 2 * n_split, slot_k, node_k,
                new_pair_light, new_pair_hist)
    return nodes, leaf_val, 2 * n_split, slot_k, node_k


def grow_forest(Xb, g, h, w_t, feat_t, max_depth: int, n_bins: int,
                frontier: int, reg_lambda_t, gamma_t, mcw_t, mig_t,
                exact_cap: bool = False, return_row_node: bool = False,
                gh_t=None, axis_name: Optional[str] = None):
    """Grow T second-order histogram trees together (traceable; static
    shapes); ONE GEMM per level (see header note).

    Gain (XGBoost): sum_c GL_c^2/(HL+l) + GR_c^2/(HR+l) - GT_c^2/(HT+l);
    leaf value: -G/(H+l).  With g=-y, h=1, l~0 this is exactly variance-gain
    splitting with mean leaves (Spark variance impurity), and with
    g=-onehot(y) it is gini-equivalent gain with class-distribution leaves
    (Spark gini impurity).

    Shared: Xb int[n, d].  Gradients either SHARED (g f32[n, c], h f32[n] —
    forests) or PER TREE (``gh_t`` f32[T, n, c1]; pass g/h as None —
    boosting).  Per tree: w_t f32[T, n], reg_lambda/gamma/mcw/mig f32[T],
    and ``feat_t``, the features a tree may split on: a mask f32[T, d], or
    the kept features' indices i32[T, k] (``kept_features``: ascending).
    The table's static width is what adapts the program: with k < d each
    tree's k columns are gathered once, here, and every level is built k
    wide (``_grow_level_batch``'s compacted layout: a forest that keeps 28
    of 760 features never builds the other 732); with k == d, or a mask,
    the levels are d wide over the shared matrix and nothing is gathered.
    The rows are cut into blocks once, here, sized for the widest level
    (``hist_blocks``).  Node records hold original feature indices in
    either layout.  Returns Tree with leading [T] axis (+ row_node on
    request: ``leaf_val[row_node]`` is a tree's prediction on the training
    rows, which ``read_leaves`` reads without a predict walk).
    """
    n, d = Xb.shape
    per_tree = gh_t is not None
    c = gh_t.shape[2] - 1 if per_tree else g.shape[1]
    c1 = c + 1
    T = w_t.shape[0]
    if jnp.issubdtype(feat_t.dtype, jnp.integer):
        feat_idx_t, feat_mask_t = feat_t, None
    else:
        feat_idx_t, feat_mask_t = None, feat_t
    compact = feat_idx_t is not None and feat_idx_t.shape[1] < d
    if per_tree:
        gw_sum = (gh_t[:, :, :c] * w_t[:, :, None]).sum(axis=1)
        hw_sum = (gh_t[:, :, c] * w_t).sum(axis=1)
    else:
        gw_sum = (g[None, :, :] * w_t[:, :, None]).sum(axis=1)      # [T, c]
        hw_sum = (h[None, :] * w_t).sum(axis=1)                     # [T]
    gw_sum = mesh_psum(gw_sum, axis_name)
    hw_sum = mesh_psum(hw_sum, axis_name)
    P = _pool_size(max_depth, frontier)
    # a tree without weight (padding; reg_lambda 0) would read 0 / 0: its
    # leaves are read by selection, which a NaN poisons (``read_leaves``)
    root_den = (hw_sum + reg_lambda_t)[:, None]
    root_val = jnp.where(root_den != 0, -gw_sum / root_den, 0.0)
    nodes = jnp.tile(jnp.asarray([-1, 0, 0, 0], jnp.int32), (T, P, 1))
    leaf_val = jnp.zeros((T, P, c), jnp.float32).at[:, 0].set(root_val)

    def as_tree(nodes, leaf_val):
        return Tree(split_feat=nodes[:, :, 0], split_bin=nodes[:, :, 1],
                    left=nodes[:, :, 2], right=nodes[:, :, 3],
                    leaf_val=leaf_val)

    if max_depth <= 0:
        tree = as_tree(nodes, leaf_val)
        return (tree, jnp.zeros((T, n), jnp.int32)) if return_row_node else tree

    M = frontier
    L = M.bit_length() - 1
    # histogram subtraction only pays from level 1 on (the root has no
    # sibling); the pair carry rides alongside the 5-tuple when enabled
    sub = _hist_subtract() and max_depth > 1
    # row blocks, sized for the widest level's operands
    mh = min(M, 1 << (max_depth - 1))
    mh = max(mh // 2, 1) if sub else mh
    if compact:
        k = feat_idx_t.shape[1]
        nb, bn = hist_blocks(n, T * mh * c1, T * k * n_bins)
    else:
        nb, bn = (hist_blocks(n, T * mh * c1, d * n_bins) if per_tree
                  else hist_blocks(n, T * mh, c1 * d * n_bins))

    def blocks(a, axis: int, fill=0):
        """[.., n, ..] -> [nb, .., bn, ..]: pad the row axis, cut it, and
        put the block axis first (what ``lax.scan`` walks)."""
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, nb * bn - n)
        a = jnp.pad(a, widths, constant_values=fill)
        a = a.reshape(a.shape[:axis] + (nb, bn) + a.shape[axis + 1:])
        return jnp.moveaxis(a, axis, 0)

    # each tree's kept columns, gathered once for all its levels: whole rows
    # of the transposed matrix, [T, k, n] at the binned dtype's width
    Xk = blocks(jnp.take(Xb.T, feat_idx_t, axis=0), 2) if compact \
        else blocks(Xb.astype(jnp.int32), 0)
    wk = blocks(w_t, 1)
    ghk = (blocks(gh_t, 1) if per_tree
           else blocks(jnp.concatenate([g, h[:, None]], axis=1), 0))
    carry = (nodes, leaf_val, jnp.ones((T,), jnp.int32),
             blocks(jnp.zeros((T, n), jnp.int32), 1, fill=-1),      # row slot
             jnp.zeros((nb, T, bn), jnp.int32))                     # row node

    def level(state, slot_base, next_free, m, next_cap, want_pairs):
        return _grow_level_batch(
            Xk, ghk, wk, feat_idx_t if compact else feat_mask_t, state[0],
            state[1], slot_base, next_free, *state[2:5], m=m,
            next_cap=next_cap, n_bins=n_bins,
            reg_lambda_t=reg_lambda_t, gamma_t=gamma_t, mcw_t=mcw_t,
            mig_t=mig_t, exact_cap=exact_cap, per_tree=per_tree,
            axis_name=axis_name,
            pair_light=state[5] if len(state) > 5 else None,
            pair_hist=state[6] if len(state) > 5 else None,
            want_pairs=want_pairs)

    # exact unrolled levels, widths 1, 2, 4, ...: level t's frontier block
    # starts at 2^t - 1 (static pool layout, ``_pool_size``), next_cap = 2m
    for t in range(min(max_depth, L)):
        carry = level(carry, (1 << t) - 1, (1 << (t + 1)) - 1, 1 << t,
                      1 << (t + 1), sub)
    # deep levels: ONE fori_loop body at fixed M slots, block starts affine
    # in t.  The last unrolled level's next_cap is exactly M, so the carried
    # pair histograms keep one static shape across iterations.
    if max_depth > L:
        def body(t, state):
            sb = M - 1 + (t - L) * M
            return level(state, sb, sb + M, M, M, sub)

        carry = lax.fori_loop(L, max_depth, body, tuple(carry))
    tree = as_tree(carry[0], carry[1])
    if not return_row_node:
        return tree
    return tree, jnp.moveaxis(carry[4], 0, 1).reshape(T, nb * bn)[:, :n]


def grow_tree(Xb, g, h, w, feat_mask, max_depth: int, n_bins: int,
              frontier: int, reg_lambda: float = 1.0, gamma: float = 0.0,
              min_child_weight: float = 1.0, min_info_gain=0.0,
              return_row_node: bool = False, exact_cap: bool = False,
              axis_name: Optional[str] = None):
    """Grow one tree: ``grow_forest`` with T = 1, the tree axis stripped.

    Xb: int[n, d] pre-binned features; g: f32[n, c] gradients; h: f32[n]
    hessians; w: f32[n] row weights (bootstrap/balancing; 0 drops the row);
    feat_mask: f32[d] 1/0 feature subsampling mask, or i32[k] kept indices;
    ``frontier``: static frontier width M (see ``frontier_cap``).
    """
    one = lambda v: jnp.asarray(v, jnp.float32).reshape(1)
    out = grow_forest(Xb, g, h, w[None], feat_mask[None], max_depth, n_bins,
                      frontier, reg_lambda_t=one(reg_lambda),
                      gamma_t=one(gamma), mcw_t=one(min_child_weight),
                      mig_t=one(min_info_gain), exact_cap=exact_cap,
                      return_row_node=return_row_node, axis_name=axis_name)
    return jax.tree.map(lambda a: a[0], out)


# ---------------------------------------------------------------------------
# Random forest — chunks of trees
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("max_depth", "n_bins", "frontier",
                                             "exact_cap"))
def fit_forest(Xb, g, h, w_trees, feat_masks, max_depth: int, n_bins: int,
               frontier: int, reg_lambda: float = 1e-6,
               min_child_weight: float = 1.0, min_info_gain: float = 0.0,
               exact_cap: bool = False) -> Tree:
    """Train all trees of a forest in one launch.

    w_trees: f32[T, n] bootstrap weights; feat_masks: f32[T, d], or the
    kept features' indices i32[T, k] (``kept_features``; see
    ``grow_forest``).  Returns Tree with leading tree axis.
    """
    T = w_trees.shape[0]
    return grow_forest(Xb, g, h, w_trees, feat_masks, max_depth, n_bins,
                       frontier,
                       reg_lambda_t=jnp.full(T, reg_lambda, jnp.float32),
                       gamma_t=jnp.zeros(T, jnp.float32),
                       mcw_t=jnp.full(T, min_child_weight, jnp.float32),
                       mig_t=jnp.full(T, min_info_gain, jnp.float32),
                       exact_cap=exact_cap)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_forest(Xb, forest: Tree, max_depth: int) -> jax.Array:
    """Average the trees' leaf vectors: f32[n, c]."""
    preds = jax.vmap(lambda t: predict_tree(Xb, t, max_depth))(forest)  # [T, n, c]
    return preds.mean(axis=0)


def forest_chunk_size(max_depth: int, n_bins: int, d: int, c: int,
                      frontier: int, budget_bytes: float = _CHUNK_BUDGET_BYTES,
                      n_rows: int = 0, n_kept: Optional[int] = None) -> int:
    """Trees per chunk so one chunk's level tensors fit the budget.

    A level materializes G [M, k, B, c] + cumsums per tree (x3 covers the
    cumsum/gain temporaries); with histogram subtraction on, the carried
    parent pair histograms add about half a level's histograms (the 0.5
    bump).  ``k`` is the width the levels are built at: ``n_kept`` where the
    forest is grown on its kept features (``grow_forest`` with an index
    table), else all ``d``.  Of the
    rows a tree keeps its weights, slots and nodes (the ``3 * n_rows`` term),
    the leaf it read for each row, a plane a channel (``c * n_rows``: the
    fused sweep's ``_forest_group_scores``) and, compacted, its k columns of
    the binned matrix: the [M, rows] slot
    one-hot exists one row block at a time and has its own quarter of the
    budget (``hist_blocks``)."""
    hist_factor = 3.5 if _hist_subtract() else 3.0
    k = d
    if n_kept is not None and n_kept < d:
        k = n_kept
    per_tree = (frontier * n_bins * k * (c + 1) * hist_factor
                + (3 + c) * n_rows) * 4
    if k < d:
        per_tree += n_rows * k * np.dtype(_bin_dtype(n_bins)).itemsize
    return max(1, int(budget_bytes / max(per_tree, 1)))


def balanced_chunk(total: int, chunk_max: int, group: int = 1) -> int:
    """Even chunk size: ceil-divide ``total`` into the fewest chunks that
    respect ``chunk_max``, then size chunks evenly so zero-weight padding is
    at most ``n_chunks - 1`` trees (a naive min(total, chunk_max) padded a
    900-tree group to 2 x 635 = 41% waste — round-5 profile).

    ``group`` is the trees that belong together, a forest laid tree after
    tree: a chunk is then whole groups, cut as evenly, where ``chunk_max``
    holds one, and else the even part of ONE group — so no chunk straddles
    two forests and a chunk can add up its own trees' leaves
    (``ops.sweep._forest_group_scores``, which fills a forest up to whole
    parts)."""
    total, chunk_max = max(int(total), 1), max(int(chunk_max), 1)
    if group > 1:
        if chunk_max >= group:
            return group * balanced_chunk(total // group, chunk_max // group)
        total = group
    n_chunks = -(-total // chunk_max)
    return -(-total // n_chunks)


@functools.partial(jax.jit,
                   static_argnames=("max_depth", "n_bins", "chunk", "frontier",
                                    "exact_cap"))
def fit_forest_chunked(Xb, g, h, w_trees, feat_masks, mcw_trees, max_depth: int,
                       n_bins: int, chunk: int, frontier: int,
                       reg_lambda: float = 1e-6, mig_trees=None,
                       exact_cap: bool = False) -> Tree:
    """Train an arbitrary tree population with bounded memory: ``lax.map``
    over chunks of ``chunk`` trees — one compile, sequential chunks.

    ``feat_masks`` is f32[TT, d] masks or i32[TT, k] kept-feature indices
    (``grow_forest``).  The tree axis TT (a multiple of ``chunk``; callers
    pad with zero-weight trees) may interleave folds x grid candidates x
    bootstrap replicas — per-tree ``mcw_trees``/``mig_trees`` carry the
    grid's min-child-weight and min-info-gain, so a whole RF fold x grid
    sweep is a single launch (SURVEY §2.7 axis 2).
    """
    n = Xb.shape[0]
    if mig_trees is None:
        mig_trees = jnp.zeros_like(mcw_trees)

    def one_chunk(args):
        wts, fms, mcws, migs = args
        lam = jnp.full(wts.shape[0], reg_lambda, jnp.float32)
        gam = jnp.zeros(wts.shape[0], jnp.float32)
        return grow_forest(Xb, g, h, wts, fms, max_depth, n_bins, frontier,
                           reg_lambda_t=lam, gamma_t=gam, mcw_t=mcws,
                           mig_t=migs, exact_cap=exact_cap)

    trees = lax.map(one_chunk, (w_trees.reshape(-1, chunk, n),
                                feat_masks.reshape(-1, chunk,
                                                   feat_masks.shape[1]),
                                mcw_trees.reshape(-1, chunk),
                                mig_trees.reshape(-1, chunk)))
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), trees)


def fit_forest_sharded(mesh, axis_name: str, Xb, g, h, w_trees, feat_masks,
                       mcw_trees, max_depth: int, n_bins: int, chunk: int,
                       frontier: int, reg_lambda: float = 1e-6,
                       mig_trees=None, exact_cap: bool = False) -> Tree:
    """Tree-axis-sharded forest training: each mesh shard grows its slice of
    the tree population with the memory-chunked kernel — zero communication
    (SURVEY §2.7 axis 2; the OpValidator thread pool spread over chips).

    TT must be a multiple of shards * chunk (callers pad with zero-weight
    trees).  Returns the full forest with the tree axis sharded over
    ``axis_name``.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if mig_trees is None:
        mig_trees = jnp.zeros_like(mcw_trees)

    def local(xb, gg, hh, w, fm, mc, mg):
        return fit_forest_chunked(xb, gg, hh, w, fm, mc, max_depth=max_depth,
                                  n_bins=n_bins, chunk=chunk, frontier=frontier,
                                  reg_lambda=reg_lambda, mig_trees=mg,
                                  exact_cap=exact_cap)

    sm = shard_map(local, mesh=mesh,
                   in_specs=(P(), P(), P(), P(axis_name), P(axis_name),
                             P(axis_name), P(axis_name)),
                   out_specs=P(axis_name), check_vma=False)
    return sm(Xb, g, h, w_trees, feat_masks, mcw_trees, mig_trees)


@functools.partial(jax.jit, static_argnames=("max_depth", "n_groups"))
def predict_forest_groups(Xb, forest: Tree, max_depth: int, n_groups: int) -> jax.Array:
    """Mean leaf vector per group of trees: f32[n_groups, n, c] — the eval
    half of the batched fold x grid RF sweep (forest axis = n_groups * T)."""
    preds = jax.vmap(lambda t: predict_tree(Xb, t, max_depth))(forest)  # [TT, n, c]
    return preds.reshape((n_groups, -1) + preds.shape[1:]).mean(axis=1)


# ---------------------------------------------------------------------------
# Gradient boosting — lax.scan over rounds
# ---------------------------------------------------------------------------
def _grad_hess(loss: str, F, y, Y_onehot):
    if loss == "squared":
        return (F[:, 0] - y)[:, None], jnp.ones_like(y)
    if loss == "logistic":
        p = jax.nn.sigmoid(F[:, 0])
        return (p - y)[:, None], jnp.maximum(p * (1 - p), 1e-6)
    if loss == "softmax":
        p = jax.nn.softmax(F, axis=-1)
        # scalar hessian approximation: mean over classes of p(1-p)
        return p - Y_onehot, jnp.maximum((p * (1 - p)).mean(axis=-1), 1e-6)
    raise ValueError(f"unknown loss {loss!r}")


def _gbt_impl(Xb, y, w, row_w_rounds, feat_mask_rounds, loss: str, n_rounds: int,
              max_depth: int, n_bins: int, frontier: int, eta, reg_lambda,
              gamma, min_child_weight, base_score: float, n_classes: int,
              min_info_gain=0.0, exact_cap: bool = False,
              axis_name: Optional[str] = None,
              trees_per_round: int = 1,
              init_margins=None) -> Tuple[Tree, jax.Array]:
    """Traceable boosting body shared by fit_gbt and fit_gbt_batch.

    ``trees_per_round`` = K > 1 collapses the boosting chain: the scan takes
    ``n_rounds / K`` steps, each growing K trees against the SAME gradients
    (their round-specific subsample/colsample draws kept) at learning rate
    ``eta / K`` — the boosted-forest round-collapse.  K must divide
    ``n_rounds``.  The stacked tree axis stays [n_rounds, ...] and
    ``predict_gbt`` with ``eta / K`` scores it unchanged.

    ``init_margins`` (f32[n, c], default None) seeds the boosting carry F
    instead of ``base_score`` — a later segment of a checkpointed fit
    resumes from the previous segment's final margins and grows the exact
    trees the unsegmented scan would have (boosting is sequential over F,
    so carrying F is the whole fit state besides the up-front rw/fm draws).
    """
    n = Xb.shape[0]
    c = n_classes if loss == "softmax" else 1
    Y = jax.nn.one_hot(y.astype(jnp.int32), max(c, 2), dtype=jnp.float32) \
        if loss == "softmax" else jnp.zeros((n, 2), jnp.float32)
    F0 = (jnp.asarray(init_margins, jnp.float32) if init_margins is not None
          else jnp.full((n, c), base_score, jnp.float32))
    K = max(int(trees_per_round), 1)

    record_trace_event("gbt_chain", loss, n_rounds // K)
    # a round is a forest of its K trees (K = 1: the same scan, no collapse)
    if n_rounds % K:
        raise ValueError(
            f"trees_per_round={K} must divide n_rounds={n_rounds}")
    steps = n_rounds // K
    rw_s = row_w_rounds.reshape(steps, K, n)
    fm_s = feat_mask_rounds.reshape(steps, K, -1)
    as_k = lambda v: jnp.broadcast_to(jnp.asarray(v, jnp.float32), (K,))

    def step_fn(F, xs):
        rwk, fmk = xs                              # [K, n], [K, d]
        g, hh = _grad_hess(loss, F, y, Y)
        trees, row_node = grow_forest(
            Xb, g, hh, w[None, :] * rwk, fmk, max_depth, n_bins,
            frontier, reg_lambda_t=as_k(reg_lambda), gamma_t=as_k(gamma),
            mcw_t=as_k(min_child_weight), mig_t=as_k(min_info_gain),
            exact_cap=exact_cap, return_row_node=True,
            axis_name=axis_name)
        # row_node is each row's resting node — no predict walk needed
        leaves = read_leaves(trees.leaf_val, row_node)         # [K, c, n]
        F = F + (eta / K) * leaves.sum(axis=0).T
        return F, trees

    F, trees = lax.scan(step_fn, F0, (rw_s, fm_s))
    # restore the flat [n_rounds, ...] tree axis
    trees = jax.tree.map(
        lambda a: a.reshape((n_rounds,) + a.shape[2:]), trees)
    return trees, F


@functools.partial(jax.jit, static_argnames=("loss", "n_rounds", "max_depth",
                                             "n_bins", "n_classes", "frontier",
                                             "exact_cap", "trees_per_round"))
def fit_gbt(Xb, y, w, row_w_rounds, feat_mask_rounds, loss: str, n_rounds: int,
            max_depth: int, n_bins: int, frontier: int, eta: float = 0.3,
            reg_lambda: float = 1.0, gamma: float = 0.0,
            min_child_weight: float = 1.0, base_score: float = 0.0,
            n_classes: int = 1, min_info_gain: float = 0.0,
            exact_cap: bool = False,
            trees_per_round: int = 1,
            init_margins=None) -> Tuple[Tree, jax.Array]:
    """XGBoost-style boosting: scan over rounds, one histogram tree per round.

    row_w_rounds: f32[R, n] subsample weights per round; feat_mask_rounds:
    f32[R, d] colsample masks.  Multiclass uses multi-output trees (leaf
    vector per class) — a TPU-friendly variant of per-class tree sets.
    ``trees_per_round`` = K > 1 grows K trees per boosting step at eta / K
    (round-collapse; callers scoring the stacked trees must scale eta the
    same way).  ``init_margins`` seeds the carry F for segmented
    (checkpoint-resumable) fits.  Returns (stacked Tree [R, ...], final
    margins F [n, c]).
    """
    return _gbt_impl(Xb, y, w, row_w_rounds, feat_mask_rounds, loss, n_rounds,
                     max_depth, n_bins, frontier, eta, reg_lambda, gamma,
                     min_child_weight, base_score, n_classes,
                     min_info_gain=min_info_gain, exact_cap=exact_cap,
                     trees_per_round=trees_per_round,
                     init_margins=init_margins)


def _gbt_batch_impl(Xb, y, w_batch, row_w_rounds, feat_mask_rounds, loss: str,
                    n_rounds: int, max_depth: int, n_bins: int, frontier: int,
                    eta_b, reg_lambda_b, gamma_b, min_child_weight_b,
                    base_score_b=None, n_classes: int = 1,
                    min_info_gain_b=None, exact_cap: bool = False,
                    axis_name: Optional[str] = None,
                    trees_per_round: int = 1) -> jax.Array:
    """Traceable body of :func:`fit_gbt_batch` — also called directly by the
    fused sweep (ops/sweep.py) with ``axis_name`` set on the row-sharded
    path and ``trees_per_round`` > 1 for round-collapsed GBT groups.

    With K = ``trees_per_round``, every scan step grows B * K trees as one
    flat-GEMM forest (K per candidate, against that candidate's step
    gradients, each keeping its own round subsample/colsample draw) and
    applies their mean at learning rate ``eta_b`` (i.e. eta / K each) — the
    boosted-forest round-collapse.  K = 1 reproduces the per-round scan
    bit-for-bit (the K-generalized reshapes are layout no-ops).
    """
    if base_score_b is None:
        base_score_b = jnp.zeros(w_batch.shape[0], jnp.float32)
    if min_info_gain_b is None:
        min_info_gain_b = jnp.zeros(w_batch.shape[0], jnp.float32)

    Xb = Xb.astype(jnp.int32)
    n, d = Xb.shape
    B = w_batch.shape[0]
    c = n_classes if loss == "softmax" else 1
    K = int(trees_per_round)
    if n_rounds % max(K, 1):
        raise ValueError(
            f"trees_per_round={K} must divide n_rounds={n_rounds}")
    # every step grows its B * K trees as ONE
    # flat-GEMM forest: per-tree gradients ride the LHS, the RHS is the
    # gradient-free bin one-hot of a row block (see _grow_level_batch)
    Y = jax.nn.one_hot(y.astype(jnp.int32), max(c, 2), dtype=jnp.float32) \
        if loss == "softmax" else jnp.zeros((n, 2), jnp.float32)
    F0 = jnp.broadcast_to(base_score_b[:, None, None], (B, n, c)).astype(jnp.float32)
    steps = n_rounds // K
    record_trace_event("gbt_chain", loss, steps)
    rw_s = row_w_rounds.reshape(steps, K, n)
    fm_s = feat_mask_rounds.reshape(steps, K, d)

    def step_fn(F, xs):
        rwk, fmk = xs                                  # [K, n], [K, d] shared
        if loss == "squared":
            gb = F[..., 0] - y[None, :]
            hb = jnp.ones((B, n), jnp.float32)
            g3 = gb[..., None]
        elif loss == "logistic":
            p = jax.nn.sigmoid(F[..., 0])
            g3 = (p - y[None, :])[..., None]
            hb = jnp.maximum(p * (1 - p), 1e-6)
        else:  # softmax
            p = jax.nn.softmax(F, axis=-1)
            g3 = p - Y[None, :, :]
            hb = jnp.maximum((p * (1 - p)).mean(axis=-1), 1e-6)
        gh_t = jnp.concatenate([g3, hb[..., None]], axis=-1)   # [B, n, c1]
        # candidate-major tree axis [B * K]: candidate b's K trees share its
        # gradients but keep their own round draws
        gh_T = jnp.repeat(gh_t, K, axis=0)
        w_T = (w_batch[:, None, :] * rwk[None, :, :]).reshape(B * K, n)
        fm_T = jnp.broadcast_to(fmk[None, :, :], (B, K, d)).reshape(B * K, d)
        tree, row_node = grow_forest(
            Xb, None, None, w_T, fm_T, max_depth, n_bins,
            frontier, reg_lambda_t=jnp.repeat(reg_lambda_b, K),
            gamma_t=jnp.repeat(gamma_b, K),
            mcw_t=jnp.repeat(min_child_weight_b, K),
            mig_t=jnp.repeat(min_info_gain_b, K),
            exact_cap=exact_cap, return_row_node=True,
            gh_t=gh_T, axis_name=axis_name)
        # row_node tracks each row's leaf: one selection a step
        leaves = read_leaves(tree.leaf_val, row_node)        # [B * K, c, n]
        leaves = leaves.reshape(B, K, c, n).sum(axis=1)
        F = F + (eta_b / K)[:, None, None] * jnp.swapaxes(leaves, 1, 2)
        return F, None

    F, _ = lax.scan(step_fn, F0, (rw_s, fm_s))
    return F


@functools.partial(jax.jit, static_argnames=("loss", "n_rounds", "max_depth",
                                             "n_bins", "n_classes", "frontier",
                                             "exact_cap", "trees_per_round"))
def fit_gbt_batch(Xb, y, w_batch, row_w_rounds, feat_mask_rounds, loss: str,
                  n_rounds: int, max_depth: int, n_bins: int, frontier: int,
                  eta_b, reg_lambda_b, gamma_b, min_child_weight_b,
                  base_score_b=None, n_classes: int = 1,
                  min_info_gain_b=None, exact_cap: bool = False,
                  trees_per_round: int = 1) -> jax.Array:
    """The fold x grid boosting sweep as ONE launch (the OpValidator
    thread-pool analog for boosted models — SURVEY §2.7 axis 2).

    ``w_batch`` f32[B, n] carries fold-mask x sample weights per batch
    element; ``eta_b``/``reg_lambda_b``/``gamma_b``/``min_child_weight_b``
    f32[B] are the grid's dynamic hyperparameters (static shape params —
    depth, rounds, bins, trees_per_round — must match across the batch; the
    caller groups grids accordingly).  Returns final margins F f32[B, n, c]
    on the FULL dataset, from which fold-validation slices are taken.
    """
    return _gbt_batch_impl(Xb, y, w_batch, row_w_rounds, feat_mask_rounds,
                           loss, n_rounds, max_depth, n_bins, frontier,
                           eta_b, reg_lambda_b, gamma_b, min_child_weight_b,
                           base_score_b=base_score_b, n_classes=n_classes,
                           min_info_gain_b=min_info_gain_b,
                           exact_cap=exact_cap,
                           trees_per_round=trees_per_round)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def predict_gbt(Xb, trees: Tree, max_depth: int, eta: float,
                base_score: float = 0.0) -> jax.Array:
    """Sum of shrunken tree outputs: f32[n, c]."""
    preds = jax.vmap(lambda t: predict_tree(Xb, t, max_depth))(trees)  # [R, n, c]
    return base_score + eta * preds.sum(axis=0)


# ---------------------------------------------------------------------------
# Subsampling masks — DEVICE-side RNG (threefry: identical draws on every
# backend).  These are traceable and run INSIDE the fit kernels, so the
# sweep never uploads [T, n] bootstrap matrices (one host->device transfer
# per draw otherwise).
# fit_arrays and the fused sweep interpreter share the same (seed -> key ->
# draw) scheme, so the batched fold x grid path trains on EXACTLY the same
# bootstraps as the per-candidate loop path (tests/test_batched_tree_sweep).
# ---------------------------------------------------------------------------
def rng_keys(seed: int):
    """(bootstrap_key, feature_key) — the canonical split both paths use."""
    kb, kf = jax.random.split(jax.random.PRNGKey(jnp.uint32(seed)))
    return kb, kf


def bootstrap_weights(key, n: int, n_trees: int, bootstrap: bool = True,
                      rate: float = 1.0) -> jax.Array:
    """Poisson(rate) bootstrap weights — the with-replacement limit Spark's
    BaggedPoint uses, with ``rate`` = RF subsamplingRate (each tree sees a
    bootstrap of expected size ``n * rate``).  Traceable."""
    if not bootstrap:
        return jnp.ones((n_trees, n), jnp.float32)
    return jax.random.poisson(key, rate, (n_trees, n)).astype(jnp.float32)


def n_kept(d: int, frac: float) -> int:
    """Features a tree keeps of ``d`` at subset fraction ``frac`` (static)."""
    return d if frac >= 1.0 else min(d, max(1, int(round(frac * d))))


def feature_masks(key, d: int, n_trees: int, frac: float) -> jax.Array:
    """Per-tree feature-subset masks (featureSubsetStrategy / colsample):
    exactly ``n_kept(d, frac)`` features per tree, those with the k smallest
    of one uniform draw per feature; of two draws that tie at the k-th place
    the lower feature index is kept (never k + 1).  Traceable."""
    if frac >= 1.0:
        return jnp.ones((n_trees, d), jnp.float32)
    r = jax.random.uniform(key, (n_trees, d))
    rank = jnp.argsort(jnp.argsort(r, axis=1, stable=True), axis=1)
    return (rank < n_kept(d, frac)).astype(jnp.float32)


def kept_features(key, d: int, n_trees: int, frac: float) -> jax.Array:
    """i32[T, k], k = ``n_kept(d, frac)``: the original indices of the
    features each tree's ``feature_masks`` draw keeps, ascending — the table
    ``grow_forest`` grows a tree's compacted feature axis from; its static
    width is the width the levels are built at.  Traceable."""
    masks = feature_masks(key, d, n_trees, frac)
    cols = jnp.where(masks > 0, jnp.arange(d, dtype=jnp.int32), d)
    return jnp.sort(cols, axis=1)[:, :n_kept(d, frac)]


def subsample_weights(key, n: int, n_rounds: int, frac: float) -> jax.Array:
    """Per-round row-subsample masks (GBT subsamplingRate / XGB subsample).
    Traceable."""
    if frac >= 1.0:
        return jnp.ones((n_rounds, n), jnp.float32)
    return (jax.random.uniform(key, (n_rounds, n)) < frac).astype(jnp.float32)


# ---------------------------------------------------------------------------
# FLOPs accounting (bench MFU): wrap the tree kernels so every call records
# its XLA cost_analysis when utils.flops is enabled.  NOTE: the recorded
# flops are XLA's arithmetic count for the optimized HLO (the one-hot GEMM's
# B*m-fold contraction included), not the work the histogram method requires
# (benchmarks/trees_ops_count.py).
# ---------------------------------------------------------------------------
from ..utils import flops as _flops  # noqa: E402

for _n in ("fit_forest", "fit_forest_chunked", "fit_gbt", "fit_gbt_batch",
           "predict_forest", "predict_forest_groups", "predict_gbt"):
    globals()[_n] = _flops.wrap(f"trees.{_n}", globals()[_n])
del _n
