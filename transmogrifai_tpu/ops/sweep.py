"""The fused selector sweep: the WHOLE fold x grid model sweep as ONE launch.

Reference parity: OpValidator.scala:299-357 trains numFolds x models x grids
Spark fits on an 8-thread JVM pool and evaluates each on its own Spark job.
The TPU-first replacement batches everything:

- every family's fold x grid block is a vmapped training program (linear
  FISTA/Newton, histogram forests, scan-over-rounds boosting),
- bootstrap / feature-subset / row-subsample draws happen ON DEVICE
  (ops/trees.rng_keys scheme, shared with ``fit_arrays`` for parity),
- validation metrics (ops/metrics) are computed on device for all
  fold x candidate pairs at once,

and — the round-5 step — ALL of it runs inside ONE jitted program driven by
a hashable static ``spec``, so a steady-state sweep costs one host->device
upload (fold weights + hyperparameter blob), one launch, and one [F, C, M]
metrics pull.  Every launch, upload and pull is a host round trip, which
made the legacy per-family path latency-bound; the fused program removes
~all of them.

Spec grammar (static, hashable; built by impl/sweep_fragments.py).  Every
fragment's ``cis`` is the tuple of candidate positions (static ints) it
fills in the GLOBAL candidate order; ``off_*`` index the dynamic f32
hyperparameter ``blob``; ``xb_idx`` picks the pre-binned matrix in ``xbs``:

    spec = (problem, frags, strict)
    problem ∈ {"binary", "regression", ("multiclass", k)}
    frag = ("fista",  cis, max_iter, fit_intercept, off_l1, off_l2)
         | ("newton", cis, max_iter, fit_intercept, off_l2)
         | ("svc",    cis, max_iter, fit_intercept, off_l2)
         | ("mlp",    cis, layers, max_iter, off_lr, off_seed)
         | ("forest", out_c, groups)   # RF / DT
         | ("gbt", loss, out_c, groups)
    forest group = (cis, depth, n_trees, xb_idx, n_bins, frac, rate,
                    bootstrap, seed, frontier, exact_cap, chunk,
                    off_mcw, off_mig)
    gbt group    = (cis, rounds, depth, xb_idx, n_bins, subsample, colsample,
                    seed, frontier, exact_cap, fold_base, trees_per_round,
                    off_eta, off_lam, off_gam, off_mcw, off_mig)

``trees_per_round`` (K) is the round-collapse factor: K > 1 shortens the
boosting scan to rounds / K steps, growing K trees per step at eta / K
(ops/trees._gbt_batch_impl).  K = 1 is the exact per-round scan.

``strict`` is the per-candidate 0/1 tuple choosing ``score > 0.5`` vs
``>= 0.5`` for the class decision (matches each family's host
``predict_arrays`` convention).  The interpreter returns the stacked
metrics tensor [F, C, M] (metric order: ops/metrics.BINARY_METRICS or
REGRESSION_METRICS).
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map as _shard_map

from ..obs import ledger as _ledger
from ..obs import registry as obs_registry
from ..obs import trace
from ..parallel import mesh as mesh_mod
from ..resilience import checkpoint as _ckpt
from ..resilience import hedge as _hedge
from ..resilience import health as _health
from ..resilience import inject as _inject
from ..resilience import retry as _retry
from ..parallel.mesh import mesh_all_gather, mesh_psum
from ..utils import devcache, flops
from ..utils.backend import compile_cache_dir
from . import linear as L
from . import trees as Tr
from .metrics import (BINARY_METRICS, MULTICLASS_METRICS, REGRESSION_METRICS,
                      _binary_grid_metrics, _binary_one,
                      _multiclass_grid_metrics, _multiclass_one,
                      _regression_grid_metrics, _regression_one)

__all__ = ["run_sweep", "run_sweep_partitioned", "run_sweep_rowsharded",
           "reset_run_stats", "run_stats", "record_fallback",
           "BINARY_METRICS", "MULTICLASS_METRICS", "REGRESSION_METRICS"]


# ---------------------------------------------------------------------------
# Fragment interpreters (traced inline inside the one fused program)
#
# Every interpreter takes an optional row-shard context ``rs = (axis_name,
# n_orig, n_data)`` (static).  With ``rs=None`` the trace is byte-identical
# to the replicated program.  With it, the interpreter's row axis holds ONE
# data shard of ``n_orig`` padded rows: the training kernels psum their
# cross-row reductions over ``axis_name`` (ops/linear, ops/trees, ops/mlp),
# on-device RNG draws happen at the ORIGINAL row count (shape-keyed Poisson/
# uniform draws must match the single-device stream bit-for-bit) and are then
# sliced to the local block, and all per-row state stays local.
# ---------------------------------------------------------------------------
def _rs_axis(rs) -> Optional[str]:
    return None if rs is None else rs[0]


def _local_rows(full, n_local: int, rs, axis: int = 0):
    """This shard's contiguous block of a full-row array drawn at n_orig.

    Zero-pads ``axis`` from n_orig up to ``n_data * n_local`` (padding rows
    carry zero weight everywhere downstream) and slices the block at
    ``axis_index * n_local`` — shard_map row shards are contiguous."""
    axis_name, _, n_data = rs
    pad = n_data * n_local - full.shape[axis]
    if pad:
        widths = [(0, 0)] * full.ndim
        widths[axis] = (0, pad)
        full = jnp.pad(full, widths)
    start = lax.axis_index(axis_name) * n_local
    return lax.dynamic_slice_in_dim(full, start, n_local, axis=axis)


def _fista_scores(frag, X, y, train_w, blob, classification: bool, rs=None):
    _, cis, max_iter, fit_intercept, off_l1, off_l2 = frag
    G = len(cis)
    l1 = blob[off_l1:off_l1 + G]
    l2 = blob[off_l2:off_l2 + G]
    ax = _rs_axis(rs)
    if classification:
        fit = L.fit_logistic_grid_folds_fista(X, y, train_w, l1, l2,
                                              max_iter=max_iter,
                                              fit_intercept=fit_intercept,
                                              axis_name=ax)
        z = jnp.einsum("nd,fgd->fgn", X, fit.coef) + fit.intercept[..., :1]
        return jax.nn.sigmoid(z)
    fit = L.fit_linear_grid_folds_fista(X, y, train_w, l1, l2,
                                        max_iter=max_iter,
                                        fit_intercept=fit_intercept,
                                        axis_name=ax)
    return jnp.einsum("nd,fgd->fgn", X, fit.coef) + fit.intercept[..., :1]


def _softmax_scores(frag, X, y, train_w, blob, k: int, rs=None):
    """Multiclass logistic: class probabilities [F, G, n, k]."""
    _, cis, max_iter, fit_intercept, off_l1, off_l2 = frag
    G = len(cis)
    l1 = blob[off_l1:off_l1 + G]
    l2 = blob[off_l2:off_l2 + G]
    fit = L.fit_softmax_grid_folds(X, y, train_w, l1, l2, num_classes=k,
                                   max_iter=max_iter,
                                   fit_intercept=fit_intercept,
                                   axis_name=_rs_axis(rs))
    z = jnp.einsum("nd,fgdk->fgnk", X, fit.coef) + fit.intercept[:, :, None, :]
    return jax.nn.softmax(z, axis=-1)


def _newton_scores(frag, X, y, train_w, blob, rs=None):
    _, cis, max_iter, fit_intercept, off_l2 = frag
    l2 = blob[off_l2:off_l2 + len(cis)]
    fit = L.fit_logistic_grid_folds_newton(X, y, train_w, l2,
                                           max_iter=max_iter,
                                           fit_intercept=fit_intercept,
                                           axis_name=_rs_axis(rs))
    z = jnp.einsum("nd,fgd->fgn", X, fit.coef) + fit.intercept[..., :1]
    return jax.nn.sigmoid(z)


def _svc_scores(frag, X, y, train_w, blob, rs=None):
    """Squared-hinge SVC: the host path emits raw margins but NO probability
    (Spark LinearSVC parity), so its evaluator sees the HARD prediction as
    the score — the fused score reproduces exactly that 0/1 score."""
    _, cis, max_iter, fit_intercept, off_l2 = frag
    l2 = blob[off_l2:off_l2 + len(cis)]
    fit = L.fit_svc_grid_folds(X, y, train_w, l2, max_iter=max_iter,
                               fit_intercept=fit_intercept,
                               axis_name=_rs_axis(rs))
    z = jnp.einsum("nd,fgd->fgn", X, fit.coef) + fit.intercept[..., :1]
    return (z >= 0.0).astype(jnp.float32)


def _mlp_scores(frag, X, y, train_w, blob, full_prob: bool = False, rs=None):
    """Batched MLP: p(class 1) — or the full [F, G, n, k] distribution."""
    from . import mlp as M

    _, cis, layers, max_iter, off_lr, off_seed = frag
    G = len(cis)
    lrs = blob[off_lr:off_lr + G]
    seeds = blob[off_seed:off_seed + G].astype(jnp.int32)
    params = M.fit_mlp_grid_folds(X, y, train_w, lrs, seeds,
                                  layers=layers, max_iter=max_iter,
                                  axis_name=_rs_axis(rs))
    _, prob, _ = M.predict_mlp_grid(params, X)
    return prob if full_prob else prob[..., 1]


def _forest_group_scores(group, xbs, y, train_w, blob, out_c: int, rs=None):
    """One static forest group -> mean leaf vectors [F, Gc, n, c].

    Grouping (builder side) keys on (depth, n_trees, n_bins, frac, rate,
    bootstrap, seed), so ONE (bootstrap, feature-mask) draw — keyed exactly
    as ``fit_arrays`` keys it — serves every (fold, candidate) of the group,
    matching the legacy per-candidate path draw-for-draw.
    """
    (cis, depth, n_trees, xb_idx, n_bins, frac, rate, bootstrap, seed,
     frontier, exact_cap, chunk, off_mcw, off_mig) = group
    Xb = xbs[xb_idx]
    n, d = Xb.shape
    F = train_w.shape[0]
    Gc = len(cis)
    kb, kf = Tr.rng_keys(seed)
    if rs is None:
        boot = Tr.bootstrap_weights(kb, n, n_trees, bootstrap, rate)  # [T, n]
    else:
        # Poisson draws are shape-keyed: parity with the single-device launch
        # requires drawing the FULL [T, n_orig] stream, then slicing this
        # shard's contiguous row block (padding rows get fresh draws that are
        # zeroed by the padded train_w)
        boot = _local_rows(
            Tr.bootstrap_weights(kb, rs[1], n_trees, bootstrap, rate),
            n, rs, axis=1)
    # the draw's kept features as an index table: its static width k is what
    # grow_forest grows the chunk at (k < d: on the kept columns alone)
    fi = Tr.kept_features(kf, d, n_trees, frac)                   # [T, k]
    # a chunk adds up its own trees' leaves, ``unit`` trees at a time: whole
    # forests where the plan's chunk holds one (``Tr.balanced_chunk`` with
    # the forest as its group), else equal parts of one, each forest filled
    # up to whole parts with zero-weight trees, which grow nothing and read
    # 0 at every leaf — the [TT, n, c] leaf reads of a group (1.18 GB at 900
    # trees x 32,768 rows x 10 classes) never exist side by side
    unit = min(chunk, n_trees)
    chunk -= chunk % unit
    per = -(-n_trees // unit) * unit
    if per > n_trees:
        boot = jnp.concatenate([boot, jnp.zeros((per - n_trees, n), boot.dtype)])
        fi = jnp.concatenate([fi, jnp.tile(fi[:1], (per - n_trees, 1))])
    g = -y[:, None] if out_c == 1 else -jax.nn.one_hot(
        y.astype(jnp.int32), out_c, dtype=jnp.float32)
    h = jnp.ones_like(y)

    mcw = blob[off_mcw:off_mcw + Gc]
    mig = blob[off_mig:off_mig + Gc]
    # tree population: (fold, candidate, tree) -> [F*Gc*T, n]
    wt = jnp.broadcast_to(boot[None, None] * train_w[:, None, None, :],
                          (F, Gc, per, n)).reshape(F * Gc * per, n)
    mcw_t = jnp.tile(jnp.repeat(mcw, per), F)
    mig_t = jnp.tile(jnp.repeat(mig, per), F)
    fi_t = jnp.tile(fi, (F * Gc, 1))
    TT = F * Gc * per
    pad = (-TT) % chunk
    if pad:  # zero-weight padding trees grow nothing and are sliced off
        wt = jnp.concatenate([wt, jnp.zeros((pad, n), jnp.float32)])
        fi_t = jnp.concatenate([fi_t, jnp.tile(fi[:1], (pad, 1))])
        mcw_t = jnp.concatenate([mcw_t, jnp.ones(pad, jnp.float32)])
        mig_t = jnp.concatenate([mig_t, jnp.zeros(pad, jnp.float32)])

    def one_chunk(args):
        wts, fis, mcws, migs = args
        lam = jnp.full(wts.shape[0], 1e-6, jnp.float32)
        gam = jnp.zeros(wts.shape[0], jnp.float32)
        tree, row_node = Tr.grow_forest(
            Xb, g, h, wts, fis, depth, n_bins, frontier,
            reg_lambda_t=lam, gamma_t=gam, mcw_t=mcws, mig_t=migs,
            exact_cap=exact_cap, return_row_node=True,
            axis_name=_rs_axis(rs))
        # growth routes EVERY row (weights only gate histograms), so
        # row_node already holds each row's leaf: no depth-step pointer walk.
        # The leaves are read by selection, a plane a class with the rows
        # minor, [chunk, c, n] — as per-element gathers they were ~8.7 s of
        # the ten-class grid's 19.3 s step on a v5e (PERF.md, PR 34), and a
        # [chunk, n, c] read would lay the c classes on the TPU's 128 lanes
        # (12.8 x its bytes at c = 10, PERF.md PR 33)
        leaf = Tr.read_leaves(tree.leaf_val, row_node)
        return leaf.reshape((chunk // unit, unit) + leaf.shape[1:]).sum(axis=1)

    sums = lax.map(one_chunk, (wt.reshape(-1, chunk, n),
                               fi_t.reshape(-1, chunk, fi.shape[1]),
                               mcw_t.reshape(-1, chunk),
                               mig_t.reshape(-1, chunk)))
    sums = sums.reshape((-1,) + sums.shape[2:])[:TT // unit]  # [TT/unit, c, n]
    mean = sums.reshape((F, Gc, per // unit) + sums.shape[1:]).sum(axis=2) \
        / n_trees
    return jnp.moveaxis(mean, 2, 3)                           # [F, Gc, n, c]


def _gbt_group_scores(group, xbs, y, train_w, blob, loss: str, out_c: int,
                      rs=None):
    """One static boosting group -> final margins [F, Gc, n, c]."""
    (cis, rounds, depth, xb_idx, n_bins, subsample, colsample, seed,
     frontier, exact_cap, fold_base, trees_per_round, off_eta, off_lam,
     off_gam, off_mcw, off_mig) = group
    Xb = xbs[xb_idx]
    n, d = Xb.shape
    F = train_w.shape[0]
    Gc = len(cis)
    ax = _rs_axis(rs)
    ks, kf = Tr.rng_keys(seed)
    if rs is None:
        rw = Tr.subsample_weights(ks, n, rounds, subsample)
    else:  # full-stream draw then local slice — see _forest_group_scores
        rw = _local_rows(Tr.subsample_weights(ks, rs[1], rounds, subsample),
                         n, rs, axis=1)
    fms = Tr.feature_masks(kf, d, rounds, colsample)

    eta = blob[off_eta:off_eta + Gc]
    lam = jnp.maximum(blob[off_lam:off_lam + Gc], 1e-6)
    gam = blob[off_gam:off_gam + Gc]
    mcw = blob[off_mcw:off_mcw + Gc]
    mig = blob[off_mig:off_mig + Gc]

    if fold_base:  # regression boosting starts from the fold's label mean
        base_f = (mesh_psum((y[None, :] * train_w).sum(1), ax)
                  / jnp.maximum(mesh_psum(train_w.sum(1), ax), 1e-12))
    else:
        base_f = jnp.zeros(F, jnp.float32)

    w_b = jnp.repeat(train_w, Gc, axis=0)              # [F*Gc, n]
    eta_b = jnp.tile(eta, F)
    lam_b = jnp.tile(lam, F)
    gam_b = jnp.tile(gam, F)
    mcw_b = jnp.tile(mcw, F)
    mig_b = jnp.tile(mig, F)
    base_b = jnp.repeat(base_f, Gc)

    # one batch-native scan for every K (K = 1: the exact per-round scan)
    Fm = Tr._gbt_batch_impl(Xb, y, w_b, rw, fms, loss, rounds, depth,
                            n_bins, frontier, eta_b, lam_b, gam_b, mcw_b,
                            base_score_b=base_b, n_classes=out_c,
                            min_info_gain_b=mig_b, exact_cap=exact_cap,
                            axis_name=ax, trees_per_round=trees_per_round)
    return Fm.reshape(F, Gc, n, -1)


def _frag_scores(frag, X, xbs, y, train_w, blob, problem, rs=None):
    """Returns (cis, scores [F, Gf, n] — or [F, Gf, n, k] multiclass)."""
    kind = frag[0]
    multiclass = isinstance(problem, tuple)
    classification = problem == "binary" or multiclass
    # each family's device ops carry its scope name in a profiler trace
    # (metadata only: benchmarks/program_spans.py splits _run_scores by it)
    if kind == "fista":
        if multiclass:
            with jax.named_scope("scores.softmax"):
                return frag[1], _softmax_scores(frag, X, y, train_w, blob,
                                                problem[1], rs=rs)
        with jax.named_scope("scores.fista"):
            return frag[1], _fista_scores(frag, X, y, train_w, blob,
                                          classification, rs=rs)
    if kind == "newton":
        with jax.named_scope("scores.newton"):
            return frag[1], _newton_scores(frag, X, y, train_w, blob, rs=rs)
    if kind == "svc":
        with jax.named_scope("scores.svc"):
            return frag[1], _svc_scores(frag, X, y, train_w, blob, rs=rs)
    if kind == "mlp":
        with jax.named_scope("scores.mlp"):
            return frag[1], _mlp_scores(frag, X, y, train_w, blob,
                                        full_prob=multiclass, rs=rs)
    if kind == "forest":
        _, out_c, groups = frag
        cis_all, outs = [], []
        for grp in groups:
            with jax.named_scope("scores.forest"):
                dist = _forest_group_scores(grp, xbs, y, train_w, blob,
                                            out_c, rs=rs)
            # binary classification: 1-channel leaves ARE p(class=1);
            # regression: mean leaves are the prediction; multiclass keeps
            # the class-distribution leaves (argmax-equivalent unnormalized);
            # k=2-multiclass trains the SAME 1-channel binary kernel as the
            # legacy path and expands p -> [1-p, p] here
            if multiclass and dist.shape[-1] == 1:
                dist = jnp.concatenate([1.0 - dist, dist], axis=-1)
            outs.append(dist if multiclass else dist[..., 0])
            cis_all.extend(grp[0])
        return cis_all, jnp.concatenate(outs, axis=1)
    if kind == "gbt":
        _, loss, out_c, groups = frag
        cis_all, outs = [], []
        for grp in groups:
            with jax.named_scope("scores.gbt"):
                Fm = _gbt_group_scores(grp, xbs, y, train_w, blob, loss,
                                       out_c, rs=rs)
            if loss == "softmax":
                outs.append(jax.nn.softmax(Fm, axis=-1))
            elif loss == "logistic":
                outs.append(jax.nn.sigmoid(Fm[..., 0]))
            else:  # squared: the margin IS the prediction
                outs.append(Fm[..., 0])
            cis_all.extend(grp[0])
        return cis_all, jnp.concatenate(outs, axis=1)
    raise ValueError(f"unknown sweep fragment {kind!r}")


def _all_scores(spec, X, xbs, y, train_w, blob, rs=None):
    problem, frags, strict = spec
    n = y.shape[0]
    F = train_w.shape[0]
    C = len(strict)
    if isinstance(problem, tuple):  # ("multiclass", k)
        scores = jnp.zeros((F, C, n, problem[1]), jnp.float32)
    else:
        scores = jnp.zeros((F, C, n), jnp.float32)
    for frag in frags:
        cis, sc = _frag_scores(frag, X, xbs, y, train_w, blob, problem, rs=rs)
        if isinstance(problem, tuple) and sc.ndim == 3:
            # binary-family fragment under a k=2 multiclass evaluator:
            # expand the class-1 score to the [p0, p1] plane
            sc = jnp.stack([1.0 - sc, sc], axis=-1)
        scores = scores.at[:, np.asarray(cis, np.int64)].set(sc)
    return scores


def _metrics_scope(problem) -> str:
    """The profiler-trace scope of the metric pass for this problem type."""
    if isinstance(problem, tuple):
        return "metrics.multiclass"
    return "metrics.binary" if problem == "binary" else "metrics.regression"


def _metrics_of(spec, y, scores, val_w):
    problem, _, strict = spec
    with jax.named_scope(_metrics_scope(problem)):
        if isinstance(problem, tuple):
            y1 = jax.nn.one_hot(y.astype(jnp.int32), problem[1],
                                dtype=jnp.float32)
            return _multiclass_grid_metrics(y1, scores, val_w)
        if problem == "binary":
            return _binary_grid_metrics(y, scores, val_w,
                                        jnp.asarray(strict, jnp.float32))
        return _regression_grid_metrics(y, scores, val_w)


@functools.partial(jax.jit, static_argnames=("spec",))
def _run(spec, X, xbs, y, train_w, val_w, blob):
    return _metrics_of(spec, y, _all_scores(spec, X, xbs, y, train_w, blob),
                       val_w)


@functools.partial(jax.jit, static_argnames=("spec",))
def _run_scores(spec, X, xbs, y, train_w, blob):
    return _all_scores(spec, X, xbs, y, train_w, blob)


@functools.partial(jax.jit, static_argnames=("spec",))
def _run_metrics(spec, y, scores, val_w):
    return _metrics_of(spec, y, scores, val_w)


def _metrics_of_rs(spec, y, scores, val_w, rs):
    """Row-sharded metrics pass -> [F, C, M], identical on every data shard.

    The sum-shaped metrics could psum their accumulators, but AuROC/AuPR are
    rank-based and need the GLOBAL row order.  Reassembling the whole
    [F, C, n] score tensor at once would forfeit the 1/data_shards score-
    memory win, so the candidate axis runs under ``lax.map``: per candidate,
    all_gather this shard's [F, n_local] score block to [F, n_pad] (a
    transient), evaluate the single-candidate metric kernels on globally
    ordered rows, and move on.  Padding rows carry zero validation weight and
    the metric kernels already treat vm=0 rows as excluded.

    Candidate packing (``TMOG_SWEEP_PACK``): the map runs
    ``_metric_pack_size()`` candidates per step (inner ``vmap``), so the
    sequential step count drops from C to ``ceil(C / P)`` while each
    candidate's math is the untouched single-candidate kernel.  The
    candidate axis zero-pads up to a multiple of P (dummy lanes are
    sliced off; their scores are zeros and their outputs discarded)."""
    problem, _, strict = spec
    ax = rs[0]
    C = int(scores.shape[1])
    k = problem[1] if isinstance(problem, tuple) else 1
    P_pack = _metric_pack_size(C, int(scores.shape[0]),
                               int(scores.shape[2]) * int(rs[2]), k)

    def packed_map(body, xs):
        if P_pack <= 1:
            return lax.map(body, xs)
        pad = (-C) % P_pack

        def prep(a):
            if pad:
                a = jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
            return a.reshape((-(-C // P_pack), P_pack) + a.shape[1:])

        out = lax.map(jax.vmap(body), jax.tree.map(prep, xs))
        return out.reshape((-1,) + out.shape[2:])[:C]

    y_full = mesh_all_gather(y, ax, axis=0)             # [n_pad]
    vw_full = mesh_all_gather(val_w, ax, axis=1)        # [F, n_pad]
    if isinstance(problem, tuple):
        y1 = jax.nn.one_hot(y_full.astype(jnp.int32), problem[1],
                            dtype=jnp.float32)

        def one_mc(sc):                                 # sc [F, n_local, k]
            sf = mesh_all_gather(sc, ax, axis=1)        # [F, n_pad, k]
            return jax.vmap(_multiclass_one, in_axes=(None, 0, 0))(
                y1, sf, vw_full)                        # [F, M]

        out = packed_map(one_mc, jnp.moveaxis(scores, 1, 0))
        return jnp.moveaxis(out, 0, 1)                  # [F, C, M]
    if problem == "binary":
        def one_bin(args):
            sc, st = args                               # [F, n_local], f32
            sf = mesh_all_gather(sc, ax, axis=1)        # [F, n_pad]
            return jax.vmap(_binary_one, in_axes=(None, 0, 0, None))(
                y_full, sf, vw_full, st)                # [F, M]

        out = packed_map(one_bin, (jnp.moveaxis(scores, 1, 0),
                                   jnp.asarray(strict, jnp.float32)))
        return jnp.moveaxis(out, 0, 1)

    def one_reg(sc):
        sf = mesh_all_gather(sc, ax, axis=1)
        return jax.vmap(_regression_one, in_axes=(None, 0, 0))(
            y_full, sf, vw_full)

    out = packed_map(one_reg, jnp.moveaxis(scores, 1, 0))
    return jnp.moveaxis(out, 0, 1)


@functools.partial(jax.jit, static_argnames=("spec", "mesh", "n_orig"))
def _run_rs(spec, mesh, n_orig, X, xbs, y, train_w, val_w, blob):
    """ONE model column's fused program, row-sharded over its (data,) submesh.

    Every array argument must be committed with the matching sharding (rows
    over DATA_AXIS for X/xbs/y, axis 1 for the fold-weight matrices, blob
    replicated).  Inside shard_map each device sees one contiguous row block
    of n_pad/n_data rows; the interpreters' cross-row reductions become psums
    over the data axis (normal-equation blocks, gradient/hessian histograms,
    fold accumulators) while per-candidate state stays local, and the metric
    pass reassembles global row order per candidate.  ``n_orig`` is static so
    the RNG parity slices bake in.  NOTE: no SPLIT_METRICS two-launch variant
    here — the lax.map over candidates already bounds the metric transient to
    one [F, n_pad] block."""
    ax = mesh_mod.DATA_AXIS
    n_data = int(mesh.shape[ax])
    rs = (ax, n_orig, n_data)

    def local(Xl, xbs_l, yl, twl, vwl, bl):
        scores = _all_scores(spec, Xl, xbs_l, yl, twl, bl, rs=rs)
        with jax.named_scope(_metrics_scope(spec[0])):
            return _metrics_of_rs(spec, yl, scores, vwl, rs)

    return _shard_map(
        local, mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(None, ax), P(None, ax), P()),
        out_specs=P(), check_vma=False)(X, xbs, y, train_w, val_w, blob)


#: above this many score ELEMENTS the sweep runs as TWO launches (scores,
#: then metrics): compiling family training together with the metric sort
#: pipeline into one program crashed the TPU worker at 500k x 33 candidates
#: even though each half runs fine alone (round-5 bisection); at small n
#: the single launch saves a host round trip.
SPLIT_METRICS_ELEMS = 20_000_000


def _sweep_pack() -> bool:
    """Candidate-packed launches (``TMOG_SWEEP_PACK``, default off).

    On: the launcher builds cost-model-sized launch packs
    (``parallel.spec_partition.launch_packs``) instead of one monolithic
    queue per device, and the row-sharded metric pass evaluates
    ``_metric_pack_size()`` candidates per ``lax.map`` step instead of one
    — fewer sequential dispatches, bit-identical per-candidate math."""
    from ..utils.env import env_flag

    return env_flag("TMOG_SWEEP_PACK", False)


def _gbt_pipeline() -> bool:
    """Cross-device GBT pipelining (``TMOG_GBT_PIPELINE``, default off).

    On (and > 1 shard): every partitioned shard forces the two-launch
    stage split and dispatch is double-buffered across shards — shard i
    holds its metrics (stage 2) dispatch until shard i+1's training/
    histogram launch (stage 1) is in flight, so scoring on one device
    overlaps histogram building on the next.  The hedge deadline clock
    starts AFTER the pipelined prologue (stage compiles + stage-1
    dispatch + the neighbor handshake)."""
    from ..utils.env import env_flag

    return env_flag("TMOG_GBT_PIPELINE", False)


def _metric_pack_size(C: int, F: int, n_pad: int, k: int = 1) -> int:
    """Candidates per packed metric-map step (row-sharded path).

    The per-candidate transient of ``_metrics_of_rs`` is one gathered
    [F, n_pad(, k)] score block; packing P candidates per ``lax.map``
    step multiplies that transient by P, so P is the largest count whose
    transients fit the ``TMOG_PACK_HBM_MB`` budget (the same analytic
    budget ``launch_packs`` bins by).  Returns 1 unless
    ``TMOG_SWEEP_PACK`` is on — the exact historical one-candidate map.
    Pure function of static shapes, so the traced program and the
    launcher's host-side telemetry agree by construction."""
    if C <= 1 or not _sweep_pack():
        return 1
    from ..utils.env import env_float

    budget = env_float("TMOG_PACK_HBM_MB", 2048.0) * 1e6
    per_cand = max(float(F) * float(n_pad) * max(int(k), 1) * 4.0, 1.0)
    return int(max(1, min(int(C), budget // per_cand)))


def _trace_knobs() -> Tuple:
    """Trace-affecting env knobs baked into compiled programs — part of
    every AOT cache key, so flipping a knob mid-process re-lowers instead
    of silently reusing the other configuration's executable (the jit
    paths still need ``jax.clear_caches()``; see
    tests/test_hist_subtract_parity.py)."""
    return (Tr._hist_subtract(), _sweep_pack())


#: kernel trace events (hist-subtraction savings) per (spec, n_rows).  jit
#: caches traces, so only the FIRST execution of a program re-runs the
#: Python-level ``record_trace_event`` calls — later calls (and ``.lower``
#: for cost analysis) see an empty trace.  run_sweep captures the first
#: trace here and replays it into utils/flops on every call, matching the
#: per-call replay the AOT shard paths get from their cached (compiled,
#: events) pairs.
_TRACE_EVENT_CACHE: Dict[Tuple, Tuple] = {}


def _replay_trace_events(spec, n: int, colls) -> None:
    # keyed on the trace-shaping flag too: flipping TMOG_HIST_SUBTRACT
    # mid-process must not replay the other configuration's savings
    key = (spec, int(n), Tr._hist_subtract())
    events = tuple(c for c in colls
                   if c[0] in ("hist_subtracted", "gbt_chain"))
    if events:
        _TRACE_EVENT_CACHE[key] = events
    else:
        events = _TRACE_EVENT_CACHE.get(key, ())
    flops.record_collectives(events)


def run_sweep(spec, X, xbs: Tuple, y, train_w, val_w, blob):
    """Execute a fused sweep program; returns device metrics [F, C, M].

    ``spec`` must be a hashable static tuple (see module docstring); arrays
    may be host or device (device-resident via utils.devcache recommended).
    """
    C = len(spec[2])
    n = int(np.asarray(y).shape[0] if not hasattr(y, "shape") else y.shape[0])
    F = train_w.shape[0]
    k = spec[0][1] if isinstance(spec[0], tuple) else 1
    split = F * C * n * k > SPLIT_METRICS_ELEMS or _kept_scores["on"]
    # whole-launch checkpoint (the single-device sweep is one work unit)
    _ck = _ckpt.store()
    ck_key = None
    if _ck.enabled:
        ck_key = _ckpt.content_key(
            "sweep_launch", spec, blob, *_ckpt.host_key_part(),
            _ckpt.data_fingerprint(X),
            _ckpt.data_fingerprint(y), _ckpt.data_fingerprint(train_w),
            _ckpt.data_fingerprint(val_w))
        hit = _ck.load("sweep_launch", ck_key)
        if hit is not None:
            _sweep_scope.inc("checkpoint_skips")
            _sweep_scope.append("launches", {
                "shards": 1, "candidates": C, "checkpoint": "hit"})
            return jnp.asarray(hit[0]["metrics"])
    entry = {"shards": 1, "candidates": C, "split": bool(split),
             "classes": int(k), "score_block_bytes": 4 * F * C * n * k}
    chain = _spec_gbt_chain(spec)
    if chain:
        entry["gbt_chain"] = chain
    _sweep_scope.append("launches", entry)
    for name, levels in _spec_tree_levels(spec, F, n).items():
        _sweep_scope.inc(name, levels)
    with trace.span("sweep.launch", shards=1, candidates=C,
                    split=bool(split)):
        if chain:
            trace.instant("gbt.chain", steps=chain["steps"],
                          levels=chain["levels"])
        _lg = _ledger.get()

        def _dispatch(ctl=None):
            _inject.maybe_fail("sweep.dispatch", key="fused")
            if ctl is not None:
                ctl.mark_dispatch()
            _t0 = _lg.now()
            if split:
                with trace.span("sweep.dispatch", shards=1, split=True):
                    with mesh_mod.trace_collectives() as colls:
                        scores = _run_scores(spec, X, tuple(xbs), y, train_w,
                                             blob)
                    res = _run_metrics(spec, y, scores, val_w)
            else:
                scores = None
                with trace.span("sweep.dispatch", shards=1, split=False):
                    with mesh_mod.trace_collectives() as colls:
                        res = _run(spec, X, tuple(xbs), y, train_w, val_w,
                                   blob)
            return res, scores, tuple(colls), _lg.now() - _t0

        hedged = False
        if _hedge.enabled():
            # same-slot redundant dispatch: this path's dispatch is async,
            # so the deadline only fires when the dispatch CALL itself
            # stalls (an injected delay, a hung transfer) — the duplicate
            # re-enters the jit cache and whichever returns first wins
            feat0 = _shard_feat(spec, n, int(X.shape[1]), F)
            deadline = _hedge.shard_deadline(_feat_units(feat0), feat0)

            def _waste(task, slot, wall, result):
                _sweep_scope.inc("hedge_wasted_s", wall)
                entry.setdefault("hedges", []).append(
                    {"shard": 0, "wall_s": round(wall, 4), "wasted": True})
                lg = _ledger.get()
                if lg.enabled:
                    lg.launch("sweep.run_scores+metrics" if split
                              else "sweep.run",
                              wall_s=wall, flops=0.0, bytes=0.0,
                              families=_launch_families(
                                  spec, n, int(X.shape[1]), F),
                              shard=0, wasted=True)

            def _attempt(task, slot, ctl):
                if ctl.attempt > 0:
                    with trace.span("sweep.hedge", shard=0,
                                    attempt=ctl.attempt):
                        return _dispatch(ctl)
                return _dispatch(ctl)

            winners, hstats = _hedge.run_hedged(
                1, 1, trace.bind(_attempt), [deadline], same_slot=True,
                on_hedge=lambda *a: _sweep_scope.inc("hedges_fired"),
                on_waste=_waste)
            (out, scores, colls, _lwall), _slot, att_no, _awall = winners[0]
            hedged = att_no > 0
            if hstats["hedges_fired"]:
                entry["hedges_fired"] = hstats["hedges_fired"]
        else:
            out, scores, colls, _lwall = _dispatch()
        _replay_trace_events(spec, n, colls)
        if _kept_scores["on"]:
            _kept_scores["block"] = scores
        if split:
            with trace.span("sweep.account", fn="sweep.run_scores+metrics"):
                costs = [
                    flops.record("sweep.run_scores", _run_scores, spec, X,
                                 tuple(xbs), y, train_w, blob),
                    flops.record("sweep.run_metrics", _run_metrics, spec, y,
                                 scores, val_w)]
            kernel = "sweep.run_scores+metrics"
        else:
            with trace.span("sweep.account", fn="sweep.run"):
                costs = [flops.record("sweep.run", _run, spec, X, tuple(xbs),
                                      y, train_w, val_w, blob)]
            kernel = "sweep.run"
        if _lg.enabled:
            # dispatch is async on this path (nothing gathers here), so the
            # wall is the dispatch span only — classification still holds
            # (a tiny wall reads launch-bound, which is the truth for a
            # launch whose device time we haven't observed yet)
            costs = [c for c in costs if c]
            _lg.launch(kernel, wall_s=_lwall,
                       flops=sum(c.get("flops", 0.0) for c in costs),
                       bytes=sum(c.get("bytes_accessed", 0.0)
                                 for c in costs),
                       families=_launch_families(spec, n, int(X.shape[1]),
                                                 F),
                       shard=0, split=bool(split),
                       **({"hedged": True} if hedged else {}))
        if ck_key is not None:
            with trace.span("sweep.checkpoint", candidates=C):
                _ck.save("sweep_launch", ck_key,
                         {"metrics": np.asarray(out)},
                         meta={"candidates": C, "split": bool(split)})
        return out


# ---------------------------------------------------------------------------
# Multi-chip execution: one sub-spec program per mesh ``model`` device
# ---------------------------------------------------------------------------
#: sweep launch telemetry since the last ``reset_run_stats`` — one entry per
#: ``run_sweep`` ({"shards": 1, ...}) / ``run_sweep_partitioned`` call
#: ({"shards": k, "per_shard": [...], ...}); the bench and the multichip
#: dryrun read it to report ``sweep_shards`` + per-shard wall/compile times.
#: Storage lives in the central obs registry (scope "sweep");
#: ``run_stats()`` below is the backward-compatible view over it, and is
#: also what ``obs.snapshot()["sweep"]`` reports.
_sweep_scope = obs_registry.scope("sweep", defaults={
    "launches": [], "fallbacks": [], "compiles": 0, "compile_s": 0.0,
    "pruned_candidates": 0, "full_candidates": 0, "checkpoint_skips": 0,
    "hedges_fired": 0, "hedge_wasted_s": 0.0, "asha_rungs": [],
    "sweep_pack_count": 0, "launches_avoided": 0,
    "tree_level_builds": 0, "tree_beam_levels": 0, "tree_kept_levels": 0,
    "tree_leaf_reads": 0})
obs_registry.register_provider("sweep", lambda: run_stats())

#: per-(name, spec, device, arg-signature) AOT executables.  jit's own cache
#: would recompile nothing either, but going through ``.lower().compile()``
#: explicitly (a) lets the thread pool compile the per-shard programs
#: CONCURRENTLY — the warmup is one compile's wall, not the sum (the 8.1 s
#: single-chip warmup of BENCH_r05 was the sum of fragment compiles) — and
#: (b) gives an executable whose ``cost_analysis`` flops.record_compiled can
#: read without re-lowering.
_aot_cache: Dict[Tuple, Any] = {}
_aot_lock = threading.Lock()

def reset_run_stats() -> None:
    _sweep_scope.reset()


#: what ``keep_scores`` asked ``run_sweep`` to hold on to: the score block of
#: its last single-device launch, on the device
_kept_scores: Dict[str, Any] = {"on": False, "block": None}


def keep_scores(on: bool = True) -> None:
    """Hold the [F, C, n(, k)] score block of every single-device launch
    that follows until the next one replaces it (``last_scores``): what each
    candidate scored each row on each fold, its validation rows among them
    (out-of-fold predictions for stacking or calibration; a benchmark's
    comparison with a reference, row by row).  A launch then runs as
    ``_run_scores`` + ``_run_metrics`` whatever its size, the block being
    what the first hands the second.  ``keep_scores(False)`` lets go."""
    _kept_scores.update(on=bool(on), block=None)


def last_scores():
    """The score block kept by ``keep_scores``: a device array whose
    candidate axis runs in the launch's flat candidate order and whose rows
    are the sweep's, or None where no launch has run since."""
    return _kept_scores["block"]


def record_fallback(reason: str, **detail) -> None:
    """Note that a launch declined row-sharding (or fusion) and why.

    The graceful-degradation contract: when rows are too few for the data
    axis or a custom estimator blocks fusion, the validator routes through
    the replicated path and RECORDS the reason here instead of erroring —
    ``run_stats()['fallbacks']`` is the audit trail.  Delegates to the one
    central recorder (obs.registry.record_fallback, domain="sweep")."""
    obs_registry.record_fallback("sweep", reason, **detail)


def run_stats() -> Dict[str, Any]:
    """Aggregate view of launches since the last reset (host-side stats)."""
    launches = _sweep_scope.list("launches")
    return {"launches": launches,
            "sweep_shards": max((e["shards"] for e in launches), default=0),
            "data_shards": max((e.get("data_shards", 1) for e in launches),
                               default=0),
            # longest post-collapse boosting chain any launch dispatched
            "gbt_chain_steps": max(
                (e.get("gbt_chain", {}).get("steps", 0) for e in launches),
                default=0),
            "gbt_chain_levels": max(
                (e.get("gbt_chain", {}).get("levels", 0) for e in launches),
                default=0),
            # AOT compile telemetry (cache misses since reset); the per-shape
            # compile-count feature of the learned-cost-model training row
            "compiles": _sweep_scope.get("compiles"),
            "compile_s": _sweep_scope.get("compile_s"),
            # warm-start retrain accounting (continual loop): how many grid
            # candidates actually swept vs the cold grid's full count
            "pruned_candidates": _sweep_scope.get("pruned_candidates"),
            "full_candidates": _sweep_scope.get("full_candidates"),
            # shards/launches skipped because a TMOG_CHECKPOINT_DIR
            # checkpoint from a previous (possibly killed) run covered them
            "checkpoint_skips": _sweep_scope.get("checkpoint_skips"),
            # straggler defense: duplicate dispatches fired past their
            # deadline, and the losers' discarded wall (resilience/hedge)
            "hedges_fired": _sweep_scope.get("hedges_fired"),
            "hedge_wasted_s": _sweep_scope.get("hedge_wasted_s"),
            # candidate packing (TMOG_SWEEP_PACK): packed launches built
            # since reset, and sequential dispatches avoided vs the
            # one-launch-per-candidate baseline (record_packs + the
            # row-sharded metric map)
            # the widest score block a single-device launch held: classes k
            # of its plan (1: one score a row), bytes of its [F, C, n(, k)]
            "classes": max((e.get("classes", 0) for e in launches), default=0),
            "score_block_bytes": max(
                (e.get("score_block_bytes", 0) for e in launches), default=0),
            # tree levels grown by the single-device launches since reset,
            # those that ranked a full frontier, those grown on a tree's
            # kept features alone, and the leaf values their trees handed
            # the training rows (_spec_tree_levels)
            "tree_level_builds": _sweep_scope.get("tree_level_builds"),
            "tree_beam_levels": _sweep_scope.get("tree_beam_levels"),
            "tree_kept_levels": _sweep_scope.get("tree_kept_levels"),
            "tree_leaf_reads": _sweep_scope.get("tree_leaf_reads"),
            "sweep_pack_count": _sweep_scope.get("sweep_pack_count"),
            "launches_avoided": _sweep_scope.get("launches_avoided"),
            # sequential non-overlapped GBT launch-levels on the critical
            # path: per launch the pipelined effective chain
            # (gbt_chain_eff, measured dispatch-window overlap) when
            # present, else the full dependency chain — knobs off this
            # EQUALS gbt_chain_levels (the bench's historical
            # gbt_sequential_launches number)
            "gbt_sequential_launches": max(
                (int((e.get("gbt_chain_eff") or e.get("gbt_chain", {}))
                     .get("levels", 0)) for e in launches), default=0),
            # ASHA search: one record per completed rung (search/asha)
            "asha_rungs": _sweep_scope.list("asha_rungs"),
            "fallbacks": _sweep_scope.list("fallbacks")}


def record_warm_start(pruned: int, full: int) -> None:
    """Stamp a warm-started sweep's pruned-vs-full candidate counts (called
    by the validator after the sweep so the fused path's scope reset cannot
    wipe them)."""
    _sweep_scope.set("pruned_candidates", int(pruned))
    _sweep_scope.set("full_candidates", int(full))


def record_packs(n_packs: int, n_candidates: int) -> None:
    """Stamp one packed dispatch's launch-count telemetry
    (``TMOG_SWEEP_PACK``): ``n_candidates`` candidates ran as ``n_packs``
    fused launches.  ``launches_avoided`` counts against the honest
    one-launch-per-candidate dispatch baseline (the legacy per-family
    path), the same basis ``sweep_pack_count`` packs are bounded by."""
    _sweep_scope.inc("sweep_pack_count", int(n_packs))
    _sweep_scope.inc("launches_avoided",
                     max(int(n_candidates) - int(n_packs), 0))


def record_rungs(rows) -> None:
    """Stamp the ASHA scheduler's per-rung records after the search (same
    post-sweep stamping contract as :func:`record_warm_start`: the fused
    path resets this scope on entry, so the scheduler accumulates rung
    rows locally and stamps them once at the end)."""
    _sweep_scope.set("asha_rungs", [dict(r) for r in rows])


def _aot(name: str, fn, spec, device, dyn_args) -> Tuple[Any, float, Tuple]:
    """AOT executable of ``fn`` for ``spec`` at these (device-committed)
    arguments + compile seconds (0.0 on cache hit) + the program's traced
    (kind, axis, bytes) event list (hist-subtraction savings etc., replayed
    into utils/flops per call).  All ``dyn_args`` must be committed to
    ``device`` so lowering bakes the placement in."""
    key = (name, spec, device, _trace_knobs(),
           flops._signature(dyn_args, {}))
    with _aot_lock:
        hit = _aot_cache.get(key)
    if hit is not None:
        return hit[0], 0.0, hit[1]
    compile_cache_dir()
    t0 = time.perf_counter()
    with trace.span("sweep.compile", fn=name, device=str(device)):
        with mesh_mod.trace_collectives() as colls:
            def _compile():
                _inject.maybe_fail("sweep.compile", key=name)
                return fn.lower(spec, *dyn_args).compile()

            compiled = _retry.with_retry("sweep.compile", _compile)
    dt = time.perf_counter() - t0
    _sweep_scope.inc("compiles")
    _sweep_scope.inc("compile_s", dt)
    with _aot_lock:
        # a racing thread may have compiled the same key; keep the first
        hit = _aot_cache.setdefault(key, (compiled, tuple(colls)))
    return hit[0], dt, hit[1]


def _spec_gbt_chain(spec) -> Optional[Dict[str, int]]:
    """Longest sequential boosting chain in ``spec``: {"steps", "levels"} —
    scan steps and dependent tree levels AFTER round-collapse (gbt group
    index 11 = trees_per_round).  None when the spec has no gbt fragment.
    This is the critical-path telemetry the bench reports as
    ``gbt_sequential_launches``."""
    steps = levels = 0
    for frag in spec[1]:
        if frag[0] != "gbt":
            continue
        for g in frag[3]:
            k = max(int(g[11]), 1)
            s = -(-int(g[1]) // k)
            steps = max(steps, s)
            levels = max(levels, s * int(g[2]))
    if steps == 0:
        return None
    return {"steps": steps, "levels": levels}


def _spec_tree_levels(spec, F: int, n: int = 0) -> Dict[str, int]:
    """Tree levels one launch of ``spec`` grows over ``F`` folds:
    ``tree_level_builds`` (levels x trees: one level histogram each),
    ``tree_beam_levels`` (those at which a full frontier ranked its splits
    by gain and kept half: ``frontier`` slots, not provably enough) and
    ``tree_kept_levels`` (those built on a compacted feature axis, the
    tree's kept features alone: forests with a subset fraction under 1;
    boosting builds full width); and, over its ``n`` rows,
    ``tree_leaf_reads``: the leaf values its grown trees hand their training
    rows (trees x rows x channels, ``ops.trees.read_leaves``)."""
    builds = beam = kept = reads = 0
    for frag in spec[1]:
        if frag[0] == "forest":
            c = frag[1]
            groups = [(len(g[0]) * g[2], g[1], g[9], g[10], g[5])
                      for g in frag[2]]
        elif frag[0] == "gbt":
            c = frag[2]
            groups = [(len(g[0]) * g[1], g[2], g[8], g[9], 1.0)
                      for g in frag[3]]
        else:
            continue
        for trees, depth, frontier, exact_cap, frac in groups:
            builds += F * trees * depth
            reads += F * trees * n * c
            if not exact_cap:
                beam += F * trees * max(depth - (frontier.bit_length() - 1), 0)
            if frac < 1.0:
                kept += F * trees * depth
    return {"tree_level_builds": builds, "tree_beam_levels": beam,
            "tree_kept_levels": kept, "tree_leaf_reads": reads}


def _max_gbt_chain(specs) -> Optional[Dict[str, int]]:
    chains = [c for c in (_spec_gbt_chain(s) for s in specs) if c]
    if not chains:
        return None
    return {"steps": max(c["steps"] for c in chains),
            "levels": max(c["levels"] for c in chains)}


def _shard_feat(spec, n, d, F, data_shards=1, rows_local=None):
    """Static fragment-shape features of one shard's sub-spec, stamped into
    the per-shard launch telemetry so recorded JSONL rows are
    self-describing cost-model training rows (costmodel/features.py reads
    them back offline).  Telemetry must never kill the launch: any failure
    returns None and the entry simply has no ``feat``."""
    try:
        from ..costmodel.features import shard_feature_dict

        return shard_feature_dict(spec, n, d, F, data_shards=data_shards,
                                  rows_local=rows_local)
    except Exception:
        return None


def _feat_units(feat) -> float:
    """Total analytic cost units of one shard's feature dict (the
    calibration basis ``resilience.health`` prices deadlines in)."""
    if not feat:
        return 0.0
    try:
        from ..costmodel.features import family_units

        return float(sum(family_units(feat).values()))
    except Exception:
        return 0.0


#: costmodel family names -> the ledger/report labels the paper uses
_FAM_LABEL = {"linear": "LR", "mlp": "MLP", "forest": "RF", "gbt": "XGB"}
_fam_cache: Dict[Tuple, Dict[str, float]] = {}


def _launch_families(spec, n, d, F) -> Dict[str, float]:
    """Family label -> fraction of one launch's work, from the costmodel's
    per-family unit estimates (the PR-4 per-family lowering split) — how the
    launch ledger splits a mixed-family launch's FLOPs/bytes/wall.  Cached
    per (spec, n, d, F); degrades to a single "sweep" bucket on any failure
    (telemetry must never kill the launch)."""
    key = (spec, int(n), int(d), int(F))
    hit = _fam_cache.get(key)
    if hit is not None:
        return dict(hit)
    fams: Dict[str, float] = {}
    try:
        from ..costmodel.features import FAMILIES, family_units

        feat = _shard_feat(spec, n, d, F)
        if feat:
            units = family_units(feat)
            for f in FAMILIES:
                u = float(units.get(f, 0.0))
                if u > 0:
                    fams[_FAM_LABEL.get(f, f)] = u
    except Exception:
        fams = {}
    if not fams:
        fams = {"sweep": 1.0}
    tot = sum(fams.values())
    fams = {k: v / tot for k, v in fams.items()}
    _fam_cache[key] = fams
    return dict(fams)


def _stamp_cost_features(stat, costs) -> None:
    """Fold measured FLOPs/bytes into the shard's cost-model feature dict so
    recorded JSONL rows carry the memory-traffic features (FEATURE_NAMES
    tail) the learned cost model prices."""
    feat = stat.get("feat")
    if feat is None or not costs:
        return
    try:
        from ..costmodel.features import cost_feature_dict

        feat.update(cost_feature_dict(
            sum(c.get("flops", 0.0) for c in costs),
            sum(c.get("bytes_accessed", 0.0) for c in costs)))
    except Exception:
        pass


def _interval_cover(a: float, b: float, wins) -> float:
    """Total length of [a, b] covered by the union of intervals ``wins``."""
    segs = sorted((max(a, w0), min(b, w1)) for w0, w1 in wins
                  if w1 > a and w0 < b)
    tot, cur = 0.0, a
    for s0, s1 in segs:
        s0 = max(s0, cur)
        if s1 > s0:
            tot += s1 - s0
            cur = s1
    return tot


def _pipeline_chain_eff(shards, stats, n_shards: int
                        ) -> Optional[Dict[str, Any]]:
    """Effective sequential (non-overlapped) GBT chain of one pipelined
    launch: {"levels", "steps", "overlap_fraction"}.

    The f32 boosting chain is a true data dependency — its level count
    cannot shrink bit-identically — but under pipelined dispatch the
    chain-bearing shard's device window runs CONCURRENTLY with the other
    shards' windows, so the launch-critical-path accounting credits the
    measured overlap: ``eff = ceil(levels * (1 - cover))`` where
    ``cover`` is the fraction of the chain shard's dispatch->gather
    window covered by the union of the other shards' windows, floored at
    ``ceil(levels / n_shards)`` (perfect overlap still leaves the chain
    spread across the fleet).  Telemetry only — never raises; None when
    no chain shard carries a measured window."""
    try:
        import math

        best = None
        wins = [st.get("_win") for st in stats]
        for i, (sh, st) in enumerate(zip(shards, stats)):
            c = _spec_gbt_chain(sh.spec)
            win = wins[i]
            if not c or win is None or win[1] <= win[0]:
                continue
            a, b = win
            others = [w for j, w in enumerate(wins) if j != i and w]
            frac = min(max(_interval_cover(a, b, others) / (b - a), 0.0),
                       1.0)
            floor_div = max(int(n_shards), 1)
            cand = {
                "levels": max(int(math.ceil(c["levels"] * (1.0 - frac))),
                              -(-int(c["levels"]) // floor_div)),
                "steps": max(int(math.ceil(c["steps"] * (1.0 - frac))),
                             -(-int(c["steps"]) // floor_div)),
                "overlap_fraction": round(frac, 4)}
            if best is None or cand["levels"] > best["levels"]:
                best = cand
        return best
    except Exception:
        return None


def _shard_arrays(shard, dev, X, xbs, y, X_host, y_host, xb_bins):
    """Per-device copies of the shard's static arrays.

    With host identities available the copies go through utils.devcache
    (keyed per device), so repeated sweeps on the same dataset re-upload
    nothing; the binned matrices are a deterministic function of
    (X identity, n_bins), which is exactly their cache key.
    """
    if X_host is not None:
        Xd = devcache.device_array(X_host, np.float32, device=dev)
    else:
        Xd = jax.device_put(X, dev)
    if y_host is not None:
        yd = devcache.device_array(y_host, np.float32, device=dev)
    else:
        yd = jax.device_put(y, dev)
    xbs_d = []
    for i, xb in enumerate(xbs):
        if X_host is not None and xb_bins is not None:
            xbs_d.append(devcache.derived(
                X_host, ("sweep_xb_dev", int(xb_bins[i]), str(dev)),
                lambda xb=xb: jax.device_put(xb, dev)))
        else:
            xbs_d.append(jax.device_put(xb, dev))
    return Xd, tuple(xbs_d), yd


def run_sweep_partitioned(shards, X, xbs: Tuple, y, train_w, val_w,
                          n_candidates: int, devices,
                          X_host: Optional[np.ndarray] = None,
                          y_host: Optional[np.ndarray] = None,
                          xb_bins: Optional[Tuple[int, ...]] = None
                          ) -> np.ndarray:
    """Execute cost-balanced sub-spec programs, one per device, and gather.

    ``shards`` are ``parallel.spec_partition.ShardSpec``s (shard ``i`` runs
    on ``devices[i]``).  Each worker thread AOT-compiles its shard's program
    (concurrently — distinct cache keys never serialize on the lock) and
    dispatches it; JAX async dispatch overlaps execution across distinct
    devices with no SPMD constraint, so the heterogeneous per-shard fragment
    mixes are fine.  Each shard applies the ``SPLIT_METRICS_ELEMS``
    two-launch split to its OWN candidate count.  Returns host metrics
    [F, n_candidates, M] in the GLOBAL candidate order.
    """
    F = int(train_w.shape[0])
    n = int(X_host.shape[0]) if X_host is not None else int(X.shape[0])
    d = int(X_host.shape[1]) if X_host is not None else int(X.shape[1])
    k = shards[0].spec[0][1] if isinstance(shards[0].spec[0], tuple) else 1
    t_all = time.perf_counter()
    # preemption-safe shard checkpoints: content-keyed on (sub-spec, global
    # candidate ids, hyperparam blob, data fingerprints) so a killed sweep
    # that restarts with the same inputs skips its completed shards
    _ck = _ckpt.store()
    ck_data = () if not _ck.enabled else (
        *_ckpt.host_key_part(),
        _ckpt.data_fingerprint(X_host if X_host is not None else X),
        _ckpt.data_fingerprint(y_host if y_host is not None else y),
        _ckpt.data_fingerprint(train_w), _ckpt.data_fingerprint(val_w))

    # cross-device GBT pipelining: one handshake event per shard, set once
    # that shard's stage-1 (training/histogram) launch is in flight
    pipelined = _gbt_pipeline() and len(shards) > 1
    pipe_evs = ([threading.Event() for _ in shards] if pipelined else None)

    def worker(shard, dev, idx, ctl=None):
        t0 = time.perf_counter()
        ck_key = None
        if _ck.enabled:
            ck_key = _ckpt.content_key(
                "sweep_shard", shard.spec, tuple(map(int, shard.cis)),
                shard.blob, *ck_data)
            hit = _ck.load("sweep_shard", ck_key)
            if hit is not None:
                # a checkpoint hit completes instantly, so it also
                # short-circuits any pending hedge for this shard — and
                # must still release the pipeline handshake so the
                # predecessor shard's stage 2 is not held back
                if pipe_evs is not None:
                    pipe_evs[idx].set()
                _sweep_scope.inc("checkpoint_skips")
                stat = {"device": str(dev), "candidates": len(shard.cis),
                        "predicted_cost": float(shard.cost),
                        "compile_s": 0.0, "split": False,
                        "checkpoint": "hit",
                        "wall_s": round(time.perf_counter() - t0, 4)}
                return hit[0]["metrics"], stat, []
        _deadline = None if ctl is None else ctl.deadline_s
        with trace.span("sweep.shard", device=str(dev), shard=idx,
                        candidates=len(shard.cis)):
            with trace.span("sweep.upload", device=str(dev), shard=idx):
                Xd, xbs_d, yd = _shard_arrays(shard, dev, X, xbs, y,
                                              X_host, y_host, xb_bins)
                tw = jax.device_put(jnp.asarray(train_w), dev)
                vw = jax.device_put(jnp.asarray(val_w), dev)
                bl = jax.device_put(jnp.asarray(shard.blob), dev)
            C_s = len(shard.cis)
            # the pipelined path NEEDS the two-launch stage split: the
            # scores/metrics boundary is where one shard's scoring can
            # overlap the next shard's histogram building
            split = pipelined or F * C_s * n * k > SPLIT_METRICS_ELEMS
            records = []
            win = None
            _lg = _ledger.get()
            if split:
                args_s = (Xd, xbs_d, yd, tw, bl)
                cs, dt_s, ev_s = _aot("sweep.run_scores", _run_scores,
                                      shard.spec, dev, args_s)
                _lt0 = _lg.now()
                if ctl is not None and not pipelined:
                    # deadline clock starts at dispatch (pipelined: the
                    # clock starts inside _go_split, after the prologue)
                    ctl.mark_dispatch()

                def _go_split():
                    _inject.maybe_fail("sweep.dispatch", key=str(dev))
                    with trace.span("sweep.dispatch", device=str(dev),
                                    shard=idx, split=True,
                                    pipelined=bool(pipelined)):
                        t_s1 = time.perf_counter()
                        scores = cs(*args_s)   # stage 1 in flight (async)
                        if pipelined:
                            pipe_evs[idx].set()
                        args_m = (yd, scores, vw)
                        # stage-2 AOT overlaps stage-1 execution: lowering
                        # reads only the pending scores' aval
                        cm, dt_m, ev_m = _aot("sweep.run_metrics",
                                              _run_metrics, shard.spec, dev,
                                              args_m)
                        if pipelined:
                            # double buffer: hold MY metrics dispatch until
                            # the NEXT shard's histogram launch is in its
                            # stream, so stage 2 here overlaps stage 1 there
                            if idx + 1 < len(pipe_evs):
                                pipe_evs[idx + 1].wait(timeout=60.0)
                            if ctl is not None:
                                # hedge clock starts AFTER the pipelined
                                # prologue (compiles + stage-1 dispatch +
                                # neighbor handshake) — a deadline that
                                # included the prologue would hedge on
                                # compile time, not device health
                                ctl.mark_dispatch()
                        return (cm(*args_m), args_m, cm, dt_m, ev_m, t_s1)

                out, args_m, cm, dt_m, ev_m, _ts1 = _retry.with_retry(
                    "sweep.dispatch", _go_split, deadline_s=_deadline)
                win = _ts1
                compile_s = dt_s + dt_m
                records = [("sweep.run_scores", cs, args_s, ev_s),
                           ("sweep.run_metrics", cm, args_m, ev_m)]
            else:
                args = (Xd, xbs_d, yd, tw, vw, bl)
                c, compile_s, ev = _aot("sweep.run", _run, shard.spec, dev,
                                        args)
                _lt0 = _lg.now()
                if ctl is not None:   # deadline clock starts at dispatch
                    ctl.mark_dispatch()

                def _go():
                    _inject.maybe_fail("sweep.dispatch", key=str(dev))
                    with trace.span("sweep.dispatch", device=str(dev),
                                    shard=idx, split=False):
                        return c(*args)

                out = _retry.with_retry("sweep.dispatch", _go,
                                        deadline_s=_deadline)
                records = [("sweep.run", c, args, ev)]
            # block in THIS thread only: other shards keep dispatching/running
            with trace.span("sweep.gather", device=str(dev),
                            shard=idx) as _gsp:
                out = np.asarray(out)
                _gsp.set(bytes=int(out.nbytes))
        t_done = time.perf_counter()
        stat = {"device": str(dev), "candidates": C_s,
                "predicted_cost": float(shard.cost),
                "compile_s": round(compile_s, 4), "split": bool(split),
                "wall_s": round(t_done - t0, 4)}
        if pipelined and win is not None:
            stat["pipelined"] = True
            # stage-1-dispatch -> gather-end device window; consumed (and
            # popped) by _pipeline_chain_eff's overlap accounting
            stat["_win"] = (win, t_done)
        if _lg.enabled:
            # dispatch start -> gather end: the full device round trip the
            # ledger row reports (gather blocks in this thread, so this IS
            # the launch's measured wall, compile/upload excluded)
            stat["launch_wall_s"] = _lg.now() - _lt0
        feat = _shard_feat(shard.spec, n, d, F)
        if feat is not None:
            # cost-model features for the new launch shapes (append-only
            # FEATURE_NAMES tail; 0.0 == the historical unpacked launch)
            feat["pack_size"] = float(C_s) if _sweep_pack() else 0.0
            feat["pipeline_depth"] = 2.0 if pipelined else 0.0
            stat["feat"] = feat
        if ck_key is not None:
            _ck.save("sweep_shard", ck_key, {"metrics": out},
                     meta={"candidates": C_s, "split": bool(split)})
            stat["checkpoint"] = "saved"
        return out, stat, records

    with trace.span("sweep.launch", shards=len(shards),
                    candidates=int(n_candidates)):
        chain = _max_gbt_chain([s.spec for s in shards])
        if chain:
            trace.instant("gbt.chain", steps=chain["steps"],
                          levels=chain["levels"])
        hedge_events: List[Dict[str, Any]] = []
        hedges_fired = 0
        if not _hedge.enabled():
            # TMOG_HEDGE=0: the original dispatch, bit-identical
            with ThreadPoolExecutor(max_workers=len(shards)) as pool:
                results = list(pool.map(trace.bind(worker), shards, devices,
                                        range(len(shards))))
            win_devs = list(devices)
        else:
            tr = _health.tracker()
            deadlines = []
            for shard in shards:
                feat = _shard_feat(shard.spec, n, d, F)
                # health calibration is fed shard.cost units below, so the
                # analytic prediction must query in the same basis (feat
                # units ride along for the learned cost model only)
                deadlines.append(
                    _hedge.shard_deadline(float(shard.cost), feat))

            def _attempt(task, slot, ctl):
                shard, dev = shards[task], devices[slot]
                try:
                    if ctl.attempt > 0:
                        with trace.span("sweep.hedge", shard=task,
                                        device=str(dev),
                                        attempt=ctl.attempt):
                            res = worker(shard, dev, task, ctl=ctl)
                    else:
                        res = worker(shard, dev, task, ctl=ctl)
                except Exception as exc:
                    tr.record_error(str(dev), repr(exc))
                    raise
                tr.record_success(str(dev))
                return res

            def _on_hedge(task, slot, attempt_no, reason):
                nonlocal hedges_fired
                hedges_fired += 1
                _sweep_scope.inc("hedges_fired")
                hedge_events.append({
                    "shard": task, "device": str(devices[slot]),
                    "attempt": attempt_no, "reason": reason})

            def _on_waste(task, slot, wall, result):
                # runs in the LOSER's thread, possibly after the sweep
                # returned — the winner's gather never waits for this
                _sweep_scope.inc("hedge_wasted_s", wall)
                shard = shards[task]
                stat_l = result[1] if isinstance(result, tuple) else None
                ev = {"shard": task, "device": str(devices[slot]),
                      "wall_s": round(wall, 4), "wasted": True}
                if isinstance(stat_l, dict):
                    ev["wall_s"] = stat_l.get("wall_s", ev["wall_s"])
                    if stat_l.get("feat") is not None:
                        ev["feat"] = stat_l["feat"]
                hedge_events.append(ev)
                tr.record_straggler(str(devices[slot]), float(shard.cost),
                                    wall)
                lg = _ledger.get()
                if lg.enabled:
                    lg.launch("sweep.run", wall_s=wall, flops=0.0,
                              bytes=0.0,
                              families=_launch_families(shard.spec, n, d,
                                                        F),
                              shard=task, device=str(devices[slot]),
                              wasted=True)

            winners, _hstats = _hedge.run_hedged(
                len(shards), len(devices), trace.bind(_attempt), deadlines,
                on_hedge=_on_hedge, on_waste=_on_waste,
                slot_ok=lambda s: tr.usable(devices[s]))
            results, win_devs = [], []
            for res, slot, att_no, _w in winners:
                if att_no > 0 and isinstance(res, tuple):
                    res[1]["hedged"] = True
                    res[1]["attempt"] = att_no
                results.append(res)
                win_devs.append(devices[slot])

        M = results[0][0].shape[-1]
        metrics = np.zeros((F, n_candidates, M), np.float32)
        per_shard = []
        _lg = _ledger.get()
        d = int(X_host.shape[1]) if X_host is not None else int(X.shape[1])
        for sidx, ((out, stat, records), shard, dev) in enumerate(
                zip(results, shards, win_devs)):
            metrics[:, np.asarray(shard.cis, np.int64), :] = out
            per_shard.append(stat)
            costs = []
            for name, compiled, args, events in records:
                cost = flops.record_compiled(name, compiled, args,
                                             device=dev)
                flops.record_collectives(events, device=dev)
                if cost:
                    costs.append(cost)
            _stamp_cost_features(stat, costs)
            if _lg.enabled and records:
                _lg.launch("sweep.run" if len(records) == 1
                           else "sweep.run_scores+metrics",
                           wall_s=stat.get("launch_wall_s",
                                           stat.get("wall_s", 0.0)),
                           flops=sum(c.get("flops", 0.0) for c in costs),
                           bytes=sum(c.get("bytes_accessed", 0.0)
                                     for c in costs),
                           families=_launch_families(shard.spec, n, d, F),
                           shard=sidx, device=str(dev),
                           **({"hedged": True} if stat.get("hedged")
                              else {}))
        if _hedge.enabled():
            # winners' measured walls feed the device-health EWMAs that
            # weight the NEXT partition (telemetry must never kill a sweep)
            try:
                _health.tracker().observe_launch([
                    (stat["device"], float(shard.cost),
                     float(stat.get("launch_wall_s")
                           or max(stat.get("wall_s", 0.0)
                                  - stat.get("compile_s", 0.0), 0.0)))
                    for (out, stat, records), shard in zip(results, shards)
                    if stat.get("checkpoint") != "hit"])
            except Exception:
                pass
    entry = {"shards": len(shards), "candidates": int(n_candidates),
             "wall_s": round(time.perf_counter() - t_all, 4),
             "per_shard": per_shard}
    if hedges_fired:
        entry["hedges_fired"] = hedges_fired
        entry["hedges"] = hedge_events
    if chain:
        entry["gbt_chain"] = chain
        if pipelined:
            eff = _pipeline_chain_eff(shards, per_shard, len(shards))
            if eff is not None:
                entry["gbt_chain_eff"] = eff
    for st in per_shard:
        st.pop("_win", None)
    if pipelined:
        entry["pipelined"] = True
        entry["pipeline_depth"] = 2
    _sweep_scope.append("launches", entry)
    return metrics


# ---------------------------------------------------------------------------
# Row-sharded execution: a (data x model) mesh holding ONE row shard per chip
# ---------------------------------------------------------------------------
_uncached = {"lock": threading.Lock(), "depth": 0}


@contextlib.contextmanager
def _without_persistent_cache():
    """Compile inside this block without jax's persistent cache (a no-op
    where the cache is off).  For the row-sharded column programs: on the
    v5e a column executable READ BACK from the cache — an SPMD program over
    two chips of a four-chip host — hung its launch group until the runtime
    killed the process, or halted the cores ("an unexpected peer shows up
    in the launch group"), in every run that found the entries (PR 23,
    five of five); the same programs compiled in the process ran.  jax has
    no per-compile switch, so the global one is flipped while any column
    compiles; another thread compiling meanwhile only skips the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    if compile_cache_dir() is None:
        yield
        return

    def switch(on: bool):
        jax.config.update("jax_enable_compilation_cache", on)
        compilation_cache.reset_cache()  # jax memoizes "is the cache used"

    with _uncached["lock"]:
        _uncached["depth"] += 1
        if _uncached["depth"] == 1:
            switch(False)
    try:
        yield
    finally:
        with _uncached["lock"]:
            _uncached["depth"] -= 1
            if _uncached["depth"] == 0:
                switch(True)


def _aot_rs(spec, submesh, n_orig: int, dyn_args) -> Tuple[Any, float, Tuple]:
    """AOT executable of ``_run_rs`` + compile seconds + the program's traced
    (kind, axis, bytes) collective list (replayed into utils/flops per call).
    The collective trace is captured at lowering and cached WITH the
    executable, so steady-state calls replay it without re-tracing."""
    key = ("sweep.run_rs", spec, submesh, n_orig, _trace_knobs(),
           flops._signature(dyn_args, {}))
    with _aot_lock:
        hit = _aot_cache.get(key)
    if hit is not None:
        return hit[0], 0.0, hit[1]
    compile_cache_dir()
    t0 = time.perf_counter()
    with trace.span("sweep.compile", fn="sweep.run_rs",
                    devices=len(np.asarray(submesh.devices).flat)):
        with mesh_mod.trace_collectives() as colls:
            def _compile():
                _inject.maybe_fail("sweep.compile", key="sweep.run_rs")
                with _without_persistent_cache():
                    return _run_rs.lower(spec, submesh, n_orig,
                                         *dyn_args).compile()

            compiled = _retry.with_retry("sweep.compile", _compile)
    dt = time.perf_counter() - t0
    _sweep_scope.inc("compiles")
    _sweep_scope.inc("compile_s", dt)
    with _aot_lock:
        # a racing thread may have compiled the same key; keep the first
        hit = _aot_cache.setdefault(key, (compiled, tuple(colls)))
    return hit[0], dt, hit[1]


def _rs_arrays(submesh, X, xbs, y, X_host, y_host, xb_bins):
    """Row-sharded placements of the dataset over one model column's submesh.

    Rows are zero-padded to a multiple of the data-shard count (padding rows
    carry zero fold weight) and laid out over DATA_AXIS.  With host
    identities available the placements cache through utils.devcache keyed on
    (host identity, submesh devices), so repeated sweeps re-upload nothing.
    Returns (X, xbs tuple, y, original row count).
    """
    mkey = tuple(str(d) for d in np.asarray(submesh.devices).flat)
    if X_host is not None:
        Xd, n_orig = devcache.derived(
            X_host, ("sweep_rs_X", mkey),
            lambda: mesh_mod.shard_rows(np.asarray(X_host, np.float32),
                                        submesh))
    else:
        Xd, n_orig = mesh_mod.shard_rows(np.asarray(X, np.float32), submesh)
    if y_host is not None:
        yd, _ = devcache.derived(
            y_host, ("sweep_rs_y", mkey),
            lambda: mesh_mod.shard_rows(np.asarray(y_host, np.float32),
                                        submesh))
    else:
        yd, _ = mesh_mod.shard_rows(np.asarray(y, np.float32), submesh)
    xbs_d = []
    for i, xb in enumerate(xbs):
        if X_host is not None and xb_bins is not None:
            xbs_d.append(devcache.derived(
                X_host, ("sweep_rs_xb", int(xb_bins[i]), mkey),
                lambda xb=xb: mesh_mod.shard_rows(np.asarray(xb),
                                                  submesh)[0]))
        else:
            xbs_d.append(mesh_mod.shard_rows(np.asarray(xb), submesh)[0])
    return Xd, tuple(xbs_d), yd, n_orig


def run_sweep_rowsharded(shards, X, xbs: Tuple, y, train_w, val_w,
                         n_candidates: int, mesh,
                         X_host: Optional[np.ndarray] = None,
                         y_host: Optional[np.ndarray] = None,
                         xb_bins: Optional[Tuple[int, ...]] = None
                         ) -> np.ndarray:
    """Execute the sweep on a 2-D (data, model) mesh: model column ``j``
    runs ``shards[j]``'s sub-spec program row-sharded over the column's
    devices.

    Composition with the cost-balanced model partitioning is by construction:
    each column is an independent SPMD program over its own (data,)-axis
    submesh — no cross-model communication — dispatched from its own worker
    thread exactly like ``run_sweep_partitioned`` dispatches single-device
    shards.  Within a column every device holds rows/data_shards of X (the
    1/data_shards peak-memory claim; see the launch entry's
    ``per_device_bytes``) and the fragment interpreters reduce over the
    ``data`` axis with psum'd normal-equation blocks / histograms / metric
    accumulators.  Returns host metrics [F, n_candidates, M] in the GLOBAL
    candidate order.
    """
    grid = np.asarray(mesh.devices)
    ax_d = list(mesh.axis_names).index(mesh_mod.DATA_AXIS)
    ax_m = list(mesh.axis_names).index(mesh_mod.MODEL_AXIS)
    grid = np.moveaxis(grid, (ax_d, ax_m), (0, 1))
    n_data = grid.shape[0]
    if len(shards) > grid.shape[1]:
        raise ValueError(f"{len(shards)} model shards > mesh model axis "
                         f"{grid.shape[1]}")
    F = int(train_w.shape[0])
    n_feat = int(X_host.shape[1]) if X_host is not None else int(X.shape[1])
    n_rows = int(X_host.shape[0]) if X_host is not None else int(X.shape[0])
    tw_host = np.asarray(train_w, np.float32)
    vw_host = np.asarray(val_w, np.float32)
    t_all = time.perf_counter()
    # shard checkpoints, as in run_sweep_partitioned; the key carries the
    # data-shard count because the launch layout is part of the artifact
    _ck = _ckpt.store()
    ck_data = () if not _ck.enabled else (
        ("rs", int(n_data)), *_ckpt.host_key_part(),
        _ckpt.data_fingerprint(X_host if X_host is not None else X),
        _ckpt.data_fingerprint(y_host if y_host is not None else y),
        _ckpt.data_fingerprint(tw_host), _ckpt.data_fingerprint(vw_host))

    def worker(shard, j, ctl=None):
        t0 = time.perf_counter()
        ck_key = None
        if _ck.enabled:
            ck_key = _ckpt.content_key(
                "sweep_shard", shard.spec, tuple(map(int, shard.cis)),
                shard.blob, *ck_data)
            hit = _ck.load("sweep_shard", ck_key)
            if hit is not None:
                # instant completion: short-circuits any pending hedge
                _sweep_scope.inc("checkpoint_skips")
                stat = {"devices": [str(d) for d in grid[:, j]],
                        "candidates": len(shard.cis),
                        "predicted_cost": float(shard.cost),
                        "compile_s": 0.0, "checkpoint": "hit",
                        "wall_s": round(time.perf_counter() - t0, 4)}
                return hit[0]["metrics"], stat, None
        submesh = Mesh(grid[:, j], (mesh_mod.DATA_AXIS,))
        with trace.span("sweep.shard", column=j, data_shards=int(n_data),
                        candidates=len(shard.cis)):
            with trace.span("sweep.upload", column=j):
                Xd, xbs_d, yd, n_orig = _rs_arrays(submesh, X, xbs, y,
                                                   X_host, y_host, xb_bins)
                n_pad = int(Xd.shape[0])
                fold_sh = NamedSharding(submesh,
                                        P(None, mesh_mod.DATA_AXIS))
                tw = jax.device_put(
                    mesh_mod.pad_to_multiple(tw_host, n_data, axis=1)[0],
                    fold_sh)
                vw = jax.device_put(
                    mesh_mod.pad_to_multiple(vw_host, n_data, axis=1)[0],
                    fold_sh)
                bl = jax.device_put(np.asarray(shard.blob, np.float32),
                                    NamedSharding(submesh, P()))
            args = (Xd, xbs_d, yd, tw, vw, bl)
            compiled, compile_s, colls = _aot_rs(shard.spec, submesh, n_orig,
                                                 args)
            _lg = _ledger.get()
            _lt0 = _lg.now()
            if ctl is not None:   # deadline clock starts at dispatch
                ctl.mark_dispatch()

            def _go():
                _inject.maybe_fail("sweep.dispatch", key=f"rs{j}")
                with trace.span("sweep.dispatch", column=j):
                    return compiled(*args)

            out = _retry.with_retry(
                "sweep.dispatch", _go,
                deadline_s=None if ctl is None else ctl.deadline_s)
            # block in THIS thread only: other columns keep
            # dispatching/running
            with trace.span("sweep.gather", column=j) as _gsp:
                out = np.asarray(out)
                _gsp.set(bytes=int(out.nbytes))
        label = ",".join(str(d) for d in grid[:, j])
        stat = {"devices": [str(d) for d in grid[:, j]],
                "candidates": len(shard.cis),
                "predicted_cost": float(shard.cost),
                "compile_s": round(compile_s, 4),
                "rows_local": n_pad // n_data,
                "wall_s": round(time.perf_counter() - t0, 4)}
        if _lg.enabled:
            stat["launch_wall_s"] = _lg.now() - _lt0
        feat = _shard_feat(shard.spec, n_orig, n_feat, F,
                           data_shards=int(n_data),
                           rows_local=n_pad // n_data)
        if feat is not None:
            k_mc = (shard.spec[0][1]
                    if isinstance(shard.spec[0], tuple) else 1)
            feat["pack_size"] = float(_metric_pack_size(
                len(shard.cis), F, n_pad, k_mc)) if _sweep_pack() else 0.0
            feat["pipeline_depth"] = 0.0
            stat["feat"] = feat
        if ck_key is not None:
            _ck.save("sweep_shard", ck_key, {"metrics": out},
                     meta={"candidates": len(shard.cis), "rowsharded": True})
            stat["checkpoint"] = "saved"
        return out, stat, ("sweep.run_rs", compiled, args, label, colls,
                           n_orig, n_pad)

    with trace.span("sweep.launch", shards=len(shards),
                    data_shards=int(n_data), rowsharded=True,
                    candidates=int(n_candidates)):
        chain = _max_gbt_chain([s.spec for s in shards])
        if chain:
            trace.instant("gbt.chain", steps=chain["steps"],
                          levels=chain["levels"])
        hedge_events: List[Dict[str, Any]] = []
        hedges_fired = 0
        if not _hedge.enabled():
            # TMOG_HEDGE=0: the original dispatch, bit-identical
            with ThreadPoolExecutor(max_workers=len(shards)) as pool:
                results = list(pool.map(trace.bind(worker), shards,
                                        range(len(shards))))
        else:
            # a column's program only runs on its own submesh, so hedges
            # are SAME-SLOT redundant dispatches (the duplicate re-enters
            # the AOT cache; first completion wins)
            deadlines = []
            for shard in shards:
                feat = _shard_feat(shard.spec, n_rows, n_feat, F,
                                   data_shards=int(n_data))
                # same unit basis as the health calibration (shard.cost)
                deadlines.append(
                    _hedge.shard_deadline(float(shard.cost), feat))

            def _attempt(task, slot, ctl):
                if ctl.attempt > 0:
                    with trace.span("sweep.hedge", column=task,
                                    attempt=ctl.attempt):
                        return worker(shards[task], task, ctl=ctl)
                return worker(shards[task], task, ctl=ctl)

            def _on_hedge(task, slot, attempt_no, reason):
                nonlocal hedges_fired
                hedges_fired += 1
                _sweep_scope.inc("hedges_fired")
                hedge_events.append({"shard": task, "attempt": attempt_no,
                                     "reason": reason})

            def _on_waste(task, slot, wall, result):
                _sweep_scope.inc("hedge_wasted_s", wall)
                stat_l = result[1] if isinstance(result, tuple) else None
                ev = {"shard": task, "wall_s": round(wall, 4),
                      "wasted": True}
                if isinstance(stat_l, dict):
                    ev["wall_s"] = stat_l.get("wall_s", ev["wall_s"])
                    if stat_l.get("feat") is not None:
                        ev["feat"] = stat_l["feat"]
                hedge_events.append(ev)
                lg = _ledger.get()
                if lg.enabled:
                    lg.launch("sweep.run_rs", wall_s=wall, flops=0.0,
                              bytes=0.0,
                              families=_launch_families(
                                  shards[task].spec, n_rows, n_feat,
                                  F),
                              shard=task,
                              device=",".join(str(dd)
                                              for dd in grid[:, task]),
                              wasted=True)

            winners, _hstats = _hedge.run_hedged(
                len(shards), len(shards), trace.bind(_attempt), deadlines,
                same_slot=True, on_hedge=_on_hedge, on_waste=_on_waste)
            results = []
            for res, _slot, att_no, _w in winners:
                if att_no > 0 and isinstance(res, tuple):
                    res[1]["hedged"] = True
                    res[1]["attempt"] = att_no
                results.append(res)

    M = results[0][0].shape[-1]
    metrics = np.zeros((F, n_candidates, M), np.float32)
    per_shard = []
    coll_agg: Dict[str, Dict[str, float]] = {}
    n_orig = n_pad = 0
    _lg = _ledger.get()
    _d_feat = int(X_host.shape[1]) if X_host is not None else int(X.shape[1])
    for j, ((out, stat, rec), shard) in enumerate(zip(results, shards)):
        metrics[:, np.asarray(shard.cis, np.int64), :] = out[:F]
        per_shard.append(stat)
        if rec is None:  # shard restored from checkpoint: nothing ran
            continue
        name, compiled, args, label, colls, n_orig, n_pad = rec
        cost = flops.record_compiled(name, compiled, args, device=label)
        flops.record_collectives(colls, device=label)
        _stamp_cost_features(stat, [cost] if cost else [])
        # packed metric map: ceil(C/P) sequential map steps instead of C
        # (same static formula the traced program used — the launch-count
        # telemetry and the compiled loop agree by construction)
        k_mc = shard.spec[0][1] if isinstance(shard.spec[0], tuple) else 1
        mp = _metric_pack_size(len(shard.cis), F, n_pad, k_mc)
        if mp > 1:
            stat["metric_pack"] = int(mp)
            record_packs(-(-len(shard.cis) // mp), len(shard.cis))
        if _lg.enabled:
            _lg.launch(name,
                       wall_s=stat.get("launch_wall_s",
                                       stat.get("wall_s", 0.0)),
                       flops=cost.get("flops", 0.0) if cost else 0.0,
                       bytes=(cost.get("bytes_accessed", 0.0)
                              if cost else 0.0),
                       families=_launch_families(shard.spec, n_orig, _d_feat,
                                                 F),
                       shard=j, device=label)
        for kind, axis, nbytes in colls:
            if kind in ("hist_subtracted", "gbt_chain"):
                continue  # kernel trace events, not mesh traffic
            agg = coll_agg.setdefault(axis, {"count": 0.0, "bytes": 0.0})
            agg["count"] += 1
            agg["bytes"] += nbytes
    d = int(X_host.shape[1]) if X_host is not None else int(X.shape[1])
    entry = {"shards": len(shards), "data_shards": int(n_data),
             "rowsharded": True, "candidates": int(n_candidates),
             "wall_s": round(time.perf_counter() - t_all, 4),
             "per_shard": per_shard,
             "collectives": coll_agg,
             # the 1/data_shards peak-memory claim, auditable: what ONE device
             # of a model column holds vs what a replicated launch would hold
             "per_device_bytes": {
                 "X": n_pad // n_data * d * 4,
                 "y": n_pad // n_data * 4,
                 "X_replicated": n_orig * d * 4,
                 "y_replicated": n_orig * 4}}
    if hedges_fired:
        entry["hedges_fired"] = hedges_fired
        entry["hedges"] = hedge_events
    if chain:
        entry["gbt_chain"] = chain
    _sweep_scope.append("launches", entry)
    return metrics
