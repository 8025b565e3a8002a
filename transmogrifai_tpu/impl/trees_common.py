"""Shared tree-model parameter plumbing for classifiers and regressors.

Reference parity: the Spark tree params surfaced by
core/.../impl/classification/OpRandomForestClassifier.scala and
impl/regression/OpRandomForestRegressor.scala (featureSubsetStrategy,
subsamplingRate) and the boosting params of OpGBT*/OpXGBoost* wrappers.
"""
from __future__ import annotations

import math
import os
from typing import Any, Dict

_SUBSET_STRATEGIES = ("auto", "all", "sqrt", "log2", "onethird")

#: default beam caps for the bounded-frontier grower (ops/trees.frontier_cap);
#: overridable per stage via the ``max_frontier`` param.  Boosted models used
#: a tighter 64-slot beam through round 4; round-5 measurement on v5e showed
#: the beam's per-level gain-rank argsorts cost MORE than the wider exact
#: frontier's extra histogram volume (369 ms vs 265 ms on the Titanic XGB
#: fragment), so both tiers now share the 256 cap — which also makes the
#: default sweeps provably exact (no beam truncation) at their
#: min-child-weight settings.
DEFAULT_MAX_FRONTIER = 256
DEFAULT_MAX_FRONTIER_BOOSTED = 256


def round_collapse_default() -> int:
    """Env default for the boosted-forest round-collapse factor K
    (``TMOG_GBT_ROUND_COLLAPSE``; 1 = off, the exact per-round scan).
    K > 1 grows K trees per boosting step against shared gradients at
    learning rate eta / K, cutting the sequential scan to rounds / K steps
    (ops/trees._gbt_impl / _gbt_batch_impl)."""
    from ..utils.env import env_int

    return max(env_int("TMOG_GBT_ROUND_COLLAPSE", 1), 1)


def effective_trees_per_round(k: int, n_rounds: int) -> int:
    """Clamp a requested collapse factor to one the kernel honors: K must
    exceed 1, not exceed ``n_rounds``, and divide it exactly (the boosting
    scan reshapes rounds -> [rounds / K, K]).  Returns 1 (no collapse)
    otherwise — callers that care record a fallback."""
    k = int(k)
    if k <= 1 or k > n_rounds or n_rounds % k:
        return 1
    return k


def tree_params(tree, **extra) -> Dict[str, Any]:
    """Flatten a fitted ops.trees.Tree into a serializable params dict."""
    import numpy as np

    return {"split_feat": np.asarray(tree.split_feat),
            "split_bin": np.asarray(tree.split_bin),
            "left": np.asarray(tree.left), "right": np.asarray(tree.right),
            "leaf_val": np.asarray(tree.leaf_val), **extra}


def tree_from_params(params):
    """Rebuild an ops.trees.Tree pytree from a params dict."""
    import jax.numpy as jnp

    from ..ops.trees import Tree

    return Tree(jnp.asarray(params["split_feat"]),
                jnp.asarray(params["split_bin"]),
                jnp.asarray(params["left"]), jnp.asarray(params["right"]),
                jnp.asarray(params["leaf_val"]))


class TreeParamsMixin:
    """Spark featureSubsetStrategy resolution shared by all tree models.

    ``_auto_subset_frac`` is what "auto" maps to: sqrt for classification
    forests, onethird for regression forests (Spark RandomForestParams).
    """

    #: overridden per subclass ("sqrt" | "onethird" | "all")
    _auto_subset: str = "sqrt"

    def _subset_frac(self, d: int) -> float:
        strat = str(self.get_param("feature_subset_strategy", "auto")).lower()
        if strat == "auto":
            strat = self._auto_subset
        if strat == "all":
            return 1.0
        if strat == "sqrt":
            return math.sqrt(d) / d
        if strat == "log2":
            return max(math.log2(max(d, 2)), 1.0) / d
        if strat == "onethird":
            return 1.0 / 3.0
        try:
            frac = float(strat)
        except ValueError:
            raise ValueError(
                f"Unknown feature_subset_strategy {strat!r}; expected one of "
                f"{_SUBSET_STRATEGIES} or a fraction in (0, 1]") from None
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"feature_subset_strategy fraction must be in (0, 1], got {frac}")
        return frac


def gbt_boost_params(stage) -> Dict[str, Any]:
    """Spark GBT param dict (maxIter/stepSize/subsamplingRate…)."""
    return {"n_rounds": int(stage.get_param("max_iter", 20)),
            "max_depth": int(stage.get_param("max_depth", 5)),
            "n_bins": int(stage.get_param("max_bins", 32)),
            "eta": float(stage.get_param("step_size", 0.1)),
            "subsample": float(stage.get_param("subsampling_rate", 1.0)),
            "colsample": 1.0, "reg_lambda": 1e-6, "gamma": 0.0,
            "min_child_weight": float(stage.get_param("min_instances_per_node", 1)),
            "min_info_gain": float(stage.get_param("min_info_gain", 0.0)),
            "trees_per_round": int(stage.get_param("trees_per_round",
                                                   round_collapse_default()))}


#: boosting hyperparameters that are traced scalars in the kernel — grids
#: varying only these batch into one launch
_DYNAMIC_BOOST_KEYS = ("eta", "step_size", "reg_lambda", "gamma",
                       "min_child_weight", "min_instances_per_node",
                       "min_info_gain")


def boosted_grid_folds(est, X, y, train_w, grids, loss: str, n_classes: int,
                       convert, fold_base_score: bool = False) -> list:
    """fold x grid sweep for boosted models: group grids by their static
    shape params (rounds/depth/bins/subsample/colsample), train each group as
    ONE vmapped launch (ops/trees.fit_gbt_batch), convert margins to
    predictions with ``convert``.

    Returns ``preds[fold][grid] = convert(F_margins_on_full_X)``.
    """
    import jax.numpy as jnp
    import numpy as np

    from ..ops import trees as Tr

    grids = [dict(g) for g in (grids or [{}])]
    candidates = [est.copy_with_params(g) for g in grids]
    bps = [c._boost_params() for c in candidates]
    for g in grids:
        for key in g:
            # NOTE: "seed" is deliberately NOT batchable — the group shares
            # one subsample/colsample draw, so per-candidate seeds must take
            # the per-candidate fallback loop
            if key not in _DYNAMIC_BOOST_KEYS and key not in (
                    "num_round", "max_iter", "max_depth", "max_bins",
                    "subsample", "subsampling_rate", "colsample_bytree",
                    "trees_per_round"):
                raise NotImplementedError(f"non-batchable boosting grid key {key}")

    n_folds = train_w.shape[0]
    n, d = X.shape
    out = [[None] * len(grids) for _ in range(n_folds)]
    groups: Dict[tuple, list] = {}
    for ci, bp in enumerate(bps):
        static = (bp["n_rounds"], bp["max_depth"], bp["n_bins"],
                  bp["subsample"], bp["colsample"],
                  effective_trees_per_round(bp.get("trees_per_round", 1),
                                            bp["n_rounds"]))
        groups.setdefault(static, []).append(ci)

    h_max = 0.25 if loss in ("logistic", "softmax") else 1.0
    for (n_rounds, max_depth, n_bins, subsample, colsample,
         k_eff), cis in groups.items():
        Xb, _ = Tr.quantize(X, n_bins)
        ks, kfm = Tr.rng_keys(int(est.get_param("seed", 42)))
        rw = Tr.subsample_weights(ks, n, n_rounds, subsample)
        fms = Tr.feature_masks(kfm, d, n_rounds, colsample)
        mcw_min = min(bps[ci]["min_child_weight"] for ci in cis)
        B = n_folds * len(cis)
        w_batch = np.empty((B, n), np.float32)
        eta_b = np.empty(B, np.float32)
        lam_b = np.empty(B, np.float32)
        gam_b = np.empty(B, np.float32)
        mcw_b = np.empty(B, np.float32)
        mig_b = np.zeros(B, np.float32)
        base_b = np.zeros(B, np.float32)
        yf = np.asarray(y, np.float32)
        for bi, (f, ci) in enumerate((f, ci) for f in range(n_folds) for ci in cis):
            bp = bps[ci]
            w_batch[bi] = train_w[f]
            eta_b[bi] = bp["eta"]
            lam_b[bi] = max(bp["reg_lambda"], 1e-6)
            gam_b[bi] = bp["gamma"]
            mcw_b[bi] = bp["min_child_weight"]
            mig_b[bi] = bp.get("min_info_gain", 0.0)
            if fold_base_score:  # regression starts from the fold's label mean
                wsum = max(float(train_w[f].sum()), 1e-12)
                base_b[bi] = float((yf * train_w[f]).sum() / wsum)
        # frontier bound from the ACTUAL weight sums (DataBalancer folds can
        # sum to n/(1-p) > 1.25n); per-round subsample masks rw are <= 1 so
        # the fold sum dominates every round's hessian total
        w_sum_max = float(w_batch.sum(axis=1).max())
        frontier = Tr.frontier_cap(
            n, max_depth, mcw_min, h_max=h_max,
            max_frontier=int(est.get_param("max_frontier",
                                           DEFAULT_MAX_FRONTIER_BOOSTED)),
            total_weight=w_sum_max)
        exact_cap = Tr.frontier_is_exact(n, max_depth, mcw_min, h_max, frontier,
                                         total_weight=w_sum_max)
        # candidate axis sharded over the active mesh's model axis (zero-weight
        # padding candidates train on no rows); inputs replicated
        from ..parallel.mesh import replicate_input, shard_candidates

        w_dev, _ = shard_candidates(w_batch, fill=0.0)
        eta_dev, _ = shard_candidates(eta_b, fill=0.1)
        lam_dev, _ = shard_candidates(lam_b, fill=1.0)
        gam_dev, _ = shard_candidates(gam_b, fill=0.0)
        mcw_dev, _ = shard_candidates(mcw_b, fill=1.0)
        mig_dev, _ = shard_candidates(mig_b, fill=0.0)
        base_dev, _ = shard_candidates(base_b, fill=0.0)
        F = Tr.fit_gbt_batch(
            replicate_input(Xb), replicate_input(yf),
            w_dev, replicate_input(rw), replicate_input(fms), loss=loss,
            n_rounds=n_rounds, max_depth=max_depth, n_bins=n_bins,
            frontier=frontier,
            eta_b=eta_dev, reg_lambda_b=lam_dev,
            gamma_b=gam_dev, min_child_weight_b=mcw_dev,
            base_score_b=base_dev, n_classes=n_classes,
            min_info_gain_b=mig_dev, exact_cap=exact_cap,
            trees_per_round=k_eff)
        F = np.asarray(F)[:B]
        for bi, (f, ci) in enumerate((f, ci) for f in range(n_folds) for ci in cis):
            out[f][ci] = convert(F[bi])
    return out


#: forest grid keys that batch (host-side or per-tree traced)
_FOREST_GRID_KEYS = ("max_depth", "num_trees", "min_instances_per_node",
                     "subsampling_rate", "feature_subset_strategy", "max_bins",
                     "impurity", "min_info_gain")


def forest_grid_folds(est, X, y, train_w, grids, n_classes: int, convert) -> list:
    """fold x grid RF sweep: per (max_depth, num_trees, max_bins) group all
    (fold, candidate, bootstrap-tree) triples train as one memory-chunked
    launch (ops/trees.fit_forest_chunked) and evaluate with one grouped
    predict.  ``convert(dist)`` maps each group's mean leaf vector on the
    full X to (pred, raw, prob)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..ops import trees as Tr

    grids = [dict(g) for g in (grids or [{}])]
    for g in grids:
        for key in g:
            if key not in _FOREST_GRID_KEYS:
                raise NotImplementedError(f"non-batchable forest grid key {key}")
    candidates = [est.copy_with_params(g) for g in grids]
    n_folds = train_w.shape[0]
    n, d = X.shape
    c = 1 if n_classes <= 2 else n_classes
    out = [[None] * len(grids) for _ in range(n_folds)]
    groups: Dict[tuple, list] = {}
    for ci, cand in enumerate(candidates):
        # the kept-feature count is static too: it is the width a group's
        # trees are grown at (ops/trees.grow_forest)
        bag = bool(getattr(cand, "_grid_bootstrap", True))
        static = (int(cand.get_param("max_depth", 5)),
                  int(cand.get_param("num_trees", 20)),
                  int(cand.get_param("max_bins", 32)),
                  Tr.n_kept(d, cand._subset_frac(d) if bag else 1.0))
        groups.setdefault(static, []).append(ci)

    # Binary classification uses the 1-channel variance kernel: for 0/1
    # labels, variance impurity p(1-p) is gini/2, so variance-gain splits are
    # IDENTICAL to gini splits and the leaf mean is p(class=1) — half the
    # histogram work of a 2-channel one-hot kernel.
    binary = n_classes == 2
    if n_classes >= 2 and not binary:
        G = -np.eye(n_classes, dtype=np.float32)[np.asarray(y, np.int64)]
    else:
        G = -np.asarray(y, np.float32)[:, None]
    H = np.ones(n, np.float32)

    for (max_depth, n_trees, n_bins, n_kept), cis in groups.items():
        Xb, _ = Tr.quantize(X, n_bins)
        mcw_min = min(float(candidates[ci].get_param("min_instances_per_node", 1))
                      for ci in cis)
        pairs = [(f, ci) for f in range(n_folds) for ci in cis]
        TT = len(pairs) * n_trees
        w_trees = np.empty((TT, n), np.float32)
        fis = np.empty((TT, n_kept), np.int32)
        mcw = np.empty(TT, np.float32)
        mig = np.zeros(TT, np.float32)
        draw_cache: Dict[tuple, tuple] = {}
        for gi, (f, ci) in enumerate(pairs):
            cand = candidates[ci]
            seed = int(cand.get_param("seed", 42))
            rate = float(cand.get_param("subsampling_rate", 1.0))
            frac = cand._subset_frac(d)
            bag = bool(getattr(cand, "_grid_bootstrap", True))
            dkey = (seed, rate, frac, bag)
            if dkey not in draw_cache:  # one device draw + pull per config
                kb, kfm = Tr.rng_keys(seed)
                draw_cache[dkey] = (
                    np.asarray(Tr.bootstrap_weights(kb, n, n_trees, bag, rate)),
                    np.asarray(Tr.kept_features(kfm, d, n_trees,
                                                frac if bag else 1.0)))
            boot, fi = draw_cache[dkey]
            w_trees[gi * n_trees:(gi + 1) * n_trees] = boot * train_w[f][None, :]
            fis[gi * n_trees:(gi + 1) * n_trees] = fi
            mcw[gi * n_trees:(gi + 1) * n_trees] = float(
                cand.get_param("min_instances_per_node", 1))
            mig[gi * n_trees:(gi + 1) * n_trees] = float(
                cand.get_param("min_info_gain", 0.0))
        # frontier bound from the ACTUAL per-tree weight sums: Poisson
        # bootstrap x DataBalancer fold weights routinely exceed the 1.25*n
        # heuristic, and exact_cap's count clamp must provably never bind
        w_sum_max = float(w_trees.sum(axis=1).max())
        frontier = Tr.frontier_cap(
            n, max_depth, mcw_min, h_max=1.0,
            max_frontier=int(est.get_param("max_frontier", DEFAULT_MAX_FRONTIER)),
            total_weight=w_sum_max)
        exact_cap = Tr.frontier_is_exact(n, max_depth, mcw_min, 1.0, frontier,
                                         total_weight=w_sum_max)
        from ..parallel.mesh import MODEL_AXIS, active_mesh, model_shards

        n_shard = model_shards()
        chunk = Tr.balanced_chunk(
            max(TT // n_shard, 1),
            Tr.forest_chunk_size(max_depth, n_bins, d, c, frontier, n_rows=n,
                                 n_kept=n_kept))
        pad = (-TT) % (chunk * n_shard)
        if pad:  # zero-weight padding trees grow no splits and are dropped
            w_trees = np.concatenate([w_trees, np.zeros((pad, n), np.float32)])
            fis = np.concatenate([fis, np.tile(fis[:1], (pad, 1))])
            mcw = np.concatenate([mcw, np.ones(pad, np.float32)])
            mig = np.concatenate([mig, np.zeros(pad, np.float32)])
        if n_shard > 1:  # tree axis spread over the mesh model axis
            forest = Tr.fit_forest_sharded(
                active_mesh(), MODEL_AXIS, jnp.asarray(Xb), jnp.asarray(G),
                jnp.asarray(H), jnp.asarray(w_trees), jnp.asarray(fis),
                jnp.asarray(mcw), max_depth=max_depth, n_bins=n_bins,
                chunk=chunk, frontier=frontier, mig_trees=jnp.asarray(mig),
                exact_cap=exact_cap)
            forest = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), forest)
        else:
            forest = Tr.fit_forest_chunked(
                jnp.asarray(Xb), jnp.asarray(G), jnp.asarray(H), jnp.asarray(w_trees),
                jnp.asarray(fis), jnp.asarray(mcw), max_depth=max_depth,
                n_bins=n_bins, chunk=chunk, frontier=frontier,
                mig_trees=jnp.asarray(mig), exact_cap=exact_cap)
        if pad:
            forest = jax.tree.map(lambda a: a[:TT], forest)
        dist = np.asarray(Tr.predict_forest_groups(jnp.asarray(Xb), forest,
                                                   max_depth, len(pairs)))
        if binary:  # expand the 1-channel class-1 proportion to [p0, p1]
            dist = np.concatenate([1.0 - dist, dist], axis=-1)
        for gi, (f, ci) in enumerate(pairs):
            out[f][ci] = convert(dist[gi], candidates[ci])
    return out


def xgb_boost_params(stage) -> Dict[str, Any]:
    """XGBoost param dict (numRound/eta/lambda/gamma/subsample/colsample).

    ``max_bins`` defaults to 32 — the Spark MLlib maxBins default, applied
    uniformly to our histogram formulation (xgboost4j used exact greedy
    splits; a TPU-native static-shape kernel must bin)."""
    return {"n_rounds": int(stage.get_param("num_round", 100)),
            "max_depth": int(stage.get_param("max_depth", 6)),
            "n_bins": int(stage.get_param("max_bins", 32)),
            "eta": float(stage.get_param("eta", 0.3)),
            "subsample": float(stage.get_param("subsample", 1.0)),
            "colsample": float(stage.get_param("colsample_bytree", 1.0)),
            "reg_lambda": float(stage.get_param("reg_lambda", 1.0)),
            "gamma": float(stage.get_param("gamma", 0.0)),
            "min_child_weight": float(stage.get_param("min_child_weight", 1.0)),
            "trees_per_round": int(stage.get_param("trees_per_round",
                                                   round_collapse_default()))}
