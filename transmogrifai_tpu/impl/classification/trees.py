"""Tree-ensemble classifiers: RandomForest / GBT / DecisionTree / XGBoost-style.

Reference parity: core/.../impl/classification/{OpRandomForestClassifier,
OpGBTClassifier, OpDecisionTreeClassifier, OpXGBoostClassifier}.scala — OP
wrappers around Spark MLlib trees and the XGBoost JNI core.  TPU-native:
every model rides the histogram kernels in ops/trees.py (one XLA launch per
forest, lax.scan for boosting); Spark parameter names are kept
(num_trees/max_depth/max_bins/subsampling_rate/...).

Spark-default notes: RF numTrees=20 maxDepth=5 maxBins=32 gini
featureSubsetStrategy=sqrt(classification); GBT maxIter=20 stepSize=0.1
(binary only in Spark — here multiclass works too via multi-output trees);
XGBoost eta=0.3 numRound=100 maxDepth=6 lambda=1.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...ops import trees as Tr
from ...utils import devcache
from ..selector.predictor import PredictorEstimator
from ..trees_common import (DEFAULT_MAX_FRONTIER, DEFAULT_MAX_FRONTIER_BOOSTED,
                            TreeParamsMixin,
                            boosted_grid_folds as _boosted_grid_folds,
                            effective_trees_per_round,
                            forest_grid_folds as _forest_grid_folds,
                            gbt_boost_params, tree_from_params, tree_params,
                            xgb_boost_params)


def _as_f32(x):
    return devcache.put(np.asarray(x, np.float32))


class _TreeClassifierBase(TreeParamsMixin, PredictorEstimator):
    """Shared fit plumbing: quantize once, train, store flat arrays."""

    is_classifier = True
    _auto_subset = "sqrt"  # Spark classification-forest default

    def _n_classes(self, y: np.ndarray) -> int:
        return max(int(np.max(y)) + 1 if len(y) else 2, 2)

    @staticmethod
    def _class_grads(y: np.ndarray, k: int) -> np.ndarray:
        """Gradient channels for forest growth: binary uses the 1-channel
        variance kernel (variance impurity == gini/2 for 0/1 labels, so the
        splits are identical and the leaf mean is p(class=1)); multiclass
        uses -onehot (gini-equivalent, class-distribution leaves)."""
        if k == 2:
            return -np.asarray(y, np.float32)[:, None]
        return -np.eye(k, dtype=np.float32)[np.asarray(y, np.int64)]

    @staticmethod
    def _expand_binary_leaves(forest, k: int):
        """[..., 1] class-1 proportion leaves -> [..., 2] distribution."""
        if k != 2:
            return forest
        v = forest.leaf_val
        return forest._replace(leaf_val=jnp.concatenate([1.0 - v, v], axis=-1))

    #: boosted subclasses override with DEFAULT_MAX_FRONTIER_BOOSTED so the
    #: refit grows the same beam the CV sweep measured
    _max_frontier_default = DEFAULT_MAX_FRONTIER

    def _frontier(self, n: int, depth: int, mcw: float, h_max: float) -> int:
        return Tr.frontier_cap(
            n, depth, mcw, h_max=h_max,
            max_frontier=int(self.get_param("max_frontier",
                                            self._max_frontier_default)))


class OpRandomForestClassifier(_TreeClassifierBase):
    """Gini-equivalent histogram forest with class-distribution leaves."""

    def __init__(self, num_trees: int = 20, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", impurity: str = "gini",
                 seed: int = 42, uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpRandomForestClassifier", uid=uid,
                         num_trees=num_trees, max_depth=max_depth, max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain,
                         subsampling_rate=subsampling_rate,
                         feature_subset_strategy=feature_subset_strategy,
                         impurity=impurity, seed=seed, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        n, d = X.shape
        k = self._n_classes(y)
        n_bins = int(self.get_param("max_bins", 32))
        depth = int(self.get_param("max_depth", 5))
        n_trees = int(self.get_param("num_trees", 20))
        Xb, edges = Tr.quantize(X, n_bins)
        G = self._class_grads(y, k)
        sw = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
        kb, kf = Tr.rng_keys(int(self.get_param("seed", 42)))
        wt = Tr.bootstrap_weights(
            kb, n, n_trees,
            rate=float(self.get_param("subsampling_rate", 1.0))) * _as_f32(sw)[None, :]
        fms = Tr.kept_features(kf, d, n_trees, self._subset_frac(d))
        mcw = float(self.get_param("min_instances_per_node", 1))
        forest = Tr.fit_forest(devcache.put(Xb, tag="Xb"), devcache.put(G, tag="y"),
                               _as_f32(np.ones(n)), jnp.asarray(wt), jnp.asarray(fms),
                               max_depth=depth, n_bins=n_bins,
                               frontier=self._frontier(n, depth, mcw, 1.0),
                               min_child_weight=mcw,
                               min_info_gain=float(
                                   self.get_param("min_info_gain", 0.0)))
        forest = self._expand_binary_leaves(forest, k)
        return tree_params(forest, edges=edges, max_depth=depth, num_classes=k,
                           num_trees=n_trees)

    @staticmethod
    def _dist_to_preds(dist: np.ndarray, num_trees: int
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        dist = np.clip(dist, 0.0, None)
        prob = dist / np.maximum(dist.sum(axis=1, keepdims=True), 1e-12)
        raw = dist * num_trees  # Spark rawPrediction = vote mass
        return prob.argmax(axis=1).astype(np.float64), raw, prob

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        Xb = devcache.put(Tr.bin_with_edges(X, params["edges"]), tag="Xb")
        forest = tree_from_params(params)
        dist = np.asarray(Tr.predict_forest(Xb, forest, int(params["max_depth"])))
        return cls._dist_to_preds(dist, int(params["num_trees"]))

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid forest sweep (one chunked launch per
        max_depth group — see trees_common.forest_grid_folds)."""
        k = self._n_classes(y)
        return _forest_grid_folds(
            self, X, y, train_w, grids, n_classes=k,
            convert=lambda dist, cand: self._dist_to_preds(
                dist, int(cand.get_param("num_trees", 20))))


class OpDecisionTreeClassifier(OpRandomForestClassifier):
    """Single gini tree (num_trees=1, no bagging/subsetting)."""

    #: batched sweep grows the same deterministic un-bagged tree fit_arrays does
    _grid_bootstrap = False

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 impurity: str = "gini",
                 seed: int = 42, uid: Optional[str] = None, **extra):
        # drop fixed-by-construction params resurfacing via copy_with_params
        for k in ("num_trees", "feature_subset_strategy", "subsampling_rate",
                  "impurity"):
            extra.pop(k, None)
        super().__init__(num_trees=1, max_depth=max_depth, max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain,
                         subsampling_rate=1.0, feature_subset_strategy="all",
                         impurity=impurity, seed=seed, uid=uid, **extra)
        self.operation_name = "OpDecisionTreeClassifier"

    def fit_arrays(self, X, y, w=None):
        # no bootstrap / feature subsetting for a single deterministic tree
        n = len(y)
        d = X.shape[1]
        k = self._n_classes(y)
        n_bins = int(self.get_param("max_bins", 32))
        depth = int(self.get_param("max_depth", 5))
        Xb, edges = Tr.quantize(X, n_bins)
        G = self._class_grads(y, k)
        sw = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
        mcw = float(self.get_param("min_instances_per_node", 1))
        forest = Tr.fit_forest(devcache.put(Xb, tag="Xb"), devcache.put(G, tag="y"),
                               _as_f32(np.ones(n)), devcache.put(sw[None, :], tag="w"),
                               jnp.asarray(np.ones((1, d), np.float32)),
                               max_depth=depth, n_bins=n_bins,
                               frontier=self._frontier(n, depth, mcw, 1.0),
                               min_child_weight=mcw,
                               min_info_gain=float(
                                   self.get_param("min_info_gain", 0.0)))
        forest = self._expand_binary_leaves(forest, k)
        return tree_params(forest, edges=edges, max_depth=depth, num_classes=k,
                           num_trees=1)


class _BoostedClassifierBase(_TreeClassifierBase):
    """Shared boosting fit: binary logistic or multiclass softmax."""

    _max_frontier_default = DEFAULT_MAX_FRONTIER_BOOSTED

    def _boost_params(self) -> Dict[str, Any]:
        raise NotImplementedError

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        bp = self._boost_params()
        n, d = X.shape
        k = self._n_classes(y)
        Xb, edges = Tr.quantize(X, bp["n_bins"])
        sw = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
        ks, kf = Tr.rng_keys(int(self.get_param("seed", 42)))
        rw = Tr.subsample_weights(ks, n, bp["n_rounds"], bp["subsample"])
        fms = Tr.feature_masks(kf, d, bp["n_rounds"], bp["colsample"])
        loss = "logistic" if k == 2 else "softmax"
        frontier = self._frontier(n, bp["max_depth"], bp["min_child_weight"], 0.25)
        # round-collapse: K trees per boosting step at eta / K; predict_gbt
        # applies the stored eta uniformly over the stacked trees, so the
        # stored eta is the per-tree one
        k_eff = effective_trees_per_round(bp.get("trees_per_round", 1),
                                          bp["n_rounds"])
        # preemption-safe: with TMOG_CHECKPOINT_DIR set the fit runs in
        # checkpointed round segments (margins carried); otherwise this is
        # exactly one fit_gbt call
        from ...resilience import checkpointed_gbt_fit
        trees, _ = checkpointed_gbt_fit(
            Tr.fit_gbt, devcache.put(Xb, tag="Xb"), _as_f32(y), devcache.put(sw, tag="w"),
            jnp.asarray(rw), jnp.asarray(fms), loss=loss,
            n_rounds=bp["n_rounds"], max_depth=bp["max_depth"],
            n_bins=bp["n_bins"], frontier=frontier,
            eta=bp["eta"],
            reg_lambda=bp["reg_lambda"], gamma=bp["gamma"],
            min_child_weight=bp["min_child_weight"],
            n_classes=k,
            min_info_gain=bp.get("min_info_gain", 0.0),
            trees_per_round=k_eff)
        return tree_params(trees, edges=edges, max_depth=bp["max_depth"],
                           eta=bp["eta"] / k_eff, num_classes=k, loss=loss)

    @staticmethod
    def _margins_to_preds(loss: str, F: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        if loss == "logistic":
            z = np.asarray(F[:, 0], np.float64)
            p1 = 1.0 / (1.0 + np.exp(-z))
            raw = np.stack([-z, z], axis=1)
            prob = np.stack([1 - p1, p1], axis=1)
            return (p1 >= 0.5).astype(np.float64), raw, prob
        z = np.asarray(F, np.float64)
        ez = np.exp(z - z.max(axis=1, keepdims=True))
        prob = ez / ez.sum(axis=1, keepdims=True)
        return z.argmax(axis=1).astype(np.float64), z, prob

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        Xb = devcache.put(Tr.bin_with_edges(X, params["edges"]), tag="Xb")
        trees = tree_from_params(params)
        F = Tr.predict_gbt(Xb, trees, int(params["max_depth"]),
                           float(params["eta"]))
        return cls._margins_to_preds(str(params["loss"]), np.asarray(F))

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid sweep for boosted models (SURVEY §2.7 axis 2):
        grids sharing static shape params train as one vmapped XLA launch
        (ops/trees.fit_gbt_batch); mixed static params run one launch per
        static group."""
        k = self._n_classes(y)
        loss = "logistic" if k == 2 else "softmax"

        def convert(F):
            return self._margins_to_preds(loss, F)

        return _boosted_grid_folds(self, X, y, train_w, grids,
                                   loss=loss, n_classes=k, convert=convert)


class OpGBTClassifier(_BoostedClassifierBase):
    """Spark GBTClassifier analog (maxIter=20, stepSize=0.1)."""

    def __init__(self, max_iter: int = 20, max_depth: int = 5, max_bins: int = 32,
                 step_size: float = 0.1, subsampling_rate: float = 1.0,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 seed: int = 42, uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpGBTClassifier", uid=uid,
                         max_iter=max_iter, max_depth=max_depth, max_bins=max_bins,
                         step_size=step_size, subsampling_rate=subsampling_rate,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain, seed=seed,
                         **extra)

    def _boost_params(self):
        return gbt_boost_params(self)


class OpXGBoostClassifier(_BoostedClassifierBase):
    """XGBoost-parameterized boosting (eta/numRound/lambda/gamma/subsample)."""

    def __init__(self, num_round: int = 100, eta: float = 0.3, max_depth: int = 6,
                 max_bins: int = 32, reg_lambda: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, subsample: float = 1.0,
                 colsample_bytree: float = 1.0, seed: int = 42,
                 uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpXGBoostClassifier", uid=uid,
                         num_round=num_round, eta=eta, max_depth=max_depth,
                         max_bins=max_bins, reg_lambda=reg_lambda, gamma=gamma,
                         min_child_weight=min_child_weight, subsample=subsample,
                         colsample_bytree=colsample_bytree, seed=seed, **extra)

    def _boost_params(self):
        return xgb_boost_params(self)
