"""Tree-ensemble regressors: RandomForest / GBT / DecisionTree / XGBoost-style.

Reference parity: core/.../impl/regression/{OpRandomForestRegressor,
OpGBTRegressor, OpDecisionTreeRegressor, OpXGBoostRegressor}.scala.
Same histogram kernels as the classifiers (ops/trees.py); variance-impurity
splitting falls out of the second-order gain with g=-y, h=1.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...ops import trees as Tr
from ..selector.predictor import PredictorEstimator
from ..trees_common import (DEFAULT_MAX_FRONTIER, DEFAULT_MAX_FRONTIER_BOOSTED,
                            TreeParamsMixin,
                            boosted_grid_folds as _boosted_grid_folds,
                            effective_trees_per_round,
                            forest_grid_folds as _forest_grid_folds,
                            gbt_boost_params, tree_from_params, tree_params,
                            xgb_boost_params)


class _TreeRegressorBase(TreeParamsMixin, PredictorEstimator):
    is_classifier = False
    _auto_subset = "onethird"  # Spark regression-forest default

    #: boosted subclasses override with DEFAULT_MAX_FRONTIER_BOOSTED so the
    #: refit grows the same beam the CV sweep measured
    _max_frontier_default = DEFAULT_MAX_FRONTIER

    def _frontier(self, n: int, depth: int, mcw: float, h_max: float = 1.0) -> int:
        return Tr.frontier_cap(
            n, depth, mcw, h_max=h_max,
            max_frontier=int(self.get_param("max_frontier",
                                            self._max_frontier_default)))


class OpRandomForestRegressor(_TreeRegressorBase):
    def __init__(self, num_trees: int = 20, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 subsampling_rate: float = 1.0,
                 feature_subset_strategy: str = "auto", impurity: str = "variance",
                 seed: int = 42, uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpRandomForestRegressor", uid=uid,
                         num_trees=num_trees, max_depth=max_depth, max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain,
                         subsampling_rate=subsampling_rate,
                         feature_subset_strategy=feature_subset_strategy,
                         impurity=impurity, seed=seed, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        n, d = X.shape
        n_bins = int(self.get_param("max_bins", 32))
        depth = int(self.get_param("max_depth", 5))
        n_trees = int(self.get_param("num_trees", 20))
        Xb, edges = Tr.quantize(X, n_bins)
        sw = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
        kb, kf = Tr.rng_keys(int(self.get_param("seed", 42)))
        wt = Tr.bootstrap_weights(
            kb, n, n_trees,
            rate=float(self.get_param("subsampling_rate", 1.0))
        ) * jnp.asarray(sw)[None, :]
        fms = Tr.kept_features(kf, d, n_trees, self._subset_frac(d))
        g = jnp.asarray(-np.asarray(y, np.float32)[:, None])
        mcw = float(self.get_param("min_instances_per_node", 1))
        forest = Tr.fit_forest(jnp.asarray(Xb), g, jnp.ones(n, jnp.float32),
                               jnp.asarray(wt), jnp.asarray(fms),
                               max_depth=depth, n_bins=n_bins,
                               frontier=self._frontier(n, depth, mcw),
                               min_child_weight=mcw,
                               min_info_gain=float(
                                   self.get_param("min_info_gain", 0.0)))
        return tree_params(forest, edges=edges, max_depth=depth)

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
        Xb = jnp.asarray(Tr.bin_with_edges(X, params["edges"]))
        forest = tree_from_params(params)
        pred = np.asarray(Tr.predict_forest(Xb, forest, int(params["max_depth"])))[:, 0]
        return pred.astype(np.float64), None, None

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid forest sweep (trees_common.forest_grid_folds);
        variance-gain trees with mean leaves (n_classes=1)."""
        return _forest_grid_folds(
            self, X, y, train_w, grids, n_classes=1,
            convert=lambda dist, cand: (np.asarray(dist[:, 0], np.float64),
                                        None, None))


class OpDecisionTreeRegressor(OpRandomForestRegressor):
    #: batched sweep grows the same deterministic un-bagged tree fit_arrays does
    _grid_bootstrap = False

    def __init__(self, max_depth: int = 5, max_bins: int = 32,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 seed: int = 42, uid: Optional[str] = None, **extra):
        # drop fixed-by-construction params resurfacing via copy_with_params
        for k in ("num_trees", "feature_subset_strategy", "subsampling_rate",
                  "impurity"):
            extra.pop(k, None)
        super().__init__(num_trees=1, max_depth=max_depth, max_bins=max_bins,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain,
                         feature_subset_strategy="all", seed=seed, uid=uid, **extra)
        self.operation_name = "OpDecisionTreeRegressor"

    def fit_arrays(self, X, y, w=None):
        n, d = X.shape
        n_bins = int(self.get_param("max_bins", 32))
        depth = int(self.get_param("max_depth", 5))
        Xb, edges = Tr.quantize(X, n_bins)
        sw = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
        g = jnp.asarray(-np.asarray(y, np.float32)[:, None])
        mcw = float(self.get_param("min_instances_per_node", 1))
        forest = Tr.fit_forest(jnp.asarray(Xb), g, jnp.ones(n, jnp.float32),
                               jnp.asarray(sw[None, :]),
                               jnp.asarray(np.ones((1, d), np.float32)),
                               max_depth=depth, n_bins=n_bins,
                               frontier=self._frontier(n, depth, mcw),
                               min_child_weight=mcw,
                               min_info_gain=float(
                                   self.get_param("min_info_gain", 0.0)))
        return tree_params(forest, edges=edges, max_depth=depth)


class _BoostedRegressorBase(_TreeRegressorBase):
    _max_frontier_default = DEFAULT_MAX_FRONTIER_BOOSTED

    def _boost_params(self) -> Dict[str, Any]:
        raise NotImplementedError

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        bp = self._boost_params()
        n, d = X.shape
        Xb, edges = Tr.quantize(X, bp["n_bins"])
        sw = np.ones(n, np.float32) if w is None else np.asarray(w, np.float32)
        ks, kf = Tr.rng_keys(int(self.get_param("seed", 42)))
        rw = Tr.subsample_weights(ks, n, bp["n_rounds"], bp["subsample"])
        fms = Tr.feature_masks(kf, d, bp["n_rounds"], bp["colsample"])
        base = float(np.average(y, weights=np.maximum(sw, 1e-12)))
        frontier = self._frontier(n, bp["max_depth"], bp["min_child_weight"])
        # round-collapse: K trees per boosting step at eta / K; the stored
        # eta is the per-tree one (predict_gbt applies it to every tree)
        k_eff = effective_trees_per_round(bp.get("trees_per_round", 1),
                                          bp["n_rounds"])
        # preemption-safe: with TMOG_CHECKPOINT_DIR set the fit runs in
        # checkpointed round segments (margins carried); otherwise this is
        # exactly one fit_gbt call
        from ...resilience import checkpointed_gbt_fit
        trees, _ = checkpointed_gbt_fit(
            Tr.fit_gbt, jnp.asarray(Xb),
            jnp.asarray(np.asarray(y, np.float32)),
            jnp.asarray(sw), jnp.asarray(rw), jnp.asarray(fms),
            loss="squared", n_rounds=bp["n_rounds"],
            max_depth=bp["max_depth"], n_bins=bp["n_bins"],
            frontier=frontier,
            eta=bp["eta"], reg_lambda=bp["reg_lambda"],
            gamma=bp["gamma"],
            min_child_weight=bp["min_child_weight"],
            base_score=base,
            min_info_gain=bp.get("min_info_gain", 0.0),
            trees_per_round=k_eff)
        return tree_params(trees, edges=edges, max_depth=bp["max_depth"],
                           eta=bp["eta"] / k_eff, base_score=base)

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
        Xb = jnp.asarray(Tr.bin_with_edges(X, params["edges"]))
        trees = tree_from_params(params)
        F = Tr.predict_gbt(Xb, trees, int(params["max_depth"]),
                           float(params["eta"]),
                           base_score=float(params["base_score"]))
        return np.asarray(F[:, 0], np.float64), None, None

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid sweep (see _BoostedClassifierBase)."""
        return _boosted_grid_folds(
            self, X, y, train_w, grids, loss="squared", n_classes=1,
            convert=lambda F: (np.asarray(F[:, 0], np.float64), None, None),
            fold_base_score=True)


class OpGBTRegressor(_BoostedRegressorBase):
    def __init__(self, max_iter: int = 20, max_depth: int = 5, max_bins: int = 32,
                 step_size: float = 0.1, subsampling_rate: float = 1.0,
                 min_instances_per_node: int = 1, min_info_gain: float = 0.0,
                 seed: int = 42, uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpGBTRegressor", uid=uid,
                         max_iter=max_iter, max_depth=max_depth, max_bins=max_bins,
                         step_size=step_size, subsampling_rate=subsampling_rate,
                         min_instances_per_node=min_instances_per_node,
                         min_info_gain=min_info_gain, seed=seed,
                         **extra)

    def _boost_params(self):
        return gbt_boost_params(self)


class OpXGBoostRegressor(_BoostedRegressorBase):
    def __init__(self, num_round: int = 100, eta: float = 0.3, max_depth: int = 6,
                 max_bins: int = 32, reg_lambda: float = 1.0, gamma: float = 0.0,
                 min_child_weight: float = 1.0, subsample: float = 1.0,
                 colsample_bytree: float = 1.0, seed: int = 42,
                 uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpXGBoostRegressor", uid=uid,
                         num_round=num_round, eta=eta, max_depth=max_depth,
                         max_bins=max_bins, reg_lambda=reg_lambda, gamma=gamma,
                         min_child_weight=min_child_weight, subsample=subsample,
                         colsample_bytree=colsample_bytree, seed=seed, **extra)

    def _boost_params(self):
        return xgb_boost_params(self)
