"""OpLogisticRegression — logistic regression predictor.

Reference parity: core/.../impl/classification/OpLogisticRegression.scala
wrapping Spark's LogisticRegression with params regParam, elasticNetParam,
maxIter, tol, fitIntercept, standardization, family (auto/binomial/multinomial).

TPU-native: binary fits use full-batch Newton (pure L2) or FISTA prox-gradient
(elastic net); multiclass uses accelerated softmax regression — all
fixed-iteration jit'd kernels from ``ops.linear``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...ops import linear as L
from ...utils import devcache
from ..selector.predictor import PredictorEstimator


class OpLogisticRegression(PredictorEstimator):
    is_classifier = True

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 100, tol: float = 1e-6, fit_intercept: bool = True,
                 standardization: bool = True, family: str = "auto",
                 uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpLogisticRegression", uid=uid,
                         reg_param=reg_param, elastic_net_param=elastic_net_param,
                         max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
                         standardization=standardization, family=family, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        X = devcache.put(X, jnp.float32, tag="X")
        y = devcache.put(y, jnp.float32, tag="y")
        sw = jnp.ones(X.shape[0], jnp.float32) if w is None else devcache.put(w, jnp.float32, tag="w")
        reg = float(self.get_param("reg_param", 0.0))
        alpha = float(self.get_param("elastic_net_param", 0.0))
        fit_intercept = bool(self.get_param("fit_intercept", True))
        max_iter = int(self.get_param("max_iter", 100))
        family = self.get_param("family", "auto")
        num_classes = int(np.max(np.asarray(y))) + 1 if len(y) else 2
        multinomial = family == "multinomial" or (family == "auto" and num_classes > 2)
        if multinomial:
            fitres = L.fit_softmax(X, y, sw, reg * (1.0 - alpha), num_classes=max(num_classes, 2),
                                   max_iter=max_iter, fit_intercept=fit_intercept,
                                   l1=reg * alpha)
            return {"coef": np.asarray(fitres.coef), "intercept": np.asarray(fitres.intercept),
                    "num_classes": max(num_classes, 2), "multinomial": True}
        if alpha > 0.0 and reg > 0.0:
            fitres = L.fit_logistic_fista(X, y, sw, l1=reg * alpha, l2=reg * (1.0 - alpha),
                                          max_iter=max(max_iter, 200),
                                          fit_intercept=fit_intercept)
        else:
            fitres = L.fit_logistic_newton(X, y, sw, l2=reg,
                                           max_iter=min(max(max_iter // 4, 10), 50),
                                           fit_intercept=fit_intercept)
        return {"coef": np.asarray(fitres.coef), "intercept": np.asarray(fitres.intercept),
                "num_classes": 2, "multinomial": False}

    def fit_grid_folds(self, X, y, train_w, grids):
        """Whole fold x grid block as one/two vmapped XLA programs.

        Optimizer consistency with fit_arrays (so CV metrics measure the same
        model the refit ships): pure-L2 candidates (l1 == 0) train via the
        Newton kernel, elastic-net candidates via FISTA; multinomial via the
        softmax kernel.  Only (reg_param, elastic_net_param) are batchable;
        structural params fall back to the per-candidate loop.
        """
        base_fi = bool(self.get_param("fit_intercept", True))
        base_mi = int(self.get_param("max_iter", 100))
        base_family = self.get_param("family", "auto")
        p = self._grid_param_arrays(grids, ("reg_param", "elastic_net_param"))
        reg, alpha = p["reg_param"], p["elastic_net_param"]
        l1 = reg * alpha
        l2 = reg * (1.0 - alpha)
        from ...parallel.mesh import replicate_input, shard_candidates

        Xd = replicate_input(devcache.as_f32(X))
        yd = replicate_input(np.asarray(y, np.float32))
        twd = replicate_input(np.asarray(train_w, np.float32))
        F, G = train_w.shape[0], len(grids)
        num_classes = int(np.max(np.asarray(y))) + 1 if len(y) else 2
        multinomial = base_family == "multinomial" or (base_family == "auto"
                                                       and num_classes > 2)
        if multinomial:
            l1d, _ = shard_candidates(l1, fill=0.0)
            l2d, _ = shard_candidates(l2, fill=1.0)
            fitres = L.fit_softmax_grid_folds(Xd, yd, twd, l1d, l2d,
                                              num_classes=max(num_classes, 2),
                                              max_iter=base_mi, fit_intercept=base_fi)
            raw, prob, pred = L.predict_softmax_grid(Xd, fitres.coef, fitres.intercept)
            raw, prob, pred = np.asarray(raw), np.asarray(prob), np.asarray(pred)
            return [[(pred[f, c], raw[f, c], prob[f, c]) for c in range(G)]
                    for f in range(F)]
        # binary: match fit_arrays' optimizer choice per candidate
        newton_idx = np.where(l1 == 0.0)[0]
        fista_idx = np.where(l1 != 0.0)[0]
        d = X.shape[1]
        coef = np.zeros((F, G, d), np.float32)
        intercept = np.zeros((F, G, 1), np.float32)
        if len(newton_idx):
            l2d, gn = shard_candidates(l2[newton_idx], fill=1.0)
            fitn = L.fit_logistic_grid_folds_newton(
                Xd, yd, twd, l2d,
                max_iter=min(max(base_mi // 4, 10), 50), fit_intercept=base_fi)
            coef[:, newton_idx] = np.asarray(fitn.coef)[:, :gn]
            intercept[:, newton_idx] = np.asarray(fitn.intercept)[:, :gn]
        if len(fista_idx):
            l1d, gf = shard_candidates(l1[fista_idx], fill=0.0)
            l2d, _ = shard_candidates(l2[fista_idx], fill=1.0)
            fitf = L.fit_logistic_grid_folds_fista(
                Xd, yd, twd, l1d, l2d,
                max_iter=max(base_mi, 200), fit_intercept=base_fi)
            coef[:, fista_idx] = np.asarray(fitf.coef)[:, :gf]
            intercept[:, fista_idx] = np.asarray(fitf.intercept)[:, :gf]
        raw, prob, pred = L.predict_binary_logistic_grid(
            Xd, jnp.asarray(coef), jnp.asarray(intercept))
        raw, prob, pred = np.asarray(raw), np.asarray(prob), np.asarray(pred)
        return [[(pred[f, c], raw[f, c], prob[f, c]) for c in range(G)]
                for f in range(F)]

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        X = devcache.put(X, jnp.float32, tag="X")
        coef = jnp.asarray(params["coef"], jnp.float32)
        intercept = jnp.asarray(params["intercept"], jnp.float32)
        if params.get("multinomial"):
            raw, prob, pred = L.predict_softmax(X, coef, intercept)
        else:
            raw, prob, pred = L.predict_binary_logistic(X, coef, intercept)
        return np.asarray(pred), np.asarray(raw), np.asarray(prob)

    @classmethod
    def predict_program(cls, params: Dict[str, Any]):
        coef = jnp.asarray(params["coef"], jnp.float32)
        intercept = jnp.asarray(params["intercept"], jnp.float32)
        multinomial = bool(params.get("multinomial"))

        def program(X):
            X = jnp.asarray(X, jnp.float32)
            if multinomial:
                raw, prob, pred = L.predict_softmax(X, coef, intercept)
            else:
                raw, prob, pred = L.predict_binary_logistic(X, coef, intercept)
            return pred, raw, prob

        return program
