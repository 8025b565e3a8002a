"""Linear regression predictors.

Reference parity: core/.../impl/regression/OpLinearRegression.scala (wraps
Spark LinearRegression: regParam, elasticNetParam, maxIter, tol, fitIntercept,
solver auto = normal equations for small d — exactly our ridge closed form).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...ops import linear as L
from ...utils import devcache
from ..selector.predictor import PredictorEstimator


class OpLinearRegression(PredictorEstimator):
    is_classifier = False

    def __init__(self, reg_param: float = 0.0, elastic_net_param: float = 0.0,
                 max_iter: int = 100, tol: float = 1e-6, fit_intercept: bool = True,
                 standardization: bool = True, solver: str = "auto",
                 uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpLinearRegression", uid=uid,
                         reg_param=reg_param, elastic_net_param=elastic_net_param,
                         max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
                         standardization=standardization, solver=solver, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        X = devcache.put(X, jnp.float32, tag="X")
        y = devcache.put(y, jnp.float32, tag="y")
        sw = jnp.ones(X.shape[0], jnp.float32) if w is None else devcache.put(w, jnp.float32, tag="w")
        reg = float(self.get_param("reg_param", 0.0))
        alpha = float(self.get_param("elastic_net_param", 0.0))
        fit_intercept = bool(self.get_param("fit_intercept", True))
        if alpha > 0.0 and reg > 0.0:
            fit = L.fit_linear_fista(X, y, sw, l1=reg * alpha, l2=reg * (1.0 - alpha),
                                     max_iter=max(int(self.get_param("max_iter", 100)), 300),
                                     fit_intercept=fit_intercept)
        else:
            fit = L.fit_ridge(X, y, sw, l2=reg, fit_intercept=fit_intercept)
        return {"coef": np.asarray(fit.coef), "intercept": np.asarray(fit.intercept)}

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid fits, optimizer-consistent with fit_arrays:
        l1 == 0 candidates use the closed-form ridge kernel, elastic-net ones
        FISTA."""
        fit_intercept = bool(self.get_param("fit_intercept", True))
        p = self._grid_param_arrays(grids, ("reg_param", "elastic_net_param"))
        reg, alpha = p["reg_param"], p["elastic_net_param"]
        l1 = reg * alpha
        l2 = reg * (1.0 - alpha)
        from ...parallel.mesh import replicate_input, shard_candidates

        Xd = replicate_input(devcache.as_f32(X))
        yd = replicate_input(np.asarray(y, np.float32))
        twd = replicate_input(np.asarray(train_w, np.float32))
        F, G = train_w.shape[0], len(grids)
        d = X.shape[1]
        coef = np.zeros((F, G, d), np.float32)
        intercept = np.zeros((F, G, 1), np.float32)
        ridge_idx = np.where(l1 == 0.0)[0]
        fista_idx = np.where(l1 != 0.0)[0]
        if len(ridge_idx):
            l2d, gr = shard_candidates(l2[ridge_idx], fill=1.0)
            fitr = L.fit_ridge_grid_folds(Xd, yd, twd, l2d,
                                          fit_intercept=fit_intercept)
            coef[:, ridge_idx] = np.asarray(fitr.coef)[:, :gr]
            intercept[:, ridge_idx] = np.asarray(fitr.intercept)[:, :gr]
        if len(fista_idx):
            l1d, gf = shard_candidates(l1[fista_idx], fill=0.0)
            l2d, _ = shard_candidates(l2[fista_idx], fill=1.0)
            fitf = L.fit_linear_grid_folds_fista(
                Xd, yd, twd, l1d, l2d,
                max_iter=max(int(self.get_param("max_iter", 100)), 300),
                fit_intercept=fit_intercept)
            coef[:, fista_idx] = np.asarray(fitf.coef)[:, :gf]
            intercept[:, fista_idx] = np.asarray(fitf.intercept)[:, :gf]
        z = np.asarray(jnp.einsum("nd,fgd->fgn", Xd, jnp.asarray(coef))
                       + jnp.asarray(intercept[..., :1]))
        return [[(z[f, c], None, None) for c in range(G)] for f in range(F)]

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
        X = devcache.put(X, jnp.float32, tag="X")
        pred = L.predict_linear(X, jnp.asarray(params["coef"], jnp.float32),
                                jnp.asarray(params["intercept"], jnp.float32))
        return np.asarray(pred), None, None

    @classmethod
    def predict_program(cls, params: Dict[str, Any]):
        coef = jnp.asarray(params["coef"], jnp.float32)
        intercept = jnp.asarray(params["intercept"], jnp.float32)

        def program(X):
            pred = L.predict_linear(jnp.asarray(X, jnp.float32), coef,
                                    intercept)
            return pred, None, None

        return program
