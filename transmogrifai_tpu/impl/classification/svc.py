"""OpLinearSVC — linear support vector classifier.

Reference parity: core/.../impl/classification/OpLinearSVC.scala wrapping
Spark LinearSVC (regParam, maxIter, tol, fitIntercept; hinge loss + OWLQN).
TPU-native: squared hinge (the standard smooth surrogate, liblinear L2-loss
SVC) with Nesterov accelerated GD — ops.linear.fit_linear_svc.  Emits raw
margins but no probability (Spark LinearSVC likewise has no probabilityCol).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import linear as L
from ...utils import devcache
from ..selector.predictor import PredictorEstimator


class OpLinearSVC(PredictorEstimator):
    is_classifier = True

    def __init__(self, reg_param: float = 0.0, max_iter: int = 100, tol: float = 1e-6,
                 fit_intercept: bool = True, standardization: bool = True,
                 uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpLinearSVC", uid=uid,
                         reg_param=reg_param, max_iter=max_iter, tol=tol,
                         fit_intercept=fit_intercept, standardization=standardization,
                         **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        X = devcache.put(X, jnp.float32, tag="X")
        y = devcache.put(y, jnp.float32, tag="y")
        sw = jnp.ones(X.shape[0], jnp.float32) if w is None else devcache.put(w, jnp.float32, tag="w")
        fit = L.fit_linear_svc(X, y, sw, l2=float(self.get_param("reg_param", 0.0)),
                               max_iter=max(int(self.get_param("max_iter", 100)), 200),
                               fit_intercept=bool(self.get_param("fit_intercept", True)))
        return {"coef": np.asarray(fit.coef), "intercept": np.asarray(fit.intercept)}

    def fit_grid_folds(self, X, y, train_w, grids):
        from ...parallel.mesh import replicate_input, shard_candidates

        l2s, g = shard_candidates(
            self._grid_param_arrays(grids, ("reg_param",))["reg_param"], fill=1.0)
        Xd = replicate_input(devcache.as_f32(X))
        yd = replicate_input(np.asarray(y, np.float32))
        fits = L.fit_svc_grid_folds(Xd, yd, replicate_input(np.asarray(train_w, np.float32)),
                                    l2s,
                                    max_iter=max(int(self.get_param("max_iter", 100)), 200),
                                    fit_intercept=bool(self.get_param("fit_intercept", True)))
        fits = jax.tree.map(lambda a: a[:, :g], fits)
        z = np.asarray(jnp.einsum("nd,fgd->fgn", Xd, fits.coef) + fits.intercept[..., :1])
        pred = (z >= 0.0).astype(np.float32)
        raw = np.stack([-z, z], axis=-1)
        return [[(pred[f, c], raw[f, c], None) for c in range(len(grids))]
                for f in range(train_w.shape[0])]

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
        X = devcache.put(X, jnp.float32, tag="X")
        raw, pred = L.predict_svc(X, jnp.asarray(params["coef"], jnp.float32),
                                  jnp.asarray(params["intercept"], jnp.float32))
        return np.asarray(pred), np.asarray(raw), None
