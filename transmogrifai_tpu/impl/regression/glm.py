"""OpGeneralizedLinearRegression.

Reference parity: core/.../impl/regression/OpGeneralizedLinearRegression.scala
wrapping Spark GeneralizedLinearRegression (family, link, regParam, maxIter,
tol, fitIntercept, variancePower).  TPU-native: fixed-iteration IRLS
(ops.linear.fit_glm_irls) — each step one weighted normal-equation solve.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...ops import linear as L
from ...utils import devcache
from ..selector.predictor import PredictorEstimator


class OpGeneralizedLinearRegression(PredictorEstimator):
    is_classifier = False

    def __init__(self, family: str = "gaussian", link: Optional[str] = None,
                 reg_param: float = 0.0, max_iter: int = 25, tol: float = 1e-6,
                 fit_intercept: bool = True, variance_power: float = 0.0,
                 uid: Optional[str] = None, **extra):
        if family not in L.GLM_DEFAULT_LINK:
            raise ValueError(f"Unsupported GLM family {family!r}; one of "
                             f"{sorted(L.GLM_DEFAULT_LINK)}")
        link = link or L.GLM_DEFAULT_LINK[family]
        if link not in ("identity", "log", "logit", "inverse", "sqrt"):
            raise ValueError(f"Unsupported link {link!r}")
        super().__init__(operation_name="OpGeneralizedLinearRegression", uid=uid,
                         family=family, link=link, reg_param=reg_param,
                         max_iter=max_iter, tol=tol, fit_intercept=fit_intercept,
                         variance_power=variance_power, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        sw = np.ones(len(y), np.float32) if w is None else np.asarray(w, np.float32)
        fit = L.fit_glm_irls(
            devcache.put(X, jnp.float32, tag="X"), devcache.put(np.asarray(y, np.float32), tag="y"),
            devcache.put(sw, tag="w"), l2=float(self.get_param("reg_param", 0.0)),
            family=self.get_param("family"), link=self.get_param("link"),
            max_iter=int(self.get_param("max_iter", 25)),
            fit_intercept=bool(self.get_param("fit_intercept", True)),
            variance_power=float(self.get_param("variance_power", 0.0)))
        return {"coef": np.asarray(fit.coef), "intercept": np.asarray(fit.intercept),
                "link": self.get_param("link")}

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, Optional[np.ndarray], Optional[np.ndarray]]:
        mu = L.predict_glm(devcache.put(X, jnp.float32, tag="X"),
                           jnp.asarray(params["coef"], jnp.float32),
                           jnp.asarray(params["intercept"], jnp.float32),
                           link=params["link"])
        return np.asarray(mu, np.float64), None, None

    _GRID_KEYS = ("reg_param", "variance_power", "family", "link", "max_iter",
                  "fit_intercept")

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid IRLS sweep: one launch per
        (family, link, max_iter, fit_intercept) static group
        (ops/linear.fit_glm_grid_folds) — the reference's GLM default grid
        varies family/link, so each family-link pair is one XLA program."""
        grids = [dict(g) for g in (grids or [{}])]
        for g in grids:
            for key in g:
                if key not in self._GRID_KEYS:
                    raise NotImplementedError(f"non-batchable GLM grid key {key}")
        candidates = [self.copy_with_params(g) for g in grids]
        n_folds = train_w.shape[0]
        out = [[None] * len(grids) for _ in range(n_folds)]
        groups: Dict[tuple, list] = {}
        for ci, cand in enumerate(candidates):
            fam = cand.get_param("family", "gaussian")
            link = cand.get_param("link") or L.GLM_DEFAULT_LINK[fam]
            groups.setdefault(
                (fam, link, int(cand.get_param("max_iter", 25)),
                 bool(cand.get_param("fit_intercept", True))), []).append(ci)
        Xd = jnp.asarray(X, jnp.float32)
        yd = jnp.asarray(np.asarray(y, np.float32))
        twd = jnp.asarray(np.asarray(train_w, np.float32))
        for (fam, link, mi, fi), cis in groups.items():
            l2s = jnp.asarray([float(candidates[ci].get_param("reg_param", 0.0))
                               for ci in cis], jnp.float32)
            vps = jnp.asarray([float(candidates[ci].get_param("variance_power", 1.5))
                               for ci in cis], jnp.float32)
            fit = L.fit_glm_grid_folds(Xd, yd, twd, l2s, vps, family=fam,
                                       link=link, max_iter=mi, fit_intercept=fi)
            mu = np.asarray(L.predict_glm_grid(Xd, fit.coef, fit.intercept,
                                               link=link), np.float64)
            for gi, ci in enumerate(cis):
                for f in range(n_folds):
                    out[f][ci] = (mu[f, gi], None, None)
        return out
