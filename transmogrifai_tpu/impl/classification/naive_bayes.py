"""OpNaiveBayes — multinomial naive Bayes.

Reference parity: core/.../impl/classification/OpNaiveBayes.scala wrapping
Spark NaiveBayes (smoothing=1.0, modelType multinomial|bernoulli).  Like
Spark, multinomial/bernoulli require non-negative features; fitting is a
single weighted aggregation pass (one matmul on the MXU) — no iterations.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..selector.predictor import PredictorEstimator
from ...utils import devcache
import functools


@functools.partial(jax.jit, static_argnames=("bernoulli",))
def _nb_grid_z(Xd, Y, train_w, smoothings, bernoulli: bool):
    """Joint log-likelihood z [F, G, n, k] for every (fold, smoothing)."""
    class_mass = jnp.einsum("fn,nk->fk", train_w, Y)              # [F, k]
    feat_mass = jnp.einsum("fn,nk,nd->fkd", train_w, Y, Xd)       # [F, k, d]
    d = Xd.shape[1]
    k = Y.shape[1]

    def per_smoothing(s):
        pi = jnp.log(class_mass + s) - jnp.log(
            class_mass.sum(axis=1, keepdims=True) + s * k)        # [F, k]
        if bernoulli:
            p = (feat_mass + s) / (class_mass[:, :, None] + 2.0 * s)
            theta, tn = jnp.log(p), jnp.log1p(-p)
            z = (pi[:, None, :] + jnp.einsum("nd,fkd->fnk", Xd, theta)
                 + jnp.einsum("nd,fkd->fnk", 1.0 - Xd, tn))
        else:
            theta = jnp.log(feat_mass + s) - jnp.log(
                feat_mass.sum(axis=2, keepdims=True) + s * d)
            z = pi[:, None, :] + jnp.einsum("nd,fkd->fnk", Xd, theta)
        return z                                                   # [F, n, k]

    return jax.vmap(per_smoothing, out_axes=1)(smoothings)         # [F, G, n, k]


class OpNaiveBayes(PredictorEstimator):
    is_classifier = True

    def __init__(self, smoothing: float = 1.0, model_type: str = "multinomial",
                 uid: Optional[str] = None, **extra):
        if model_type not in ("multinomial", "bernoulli"):
            raise ValueError("model_type must be multinomial or bernoulli")
        super().__init__(operation_name="OpNaiveBayes", uid=uid,
                         smoothing=smoothing, model_type=model_type, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        X = np.asarray(X, np.float32)
        if (X < 0).any():
            raise ValueError("Naive Bayes requires non-negative feature values "
                             "(Spark NaiveBayes semantics)")
        y = np.asarray(y)
        sw = np.ones(len(y), np.float32) if w is None else np.asarray(w, np.float32)
        k = int(y.max()) + 1 if len(y) else 2
        k = max(k, 2)
        smoothing = float(self.get_param("smoothing", 1.0))
        model_type = self.get_param("model_type", "multinomial")
        Xd = devcache.put(X if model_type == "multinomial" else (X > 0).astype(np.float32), tag="X")
        Y = jax.nn.one_hot(devcache.put(y, jnp.int32, tag="y"), k, dtype=jnp.float32)
        Yw = Y * devcache.put(sw, tag="w")[:, None]
        class_mass = Yw.sum(axis=0)                     # [k]
        feat_mass = Yw.T @ Xd                           # [k, d] one MXU matmul
        pi = jnp.log(class_mass + smoothing) - jnp.log(
            class_mass.sum() + smoothing * k)
        if model_type == "multinomial":
            theta = jnp.log(feat_mass + smoothing) - jnp.log(
                feat_mass.sum(axis=1, keepdims=True) + smoothing * Xd.shape[1])
        else:
            doc_mass = class_mass[:, None]
            p = (feat_mass + smoothing) / (doc_mass + 2.0 * smoothing)
            theta = jnp.log(p)
            # bernoulli also needs log(1-p) for absent features
            return {"pi": np.asarray(pi), "theta": np.asarray(theta),
                    "theta_neg": np.asarray(jnp.log1p(-p)), "num_classes": k,
                    "model_type": model_type}
        return {"pi": np.asarray(pi), "theta": np.asarray(theta),
                "num_classes": k, "model_type": model_type}

    _GRID_KEYS = ("smoothing", "model_type")

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid NB sweep.  The fit is closed-form — per fold
        ONE weighted (class x feature) mass matmul shared by every smoothing
        candidate; smoothing only reshapes the log tables, so the whole
        sweep is a single fused XLA computation per model_type."""
        grids = [dict(g) for g in (grids or [{}])]
        for g in grids:
            for key in g:
                if key not in self._GRID_KEYS:
                    raise NotImplementedError(f"non-batchable NB grid key {key}")
        X = np.asarray(X, np.float32)
        if (X < 0).any():
            raise ValueError("Naive Bayes requires non-negative feature values")
        candidates = [self.copy_with_params(g) for g in grids]
        n_folds = train_w.shape[0]
        k = max(int(np.max(y)) + 1 if len(y) else 2, 2)
        out = [[None] * len(grids) for _ in range(n_folds)]
        groups: Dict[str, list] = {}
        for ci, cand in enumerate(candidates):
            groups.setdefault(cand.get_param("model_type", "multinomial"),
                              []).append(ci)
        Y = jax.nn.one_hot(jnp.asarray(np.asarray(y, np.int64)), k,
                           dtype=jnp.float32)
        twd = jnp.asarray(np.asarray(train_w, np.float32))
        for model_type, cis in groups.items():
            Xd = jnp.asarray(X if model_type == "multinomial"
                             else (X > 0).astype(np.float32))
            sm = jnp.asarray([float(candidates[ci].get_param("smoothing", 1.0))
                              for ci in cis], jnp.float32)
            z = _nb_grid_z(Xd, Y, twd, sm, model_type == "bernoulli")  # [F,G,n,k]
            z = np.asarray(z)
            prob = np.exp(z - z.max(axis=-1, keepdims=True))
            prob /= prob.sum(axis=-1, keepdims=True)
            pred = z.argmax(axis=-1).astype(np.float64)
            for gi, ci in enumerate(cis):
                for f in range(n_folds):
                    out[f][ci] = (pred[f, gi], z[f, gi], prob[f, gi])
        return out

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        X = devcache.put(X, np.float32, tag="X")
        pi = jnp.asarray(params["pi"])
        theta = jnp.asarray(params["theta"])
        if params.get("model_type") == "bernoulli":
            Xb = (X > 0).astype(jnp.float32)
            tn = jnp.asarray(params["theta_neg"])
            z = pi + Xb @ theta.T + (1.0 - Xb) @ tn.T
        else:
            z = pi + X @ theta.T
        prob = jax.nn.softmax(z, axis=-1)
        pred = jnp.argmax(z, axis=-1).astype(jnp.float32)
        return np.asarray(pred), np.asarray(z), np.asarray(prob)
