"""OpMultilayerPerceptronClassifier.

Reference parity: core/.../impl/classification/OpMultilayerPerceptronClassifier.scala
wrapping Spark's MLP (layers, maxIter, stepSize, seed; sigmoid hidden +
softmax output).  TPU-native: full-batch Adam over a static topology
(ops/mlp.py) — one compiled program of MXU matmuls.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ...ops import mlp as M
from ...utils import devcache
from ..selector.predictor import PredictorEstimator


class OpMultilayerPerceptronClassifier(PredictorEstimator):
    is_classifier = True

    def __init__(self, hidden_layers: Tuple[int, ...] = (10,), max_iter: int = 200,
                 step_size: float = 0.03, seed: int = 42,
                 uid: Optional[str] = None, **extra):
        super().__init__(operation_name="OpMultilayerPerceptronClassifier", uid=uid,
                         hidden_layers=tuple(hidden_layers), max_iter=max_iter,
                         step_size=step_size, seed=seed, **extra)

    def fit_arrays(self, X: np.ndarray, y: np.ndarray,
                   w: Optional[np.ndarray] = None) -> Dict[str, Any]:
        k = max(int(np.max(y)) + 1 if len(y) else 2, 2)
        layers = (X.shape[1],) + tuple(int(h) for h in
                                       self.get_param("hidden_layers", (10,))) + (k,)
        sw = np.ones(len(y), np.float32) if w is None else np.asarray(w, np.float32)
        params = M.fit_mlp(devcache.put(X, jnp.float32, tag="X"),
                           devcache.put(np.asarray(y, np.float32), tag="y"),
                           devcache.put(sw, tag="w"), layers=layers,
                           max_iter=int(self.get_param("max_iter", 200)),
                           lr=float(self.get_param("step_size", 0.03)),
                           seed=int(self.get_param("seed", 42)))
        return {"weights": [(np.asarray(W), np.asarray(b)) for W, b in params],
                "layers": layers, "num_classes": k}

    @classmethod
    def predict_arrays(cls, params: Dict[str, Any], X: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        p = [(jnp.asarray(W), jnp.asarray(b)) for W, b in params["weights"]]
        z, prob, pred = M.predict_mlp(p, devcache.put(X, jnp.float32, tag="X"))
        return np.asarray(pred), np.asarray(z), np.asarray(prob)

    #: grid keys the batched sweep understands; others raise -> loop fallback
    _GRID_KEYS = ("hidden_layers", "max_iter", "step_size", "seed")

    def fit_grid_folds(self, X, y, train_w, grids):
        """Batched fold x grid MLP sweep: one vmapped launch per
        (hidden_layers, max_iter) static group (ops/mlp.fit_mlp_grid_folds) —
        no default-zoo model falls to the per-candidate Python loop."""
        grids = [dict(g) for g in (grids or [{}])]
        for g in grids:
            for key in g:
                if key not in self._GRID_KEYS:
                    raise NotImplementedError(f"non-batchable MLP grid key {key}")
        candidates = [self.copy_with_params(g) for g in grids]
        k = max(int(np.max(y)) + 1 if len(y) else 2, 2)
        n_folds = train_w.shape[0]
        out = [[None] * len(grids) for _ in range(n_folds)]
        groups: Dict[tuple, list] = {}
        for ci, cand in enumerate(candidates):
            hl = tuple(int(h) for h in cand.get_param("hidden_layers", (10,)))
            groups.setdefault((hl, int(cand.get_param("max_iter", 200))),
                              []).append(ci)
        Xd = jnp.asarray(X, jnp.float32)
        yd = jnp.asarray(np.asarray(y, np.float32))
        twd = jnp.asarray(np.asarray(train_w, np.float32))
        for (hl, mi), cis in groups.items():
            layers = (X.shape[1],) + hl + (k,)
            lrs = jnp.asarray([float(candidates[ci].get_param("step_size", 0.03))
                               for ci in cis], jnp.float32)
            seeds = jnp.asarray([int(candidates[ci].get_param("seed", 42))
                                 for ci in cis], jnp.int32)
            params = M.fit_mlp_grid_folds(Xd, yd, twd, lrs, seeds,
                                          layers=layers, max_iter=mi)
            z, prob, pred = M.predict_mlp_grid(params, Xd)
            z, prob, pred = np.asarray(z), np.asarray(prob), np.asarray(pred)
            for gi, ci in enumerate(cis):
                for f in range(n_folds):
                    out[f][ci] = (pred[f, gi], z[f, gi], prob[f, gi])
        return out
