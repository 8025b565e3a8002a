"""``wide_tabular``'s table with a k-class label in place of its 0/1 one.

The draws are ``wide_tabular.synthesize``'s, in its order, up to the label:
for the same ``table.draw_seed`` and ``--seed`` every predictor column is
bit-equal to ``scale-500``'s (``tests/benchmarks/test_multiclass_files.py``
holds the two side by side), so the fitted fills, the streamed transform
program and its cache entries are the ones the binary cells use.

The label is the class of the same latent the binary label thresholds at 0,
``1.5 signal + 0.5 num_0 + logistic noise``, cut at its own quantiles to the
configuration's cumulative ``table.class_shares``: class 0 holds the rows of
lowest latent.  The cut is by rank, so every class holds exactly its share
of the rows (to one row) whatever the draw, and ``--seed``, which only
orders the rows, leaves the class counts what they are.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np

from benchmarks.tables import wide_tabular

LABEL = wide_tabular.LABEL
features = wide_tabular.features


def class_of_rank(n: int, cumulative_shares: Sequence[float]) -> np.ndarray:
    """i64[n]: the class of the row of each rank (0 = lowest latent): class
    c ends at row ``round(cumulative_shares[c] * n)``."""
    ends = np.round(np.asarray(cumulative_shares, np.float64) * n).astype(np.int64)
    if ends[-1] != n or np.any(np.diff(ends) <= 0):
        raise ValueError(f"class_shares must rise to 1.0: {cumulative_shares}")
    return np.searchsorted(ends, np.arange(n), side="right")


def synthesize(rows: int, seed: int, n_real: int, n_picklist: int,
               picklist_categories: int,
               cumulative_shares: Sequence[float]) -> Dict[str, np.ndarray]:
    """``wide_tabular.synthesize`` draw for draw, then the k-class label."""
    rng = np.random.default_rng(int(seed))
    n = int(rows)
    cols: Dict[str, np.ndarray] = {}
    signal = rng.normal(size=n).astype(np.float32)
    prev = None
    for j in range(n_real):
        noise = rng.normal(size=n).astype(np.float32)
        if j % 50 == 0:
            v = signal * np.float32(0.8) + noise * np.float32(0.6)
        elif j % 50 == 1:
            v = prev + noise * np.float32(0.02)
        elif j % 50 == 2:
            v = np.full(n, 3.14, np.float32)
        else:
            v = noise
        cols[f"num_{j}"] = v
        prev = v
    cats = np.array([f"c{k}" for k in range(picklist_categories)], dtype=object)
    for j in range(n_picklist):
        idx = rng.integers(0, picklist_categories, n)
        if j % 10 == 0:
            idx = np.where((signal > 0.5) & (rng.random(n) < 0.7), 0, idx)
        cols[f"cat_{j}"] = cats[idx]
    latent = signal * 1.5 + cols["num_0"] * 0.5 + rng.logistic(size=n)
    label = np.empty(n, np.float32)
    label[np.argsort(latent, kind="stable")] = class_of_rank(n, cumulative_shares)
    cols[LABEL] = label
    return cols


def make(cfg: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """The configuration's table with its rows in ``seed``'s order."""
    t = cfg["table"]
    cols = synthesize(cfg["rows"], t["draw_seed"], cfg["n_real"],
                      cfg["n_picklist"], cfg["picklist_categories"],
                      t["class_shares"])
    step = np.float32(t["real_step"])
    order = np.random.default_rng(int(seed)).permutation(int(cfg["rows"]))
    for name, v in cols.items():
        if v.dtype == np.float32 and name != LABEL:
            v = np.round(v / step) * step
        cols[name] = v[order]
    return cols
