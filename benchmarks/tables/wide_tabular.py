"""The table generator: one synthetic wide table, its rows ordered by ``--seed``.

The draws are a copy of ``scale10m.synthesize`` (PR 23 tree), returned as
plain numpy columns so the plain reference reads the same table without
touching the program.  Two things are the benchmark's own, both so that
every seed gives the program the same amount of work:

- the draws come from the configuration's ``table.draw_seed``, and ``--seed``
  orders the rows.  The holdout, the training sample, the folds and the
  sanity check's row sample are all drawn by position, so every seed trains
  and validates on other rows, while the table's sizes, category counts and
  class balance stay what they are;
- Real values are rounded to ``table.real_step`` (2^-8).  Sums of such
  values are exact in float32 whatever the order, so the column means the
  program fits as null fills — which it bakes into its streamed transform
  program as constants — are the same numbers for every seed, and that
  program is compiled once per checkout and not once per seed.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

LABEL = "label"


def synthesize(rows: int, seed: int, n_real: int, n_picklist: int,
               picklist_categories: int = 8) -> Dict[str, np.ndarray]:
    """``{"num_j": f32[rows], "cat_j": object[rows], "label": f32[rows]}``.

    Per 50 Real features: one strongly informative, one near-duplicate of it
    (correlation ~0.999), one constant; the rest noise.  Every 10th PickList
    is label-associated.  The label is logistic in the hidden signal."""
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
    logits = signal * 1.5 + cols["num_0"] * 0.5
    cols[LABEL] = (logits + rng.logistic(size=n) > 0).astype(np.float32)
    return cols


def make(cfg: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """The configuration's table with its rows in ``seed``'s order."""
    t = cfg["table"]
    cols = synthesize(cfg["rows"], t["draw_seed"], cfg["n_real"],
                      cfg["n_picklist"], cfg["picklist_categories"])
    step = np.float32(t["real_step"])
    order = np.random.default_rng(int(seed)).permutation(int(cfg["rows"]))
    for name, v in cols.items():
        if v.dtype == np.float32 and name != LABEL:
            v = np.round(v / step) * step
        cols[name] = v[order]
    return cols


def features(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(column, type name) of every predictor, in the program's order."""
    return ([(f"num_{j}", "Real") for j in range(cfg["n_real"])]
            + [(f"cat_{j}", "PickList") for j in range(cfg["n_picklist"])])
