"""Operations and bytes one step REQUIRES, from shapes alone.

Never from XLA's ``cost_analysis``: that counts what the compiled program
does (masked rows, recomputation), not what the algorithm needs.  The
program trains each fold on the full matrix with zero weights on the held
rows; required work counts the fold's own training rows.

Per CV fit on ``n`` training rows of width ``d`` (+1 for the intercept):

- LR (FISTA) and SVC: ``iters x 4 n (d+1)`` — one ``X b`` and one ``X^T r``
  per iteration,
- MLP ``d -> h -> k``: ``iters x (4 n d h + 6 n h k)`` — forward and weight
  gradient of the first layer (no input gradient is needed), forward, weight
  gradient and hidden gradient of the head,
- scoring the fold's ``n_val`` rows once: ``2 n_val (d+1)`` or
  ``2 n_val (d h + h k)``.

Bytes: the matrix cannot stay on chip between iterations, so each
iteration of each family's chain streams the training matrix once, shared
by all its candidates and folds, plus one pass for scoring.  A pass needs
``2 rows d`` bytes: at the configuration's precision (float32 arrays, one
bf16 pass per matmul) the multiplier reads bf16 roundings of X, and the
program does keep a bf16 copy (PR 26's first trace).  Elementwise work, the
sort of the metric pass and the hyperparameter vectors are left out of
both counts.
"""
from __future__ import annotations

from typing import Any, Dict

#: bytes one element of X costs a matmul pass (bf16 rounding of a float32)
X_BYTES = 2.0


def _family_sizes(cfg: Dict[str, Any]) -> Dict[str, Dict[str, int]]:
    """Per grid family: candidates, iterations and (MLP) the hidden width.
    A family without a formula below is an error: its counts arrive as a
    new file beside this one, with the readers that use them."""
    out = {}
    for fam, g in cfg["grid"].items():
        if fam not in ("lr", "svc", "mlp"):
            raise KeyError(f"ops_count has no formula for grid family {fam!r}")
        out[fam] = {"cands": len(g["points"]), "iters": int(g["fixed"]["max_iter"]),
                    "hidden": int(g["fixed"].get("hidden_layers", [0])[0])}
    return out


def fit_flops(family: str, n: int, d: int, iters: int, hidden: int = 0,
              k: int = 2) -> float:
    """Required FLOPs of ONE fit on ``n`` rows."""
    if family in ("lr", "svc"):
        return float(iters) * 4.0 * n * (d + 1)
    return float(iters) * (4.0 * n * d * hidden + 6.0 * n * hidden * k)


def score_flops(family: str, n: int, d: int, hidden: int = 0, k: int = 2) -> float:
    if family in ("lr", "svc"):
        return 2.0 * n * (d + 1)
    return 2.0 * n * (d * hidden + hidden * k)


def sweep_step(cfg: Dict[str, Any], sweep_rows: int, width: int,
               winner_family: str = "lr", holdout_rows: int = 0,
               refit: bool = True) -> Dict[str, float]:
    """Required work of one selector fit: the 64 x folds CV fits with their
    validation scoring, and (``refit``) the winner's refit with its train and
    holdout scoring.  ``{"flops", "bytes", "cv_fits"}``."""
    folds = int(cfg["folds"])
    n_tr = sweep_rows * (folds - 1) // folds
    n_val = sweep_rows - n_tr
    flops = bytes_ = 0.0
    fits = 0
    fams = _family_sizes(cfg)
    for fam, s in fams.items():
        h = s.get("hidden", 0)
        per_fit = (fit_flops(fam, n_tr, width, s["iters"], h)
                   + score_flops(fam, n_val, width, h))
        flops += s["cands"] * folds * per_fit
        fits += s["cands"] * folds
        bytes_ += (s["iters"] + 1) * X_BYTES * sweep_rows * width
    if not refit:
        return {"flops": flops, "bytes": bytes_, "cv_fits": float(fits)}
    w = fams[winner_family]
    h = w.get("hidden", 0)
    flops += (fit_flops(winner_family, sweep_rows, width, w["iters"], h)
              + score_flops(winner_family, sweep_rows + holdout_rows, width, h))
    bytes_ += (w["iters"] + 1) * X_BYTES * sweep_rows * width \
        + X_BYTES * holdout_rows * width
    return {"flops": flops, "bytes": bytes_, "cv_fits": float(fits)}


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, float]
                     ) -> Dict[str, Any]:
    """The least time the chip could take, and which roof sets it."""
    t_f = work["flops"] / peaks["bf16_flops_per_s"]
    t_b = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "bound": "flops" if t_f >= t_b else "bytes",
            "flops_s": t_f, "bytes_s": t_b}


def load_peaks(path: str, device_kind: str) -> Dict[str, float]:
    """The table's row for ``device_kind``; an unknown kind is an error."""
    import json

    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}: "
                       "add it with its source")
    return table[device_kind]
