"""Operations and bytes one selector fit of a k-class grid REQUIRES, from
shapes alone (``ops_count``'s and ``trees_ops_count``'s rules: never XLA's
``cost_analysis``, never what a formulation spends).

What a class count changes, and nothing else:

- a tree's histogram has ``c + 1`` planes, one gradient channel a class
  (``c = k``; one channel, ``c = 1``, is ``trees_ops_count``'s binary tree)
  and the hessian: ``(c + 1) n kept`` adds a level, and ``c + 1`` float32
  entries written per (open node, kept feature, bin);
- the split scan of one (open node, kept feature, bin) cell: a running sum
  and a right-hand sum per plane (``2 (c + 1)``), a square of each channel
  on both sides and their sums over the channels (``2 c + 2 (c - 1)``), two
  quotients, four adds — ``6 (c + 1)``, which is ``trees_ops_count``'s 12 at
  ``c = 1``;
- softmax regression's ``X B`` and ``X^T R`` are k columns wide:
  ``iters x 4 n (d + 1) k`` a fit, ``2 n_val (d + 1) k`` to score.

Bytes as in ``trees_ops_count``: the binned matrix (one byte a cell) streamed
once a level per forest depth, each histogram written once; one stream of X
(``ops_count.X_BYTES`` an element) per softmax iteration, shared by its
candidates and folds.  The one-hot contraction's FLOPs are counted nowhere.
What is left out and what is counted a little high is what
``trees_ops_count`` states; an RF winner's refit is counted at the family's
shallowest depth.
"""
from __future__ import annotations

from typing import Any, Dict

from benchmarks import ops_count, trees_ops_count as T


def scan_flops(channels: int) -> float:
    """FLOPs of one (open node, kept feature, bin) cell of the split scan."""
    return 6.0 * (channels + 1)


def tree_fit(s: Dict[str, Any], n: int, channels: int) -> Dict[str, float]:
    """Required work of ONE forest candidate's fit on ``n`` training rows
    (``s``: a row of ``trees_ops_count.tree_shapes``), without the stream of
    the binned matrix."""
    cells = T.open_nodes(s["depth"], s["frontier"]) * s["kept"] * s["bins"]
    return {"hist_flops": s["trees"] * s["depth"] * (channels + 1.0) * n * s["kept"],
            "split_flops": s["trees"] * scan_flops(channels) * cells,
            "hist_bytes": s["trees"] * (channels + 1.0) * T.HIST_BYTES * cells}


def softmax_fit_flops(n: int, d: int, iters: int, k: int) -> float:
    return float(iters) * 4.0 * n * (d + 1) * k


def softmax_score_flops(n: int, d: int, k: int) -> float:
    return 2.0 * n * (d + 1) * k


def sweep_step(cfg: Dict[str, Any], sweep_rows: int, width: int,
               winner_family: str = "lr", holdout_rows: int = 0,
               refit: bool = True) -> Dict[str, float]:
    """Required work of one selector fit of ``cfg``'s LR + RF grid under its
    ``classes``-way label: ``{"flops", "bytes", "hist_flops", "hist_bytes",
    "split_flops", "lr_flops", "cv_fits"}``."""
    k = int(cfg["classes"])
    c = k if k > 2 else 1
    folds = int(cfg["folds"])
    n_tr = sweep_rows * (folds - 1) // folds
    n_val = sweep_rows - n_tr
    trees: Dict[str, float] = {"hist_flops": 0.0, "split_flops": 0.0,
                               "hist_bytes": 0.0}
    shapes = T.tree_shapes(cfg, "rf", width)
    for s in shapes:
        T._add(trees, tree_fit(s, n_tr, c), folds)
    stream_bytes = T.streams(shapes) * T.BIN_BYTES * sweep_rows * width
    g = cfg["grid"]["lr"]
    iters, cands = int(g["fixed"]["max_iter"]), len(g["points"])
    lr_flops = cands * folds * (softmax_fit_flops(n_tr, width, iters, k)
                                + softmax_score_flops(n_val, width, k))
    lr_bytes = (iters + 1) * ops_count.X_BYTES * sweep_rows * width
    if refit and winner_family == "rf":
        s = min(shapes, key=lambda s: s["depth"])
        T._add(trees, tree_fit(s, sweep_rows, c))
        stream_bytes += T.streams([s]) * T.BIN_BYTES * (sweep_rows + holdout_rows) * width
    elif refit:
        lr_flops += (softmax_fit_flops(sweep_rows, width, iters, k)
                     + softmax_score_flops(sweep_rows + holdout_rows, width, k))
        lr_bytes += (iters + 1) * ops_count.X_BYTES * sweep_rows * width \
            + ops_count.X_BYTES * holdout_rows * width
    hist_bytes = trees["hist_bytes"] + stream_bytes
    return {"flops": trees["hist_flops"] + trees["split_flops"] + lr_flops,
            "bytes": hist_bytes + lr_bytes,
            "hist_flops": trees["hist_flops"], "hist_bytes": hist_bytes,
            "split_flops": trees["split_flops"], "lr_flops": lr_flops,
            "cv_fits": float(folds * (len(shapes) + cands))}


def of_run(r) -> Dict[str, float]:
    """``sweep_step`` for the run a reader is handed."""
    sh = r.shapes
    return sweep_step(r.cfg, sh["sweep_rows"], sh["width"], sh["winner_family"],
                      sh["holdout_rows"])
