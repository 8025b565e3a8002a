"""Operations and bytes one selector fit of a grid with tree families
REQUIRES, from shapes alone (``ops_count``'s rules: never XLA's
``cost_analysis``, never what a formulation spends).

A histogram tree of depth ``D`` on ``n`` training rows, ``k`` kept features
(a forest keeps ``round(sqrt(d))`` of ``d`` a tree, boosting all), ``B``
bins, at most ``M`` open nodes a level (``m_l = min(2^l, M)``) needs, however
the histogram is built:

- accumulate: one add of g and one of h per training row, kept feature and
  level — ``2 n k`` a level;
- split scan: per open node, kept feature and bin the two running sums, the
  two right-hand sums and the gain (two squares, two quotients, four adds):
  ``SCAN_FLOPS`` = 12 — ``12 m_l k B`` a level.

The one-hot contraction's FLOPs (``2 n m_l k B`` a level and channel, B·m-fold
the accumulate) are what ONE formulation spends and are counted nowhere.

Bytes: the binned matrix (one byte a cell) is streamed once a level for a
whole group of trees grown together — all folds, candidates and trees of one
forest depth, or one boosting round of every fold and candidate, since a
round needs the one before it; each level's histogram (g and h, float32) is
written once a tree.

Left out of both counts: the quantile sketch and the binning (host), the
bootstrap and feature-subset draws, routing (one compare per row and level),
leaf reads, the metric pass's sort, every elementwise pass.  Counted high on
purpose nowhere; counted a little high in two places, both stated: a row that
its bootstrap drew zero times or that rests at a leaf above the level still
counts as accumulated, and a level counts ``m_l`` open nodes whether or not
so many opened.  For a forest winner of unknown depth the refit is counted at
the family's shallowest depth.

The logistic family's counts are ``ops_count``'s, at the iterations the
configuration says the program runs (``assumed_numbers.lr_min_iterations``).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List

from benchmarks import ops_count

#: FLOPs of one (open node, kept feature, bin) cell of the split scan
SCAN_FLOPS = 12.0
#: bytes of one cell of the binned matrix, of one histogram entry
BIN_BYTES, HIST_BYTES = 1.0, 4.0


def tree_shapes(cfg: Dict[str, Any], family: str, width: int
                ) -> List[Dict[str, Any]]:
    """Per candidate of a tree family: trees, depth, kept features, bins,
    frontier, and whether each tree waits for the one before (boosting)."""
    g = cfg["grid"][family]
    frontier = int(cfg["assumed_numbers"]["max_frontier"])
    out = []
    for point in g["points"]:
        p = dict(g["fixed"], **dict(zip(g["keys"], point)))
        if family == "rf":
            out.append({"trees": int(p["num_trees"]), "depth": int(p["max_depth"]),
                        "kept": max(1, int(round(math.sqrt(width)))),
                        "bins": int(p["max_bins"]), "frontier": frontier,
                        "chained": False})
        elif family == "xgb":
            out.append({"trees": int(p["num_round"]), "depth": int(p["max_depth"]),
                        "kept": int(width), "bins": int(p["max_bins"]),
                        "frontier": frontier, "chained": True})
        else:
            raise KeyError(f"trees_ops_count has no formula for family {family!r}")
    return out


def open_nodes(depth: int, frontier: int) -> int:
    """Sum over a tree's levels of the nodes a level may hold open."""
    return sum(min(1 << level, frontier) for level in range(depth))


def tree_fit(s: Dict[str, Any], n: int) -> Dict[str, float]:
    """Required work of ONE candidate's fit on ``n`` training rows, without
    the stream of the binned matrix (shared by a group, see ``streams``)."""
    cells = open_nodes(s["depth"], s["frontier"]) * s["kept"] * s["bins"]
    return {"hist_flops": s["trees"] * s["depth"] * 2.0 * n * s["kept"],
            "split_flops": s["trees"] * SCAN_FLOPS * cells,
            "hist_bytes": s["trees"] * 2.0 * HIST_BYTES * cells}


def streams(shapes: List[Dict[str, Any]]) -> float:
    """Passes over the binned matrix one family's candidates need: one a
    level for every distinct forest depth, one a level and round for a
    boosted family (its candidates share the rounds)."""
    chained = [s for s in shapes if s["chained"]]
    passes = sum({s["depth"] for s in shapes if not s["chained"]})
    if chained:
        passes += max(s["trees"] * s["depth"] for s in chained)
    return float(passes)


def _add(into: Dict[str, float], part: Dict[str, float], times: float = 1.0):
    for k, v in part.items():
        into[k] = into.get(k, 0.0) + times * v


def tree_families(cfg: Dict[str, Any]) -> List[str]:
    return [f for f in cfg["grid"] if f in ("rf", "xgb")]


def sweep_step(cfg: Dict[str, Any], sweep_rows: int, width: int,
               winner_family: str = "lr", holdout_rows: int = 0,
               refit: bool = True) -> Dict[str, float]:
    """Required work of one selector fit: ``{"flops", "bytes", "hist_flops",
    "hist_bytes", "split_flops", "cv_fits"}`` — the whole step's FLOPs and
    bytes, and of them the level histograms' (accumulates; the streams of
    the binned matrix and the histogram writes)."""
    folds = int(cfg["folds"])
    n_tr = sweep_rows * (folds - 1) // folds
    n_val = sweep_rows - n_tr
    trees: Dict[str, float] = {}
    stream_bytes = 0.0
    fits = 0
    for fam in tree_families(cfg):
        shapes = tree_shapes(cfg, fam, width)
        for s in shapes:
            _add(trees, tree_fit(s, n_tr), folds)
        stream_bytes += streams(shapes) * BIN_BYTES * sweep_rows * width
        fits += folds * len(shapes)
    if refit and winner_family in tree_families(cfg):
        shapes = tree_shapes(cfg, winner_family, width)
        s = min(shapes, key=lambda s: s["depth"])
        _add(trees, tree_fit(s, sweep_rows))
        stream_bytes += streams([s]) * BIN_BYTES * (sweep_rows + holdout_rows) * width
    flops = trees.get("hist_flops", 0.0) + trees.get("split_flops", 0.0)
    hist_bytes = trees.get("hist_bytes", 0.0) + stream_bytes
    bytes_ = hist_bytes
    if "lr" in cfg["grid"]:
        g = cfg["grid"]["lr"]
        iters = max(int(g["fixed"]["max_iter"]),
                    int(cfg["assumed_numbers"]["lr_min_iterations"]))
        cands = len(g["points"])
        flops += cands * folds * (ops_count.fit_flops("lr", n_tr, width, iters)
                                  + ops_count.score_flops("lr", n_val, width))
        bytes_ += (iters + 1) * ops_count.X_BYTES * sweep_rows * width
        fits += cands * folds
        if refit and winner_family == "lr":
            flops += (ops_count.fit_flops("lr", sweep_rows, width, iters)
                      + ops_count.score_flops("lr", sweep_rows + holdout_rows, width))
            bytes_ += (iters + 1) * ops_count.X_BYTES * sweep_rows * width \
                + ops_count.X_BYTES * holdout_rows * width
    return {"flops": flops, "bytes": bytes_,
            "hist_flops": trees.get("hist_flops", 0.0), "hist_bytes": hist_bytes,
            "split_flops": trees.get("split_flops", 0.0), "cv_fits": float(fits)}


def of_run(r) -> Dict[str, float]:
    """``sweep_step`` for the run a reader is handed."""
    sh = r.shapes
    return sweep_step(r.cfg, sh["sweep_rows"], sh["width"], sh["winner_family"],
                      sh["holdout_rows"])
