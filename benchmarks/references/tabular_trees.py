"""Plain reference of the selector's default binary grid on the ``scale-500``
table (configuration ``scale-500-trees``): elastic-net logistic regression,
histogram random forests and second-order (Newton) boosted trees with
logistic loss, under k-fold cross-validation on AuPR, the winner refitted
and evaluated on the holdout.

The host half (vectorize, sanity check, split, AuPR / AuROC, the logistic
fit, the sampling of what is fitted) is ``tabular_automl``'s; this file adds
the trees.  It imports nothing of the program and takes nothing the program
made: its inputs are the table and the configuration's file, and every draw
(bootstrap weights, per-tree feature subsets) is made again here from the
seeds by the rule the configuration states under ``assumed_numbers``.

How a tree is grown, straightforwardly — one tree and one level at a time:

- features are binned once by the stated quantile sketch (``bin_edges``);
- a level's histogram holds, for every open node, KEPT feature and bin, the
  sum of weighted gradients and of weighted hessians over the node's rows.
  It is one matrix product of the rows' (node, gradient) table with their
  bin one-hot; each float32 factor is cut into three bfloat16 pieces, so
  every product is exact and the sums are float32 (the control keeps every
  number a level stores — these sums, the running sums over bins, the gains
  and the leaf values — to bfloat16 instead);
- a split's gain is ``GL^2/(HL+l) + GR^2/(HR+l) - GT^2/(HT+l)``, valid while
  both children hold ``min_child_weight`` of hessian, the best is the first
  largest in (feature, bin) order, and a node splits while its gain passes
  ``gamma`` and ``min_info_gain`` times its hessian;
- at most ``max_frontier`` nodes are open a level: where more could open,
  the nodes with the largest gains split (the beam rule) — the departure
  from exact depth-wise growth that the configuration states;
- a leaf predicts ``-G/(H+l)``: with g = -y, h = 1 that is the mean label,
  p(1), of a variance tree; a forest averages its trees; boosting adds
  ``eta`` times the leaf to the margin and takes g = p - y, h = p(1-p).

Gains are float32 on the device, not float64: a float64 pass needs every
level's [nodes, 2, 760, 32] histogram on the host, 2,000 levels a boosted
fit (``PERF.md``, PR 29).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import tabular_automl as base

LABEL = base.LABEL


# ---------------------------------------------------------------------------
# binning and draws, by the configuration's stated rules
# ---------------------------------------------------------------------------
def bin_edges(X: np.ndarray, n_bins: int, rule: Dict[str, Any]) -> np.ndarray:
    """f32[d, n_bins-1]: the interior quantiles of each column, over all
    rows or, past ``rule["rows"]``, over a seeded subsample of that many."""
    X = np.asarray(X, np.float32)
    if len(X) > int(rule["rows"]):
        X = X[np.random.default_rng(int(rule["seed"])).choice(
            len(X), int(rule["rows"]), replace=False)]
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(X, qs, axis=0).T.astype(np.float32)


def bins_of(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """i32[n, d]: how many of a column's edges lie below the value."""
    X = np.asarray(X, np.float32)
    return np.stack([np.searchsorted(edges[j], X[:, j], side="left")
                     for j in range(X.shape[1])], axis=1).astype(np.int32)


def tree_draws(seed: int, n: int, d: int, n_trees: int, frac: float
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(bootstrap weights f32[T, n], kept-feature masks bool[T, d]): the key
    of ``seed`` split in two; Poisson(1) weights from the first half; from
    the second one uniform per (tree, feature), a tree keeping its
    ``max(1, round(frac * d))`` smallest."""
    kb, kf = jax.random.split(jax.random.PRNGKey(jnp.uint32(seed)))
    boot = np.asarray(jax.random.poisson(kb, 1.0, (n_trees, n))).astype(np.float32)
    if frac >= 1.0:
        return boot, np.ones((n_trees, d), bool)
    k = max(1, int(round(frac * d)))
    r = np.asarray(jax.random.uniform(kf, (n_trees, d)))
    return boot, r <= np.sort(r, axis=1)[:, k - 1:k]


# ---------------------------------------------------------------------------
# one level of one tree (device)
# ---------------------------------------------------------------------------
def _exact_dot(lhs, onehot):
    """f32[a, n] x bf16 one-hot [n, b] with exact products: the float32
    factor in three bfloat16 pieces, float32 sums."""
    out = 0.0
    for _ in range(3):
        piece = lhs.astype(jnp.bfloat16)
        out = out + jax.lax.dot_general(
            piece, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lhs = lhs - piece.astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("m", "keep", "n_bins", "low"))
def _level(onehot, bins, slot, value, gw, hw, n_open, lam, gamma, mcw, mig,
           m: int, keep: Optional[int], n_bins: int, low: bool):
    """Split the ``m`` open nodes of one tree once.

    onehot bf16[n, dk*B] and bins i32[n, dk] of the kept features; per row
    its open node (``slot``, -1 once it rests at a leaf), its prediction so
    far (``value``) and its weighted gradient and hessian.  ``keep``: how
    many of the level's splits the next level has room for (None: all).
    Returns the rows' new slots and predictions, the nodes opened, and the
    level's record (split?, feature, bin, left child's slot, child values)
    for rows that never trained."""
    B = n_bins
    dk = bins.shape[1]
    nodes = jnp.arange(m)
    S = slot[None, :] == nodes[:, None]                           # [m, n]
    lhs = jnp.concatenate([jnp.where(S, gw, 0.0), jnp.where(S, hw, 0.0)])
    # the control keeps every number a level stores — histogram sums, running
    # sums, gains, leaf values — to bfloat16 (an explicit rounding, which no
    # compiler setting may drop as excess precision)
    q = ((lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7))
         if low else (lambda x: x))
    hist = q(_exact_dot(lhs, onehot))
    G = hist[:m].reshape(m, dk, B)
    H = hist[m:].reshape(m, dk, B)
    GT, HT = q(G[:, 0, :].sum(-1)), q(H[:, 0, :].sum(-1))
    GL, HL = q(jnp.cumsum(G, axis=-1)), q(jnp.cumsum(H, axis=-1))
    GR, HR = q(GT[:, None, None] - GL), q(HT[:, None, None] - HL)
    gain = q(q(GL * GL / (HL + lam)) + q(GR * GR / (HR + lam))
             - q(GT * GT / (HT + lam))[:, None, None])
    ok = (HL >= mcw) & (HR >= mcw) & (jnp.arange(B) < B - 1)
    flat = jnp.where(ok, gain, -jnp.inf).reshape(m, dk * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.max(flat, axis=1)
    split = (best_gain > gamma) & (best_gain >= mig * HT) & (nodes < n_open)
    if keep is not None:  # the beam: the largest gains, ties to the lower node
        order = jnp.argsort(jnp.where(split, -best_gain, jnp.inf), stable=True)
        split &= jnp.zeros(m, jnp.int32).at[order].set(nodes) < keep
    k = jnp.cumsum(split.astype(jnp.int32))
    left = 2 * (k - 1)
    feat, thr = best // B, best % B
    GLb = jnp.take_along_axis(GL.reshape(m, -1), best[:, None], axis=1)[:, 0]
    HLb = jnp.take_along_axis(HL.reshape(m, -1), best[:, None], axis=1)[:, 0]
    lval = q(-GLb / (HLb + lam))
    rval = q(-(GT - GLb) / ((HT - HLb) + lam))
    s = jnp.maximum(slot, 0)
    moves = (slot >= 0) & split[s]
    right = jnp.take_along_axis(bins, feat[s][:, None], axis=1)[:, 0] > thr[s]
    slot = jnp.where(moves, left[s] + right, -1)
    value = jnp.where(moves, jnp.where(right, rval[s], lval[s]), value)
    return slot, value, 2 * k[-1], (split, feat, thr, left, lval, rval)


def kept_onehot(bins: np.ndarray, kept: np.ndarray, n_bins: int):
    """(bins of the kept features i32[n, dk], their one-hot bf16[n, dk*B])."""
    bk = jnp.asarray(bins[:, kept])
    onehot = (bk[:, :, None] == jnp.arange(n_bins)).astype(jnp.bfloat16)
    return bk, onehot.reshape(len(bins), -1)


def grow_tree(bk, onehot, g, h, w, p: Dict[str, Any], low: bool):
    """Grow one tree on the kept features; returns (each row's prediction
    f32[n], the root value, the levels' records)."""
    B, M = int(p["n_bins"]), int(p["max_frontier"])
    n = bk.shape[0]
    gw, hw = g * w, h * w
    lam = jnp.float32(p["reg_lambda"])
    root = -gw.sum() / (hw.sum() + lam)
    slot = jnp.zeros(n, jnp.int32)
    value = jnp.full(n, root, jnp.float32)
    n_open = jnp.int32(1)
    records = []
    for t in range(int(p["max_depth"])):
        m = min(1 << t, M)
        slot, value, n_open, rec = _level(
            onehot, bk, slot, value, gw, hw, n_open, lam,
            jnp.float32(p["gamma"]), jnp.float32(p["min_child_weight"]),
            jnp.float32(p["min_info_gain"]), m=m,
            keep=M // 2 if 2 * m > M else None, n_bins=B, low=low)
        records.append(rec)
    return value, root, records


def walk(bins: np.ndarray, kept: np.ndarray, root, records) -> np.ndarray:
    """The tree's prediction for rows that never trained (host)."""
    rows = np.arange(len(bins))
    slot = np.zeros(len(bins), np.int64)
    value = np.full(len(bins), float(root), np.float32)
    for rec in records:
        split, feat, thr, left, lval, rval = (np.asarray(a) for a in rec)
        s = np.maximum(slot, 0)
        moves = (slot >= 0) & split[s]
        right = bins[rows, kept[feat[s]]] > thr[s]
        slot = np.where(moves, left[s] + right, -1)
        value = np.where(moves, np.where(right, rval[s], lval[s]), value)
    return value


# ---------------------------------------------------------------------------
# the two ensembles
# ---------------------------------------------------------------------------
class TreeFitter:
    """Fits one forest or boosted candidate on weighted rows and scores
    every row (and rows that never train)."""

    def __init__(self, X: np.ndarray, y: np.ndarray, cfg: Dict[str, Any],
                 low: bool = False):
        self.cfg, self.low = cfg, low
        self.rule = cfg["assumed_numbers"]
        self.X, self.y = X, jnp.asarray(y, jnp.float32)
        self._binned: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def binned(self, n_bins: int) -> Tuple[np.ndarray, np.ndarray]:
        if n_bins not in self._binned:
            edges = bin_edges(self.X, n_bins, self.rule["sketch_edges"])
            self._binned[n_bins] = (bins_of(self.X, edges), edges)
        return self._binned[n_bins]

    def score(self, family: str, hp: Tuple, w: np.ndarray,
              X_other: Optional[np.ndarray] = None) -> np.ndarray:
        """Class-1 score of every fit row, then of ``X_other``'s rows."""
        g = self.cfg["grid"][family]
        params = dict(g["fixed"], **dict(zip(g["keys"], hp)))
        bins, edges = self.binned(int(params["max_bins"]))
        other = None if X_other is None else bins_of(X_other, edges)
        fit = self._forest if family == "rf" else self._boosted
        return fit(params, bins, jnp.asarray(w, jnp.float32), other)

    def _forest(self, params, bins, w, other) -> np.ndarray:
        n, d = bins.shape
        T = int(params["num_trees"])
        boot, masks = tree_draws(int(params["seed"]), n, d, T,
                                 np.sqrt(d) / d)  # "auto": sqrt(d) of d
        p = {"n_bins": params["max_bins"], "max_depth": params["max_depth"],
             "max_frontier": self.rule["max_frontier"],
             "reg_lambda": self.rule["rf_reg_lambda"], "gamma": 0.0,
             "min_child_weight": params["min_instances_per_node"],
             "min_info_gain": params["min_info_gain"]}
        g, h = -self.y, jnp.ones_like(self.y)
        total = jnp.zeros(n, jnp.float32)
        total_other = 0.0
        for t in range(T):
            kept = np.flatnonzero(masks[t])
            value, root, records = grow_tree(
                *kept_onehot(bins, kept, int(p["n_bins"])), g, h,
                w * jnp.asarray(boot[t]), p, self.low)
            total = total + value
            if other is not None:
                total_other = total_other + walk(other, kept, root, records)
        out = np.asarray(total) / T
        if other is not None:
            out = np.concatenate([out, np.asarray(total_other, np.float32) / T])
        return out.astype(np.float32)

    def _boosted(self, params, bins, w, other) -> np.ndarray:
        n, d = bins.shape
        p = {"n_bins": params["max_bins"], "max_depth": params["max_depth"],
             "max_frontier": self.rule["max_frontier"],
             "reg_lambda": max(float(params["reg_lambda"]), 1e-6),
             "gamma": params["gamma"],
             "min_child_weight": params["min_child_weight"],
             "min_info_gain": 0.0}
        eta = jnp.float32(params["eta"])
        every = np.arange(d)
        bk, onehot = kept_onehot(bins, every, int(p["n_bins"]))
        F = jnp.zeros(n, jnp.float32)
        F_other = None if other is None else np.zeros(len(other), np.float32)
        for _ in range(int(params["num_round"])):
            prob = jax.nn.sigmoid(F)
            value, root, records = grow_tree(
                bk, onehot, prob - self.y,
                jnp.maximum(prob * (1 - prob), 1e-6), w, p, self.low)
            F = F + eta * value
            if self.low:  # the control keeps the margins to bfloat16 too
                F = jax.lax.reduce_precision(F, exponent_bits=8, mantissa_bits=7)
            if other is not None:
                F_other = F_other + np.float32(eta) * walk(other, every, root,
                                                           records)
        out = np.asarray(jax.nn.sigmoid(F))
        if other is not None:
            out = np.concatenate([out, 1.0 / (1.0 + np.exp(-F_other))])
        return out.astype(np.float32)


class Fitter:
    """One fitter for the whole grid: the logistic family is
    ``tabular_automl``'s, run for the iterations the configuration says the
    program really runs; the tree families are this file's."""

    def __init__(self, X, y, cfg: Dict[str, Any], low: bool):
        lr_cfg = dict(cfg, grid={"lr": dict(cfg["grid"]["lr"], fixed={
            "max_iter": max(int(cfg["grid"]["lr"]["fixed"]["max_iter"]),
                            int(cfg["assumed_numbers"]["lr_min_iterations"]))})})
        self.linear = base.Fitter(X, y, lr_cfg, "bfloat16" if low else "float32")
        self.trees = TreeFitter(X, y, cfg, low)

    def score(self, family, hp, w, X_other=None) -> np.ndarray:
        fitter = self.linear if family == "lr" else self.trees
        return fitter.score(family, hp, w, X_other)


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------
def _gaps(got, hold_got, truth, hold_truth, by_family, wp) -> Dict[str, float]:
    out = {f"{fam}_fold_gap": float(max(abs(got[p] - truth[p]) for p in pairs))
           for fam, pairs in by_family.items() if pairs}
    out["winner_cv_gap"] = float(abs(np.mean([got[p] for p in wp])
                                     - np.mean([truth[p] for p in wp])))
    out["holdout_gap"] = float(max(abs(hold_got[k] - hold_truth[k])
                                   for k in ("AuPR", "AuROC")))
    return out


def numbers(answers: Dict[str, Any], cols: Dict[str, np.ndarray],
            cfg: Dict[str, Any], check: Dict[str, Any], seed: int,
            control: bool = False, emit=None):
    """(the program's numbers, the control's or None), as
    ``tabular_automl.numbers`` gives them, with the fold gaps by FAMILY
    (``lr_fold_gap``, ``rf_fold_gap``, ``xgb_fold_gap``): each sampling group
    of ``check["groups"]`` draws its candidates and one fold for each; the
    winner is fitted on every fold — a boosted winner on one seed-drawn fold,
    in place of the boosted group's draw — and refitted for the holdout.
    The control is this reference in bfloat16: every number a tree level
    stores, and the whole logistic fit."""
    X_ref, _, _ = base.vectorize(cols, cfg)
    y_all = np.asarray(cols[LABEL], np.float32)
    sp = base.split(y_all, cfg)
    Xtr, ytr = X_ref[sp["train"]], y_all[sp["train"]]
    X_hold, y_hold = X_ref[sp["holdout"]], y_all[sp["holdout"]]
    flat = base.flat_candidates(cfg)
    folds = int(cfg["folds"])

    V = np.asarray(answers["vector"])
    exact = {"vector_cells_differ": (
        float(np.count_nonzero(V != X_ref)) if V.shape == X_ref.shape
        else float(max(V.size, X_ref.size)))}
    means = np.asarray(answers["mean_metrics"], np.float64)
    win = base.winner_index(answers, cfg, flat)
    exact["winner_not_best"] = float(
        (means > means[win]).sum() + sum(e is not None for e in answers["errors"])
        + (len(means) != len(flat)))
    groups = base.sample_pairs(flat, check, seed, folds, win)
    wp = [(win, f) for f in range(folds)]
    if flat[win][0] in check.get("one_fold_winners", ()):
        keep = int(np.random.default_rng([int(seed), 29]).integers(folds))
        wp = [(win, keep)]
        groups = {name: [p for p in pairs if p[0] == win and p[1] == keep]
                  if check["groups"][name]["family"] == flat[win][0]
                  else pairs for name, pairs in groups.items()}
    by_family: Dict[str, List[Tuple[int, int]]] = {}
    for name, pairs in groups.items():
        by_family.setdefault(check["groups"][name]["family"], []).extend(pairs)
    pairs = sorted({p for ps in groups.values() for p in ps} | set(wp))

    def reference_answers(low: bool):
        fitter = Fitter(Xtr, ytr, cfg, low)
        got = {p: base.fold_metric(fitter, *flat[p[0]], sp["fold"], p[1], ytr)
               for p in pairs}
        return got, base.holdout_metrics(fitter, *flat[win], len(ytr),
                                         X_hold, y_hold)

    truth, hold_truth = reference_answers(False)
    got = {p: float(answers["fold_metrics"][p[0]][p[1]]) for p in pairs}
    out = dict(exact, **_gaps(got, answers["holdout"], truth, hold_truth,
                              by_family, wp))
    ctl = lowv = None
    if control:
        lowv, hold_low = reference_answers(True)
        ctl = dict(exact, **_gaps(lowv, hold_low, truth, hold_truth,
                                  by_family, wp))
    if emit is not None:
        emit(phase="pairs", winner=[flat[win][0], list(flat[win][1])], pairs=[
            {"family": flat[c][0], "hp": list(flat[c][1]), "fold": f,
             "reference": truth[(c, f)], "program": got[(c, f)],
             "control": lowv[(c, f)] if lowv else None} for c, f in pairs])
    return out, ctl
