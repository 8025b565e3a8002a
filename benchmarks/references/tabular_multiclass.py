"""Plain reference of the multiclass selector's default grid on the
``scale-500`` table with a k-class label (configuration
``scale-500-multiclass``): elastic-net multinomial (softmax) logistic
regression and histogram random forests with class-distribution leaves,
under k-fold cross-validation on the classification Error, the winner
refitted and evaluated on the holdout.

The host half (vectorize, sanity check, the stratified holdout, the
training-sample cap, the folds) is ``tabular_automl``'s, as is the sampling
of what is fitted, here with more than one condition a group; binning, the
seeded draws and the exact histogram product are ``tabular_trees``'s.  This
file adds what a class count changes.
It imports nothing of the program and takes nothing the program made: its
inputs are the table and the configuration's file.

- Softmax regression: the accelerated proximal gradient iteration the
  configuration states, for the iterations it states (``max_iter``, no
  convergence test): weighted mean cross-entropy over k classes, elastic net
  on every coefficient but the intercepts, step ``1 / L`` with
  ``L = trace(X1^T W X1) / (2 sum w) + l2 + 1e-6``.  float32 at matmul
  precision ``highest``; the control is the whole fit in bfloat16.
- A forest tree is ``tabular_trees``'s variance tree with one gradient
  channel a class: ``g = -onehot(y)``, ``h = 1``, so a node's sums are its
  weighted class counts; a split's gain is ``sum_c GL_c^2 / (HL + l) +
  sum_c GR_c^2 / (HR + l) - sum_c GT_c^2 / (HT + l)`` (the Gini decrease
  times the node's weight, stated in the configuration as a departure in
  form, not in splits); a leaf holds ``-G / (H + l)``, its class
  distribution; a forest averages its trees' leaves, the arg-max of that
  mean is its prediction, and its probability is the mean clipped at 0 and
  normalised.  One tree and one level at a time on the tree's kept features,
  every histogram product exact, float32 sums; the control keeps every
  number a level stores to bfloat16.
- Error is the weighted share of validation rows whose arg-max class is not
  the label; F1 is the class-frequency-weighted mean of the per-class F1
  (Spark ``MulticlassMetrics``); both in float64.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.references import tabular_automl as base
from benchmarks.references import tabular_trees as trees

LABEL = base.LABEL


# ---------------------------------------------------------------------------
# metrics (float64, host)
# ---------------------------------------------------------------------------
def error(y: np.ndarray, dist: np.ndarray) -> float:
    """Share of rows whose first-largest class is not the label."""
    return float(np.mean(np.argmax(dist, axis=1) != np.asarray(y, np.int64)))


def weighted_f1(y: np.ndarray, dist: np.ndarray) -> float:
    """Per-class F1 weighted by the class's share of the rows."""
    y = np.asarray(y, np.int64)
    pred = np.argmax(dist, axis=1)
    out = 0.0
    for c in np.unique(y):
        tp = float(np.sum((y == c) & (pred == c)))
        fp = float(np.sum((y != c) & (pred == c)))
        fn = float(np.sum((y == c) & (pred != c)))
        p = tp / (tp + fp) if tp + fp > 0 else 0.0
        r = tp / (tp + fn) if tp + fn > 0 else 0.0
        f = 2 * p * r / (p + r) if p + r > 0 else 0.0
        out += f * float(np.sum(y == c)) / len(y)
    return out


# ---------------------------------------------------------------------------
# softmax regression (device)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("iters", "k"))
def _fit_softmax(X, y, w, l1, l2, iters: int, k: int):
    """Class probabilities [n, k] of every row of ``X`` after ``iters``
    accelerated proximal steps on the rows of weight ``w``."""
    dt = X.dtype
    X1 = jnp.concatenate([X, jnp.ones((X.shape[0], 1), dt)], axis=1)
    p = X1.shape[1]
    w_sum = jnp.maximum(w.sum(), 1e-12)
    Y = jax.nn.one_hot(y.astype(jnp.int32), k, dtype=dt)
    pen = jnp.ones((p, 1), dt).at[-1].set(0)
    l1m, l2m = l1 * pen, l2 * pen
    step = 1.0 / (0.5 * jnp.sum((X1 * X1).T * w) / w_sum + l2 + 1e-6)

    def grad(B):
        mu = jax.nn.softmax(X1 @ B, axis=-1)
        return X1.T @ (w[:, None] * (mu - Y)) / w_sum + l2m * B

    def body(_, carry):
        B, Z, t = carry
        B2 = base._soft(Z - step * grad(Z), step * l1m)
        t2 = 0.5 * (1 + jnp.sqrt(1 + 4 * t * t))
        return B2, B2 + ((t - 1) / t2) * (B2 - B), t2

    B0 = jnp.zeros((p, k), dt)
    B, _, _ = lax.fori_loop(0, iters, body, (B0, B0, jnp.ones((), dt)))
    return jax.nn.softmax(X1 @ B, axis=-1)


class SoftmaxFitter:
    """Fits one softmax candidate on weighted rows and scores every row."""

    def __init__(self, X, y, cfg: Dict[str, Any], low: bool):
        self.cfg = cfg
        self.dt = jnp.dtype("bfloat16" if low else "float32")
        self.precision = "default" if low else "highest"
        self.X, self.y = jnp.asarray(X, self.dt), jnp.asarray(y, self.dt)

    def score(self, family, hp, w, X_other=None) -> np.ndarray:
        X, y, w = self.X, self.y, jnp.asarray(w, self.dt)
        if X_other is not None:  # rows that never train: weight 0
            X = jnp.concatenate([X, jnp.asarray(X_other, self.dt)])
            y = jnp.concatenate([y, jnp.zeros(len(X_other), self.dt)])
            w = jnp.concatenate([w, jnp.zeros(len(X_other), self.dt)])
        reg, alpha = np.float32(hp[0]), np.float32(hp[1])
        with jax.default_matmul_precision(self.precision):
            prob = _fit_softmax(
                X, y, w, jnp.asarray(reg * alpha, self.dt),
                jnp.asarray(reg * (np.float32(1) - alpha), self.dt),
                iters=int(self.cfg["grid"]["lr"]["fixed"]["max_iter"]),
                k=int(self.cfg["classes"]))
        return np.asarray(prob.astype(jnp.float32))


# ---------------------------------------------------------------------------
# one level of one k-channel tree (device)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("m", "keep", "n_bins", "low"))
def _level(onehot, bins, slot, value, gw, hw, n_open, lam, mcw, mig,
           m: int, keep: Optional[int], n_bins: int, low: bool):
    """``tabular_trees._level`` with a gradient channel a class: ``gw``
    f32[n, c] and ``value`` f32[n, c]; a node's gain sums its channels'
    squares, its children's values are vectors.  Same slots, same beam,
    same ties."""
    B = n_bins
    dk = bins.shape[1]
    c = gw.shape[1]
    nodes = jnp.arange(m)
    S = slot[None, :] == nodes[:, None]                           # [m, n]
    planes = [jnp.where(S, gw[:, j], 0.0) for j in range(c)]
    lhs = jnp.concatenate(planes + [jnp.where(S, hw, 0.0)])  # [(c+1) m, n]
    q = ((lambda x: lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7))
         if low else (lambda x: x))
    hist = q(trees._exact_dot(lhs, onehot)).reshape(c + 1, m, dk, B)
    G, H = hist[:c], hist[c]
    GT, HT = q(G[:, :, 0, :].sum(-1)), q(H[:, 0, :].sum(-1))    # [c, m], [m]
    GL, HL = q(jnp.cumsum(G, axis=-1)), q(jnp.cumsum(H, axis=-1))
    GR, HR = q(GT[:, :, None, None] - GL), q(HT[:, None, None] - HL)

    def score(Gp, Hp):
        return q(q((Gp * Gp).sum(axis=0)) / (Hp + lam))

    gain = q(score(GL, HL) + score(GR, HR) - score(GT, HT)[:, None, None])
    ok = (HL >= mcw) & (HR >= mcw) & (jnp.arange(B) < B - 1)
    flat = jnp.where(ok, gain, -jnp.inf).reshape(m, dk * B)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.max(flat, axis=1)
    split = (best_gain > 0.0) & (best_gain >= mig * HT) & (nodes < n_open)
    if keep is not None:  # the beam: the largest gains, ties to the lower node
        order = jnp.argsort(jnp.where(split, -best_gain, jnp.inf), stable=True)
        split &= jnp.zeros(m, jnp.int32).at[order].set(nodes) < keep
    kk = jnp.cumsum(split.astype(jnp.int32))
    left = 2 * (kk - 1)
    feat, thr = best // B, best % B
    GLb = jnp.take_along_axis(GL.reshape(c, m, dk * B), best[None, :, None],
                              axis=2)[..., 0]                     # [c, m]
    HLb = jnp.take_along_axis(HL.reshape(m, dk * B), best[:, None],
                              axis=1)[:, 0]                       # [m]
    lval = q(-GLb / (HLb + lam)).T                              # [m, c]
    rval = q(-(GT - GLb) / ((HT - HLb) + lam)).T
    s = jnp.maximum(slot, 0)
    moves = (slot >= 0) & split[s]
    right = jnp.take_along_axis(bins, feat[s][:, None], axis=1)[:, 0] > thr[s]
    slot = jnp.where(moves, left[s] + right, -1)
    value = jnp.where(moves[:, None],
                      jnp.where(right[:, None], rval[s], lval[s]), value)
    return slot, value, 2 * kk[-1], (split, feat, thr, left, lval, rval)


def grow_tree(bk, onehot, g, w, p: Dict[str, Any], low: bool):
    """One tree on its kept features: (each row's leaf vector f32[n, c], the
    root's, the levels' records)."""
    B, M = int(p["n_bins"]), int(p["max_frontier"])
    gw, hw = g * w[:, None], w
    lam = jnp.float32(p["reg_lambda"])
    root = -gw.sum(axis=0) / (hw.sum() + lam)
    slot = jnp.zeros(bk.shape[0], jnp.int32)
    value = jnp.broadcast_to(root, gw.shape)
    n_open = jnp.int32(1)
    records = []
    for t in range(int(p["max_depth"])):
        m = min(1 << t, M)
        slot, value, n_open, rec = _level(
            onehot, bk, slot, value, gw, hw, n_open, lam,
            jnp.float32(p["min_child_weight"]), jnp.float32(p["min_info_gain"]),
            m=m, keep=M // 2 if 2 * m > M else None, n_bins=B, low=low)
        records.append(rec)
    return value, root, records


def walk(bins: np.ndarray, kept: np.ndarray, root, records) -> np.ndarray:
    """The tree's leaf vector for rows that never trained (host)."""
    rows = np.arange(len(bins))
    slot = np.zeros(len(bins), np.int64)
    value = np.broadcast_to(np.asarray(root, np.float32),
                            (len(bins), len(root))).copy()
    for rec in records:
        split, feat, thr, left, lval, rval = (np.asarray(a) for a in rec)
        s = np.maximum(slot, 0)
        moves = (slot >= 0) & split[s]
        right = bins[rows, kept[feat[s]]] > thr[s]
        slot = np.where(moves, left[s] + right, -1)
        value = np.where(moves[:, None],
                         np.where(right[:, None], rval[s], lval[s]), value)
    return value


class ForestFitter:
    """Fits one forest candidate on weighted rows and gives every row (and
    rows that never train) the mean of its trees' class distributions."""

    def __init__(self, X, y, cfg: Dict[str, Any], low: bool):
        self.cfg, self.low = cfg, low
        self.rule = cfg["assumed_numbers"]
        self.X = X
        self.g = -jax.nn.one_hot(jnp.asarray(y, jnp.int32), int(cfg["classes"]),
                                 dtype=jnp.float32)
        self._binned: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def binned(self, n_bins: int) -> Tuple[np.ndarray, np.ndarray]:
        if n_bins not in self._binned:
            edges = trees.bin_edges(self.X, n_bins, self.rule["sketch_edges"])
            self._binned[n_bins] = (trees.bins_of(self.X, edges), edges)
        return self._binned[n_bins]

    def score(self, family, hp, w, X_other=None) -> np.ndarray:
        grid = self.cfg["grid"][family]
        params = dict(grid["fixed"], **dict(zip(grid["keys"], hp)))
        bins, edges = self.binned(int(params["max_bins"]))
        other = None if X_other is None else trees.bins_of(X_other, edges)
        n, d = bins.shape
        T = int(params["num_trees"])
        boot, masks = trees.tree_draws(int(params["seed"]), n, d, T,
                                       np.sqrt(d) / d)  # "auto": sqrt(d) of d
        p = {"n_bins": params["max_bins"], "max_depth": params["max_depth"],
             "max_frontier": self.rule["max_frontier"],
             "reg_lambda": self.rule["rf_reg_lambda"],
             "min_child_weight": params["min_instances_per_node"],
             "min_info_gain": params["min_info_gain"]}
        w = jnp.asarray(w, jnp.float32)
        total, total_other = 0.0, 0.0
        for t in range(T):
            kept = np.flatnonzero(masks[t])
            value, root, records = grow_tree(
                *trees.kept_onehot(bins, kept, int(p["n_bins"])), self.g,
                w * jnp.asarray(boot[t]), p, self.low)
            total = total + value
            if other is not None:
                total_other = total_other + walk(other, kept, root, records)
        out = np.asarray(total) / T
        if other is not None:
            out = np.concatenate([out, np.asarray(total_other, np.float32) / T])
        return out.astype(np.float32)


class Fitter:
    """One fitter for the grid's two families."""

    def __init__(self, X, y, cfg: Dict[str, Any], low: bool):
        self.by_family = {"lr": SoftmaxFitter(X, y, cfg, low),
                          "rf": ForestFitter(X, y, cfg, low)}

    def score(self, family, hp, w, X_other=None) -> np.ndarray:
        return self.by_family[family].score(family, hp, w, X_other)


def probabilities(family: str, dist: np.ndarray) -> np.ndarray:
    """What the fitted model reports as class probabilities: softmax's own;
    a forest's mean distribution clipped at 0 and normalised."""
    if family != "rf":
        return dist
    dist = np.clip(dist, 0.0, None)
    return dist / np.maximum(dist.sum(axis=1, keepdims=True), 1e-12)


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------
def sample_pairs(flat, check: Dict[str, Any], seed: int, folds: int,
                 winner: int) -> Dict[str, List[Tuple[int, int]]]:
    """``tabular_automl.sample_pairs`` (same draws from the seed) with a
    group's ``where`` a LIST of conditions that all hold: hyperparameter
    ``at`` within ``[lo, hi]``.  So a forest group can name a depth AND the
    ``min_info_gain`` under which a forest splits at all: a forest of stumps
    reads the same in every precision and tells the control nothing
    (``PERF.md`` section 7)."""
    def inside(candidate, g) -> bool:
        fam, hp = candidate
        return fam == g["family"] and all(
            w["lo"] <= hp[w["at"]] <= w["hi"] for w in g.get("where", ()))

    rng = np.random.default_rng([int(seed), 26])
    out = {}
    for name, g in check["groups"].items():
        idx = [i for i, c in enumerate(flat) if inside(c, g)]
        rest = [i for i in idx if i != winner]
        take = min(int(g["take"]), len(rest))
        out[name] = [(int(ci), int(rng.integers(folds)))
                     for ci in sorted(rng.choice(rest, size=take, replace=False))]
        if winner in idx:
            out[name] += [(winner, f) for f in range(folds)]
    return out


def _plane_gap(got, truth) -> float:
    got = np.asarray(got, np.float64)
    return (float(np.max(np.abs(got - truth))) if got.shape == truth.shape
            else float("inf"))


def _gaps(got, planes_got, hold_got, prob_got, truth, planes_truth, hold_truth,
          prob_truth, by_gap) -> Dict[str, float]:
    out = {}
    for name, pairs in by_gap.items():
        if pairs:
            out[f"{name}_fold_gap"] = float(
                max(abs(got[p] - truth[p]) for p in pairs))
            out[f"{name}_prob_gap"] = max(
                _plane_gap(planes_got[p], planes_truth[p]) for p in pairs)
    out["holdout_gap"] = float(max(abs(hold_got[k] - hold_truth[k])
                                   for k in ("Error", "F1")))
    out["holdout_prob_gap"] = _plane_gap(prob_got, prob_truth)
    return out


def numbers(answers: Dict[str, Any], cols: Dict[str, np.ndarray],
            cfg: Dict[str, Any], check: Dict[str, Any], seed: int,
            control: bool = False, emit=None):
    """(the program's numbers, the control's or None), to be held against
    ``check["limits"]``: ``tabular_trees.numbers`` with Error for AuPR (the
    winner is the candidate of LOWEST mean), and beside each sampling group's
    Error gap (under the group's ``gap`` name) its ``<gap>_prob_gap``: the
    largest absolute gap, over the sampled (candidate, fold) pairs and the
    fold's validation rows, between the class distribution the timed sweep
    scored a row (``answers["score_block"]``, the [F, C, n, k] block its
    training program handed its metric pass) and the reference's — a
    forest's mean leaf distribution as it stands, softmax's probabilities.
    Error moves in steps of one validation row and not at all where every
    forest of the grid predicts the majority class on every row, so an
    arg-max metric alone lets a lower precision, a wrong leaf or a missing
    tree through; a distribution does not.  ``holdout_prob_gap`` is the same
    for the refitted winner on the holdout rows.  The winner's folds are
    among its group's pairs, so the gap of its fold mean, which can be no
    larger than the largest of them, is not compared again.  A winner of a
    family listed under ``one_fold_winners`` is fitted on one seed-drawn
    fold.  The control is this reference in bfloat16."""
    X_ref, _, _ = base.vectorize(cols, cfg)
    y_all = np.asarray(cols[LABEL], np.float32)
    # DataCutter balances nothing: the holdout, the cap and the folds alone
    sp = base.split(y_all, dict(cfg, balancer_sample_fraction=0.0))
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
        (means < means[win]).sum() + sum(e is not None for e in answers["errors"])
        + (len(means) != len(flat)))
    groups = sample_pairs(flat, check, seed, folds, win)
    wp = [(win, f) for f in range(folds)]
    if flat[win][0] in check.get("one_fold_winners", ()):
        keep = int(np.random.default_rng([int(seed), 33]).integers(folds))
        wp = [(win, keep)]
        groups = {name: [p for p in pairs if p[0] != win or p[1] == keep]
                  for name, pairs in groups.items()}
    fam_w, hp_w = flat[win]
    by_gap: Dict[str, List[Tuple[int, int]]] = {}
    gap_of = {}
    for name, pairs in groups.items():
        g = check["groups"][name]
        gap_of[g["family"]] = g.get("gap", g["family"])
        by_gap.setdefault(gap_of[g["family"]], []).extend(pairs)
    # the winner's folds are held to its family's limits, whatever its group
    # sampled it or not (a forest outside every group's conditions)
    mine = by_gap.setdefault(gap_of.get(fam_w, fam_w), [])
    mine.extend(p for p in wp if p not in mine)
    pairs = sorted({p for ps in by_gap.values() for p in ps})

    def reference_answers(low: bool):
        fitter = Fitter(Xtr, ytr, cfg, low)
        got, planes = {}, {}
        for c, f in pairs:
            dist = fitter.score(*flat[c], (sp["fold"] != f).astype(np.float32))
            val = sp["fold"] == f
            got[(c, f)] = error(ytr[val], dist[val])
            planes[(c, f)] = dist[val].astype(np.float64)
        dist = fitter.score(fam_w, hp_w, np.ones(len(ytr), np.float32),
                            X_hold)[len(ytr):]
        return (got, planes, {"Error": error(y_hold, dist),
                              "F1": weighted_f1(y_hold, dist)},
                probabilities(fam_w, dist).astype(np.float64))

    truth, planes_truth, hold_truth, prob_truth = reference_answers(False)
    got = {p: float(answers["fold_metrics"][p[0]][p[1]]) for p in pairs}
    block = np.asarray(answers["score_block"])    # [F, C, n, k], sweep rows
    rows_ok = block.ndim == 4 and block.shape[2] == len(ytr)
    planes_got = {(c, f): block[f, c][sp["fold"] == f] if rows_ok
                  else np.zeros(0) for c, f in pairs}
    out = dict(exact, **_gaps(got, planes_got, answers["holdout"],
                              answers["holdout_prob"], truth, planes_truth,
                              hold_truth, prob_truth, by_gap))
    ctl = lowv = None
    if control:
        lowv, planes_low, hold_low, prob_low = reference_answers(True)
        ctl = dict(exact, **_gaps(lowv, planes_low, hold_low, prob_low, truth,
                                  planes_truth, hold_truth, prob_truth, by_gap))
    if emit is not None:
        emit(phase="pairs", winner=[fam_w, list(hp_w)], means=list(means), pairs=[
            {"family": flat[c][0], "hp": list(flat[c][1]), "fold": f,
             "reference": truth[(c, f)], "program": got[(c, f)],
             "control": lowv[(c, f)] if lowv else None} for c, f in pairs])
    return out, ctl
