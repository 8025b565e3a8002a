"""Plain reference of the tabular AutoML pipeline the ``scale-500`` family
of configurations runs: typed table -> default vectorization -> sanity
check -> k-fold CV fits of elastic-net logistic regression (FISTA),
squared-hinge linear SVC (Nesterov) and a one-hidden-layer perceptron
(Adam) -> area under the precision-recall curve on each validation fold ->
winner refit and holdout evaluation.

It imports nothing of the program and takes nothing the program made: its
inputs are the table (``benchmarks/tables/``) and the configuration's file.
Host steps are numpy in float64; fits are ``jax.numpy`` in float32 at
matmul precision ``highest`` (``dtype=float32``), or wholly in bfloat16 for
the control (``dtype=bfloat16``).  One fit at a time, so it fits beside
nothing: it runs after the program's state is freed.

Departures from the published algorithms, each because the configuration
states it so: iteration counts are fixed (no convergence test); the SVC's
validation score is its hard 0/1 decision (it emits no probability); the
Lipschitz bound is the trace bound, not the top eigenvalue.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LABEL = "label"


# ---------------------------------------------------------------------------
# host side: vectorize, sanity-check, split
# ---------------------------------------------------------------------------
def pivot_categories(values: np.ndarray, top_k: int, min_support: int) -> List[str]:
    """Categories kept by a top-K / min-support pivot: most frequent first,
    ties by name."""
    uniq, counts = np.unique(values.astype(str), return_counts=True)
    keep = [(u, int(c)) for u, c in zip(uniq, counts) if c >= min_support]
    keep.sort(key=lambda t: (-t[1], t[0]))
    return [u for u, _ in keep[:top_k]]


def vector_columns(cols: Dict[str, np.ndarray], cfg: Dict[str, Any]
                   ) -> Tuple[List[np.ndarray], List[List[str]]]:
    """Every column of the combined vector before the sanity check, in
    order: per PickList its kept categories, OTHER, null; then per Real its
    value (nulls filled with the mean) and its null indicator.  The table
    has no nulls, so the null columns are zero."""
    t = cfg["transmogrifier"]
    n = len(cols[LABEL])
    zero = np.zeros(n, np.float32)
    out: List[np.ndarray] = []
    cats_all: List[List[str]] = []
    for j in range(cfg["n_picklist"]):
        v = cols[f"cat_{j}"].astype(str)
        cats = pivot_categories(v, t["top_k"], t["min_support"])
        cats_all.append(cats)
        known = np.zeros(n, bool)
        for c in cats:
            hit = v == c
            known |= hit
            out.append(hit.astype(np.float32))
        out.append((~known).astype(np.float32))  # OTHER
        if t["track_nulls"]:
            out.append(zero)
    for j in range(cfg["n_real"]):
        out.append(np.asarray(cols[f"num_{j}"], np.float32))
        if t["track_nulls"]:
            out.append(zero)
    return out, cats_all


def sanity_keep(columns: Sequence[np.ndarray], y: np.ndarray,
                cfg: Dict[str, Any]) -> List[int]:
    """Indices the sanity check keeps: variance above the minimum, label
    correlation not above the maximum, and no earlier column correlated
    above the feature-feature maximum (Pearson, on the checker's row
    sample).  The association rules (Cramer's V, rule confidence) never
    fire on this table and are not reproduced; if they did, the kept sets
    would differ and the comparison would say so."""
    s = cfg["sanity_checker"]
    n = len(y)
    if n > s["sample_upper_limit"]:
        idx = np.random.default_rng(s["sample_seed"]).choice(
            n, size=s["sample_upper_limit"], replace=False)
    else:
        idx = np.arange(n)
    var = np.array([np.var(c[idx].astype(np.float64), ddof=1) for c in columns])
    live = np.flatnonzero(var > s["min_variance"])
    Z = np.stack([columns[i][idx] for i in live], axis=1).astype(np.float64)
    Z -= Z.mean(axis=0)
    Z /= np.sqrt((Z * Z).sum(axis=0))
    ys = y[idx].astype(np.float64)
    ys = ys - ys.mean()
    ys /= np.sqrt((ys * ys).sum())
    corr_label = Z.T @ ys
    Zf = Z.astype(np.float32)
    corr = np.abs(Zf.T @ Zf)
    earlier = np.triu(corr, k=1).max(axis=0)  # column j: max over i < j
    keep = [int(live[j]) for j in range(len(live))
            if abs(corr_label[j]) <= s["max_correlation"]
            and earlier[j] <= s["max_feature_corr"]]
    return keep


def vectorize(cols: Dict[str, np.ndarray], cfg: Dict[str, Any]
              ) -> Tuple[np.ndarray, List[int], List[List[str]]]:
    """(X f32[rows, width], kept column indices, pivot categories)."""
    columns, cats = vector_columns(cols, cfg)
    keep = sanity_keep(columns, np.asarray(cols[LABEL]), cfg)
    X = np.stack([columns[i] for i in keep], axis=1)
    return np.ascontiguousarray(X, np.float32), keep, cats


def split(y: np.ndarray, cfg: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Row indices of the holdout (stratified by label), of the training
    rows under the training-sample cap, and each training row's fold."""
    seed = cfg["cv_seed"]
    n = len(y)
    rng = np.random.default_rng(seed)
    hold = np.zeros(n, bool)
    for cls in np.unique(y):
        idx = np.where(y == cls)[0]
        rng.shuffle(idx)
        hold[idx[:int(round(len(idx) * cfg["holdout_fraction"]))]] = True
    train = np.where(~hold)[0]
    minority = min((y[train] == 1).mean(), (y[train] != 1).mean())
    if minority < cfg["balancer_sample_fraction"]:
        raise NotImplementedError(
            "the reference covers a table the balancer leaves as it is; "
            f"minority share {minority}")
    cap = int(cfg["max_training_sample"])
    if len(train) > cap:
        sub = np.sort(np.random.default_rng(seed).choice(
            len(train), size=cap, replace=False))
        train = train[sub]
    fold = np.random.default_rng(seed).permutation(len(train)) % cfg["folds"]
    return {"train": train, "holdout": np.where(hold)[0], "fold": fold}


# ---------------------------------------------------------------------------
# metrics (float64, host)
# ---------------------------------------------------------------------------
def aupr(y: np.ndarray, score: np.ndarray) -> float:
    """Step-wise area under precision-recall, one point per distinct
    threshold, thresholds descending (Spark BinaryClassificationMetrics)."""
    y = np.asarray(y, np.float64)
    order = np.argsort(-np.asarray(score, np.float64), kind="stable")
    s, ys = np.asarray(score, np.float64)[order], y[order]
    tp, fp = np.cumsum(ys), np.cumsum(1.0 - ys)
    last = np.r_[s[1:] != s[:-1], True]
    npos = ys.sum()
    if npos == 0:
        return 0.0
    prec = tp[last] / (tp[last] + fp[last])
    rec = tp[last] / npos
    return float((prec * np.diff(np.r_[0.0, rec])).sum())


def auroc(y: np.ndarray, score: np.ndarray) -> float:
    """Rank statistic with midrank ties."""
    y = np.asarray(y, np.float64)
    s = np.asarray(score, np.float64)
    order = np.argsort(s, kind="stable")
    ss = s[order]
    lo = np.searchsorted(ss, ss, side="left")
    hi = np.searchsorted(ss, ss, side="right")
    rank = np.empty(len(s))
    rank[order] = (lo + hi + 1.0) * 0.5
    npos, nneg = y.sum(), (1.0 - y).sum()
    if npos == 0 or nneg == 0:
        return 0.0
    return float((rank[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg))


# ---------------------------------------------------------------------------
# fits (jax.numpy; dtype float32 at precision highest, or bfloat16)
# ---------------------------------------------------------------------------
def _soft(x, thr):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - thr, 0)


@functools.partial(jax.jit, static_argnames=("iters", "svc"))
def _fit_linear(X, y, w, l1, l2, iters: int, svc: bool):
    """Accelerated proximal gradient on the weighted mean loss with an
    unpenalised intercept: logistic loss + elastic net (FISTA), or squared
    hinge + L2 (``svc``).  Returns the class-1 margin of every row."""
    dt = X.dtype
    X1 = jnp.concatenate([X, jnp.ones((X.shape[0], 1), dt)], axis=1)
    p = X1.shape[1]
    w_sum = jnp.maximum(w.sum(), 1e-12)
    pen = jnp.ones((p,), dt).at[-1].set(0)
    l1v, l2v = l1 * pen, l2 * pen
    trace = jnp.sum((X1 * X1).T * w) / w_sum
    L = (2.0 if svc else 0.25) * trace + l2 + 1e-6
    step = 1.0 / L
    ypm = 2 * y - 1

    def grad(b):
        z = X1 @ b
        if svc:
            r = -2 * ypm * jnp.maximum(1 - ypm * z, 0)
        else:
            r = jax.nn.sigmoid(z) - y
        return X1.T @ (w * r) / w_sum + l2v * b

    def body(_, carry):
        b, z, t = carry
        b2 = z - step * grad(z)
        if not svc:
            b2 = _soft(b2, step * l1v)
        t2 = 0.5 * (1 + jnp.sqrt(1 + 4 * t * t))
        return b2, b2 + ((t - 1) / t2) * (b2 - b), t2

    b0 = jnp.zeros((p,), dt)
    b, _, _ = lax.fori_loop(0, iters, body, (b0, b0, jnp.ones((), dt)))
    return X1 @ b


@functools.partial(jax.jit, static_argnames=("hidden", "iters"))
def _fit_mlp(X, y, w, lr, seed, hidden: Tuple[int, ...], iters: int):
    """Sigmoid hidden layers, softmax output, weighted mean cross-entropy,
    full-batch Adam (0.9, 0.999, 1e-8), Glorot-uniform weights from
    ``PRNGKey(seed)`` split once per layer.  Returns p(class 1) per row."""
    dt = X.dtype
    layers = (X.shape[1],) + tuple(hidden) + (2,)
    key = jax.random.PRNGKey(seed)
    params = []
    for i in range(len(layers) - 1):
        key, sub = jax.random.split(key)
        a, b = layers[i], layers[i + 1]
        scale = jnp.sqrt(6.0 / (a + b))
        W = jax.random.uniform(sub, (a, b), jnp.float32, -scale, scale)
        params.append((W.astype(dt), jnp.zeros((b,), dt)))
    Y = jax.nn.one_hot(y.astype(jnp.int32), 2, dtype=dt)
    w_sum = jnp.maximum(w.sum(), 1e-12)

    def forward(ps, A):
        for W, b in ps[:-1]:
            A = jax.nn.sigmoid(A @ W + b)
        W, b = ps[-1]
        return A @ W + b

    def loss(ps):
        ll = jax.nn.log_softmax(forward(ps, X), axis=-1)
        return -(w[:, None] * Y * ll).sum() / w_sum

    zeros = jax.tree.map(jnp.zeros_like, params)

    def body(i, carry):
        ps, m, v = carry
        g = jax.grad(loss)(ps)
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * (b * b), v, g)
        t = (i + 1).astype(dt)
        ps = jax.tree.map(
            lambda a, mm, vv: a - lr * (mm / (1 - 0.9 ** t))
            / (jnp.sqrt(vv / (1 - 0.999 ** t)) + 1e-8), ps, m, v)
        return ps, m, v

    params, _, _ = lax.fori_loop(0, iters, body, (params, zeros, zeros))
    return jax.nn.softmax(forward(params, X), axis=-1)[:, 1]


def flat_candidates(cfg: Dict[str, Any]) -> List[Tuple[str, Tuple]]:
    """The grid in the selector's candidate order: (family, hyperparameters
    in the order of the family's ``keys``)."""
    return [(fam, tuple(p)) for fam, g in cfg["grid"].items()
            for p in g["points"]]


class Fitter:
    """Fits one candidate on weighted rows and scores every row."""

    def __init__(self, X: np.ndarray, y: np.ndarray, cfg: Dict[str, Any],
                 dtype: str = "float32"):
        self.cfg = cfg
        self.dt = jnp.dtype(dtype)
        self.precision = "highest" if dtype == "float32" else "default"
        self.X = jnp.asarray(X, self.dt)
        self.y = jnp.asarray(y, self.dt)

    def score(self, family: str, hp: Tuple, w: np.ndarray,
              X_other: np.ndarray = None) -> np.ndarray:
        """Class-1 score of every row of the fit matrix followed by those of
        ``X_other`` (rows that never train, weight 0)."""
        g = self.cfg["grid"][family]["fixed"]
        X, y = self.X, self.y
        w = jnp.asarray(w, self.dt)
        if X_other is not None:
            X = jnp.concatenate([X, jnp.asarray(X_other, self.dt)])
            y = jnp.concatenate([y, jnp.zeros(len(X_other), self.dt)])
            w = jnp.concatenate([w, jnp.zeros(len(X_other), self.dt)])
        f32 = np.float32
        with jax.default_matmul_precision(self.precision):
            if family == "lr":
                reg, alpha = f32(hp[0]), f32(hp[1])
                z = _fit_linear(X, y, w, jnp.asarray(reg * alpha, self.dt),
                                jnp.asarray(reg * (f32(1) - alpha), self.dt),
                                iters=int(g["max_iter"]), svc=False)
                s = jax.nn.sigmoid(z)
            elif family == "svc":
                z = _fit_linear(X, y, w, jnp.zeros((), self.dt),
                                jnp.asarray(f32(hp[0]), self.dt),
                                iters=int(g["max_iter"]), svc=True)
                s = (z >= 0).astype(self.dt)
            else:
                s = _fit_mlp(X, y, w, jnp.asarray(f32(hp[0]), self.dt),
                             int(hp[1]), hidden=tuple(g["hidden_layers"]),
                             iters=int(g["max_iter"]))
        return np.asarray(s.astype(jnp.float32))


def fold_metric(fitter: Fitter, family: str, hp: Tuple, fold: np.ndarray,
                f: int, y: np.ndarray) -> float:
    """AuPR on fold ``f``'s rows of the candidate fitted on the others."""
    s = fitter.score(family, hp, (fold != f).astype(np.float32))
    val = fold == f
    return aupr(y[val], s[val])


def holdout_metrics(fitter: Fitter, family: str, hp: Tuple, n_train: int,
                    X_hold: np.ndarray, y_hold: np.ndarray) -> Dict[str, float]:
    """The winner refitted on every training row, evaluated on the holdout."""
    s = fitter.score(family, hp, np.ones(n_train, np.float32), X_hold)[n_train:]
    return {"AuPR": aupr(y_hold, s), "AuROC": auroc(y_hold, s)}


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------
def winner_index(answers: Dict[str, Any], cfg: Dict[str, Any],
                 flat: List[Tuple[str, Tuple]]) -> int:
    """The flat index of the candidate the program named as its winner."""
    for fam, g in cfg["grid"].items():
        if g["estimator"].split(":")[1] == answers["winner_type"]:
            hp = tuple(answers["winner_grid"][k] for k in g["keys"])
            return flat.index((fam, hp))
    raise KeyError(f"winner {answers['winner_type']} is not of the grid")


def sample_pairs(flat, check: Dict[str, Any], seed: int, folds: int,
                 winner: int) -> Dict[str, List[Tuple[int, int]]]:
    """The (candidate, fold) pairs the reference fits, by group: each group
    of ``check["groups"]`` draws ``take`` candidates of its family (with
    ``where``: hyperparameter ``key`` within ``[lo, hi]``) and one fold for
    each, from the seed; the winner is fitted on every fold, in the group
    it belongs to."""
    rng = np.random.default_rng([int(seed), 26])
    out = {}
    for name, g in check["groups"].items():
        idx = [i for i, c in enumerate(flat) if _in_group(c, g)]
        rest = [i for i in idx if i != winner]
        take = min(int(g["take"]), len(rest))
        out[name] = [(int(ci), int(rng.integers(folds)))
                     for ci in sorted(rng.choice(rest, size=take, replace=False))]
        if winner in idx:
            out[name] += [(winner, f) for f in range(folds)]
    return out


def _in_group(candidate: Tuple[str, Tuple], g: Dict[str, Any]) -> bool:
    fam, hp = candidate
    w = g.get("where")
    return fam == g["family"] and (w is None or w["lo"] <= hp[w["at"]] <= w["hi"])


def _gaps(got, hold_got, truth, hold_truth, groups, wp) -> Dict[str, float]:
    out = {f"{name}_fold_gap": float(max(abs(got[p] - truth[p]) for p in pairs))
           for name, pairs in groups.items() if pairs}
    out["winner_cv_gap"] = float(abs(np.mean([got[p] for p in wp])
                                     - np.mean([truth[p] for p in wp])))
    out["holdout_gap"] = float(max(abs(hold_got[k] - hold_truth[k])
                                   for k in ("AuPR", "AuROC")))
    return out


def numbers(answers: Dict[str, Any], cols: Dict[str, np.ndarray],
            cfg: Dict[str, Any], check: Dict[str, Any], seed: int,
            control: bool = False, emit=None):
    """(the program's numbers, the control's or None), each a dict of short
    name -> value, to be held against ``check["limits"]``.

    ``answers``: what the last timed step produced — ``vector`` (the matrix
    the sweep was fed), per candidate ``fold_metrics`` / ``mean_metrics`` /
    ``errors``, ``winner_type`` / ``winner_grid``, ``holdout``.  The control
    is this reference computed wholly in bfloat16 and put in the program's
    place (same sample, same float32 truth).  ``emit`` gets every pair's
    three readings as a fact line."""
    X_ref, _, _ = vectorize(cols, cfg)
    y_all = np.asarray(cols[LABEL], np.float32)
    sp = split(y_all, cfg)
    Xtr, ytr = X_ref[sp["train"]], y_all[sp["train"]]
    X_hold, y_hold = X_ref[sp["holdout"]], y_all[sp["holdout"]]
    flat = flat_candidates(cfg)
    folds = int(cfg["folds"])

    V = np.asarray(answers["vector"])
    exact = {"vector_cells_differ": (
        float(np.count_nonzero(V != X_ref)) if V.shape == X_ref.shape
        else float(max(V.size, X_ref.size)))}
    means = np.asarray(answers["mean_metrics"], np.float64)
    win = winner_index(answers, cfg, flat)
    exact["winner_not_best"] = float(
        (means > means[win]).sum() + sum(e is not None for e in answers["errors"])
        + (len(means) != len(flat)))
    groups = sample_pairs(flat, check, seed, folds, win)
    wp = [(win, f) for f in range(folds)]
    pairs = sorted({p for ps in groups.values() for p in ps} | set(wp))

    def reference_answers(dtype):
        fitter = Fitter(Xtr, ytr, cfg, dtype)
        got = {p: fold_metric(fitter, *flat[p[0]], sp["fold"], p[1], ytr)
               for p in pairs}
        return got, holdout_metrics(fitter, *flat[win], len(ytr), X_hold, y_hold)

    truth, hold_truth = reference_answers("float32")
    got = {p: float(answers["fold_metrics"][p[0]][p[1]]) for p in pairs}
    out = dict(exact, **_gaps(got, answers["holdout"], truth, hold_truth, groups, wp))
    ctl = low = None
    if control:
        low, hold_low = reference_answers("bfloat16")
        ctl = dict(exact, **_gaps(low, hold_low, truth, hold_truth, groups, wp))
    if emit is not None:
        emit(phase="pairs", winner=[flat[win][0], list(flat[win][1])], pairs=[
            {"family": flat[c][0], "hp": list(flat[c][1]), "fold": f,
             "reference": truth[(c, f)], "program": got[(c, f)],
             "control": low[(c, f)] if low else None} for c, f in pairs])
    return out, ctl
