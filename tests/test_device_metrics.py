"""Device-side batched metrics (ops/metrics) must equal the host evaluators.

The fused sweep selects models from these numbers, so they are held to the
host implementations (evaluators/) at 1e-5 — including score TIES (midrank
AuROC, distinct-threshold AuPR) and fold masking (excluded rows must not
shift ranks or counts).  Reference math:
OpBinaryClassificationEvaluator.scala:56, OpRegressionEvaluator.scala:55.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators.classification import (
    OpBinaryClassificationEvaluator, OpMultiClassificationEvaluator)
from transmogrifai_tpu.evaluators.regression import OpRegressionEvaluator
from transmogrifai_tpu.ops.metrics import (BINARY_METRICS,
                                           MULTICLASS_METRICS,
                                           REGRESSION_METRICS,
                                           _binary_grid_metrics, _tie_bounds,
                                           binary_grid_metrics,
                                           multiclass_grid_metrics,
                                           regression_grid_metrics)


@pytest.fixture(scope="module")
def binary_case():
    rng = np.random.default_rng(0)
    n, F, C = 257, 3, 5
    y = rng.integers(0, 2, n).astype(np.float32)
    # two-decimal scores guarantee plenty of ties (the RF vote-fraction case)
    scores = np.round(rng.random((F, C, n)), 2).astype(np.float32)
    vm = rng.random((F, n)) > 0.35
    return y, scores, vm


def _assert_binary_matches_host(y, scores, vm, strict):
    F, C, _ = scores.shape
    dev = binary_grid_metrics(y, scores, vm.astype(np.float32), strict)
    ev = OpBinaryClassificationEvaluator()
    for f in range(F):
        for c in range(C):
            m = vm[f]
            s = scores[f, c][m]
            pred = (s > 0.5) if strict[c] else (s >= 0.5)
            host = ev.evaluate_arrays(y[m], pred.astype(np.float64), s)
            for name in BINARY_METRICS:
                assert abs(host[name] - float(np.asarray(dev[name])[f, c])) < 1e-5, \
                    (f, c, name)


def test_binary_metrics_match_host_evaluator(binary_case):
    y, scores, vm = binary_case
    _assert_binary_matches_host(y, scores, vm,
                                np.array([0, 1, 0, 1, 0], np.float32))


def _rank_case(name):
    """(y f32[n], scores f32[F, C, n], vm bool[F, n]): the shapes the
    tie-group scans behind AuROC's midranks can get wrong."""
    rng = np.random.default_rng(11)
    n = {"n1": 1, "n2_distinct": 2, "n2_tied": 2}.get(name, 193)
    F, C = 2, 3
    y = rng.integers(0, 2, n).astype(np.float32)
    scores = rng.random((F, C, n)).astype(np.float32)
    vm = rng.random((F, n)) > 0.35
    if name == "all_equal":
        scores[:] = 0.5
    elif name == "two_decimal_ties":
        scores = np.round(scores, 2)
    elif name == "excluded_outnumber_validation":
        scores = np.round(scores, 1)
        vm = rng.random((F, n)) > 0.9
    elif name == "n1":
        vm[:] = True
    elif name == "n2_distinct":
        y[:] = (0.0, 1.0)
        scores[..., 0], scores[..., 1] = 0.25, 0.75
        vm[:] = True
    elif name == "n2_tied":
        y[:] = (0.0, 1.0)
        scores[:] = 0.75
        vm[:] = True
    else:
        assert name == "all_distinct", name
    return y, scores, vm


RANK_CASES = ("all_equal", "all_distinct", "two_decimal_ties",
              "excluded_outnumber_validation", "n1", "n2_distinct", "n2_tied")


def _assert_tie_bounds_are_searchsorted(sv):
    """``_tie_bounds`` on each sorted row == ``np.searchsorted(ss, ss)``."""
    for row in sv.reshape(-1, sv.shape[-1]):
        ss = np.asarray(jnp.sort(row))       # the order metrics.sort gives
        lo, hi = _tie_bounds(jnp.asarray(ss))
        assert lo.dtype == hi.dtype == jnp.int32
        np.testing.assert_array_equal(
            np.asarray(lo), np.searchsorted(ss, ss, side="left"))
        np.testing.assert_array_equal(
            np.asarray(hi), np.searchsorted(ss, ss, side="right"))


@pytest.mark.parametrize("name", RANK_CASES)
def test_binary_metrics_rank_cases_match_host_evaluator(name):
    y, scores, vm = _rank_case(name)
    _assert_binary_matches_host(y, scores, vm, np.array([0, 1, 0], np.float32))
    _assert_tie_bounds_are_searchsorted(
        np.where(vm[:, None, :], scores, -np.inf).astype(np.float32))


def test_binary_metrics_nan_scores_rank_as_one_tie_group():
    """A diverged candidate scores NaN.  NaNs sort last and ``searchsorted``
    ranks them as ONE tie group; a bare ``!=`` between neighbours would make
    each NaN its own.  The AuROC values are the ones the ``searchsorted``
    code gave on this input (all-NaN: every row shares one midrank -> 0.5)."""
    rng = np.random.default_rng(7)
    n = 96
    y = (np.arange(n) % 2).astype(np.float32)
    scores = np.round(rng.random((2, 3, n)), 2).astype(np.float32)
    scores[:, 1, ::3] = np.nan               # a third of the rows
    scores[:, 2, :] = np.nan                 # every row
    vm = rng.random((2, n)) > 0.35
    dev = binary_grid_metrics(y, scores, vm.astype(np.float32),
                              np.zeros(3, np.float32))
    auroc = np.asarray(dev["AuROC"])
    sv = np.where(vm[:, None, :], scores, -np.inf).astype(np.float32)
    _assert_tie_bounds_are_searchsorted(sv)
    for f in range(2):
        for c in range(3):
            m = vm[f]
            order = np.argsort(sv[f, c], kind="stable")  # NaN last too
            ss = sv[f, c][order]
            mid = (np.searchsorted(ss, ss, "left")
                   + np.searchsorted(ss, ss, "right") + 1.0) * 0.5
            npos, nneg = (y[m] == 1).sum(), (y[m] == 0).sum()
            want = ((m[order] * y[order] * (mid - (~m).sum())).sum()
                    - npos * (npos + 1.0) * 0.5) / (npos * nneg)
            assert abs(auroc[f, c] - want) < 1e-5, (f, c)
    np.testing.assert_allclose(auroc[:, 2], 0.5, atol=1e-6)


def test_binary_metrics_program_holds_no_search_loop():
    """The log-n ``searchsorted`` (a ``while`` whose every round gathers at
    data-dependent indices: 46 s of a 54 s step on the v5e, PERF.md PR 28)
    must not come back unnoticed on a CPU-only test run."""
    lowered = _binary_grid_metrics.lower(
        jnp.zeros(33), jnp.zeros((2, 3, 33)), jnp.zeros((2, 33)),
        jnp.zeros(3))
    text = lowered.as_text(debug_info=True)
    assert "metrics.rank" in text            # the scope the benchmark reads
    assert not re.search(r"\bwhile\b", text), "a loop in the metric pass"
    assert "searchsorted" not in text


def test_binary_metrics_program_holds_no_gather_and_one_sort():
    """The pass reads its operands in score order by sorting them with the
    key, not by permuting them after an ``argsort``, and the AuPR curve
    selects the recall at the previous threshold instead of reading it at a
    computed index: a per-element gather costs the v5e 0.5-1.3 s over the
    ``[5, 64, 180224]`` block where a streaming pass costs ~1 ms (PERF.md
    §6)."""
    lowered = _binary_grid_metrics.lower(
        jnp.zeros(33), jnp.zeros((2, 3, 33)), jnp.zeros((2, 33)),
        jnp.zeros(3))
    text = lowered.as_text(debug_info=True)
    assert "gather" not in text, "a per-element gather in the metric pass"
    assert len(re.findall(r"stablehlo\.sort\b", text)) == 1
    for scope in ("metrics.sort", "metrics.rank", "metrics.curve"):
        assert scope in text, scope


def _binary_one_by_gather(y, s, vm, strict):
    """The pass as it was written before it sorted its operands: ``argsort``
    and three permutation gathers, the AuPR curve's previous recall read at a
    ``cummax`` index.  Kept here as the reference the pass must equal bit for
    bit."""
    n = y.shape[0]
    neg_inf = jnp.float32(-jnp.inf)
    sv = jnp.where(vm > 0, s, neg_inf)
    npos = (vm * y).sum()
    nneg = (vm * (1.0 - y)).sum()
    n_exc = (1.0 - vm).sum()
    order = jnp.argsort(sv)
    ss, ys, vs = sv[order], y[order], vm[order]
    lo, hi = _tie_bounds(ss)
    rank_val = (lo.astype(jnp.float32) + hi.astype(jnp.float32)
                + 1.0) * 0.5 - n_exc
    r_pos = (vs * ys * rank_val).sum()
    auroc = jnp.where(
        (npos > 0) & (nneg > 0),
        (r_pos - npos * (npos + 1.0) * 0.5) / jnp.maximum(npos * nneg, 1.0),
        0.0)
    sd, yd, vd = ss[::-1], ys[::-1], vs[::-1]
    tp = jnp.cumsum(yd * vd)
    fp = jnp.cumsum((1.0 - yd) * vd)
    nxt = jnp.concatenate([sd[1:], jnp.full((1,), neg_inf, sd.dtype)])
    distinct = (sd != nxt) & (sd > neg_inf)
    prec_c = tp / jnp.maximum(tp + fp, 1.0)
    rec_c = tp / jnp.maximum(npos, 1.0)
    run = jax.lax.cummax(jnp.where(distinct, jnp.arange(n), -1))
    prev = jnp.concatenate([jnp.full((1,), -1), run[:-1]])
    r_prev = jnp.where(prev >= 0, rec_c[jnp.maximum(prev, 0)], 0.0)
    aupr = jnp.where(
        npos > 0,
        jnp.where(distinct, prec_c * (rec_c - r_prev), 0.0).sum(), 0.0)
    pred1 = jnp.where(strict > 0, (s > 0.5), (s >= 0.5)).astype(jnp.float32)
    tp_c = (vm * y * pred1).sum()
    fp_c = (vm * (1.0 - y) * pred1).sum()
    fn_c = (vm * y * (1.0 - pred1)).sum()
    err = (fp_c + fn_c) / jnp.maximum(npos + nneg, 1.0)
    precision = jnp.where(tp_c + fp_c > 0,
                          tp_c / jnp.maximum(tp_c + fp_c, 1.0), 0.0)
    recall = jnp.where(tp_c + fn_c > 0,
                       tp_c / jnp.maximum(tp_c + fn_c, 1.0), 0.0)
    f1 = jnp.where(precision + recall > 0,
                   2.0 * precision * recall
                   / jnp.maximum(precision + recall, 1e-30), 0.0)
    return jnp.stack([auroc, aupr, err, precision, recall, f1])


@jax.jit
def _binary_grid_by_gather(y, scores, val_w, strict_c):
    per_c = jax.vmap(_binary_one_by_gather, in_axes=(None, 0, None, 0))
    return jax.vmap(per_c, in_axes=(None, 0, 0, None))(
        y, scores, val_w, strict_c)


def _gather_case(n, kind):
    """(y f32[n], scores f32[2, 4, n], val_w f32[2, n]): every fold excludes
    about a third of its rows (score ``-inf`` inside the pass)."""
    rng = np.random.default_rng(1000 + n)
    y = rng.integers(0, 2, n).astype(np.float32)
    scores = rng.random((2, 4, n)).astype(np.float32)
    val_w = (rng.random((2, n)) > 0.35).astype(np.float32)
    if kind == "many_ties":
        scores = np.round(scores, 2)
    elif kind == "fractional_weights":
        scores[:, 1] = np.round(scores[:, 1], 1)
        val_w = val_w * rng.choice(np.float32([0.25, 0.5, 1.0, 0.3]), (2, n))
    elif kind == "nan_candidates":
        scores[:, 1, ::3] = np.nan           # a third of the rows
        scores[:, 2, :] = np.nan             # every row
    else:
        assert kind == "few_ties", kind
    return y, scores, val_w.astype(np.float32)


@pytest.mark.parametrize("kind", ("few_ties", "many_ties",
                                  "fractional_weights", "nan_candidates"))
@pytest.mark.parametrize("n", (1, 2, 3, 257, 4096, 20000))
def test_binary_metrics_equal_the_gather_formulation_bit_for_bit(n, kind):
    """Sorting the operands with the key and selecting the previous recall
    return the very floats the argsort + gather pass returned: the stable
    order of equal keys is the row index in both, and a select never
    rounds.  A shifted ``cummax`` of the recall would not: with fractional
    weights at n = 4,096 and 20,000 the float32 ``cumsum`` is not monotone,
    and AuPR moves."""
    y, scores, val_w = _gather_case(n, kind)
    strict = np.float32([0, 1, 0, 1])
    got = np.asarray(_binary_grid_metrics(y, scores, val_w, strict))
    want = np.asarray(_binary_grid_by_gather(y, scores, val_w, strict))
    assert got.shape == want.shape == (2, 4, len(BINARY_METRICS))
    for i, name in enumerate(BINARY_METRICS):
        np.testing.assert_array_equal(
            got[..., i].view(np.int32), want[..., i].view(np.int32),
            err_msg=name)


def test_binary_metrics_empty_validation_class():
    """A fold whose validation rows are all one class: AuROC/AuPR -> 0 like
    the host roc_auc/pr_auc guards, no NaN."""
    n = 64
    y = np.ones(n, np.float32)
    scores = np.random.default_rng(1).random((1, 1, n)).astype(np.float32)
    vm = np.ones((1, n), np.float32)
    dev = binary_grid_metrics(y, scores, vm, np.zeros(1, np.float32))
    assert float(np.asarray(dev["AuROC"])[0, 0]) == 0.0
    assert np.isfinite(np.asarray(dev["AuPR"])).all()


def test_regression_metrics_match_host_evaluator():
    rng = np.random.default_rng(3)
    n, F, C = 211, 2, 4
    y = rng.normal(size=n).astype(np.float32)
    preds = (y[None, None, :] + rng.normal(0, 0.5, (F, C, n))).astype(np.float32)
    vm = rng.random((F, n)) > 0.3
    dev = regression_grid_metrics(y, preds, vm.astype(np.float32))
    ev = OpRegressionEvaluator()
    for f in range(F):
        for c in range(C):
            m = vm[f]
            host = ev.evaluate_arrays(y[m], preds[f, c][m])
            for name in REGRESSION_METRICS:
                assert abs(host[name] - float(np.asarray(dev[name])[f, c])) < 1e-4, \
                    (f, c, name)


def test_multiclass_metrics_match_host_evaluator():
    rng = np.random.default_rng(5)
    n, F, C, k = 180, 2, 3, 4
    y = rng.integers(0, k, n).astype(np.float32)
    probs = rng.random((F, C, n, k)).astype(np.float32)
    probs /= probs.sum(-1, keepdims=True)
    vm = rng.random((F, n)) > 0.3
    y1 = np.eye(k, dtype=np.float32)[y.astype(np.int64)]
    dev = multiclass_grid_metrics(y1, probs, vm.astype(np.float32))
    ev = OpMultiClassificationEvaluator()
    for f in range(F):
        for c in range(C):
            m = vm[f]
            pred = probs[f, c].argmax(-1).astype(np.float64)
            host = ev.evaluate_arrays(y[m], pred[m])
            for name in MULTICLASS_METRICS:
                assert abs(host[name] - float(np.asarray(dev[name])[f, c])) < 1e-5, \
                    (f, c, name)
