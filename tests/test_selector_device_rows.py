"""The selector's row sets taken on the device (``impl/selector/
model_selector``): a fit that takes its four row sets with ``jnp.take`` from
the resident matrix gives the bits that the host path's numpy gathers give;
the size rule picks the path; the ``selector.rows`` spans and the
``selector`` counters say which one ran; the consumers take the device rows
as they are."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu import obs
from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Dataset, NumericColumn, VectorColumn
from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.mlp import \
    OpMultilayerPerceptronClassifier
from transmogrifai_tpu.impl.classification.trees import (
    OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.impl.selector import model_selector
from transmogrifai_tpu.impl.selector.model_selector import ModelSelector
from transmogrifai_tpu.impl.tuning.splitters import DataBalancer
from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
from transmogrifai_tpu.obs import trace
from transmogrifai_tpu.utils import devcache
from transmogrifai_tpu.workflow import stream


def _data(n=300, d=6, seed=0, missing=0):
    """A binary table; ``missing`` rows (every third from the top) have no
    label."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) + 0.3 * rng.standard_normal(n) > 0)
    mask = np.ones(n, bool)
    mask[:3 * missing:3] = False
    label = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
    vec = FeatureBuilder("features", T.OPVector).extract(
        field="features").as_predictor()
    ds = Dataset({"label": NumericColumn(T.RealNN, y.astype(np.float64), mask),
                  "features": VectorColumn(T.OPVector, X)})
    return label, vec, ds


def _linear_grid():
    return [(OpLogisticRegression(max_iter=20),
             [{"reg_param": r, "elastic_net_param": a}
              for r in (0.01, 0.1) for a in (0.0, 0.5)]),
            (OpMultilayerPerceptronClassifier(hidden_layers=(4,), max_iter=15),
             [{"step_size": 0.03}, {"step_size": 0.1, "seed": 7}])]


def _tree_grid():
    return [(OpRandomForestClassifier(num_trees=6),
             [{"max_depth": 3}, {"max_depth": 4, "min_instances_per_node": 5}]),
            (OpXGBoostClassifier(num_round=6, max_depth=3),
             [{"eta": 0.3}, {"eta": 0.1}])]


def _selector(models, label, vec, mesh="auto"):
    """``mesh="auto"``: the candidates sharded over the tests' virtual
    devices; ``None``: one device, as on one chip."""
    return ModelSelector(
        validator=OpCrossValidation(OpBinaryClassificationEvaluator(),
                                    num_folds=3, stratify=True, mesh=mesh),
        splitter=DataBalancer(sample_fraction=0.1, reserve_test_fraction=0.1),
        models=models).set_input(label, vec)


def _host_path(monkeypatch):
    """The size rule forced to the host path: a device limit of 8 bytes, and
    no resident copy left from an earlier fit (one the cache holds is taken
    whatever the limit)."""
    monkeypatch.setattr(model_selector, "_device_bytes_limit", lambda: 8)
    devcache.clear()


def _fit(models, label, vec, ds, mesh="auto"):
    return _selector(models, label, vec, mesh).fit(ds)


def _same_bits(a, b):
    """``a`` and ``b`` (summaries, parameter dicts) equal bit for bit."""
    if isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _same_bits(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for u, v in zip(a, b):
            _same_bits(u, v)
    elif isinstance(a, (np.ndarray, jax.Array, float, np.floating)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).dtype == np.asarray(b).dtype
    else:
        assert a == b


@pytest.mark.parametrize("grid,missing,mesh", [
    (_linear_grid, 0, "auto"), (_linear_grid, 0, None),
    (_tree_grid, 0, "auto"), (_tree_grid, 40, None), (_linear_grid, 40, "auto")],
    ids=["lr_mlp", "lr_mlp_one_device", "forest_boosting",
         "forest_boosting_missing_labels", "lr_mlp_missing_labels"])
def test_device_rows_give_the_host_paths_bits(grid, missing, mesh,
                                              monkeypatch):
    label, vec, ds = _data(missing=missing)
    models = grid()
    device = _fit(models, label, vec, ds, mesh)
    _host_path(monkeypatch)
    host = _fit(models, label, vec, ds, mesh)
    ds_, hs = device.summary, host.summary
    _same_bits(ds_.validation_results, hs.validation_results)
    assert ds_.best_grid == hs.best_grid
    assert ds_.best_model_type == hs.best_model_type
    _same_bits(device.model_params, host.model_params)
    _same_bits(ds_.train_evaluation, hs.train_evaluation)
    _same_bits(ds_.holdout_evaluation, hs.holdout_evaluation)
    assert hs.holdout_evaluation
    # the missing labels are left out on both paths
    assert ds_.to_json() == hs.to_json()


def _traced_fit(models, label, vec, ds):
    trace.disable()
    trace.reset()
    trace.enable(path=None)
    try:
        before = obs.snapshot()
        _fit(models, label, vec, ds)
        after = obs.snapshot()
        evs = [e for e in trace.events() if e["ph"] == "X"]
    finally:
        trace.disable()
        trace.reset()
    delta = {scope: {k: after[scope][k] - before[scope].get(k, 0)
                     for k in after[scope] if isinstance(after[scope][k],
                                                         (int, float))}
             for scope in ("selector", "devcache", "stream")}
    return evs, delta


@pytest.mark.parametrize("where", ["device", "host"])
def test_spans_and_counters_say_which_path_ran(where, monkeypatch):
    if where == "host":
        _host_path(monkeypatch)
    label, vec, ds = _data()
    evs, delta = _traced_fit(_linear_grid(), label, vec, ds)
    rows = [e["args"] for e in evs if e["name"] == "selector.rows"]
    assert sorted(r["what"] for r in rows) \
        == ["evaluate", "holdout", "refit", "train"]
    assert {r["where"] for r in rows} == {where}
    sel = delta["selector"]
    other = "host" if where == "device" else "device"
    assert sel[f"{where}_row_gathers"] == 4
    assert sel[f"{where}_row_bytes"] == sum(r["bytes"] for r in rows)
    assert sel[f"{other}_row_gathers"] == sel[f"{other}_row_bytes"] == 0
    puts = [e["args"] for e in evs if e["name"] == "devcache.put"]
    # every byte put_bytes counts is a span's, and on the device path none of
    # them is a row matrix
    assert delta["devcache"]["put_bytes"] == sum(p["bytes"] for p in puts)
    xs = [p for p in puts if p["tag"] == "X"]
    assert (xs == []) == (where == "device")
    assert delta["stream"]["device_handoffs"] == 0


def test_resident_matrix_goes_up_once_for_every_fit_of_the_column():
    label, vec, ds = _data()
    values = ds["features"].values
    model_selector._resident(values)  # the first fit's upload
    evs, delta = _traced_fit(_linear_grid(), label, vec, ds)
    # the second fit uploads y alone (a new array each fit, once a device);
    # the matrix and its rows go up not at all
    train, = [e["args"] for e in evs if e["name"] == "selector.rows"
              and e["args"]["what"] == "train"]
    ups = [e["args"]["bytes"] for e in evs if e["name"] == "devcache.upload"]
    assert ups and all(b == train["rows"] * 4 for b in ups)
    assert delta["devcache"]["h2d_bytes"] == sum(ups)
    assert delta["selector"]["device_row_gathers"] == 4


def _limit(monkeypatch, nbytes):
    monkeypatch.setattr(model_selector, "_device_bytes_limit", lambda: nbytes)


def test_size_rule_reads_an_eighth_of_the_device_limit(monkeypatch):
    devcache.clear()
    stream.clear_views()
    X = np.zeros((64, 8), np.float32)  # 2048 float32 bytes
    _limit(monkeypatch, 8 * 2048 - 8)
    assert model_selector._resident(X) is None
    _limit(monkeypatch, 8 * 2048)
    assert isinstance(model_selector._resident(X), jax.Array)
    _limit(monkeypatch, None)
    assert isinstance(model_selector._resident(X.copy()), jax.Array)


def test_a_copy_the_cache_holds_is_taken_under_any_limit(monkeypatch):
    devcache.clear()
    stream.clear_views()
    X = np.ones((64, 8), np.float32)
    first = model_selector._resident(X)
    _limit(monkeypatch, 8)
    assert model_selector._resident(X) is first
    assert model_selector._resident(X.copy()) is None


def test_size_rule_counts_what_is_already_resident(monkeypatch):
    devcache.clear()
    stream.clear_views()
    A, B, C = (np.full((64, 8), v, np.float32) for v in range(3))  # 2048 B
    _limit(monkeypatch, 8 * 3000)       # room for one of them, not two
    assert isinstance(model_selector._resident(A), jax.Array)
    assert devcache.resident_bytes() == 2048
    assert model_selector._resident(B) is None
    devcache.clear()                    # A's copy gone
    assert devcache.resident_bytes() == 0
    assert isinstance(model_selector._resident(B), jax.Array)
    # a streamed view's bytes count as the cache's do
    devcache.clear()
    try:
        assert stream._register_view(A, [(jnp.asarray(A), 64)], 64)
        assert stream.view_bytes() == 2048
        assert model_selector._resident(C) is None
    finally:
        stream.clear_views()
    assert isinstance(model_selector._resident(C), jax.Array)


def test_second_columns_fit_takes_the_host_path_over_the_rule(monkeypatch):
    devcache.clear()
    stream.clear_views()
    label, vec, first = _data(seed=1)
    _, _, second = _data(seed=2)
    nbytes = first["features"].values.nbytes
    # an eighth of the limit holds one column's copy and not two
    _limit(monkeypatch, 8 * (nbytes + nbytes // 2))
    evs, _ = _traced_fit(_linear_grid(), label, vec, first)
    assert {e["args"]["where"] for e in evs if e["name"] == "selector.rows"} \
        == {"device"}
    evs, delta = _traced_fit(_linear_grid(), label, vec, second)
    assert {e["args"]["where"] for e in evs if e["name"] == "selector.rows"} \
        == {"host"}
    assert delta["selector"]["device_row_gathers"] == 0
    # the first column's copy is what stood in the way: it is still held
    assert devcache.holds(first["features"].values, np.float32)
    assert not devcache.holds(second["features"].values, np.float32)


@pytest.mark.parametrize("limit", [None, 8], ids=["no_limit", "over_rule"])
def test_streamed_view_is_the_source_and_counts_the_handoff(limit,
                                                            monkeypatch):
    # the view is on the device already: it is the source whatever the size
    # rule says of a new copy
    _limit(monkeypatch, limit)
    label, vec, ds = _data()
    values = ds["features"].values
    stream.clear_views()
    try:
        assert stream._register_view(values, [(jnp.asarray(values),
                                               len(values))], len(values))
        evs, delta = _traced_fit(_linear_grid(), label, vec, ds)
    finally:
        stream.clear_views()
    rows = [e["args"] for e in evs if e["name"] == "selector.rows"]
    assert {r["where"] for r in rows} == {"device"}
    train, = [r for r in rows if r["what"] == "train"]
    assert delta["stream"]["device_handoffs"] == 1
    assert delta["stream"]["handoff_bytes"] == train["bytes"]
    # the view is the resident matrix: the matrix itself never goes up
    ups = [e["args"]["bytes"] for e in evs if e["name"] == "devcache.upload"]
    assert values.nbytes not in ups
    assert not devcache.holds(values, np.float32)


def test_binned_matrix_builds_once_a_fit_from_device_rows(monkeypatch):
    from transmogrifai_tpu.impl import sweep_fragments
    from transmogrifai_tpu.ops import trees as Tr
    from transmogrifai_tpu.utils import devcache

    X = np.random.default_rng(5).standard_normal((200, 5)).astype(np.float32)
    want = Tr.quantize(X, 8)[0]  # from the host matrix
    sketches = []
    real = Tr.sketch_edges
    monkeypatch.setattr(Tr, "sketch_edges",
                        lambda *a, **k: sketches.append(1) or real(*a, **k))
    Xtr = jnp.asarray(X)  # one fit's device rows
    xbs = []
    assert sweep_fragments._xb_index(xbs, Xtr, 8) == 0
    assert sweep_fragments._xb_index(xbs, Xtr, 8) == 0
    assert len(xbs) == 1 and len(sketches) == 1
    np.testing.assert_array_equal(np.asarray(xbs[0]), want)
    # keyed to this fit's rows: the next fit's rows build their own
    assert id(Xtr) in devcache._entries
    nxt = jnp.asarray(X)
    assert sweep_fragments._xb_index([], nxt, 8) == 0
    assert len(sketches) == 2
    key = id(Xtr)
    del Xtr
    assert key not in devcache._entries


def test_binning_keeps_device_rows_on_the_device():
    from transmogrifai_tpu.ops import trees as Tr

    X = np.random.default_rng(6).standard_normal((120, 4)).astype(np.float32)
    edges = Tr.sketch_edges(X, 16)
    before = obs.snapshot()["devcache"]["put_bytes"]
    got = Tr.bin_with_edges(jnp.asarray(X), edges)
    assert obs.snapshot()["devcache"]["put_bytes"] == before
    np.testing.assert_array_equal(got, Tr.bin_with_edges(X, edges))
