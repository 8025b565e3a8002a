"""ModelSelector / validators / splitters tests.

Reference analogs: ModelSelectorTest, OpCrossValidationTest, DataBalancerTest,
DataCutterTest (core/src/test/.../impl/{selector,tuning}/)."""
import numpy as np
import pytest

from transmogrifai_tpu import types as T
from transmogrifai_tpu.columns import Dataset, NumericColumn, VectorColumn
from transmogrifai_tpu.evaluators import (OpBinaryClassificationEvaluator,
                                          OpRegressionEvaluator)
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.svc import OpLinearSVC
from transmogrifai_tpu.impl.regression.linear import OpLinearRegression
from transmogrifai_tpu.impl.selector.model_selector import ModelSelector, SelectedModel
from transmogrifai_tpu.impl.tuning.splitters import (DataBalancer, DataCutter,
                                                     DataSplitter, Splitter)
from transmogrifai_tpu.impl.tuning.validators import (OpCrossValidation,
                                                      OpTrainValidationSplit)


def _binary_data(n=400, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d)).astype(np.float32)
    beta = rng.standard_normal(d)
    y = (X @ beta + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return X, y


def _selector_inputs(X, y):
    label = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
    vec = FeatureBuilder("features", T.OPVector).extract(field="features").as_predictor()
    ds = Dataset({
        "label": NumericColumn(T.RealNN, y.astype(np.float64), np.ones(len(y), bool)),
        "features": VectorColumn(T.OPVector, X),
    })
    return label, vec, ds


def test_cross_validation_selects_reasonable_model():
    X, y = _binary_data()
    label, vec, ds = _selector_inputs(X, y)
    cands = [
        (OpLogisticRegression(), [{"reg_param": r, "elastic_net_param": a}
                                  for r in (0.0, 0.01, 0.1) for a in (0.0, 0.5)]),
        (OpLinearSVC(), [{"reg_param": r} for r in (0.01, 0.1)]),
    ]
    sel = ModelSelector(
        validator=OpCrossValidation(OpBinaryClassificationEvaluator(), num_folds=3,
                                    stratify=True),
        splitter=DataBalancer(sample_fraction=0.1, reserve_test_fraction=0.1),
        models=cands,
    ).set_input(label, vec)
    model = sel.fit(ds)
    assert isinstance(model, SelectedModel)
    s = model.summary
    assert s is not None
    assert len(s.validation_results) == 8
    assert s.holdout_evaluation is not None
    assert s.train_evaluation["AuROC"] > 0.85
    # scoring path
    out = model.transform_dataset(ds)
    assert len(out) == len(ds)
    acc = (out.prediction == y).mean()
    assert acc > 0.8


def test_batched_and_loop_paths_agree():
    X, y = _binary_data(n=300)
    ev = OpBinaryClassificationEvaluator()
    grids = [{"reg_param": r, "elastic_net_param": 0.0} for r in (0.001, 0.1)]
    est = OpLogisticRegression()
    cv = OpCrossValidation(ev, num_folds=3, stratify=True)
    batched = cv.validate([(est, grids)], X, y)

    class NoBatch(OpLogisticRegression):
        def fit_grid_folds(self, *a, **k):
            raise NotImplementedError

    loop = cv.validate([(NoBatch(), grids)], X, y)
    for rb, rl in zip(batched.results, loop.results):
        assert rb.metric_value == pytest.approx(rl.metric_value, abs=2e-2)


def test_train_validation_split_and_failed_model_tolerated():
    X, y = _binary_data(n=200)

    class Exploding(OpLogisticRegression):
        def fit_grid_folds(self, *a, **k):
            raise NotImplementedError

        def fit_arrays(self, *a, **k):
            raise RuntimeError("boom")

    ev = OpBinaryClassificationEvaluator()
    tvs = OpTrainValidationSplit(ev, train_ratio=0.75)
    summary = tvs.validate([(Exploding(), [{}]),
                            (OpLogisticRegression(), [{"reg_param": 0.01}])], X, y)
    assert summary.results[0].error is not None
    assert summary.best.model_name == "OpLogisticRegression"
    with pytest.raises(RuntimeError):
        tvs.validate([(Exploding(), [{}])], X, y)


def test_regression_selector():
    rng = np.random.default_rng(1)
    X = rng.standard_normal((300, 5)).astype(np.float32)
    beta = rng.standard_normal(5)
    y = (X @ beta + 0.1 * rng.standard_normal(300)).astype(np.float32)
    label, vec, ds = _selector_inputs(X, y)
    sel = ModelSelector(
        validator=OpCrossValidation(OpRegressionEvaluator(), num_folds=3),
        splitter=DataSplitter(reserve_test_fraction=0.1),
        models=[(OpLinearRegression(),
                 [{"reg_param": r} for r in (0.0, 0.01, 0.1)])],
    ).set_input(label, vec)
    model = sel.fit(ds)
    assert model.summary.train_evaluation["R2"] > 0.9


def test_data_balancer_proportions():
    rng = np.random.default_rng(2)
    y = (rng.random(1000) < 0.03).astype(np.float32)  # 3% positives
    b = DataBalancer(sample_fraction=0.1)
    b.pre_validation_prepare(y)
    w = b.prepare_weights(y)
    pos_mass = w[y == 1].sum()
    assert pos_mass / w.sum() == pytest.approx(0.1, rel=0.05)
    idx = b.prepare_indices(y)
    yb = y[idx]
    assert (yb == 1).mean() == pytest.approx(0.1, rel=0.15)
    # already balanced: no-op
    y2 = (rng.random(1000) < 0.4).astype(np.float32)
    b2 = DataBalancer(sample_fraction=0.1)
    b2.pre_validation_prepare(y2)
    assert b2.already_balanced
    assert np.all(b2.prepare_weights(y2) == 1.0)


def test_data_cutter_drops_rare_labels():
    y = np.array([0.0] * 50 + [1.0] * 40 + [2.0] * 9 + [3.0])
    c = DataCutter(max_label_categories=3, min_label_fraction=0.05)
    c.pre_validation_prepare(y)
    assert c.labels_kept == [0.0, 1.0, 2.0]
    w = c.prepare_weights(y)
    assert w[y == 3.0].sum() == 0.0
    idx = c.prepare_indices(y)
    assert set(np.unique(y[idx])) == {0.0, 1.0, 2.0}


def test_splitter_stratified_holdout():
    y = np.array([1.0] * 20 + [0.0] * 80)
    s = Splitter(reserve_test_fraction=0.25)
    tr, ho = s.split(len(y), y)
    assert len(ho) == 25
    assert (y[ho] == 1).sum() == 5
    assert len(np.intersect1d(tr, ho)) == 0


# ---- the fit's spans and counts (obs/trace, utils/devcache) ------------------
#: every span one fused selector fit opens, once each (README "Observability")
FIT_SPANS = ("selector.fit", "selector.split", "selector.prepare",
             "selector.gather", "selector.validate", "sweep.plan",
             "sweep.launch", "sweep.dispatch", "sweep.gather",
             "selector.refit", "selector.evaluate")


def _traced_selector(mesh=None):
    """``mesh=None``: the one-device path, as on one chip (the tests' eight
    virtual devices would otherwise shard the two candidates)."""
    X, y = _binary_data(n=240)
    label, vec, ds = _selector_inputs(X, y)
    sel = ModelSelector(
        validator=OpCrossValidation(OpBinaryClassificationEvaluator(),
                                    num_folds=3, stratify=True, mesh=mesh),
        splitter=DataBalancer(sample_fraction=0.1, reserve_test_fraction=0.1),
        models=[(OpLogisticRegression(max_iter=10),
                 [{"reg_param": r, "elastic_net_param": 0.0}
                  for r in (0.01, 0.1)])],
    ).set_input(label, vec)
    return sel, ds


@pytest.fixture
def tracer():
    from transmogrifai_tpu.obs import trace

    trace.disable()
    trace.reset()
    trace.enable(path=None)
    yield trace
    trace.disable()
    trace.reset()


@pytest.fixture
def host_path(monkeypatch):
    """The selector's size rule forced to the host path: a device limit
    under which no matrix fits (8 bytes, so an eighth of it is 1)."""
    from transmogrifai_tpu.impl.selector import model_selector

    monkeypatch.setattr(model_selector, "_device_bytes_limit", lambda: 8)


def test_fit_opens_each_span_once_under_one_request(tracer):
    _check_fit_opens_each_span_once(tracer, "device")


def test_fit_opens_each_span_once_under_one_request_on_the_host_path(
        tracer, host_path):
    _check_fit_opens_each_span_once(tracer, "host")


def _check_fit_opens_each_span_once(tracer, where):
    sel, ds = _traced_selector()
    sel.fit(ds)
    evs = [e for e in tracer.events() if e["ph"] == "X"]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    for name in FIT_SPANS:
        assert len(by_name.get(name, [])) == 1, (name, sorted(by_name))
    # X and y go up once each: y under the plan; X under the plan as the
    # sweep's rows on the host path, whole under the gather on the device
    # path (the resident matrix the selector takes its rows from)
    uploads = by_name["devcache.upload"]
    assert len(uploads) == 2
    plan_id = by_name["sweep.plan"][0]["args"]["id"]
    gather_id = by_name["selector.gather"][0]["args"]["id"]
    X_bytes = ds["features"].values.nbytes
    assert [u["args"]["parent"] for u in uploads
            if u["args"]["bytes"] == X_bytes] \
        == ([gather_id] if where == "device" else [])
    assert all(u["args"]["parent"] == plan_id for u in uploads
               if u["args"]["bytes"] != X_bytes)
    # one tree: every span reaches selector.fit through its parents ...
    root = by_name["selector.fit"][0]["args"]
    parent_of = {e["args"]["id"]: e["args"]["parent"] for e in evs}
    for e in evs:
        at = e["args"]["id"]
        while parent_of.get(at) is not None:
            at = parent_of[at]
        assert at == root["id"], e["name"]
    # ... the dispatch too, though it runs on a hedge thread
    launch = by_name["sweep.launch"][0]
    assert by_name["sweep.dispatch"][0]["args"]["parent"] == launch["args"]["id"]
    # ... and one request
    assert root["req"] is not None
    assert {e["args"]["req"] for e in evs} == {root["req"]}
    # what each span says of its work
    X = ds["features"].values
    gathered = by_name["selector.gather"][0]["args"]
    kept = by_name["selector.prepare"][0]["args"]["kept_rows"]
    assert gathered["bytes"] == kept * X.shape[1] * X.itemsize
    assert gathered["handoff"] is False
    assert root["rows"] == len(X) and root["width"] == X.shape[1]
    assert root["candidates"] == 2 and root["folds"] == 3
    split = by_name["selector.split"][0]["args"]
    assert split["train_rows"] + split["holdout_rows"] == len(X)
    assert by_name["selector.evaluate"][0]["args"]["holdout_rows"] \
        == split["holdout_rows"]
    assert by_name["selector.refit"][0]["args"]["family"] == "OpLogisticRegression"
    assert by_name["sweep.gather"][0]["args"]["d2h_bytes"] == 3 * 2 * 6 * 4
    assert by_name["sweep.plan"][0]["args"]["candidates"] == 2


def test_second_fit_gets_another_request(tracer):
    sel, ds = _traced_selector()
    sel.fit(ds)
    sel.fit(ds)
    fits = [e["args"] for e in tracer.events() if e["name"] == "selector.fit"]
    assert len(fits) == 2
    assert fits[0]["req"] != fits[1]["req"]
    assert None not in (fits[0]["req"], fits[1]["req"])


def test_workflow_train_request_is_inherited_by_the_fit(tracer):
    sel, ds = _traced_selector()
    with tracer.request():
        with tracer.span("outer"):
            sel.fit(ds)
    evs = {e["name"]: e["args"] for e in tracer.events() if e["ph"] == "X"}
    assert evs["selector.fit"]["req"] == evs["outer"]["req"]
    assert evs["selector.fit"]["parent"] == evs["outer"]["id"]


def test_devcache_counts_uploads_and_hits():
    from transmogrifai_tpu import obs
    from transmogrifai_tpu.utils import devcache

    X = np.arange(60, dtype=np.float64).reshape(20, 3)
    before = obs.snapshot()["devcache"]
    devcache.device_array(X, np.float32)
    first = obs.snapshot()["devcache"]
    # counted at the target dtype: 20 x 3 float32
    assert first["h2d_bytes"] - before["h2d_bytes"] == 20 * 3 * 4
    assert first["h2d_uploads"] - before["h2d_uploads"] == 1
    assert first["cache_hits"] == before["cache_hits"]
    devcache.device_array(X, np.float32)
    hit = obs.snapshot()["devcache"]
    assert hit["h2d_bytes"] == first["h2d_bytes"]
    assert hit["h2d_uploads"] == first["h2d_uploads"]
    assert hit["cache_hits"] - first["cache_hits"] == 1


#: the fit's host work inside its phases: span -> (parent span, count)
HOST_SPANS = {"selector.winner_fit": ("selector.refit", 1),
              "selector.winner_predict": ("selector.evaluate", 2),
              "selector.metrics": ("selector.evaluate", 2)}
#: each ``selector.rows`` gather by its ``what``: the span it sits in
ROW_GATHERS = {"train": "selector.gather", "refit": "selector.refit",
               "evaluate": "selector.evaluate", "holdout": "selector.evaluate"}


def _spans_by_name(tracer):
    evs = [e for e in tracer.events() if e["ph"] == "X"]
    name_of = {e["args"]["id"]: e["name"] for e in evs}
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    return by_name, name_of


def test_fit_spans_its_host_work_inside_its_phases(tracer):
    _check_fit_spans_its_host_work(tracer, "device")


def test_fit_spans_its_host_work_inside_its_phases_on_the_host_path(
        tracer, host_path):
    _check_fit_spans_its_host_work(tracer, "host")


def _check_fit_spans_its_host_work(tracer, where):
    sel, ds = _traced_selector()
    sel.fit(ds)
    by_name, name_of = _spans_by_name(tracer)
    for name, (parent, count) in HOST_SPANS.items():
        got = by_name.get(name, [])
        assert len(got) == count, (name, len(got))
        assert {name_of[e["args"]["parent"]] for e in got} == {parent}, name
    X = ds["features"].values
    row_bytes = X.shape[1] * X.itemsize
    rows = {e["args"]["what"]: e for e in by_name["selector.rows"]}
    assert set(rows) == set(ROW_GATHERS) and len(by_name["selector.rows"]) == 4
    for what, parent in ROW_GATHERS.items():
        args = rows[what]["args"]
        assert name_of[args["parent"]] == parent, what
        assert args["bytes"] == args["rows"] * row_bytes, what
        assert args["where"] == where, what
    split = by_name["selector.split"][0]["args"]
    kept = by_name["selector.prepare"][0]["args"]["kept_rows"]
    assert rows["train"]["args"]["rows"] == kept
    assert rows["holdout"]["args"]["rows"] == split["holdout_rows"]
    assert rows["refit"]["args"]["rows"] \
        == by_name["selector.refit"][0]["args"]["rows"]
    assert rows["train"]["args"]["bytes"] \
        == by_name["selector.gather"][0]["args"]["bytes"]
    assert {e["args"]["what"] for e in by_name["selector.winner_predict"]} \
        == {"train", "holdout"}
    assert {e["args"]["what"] for e in by_name["selector.metrics"]} \
        == {"train", "holdout"}
    # every uncached upload sits in the sweep's dispatch or in the winner's
    # device calls, so the accepted idle readers' phases still cover it
    puts = by_name["devcache.put"]
    xs = [e for e in puts if e["args"]["tag"] == "X"]
    if where == "device":
        # the winner takes its rows as the device gave them: no row matrix
        # goes up past the cache
        assert xs == []
        assert {name_of[e["args"]["parent"]] for e in puts} == {
            "sweep.dispatch", "selector.winner_fit"}
    else:
        assert {name_of[e["args"]["parent"]] for e in puts} == {
            "sweep.dispatch", "selector.winner_fit", "selector.winner_predict"}
        fit_x, = [e for e in xs
                  if name_of[e["args"]["parent"]] == "selector.winner_fit"]
        assert fit_x["args"]["bytes"] == rows["refit"]["args"]["bytes"]
        pred_x = sorted(e["args"]["bytes"] for e in xs if name_of[
            e["args"]["parent"]] == "selector.winner_predict")
        assert pred_x == sorted([rows["holdout"]["args"]["bytes"],
                                 rows["evaluate"]["args"]["bytes"]])
    folds = [e["args"] for e in puts if e["args"]["tag"] == "fold_w"]
    assert [f["bytes"] for f in folds] == [3 * kept * 4] * 2   # train, val


def test_fit_with_the_buffer_off_appends_nothing():
    from transmogrifai_tpu.obs import trace

    trace.disable()
    trace.reset()
    sel, ds = _traced_selector()
    sel.fit(ds)
    assert trace.events() == []


def test_quantize_spans_the_sketch_alone():
    # ``sweep.quantize`` = the host sketch (its own span) + the binning, whose
    # upload of X is an uncached put beside the sketch, not inside it; the
    # binned matrix is what ``ops/trees.quantize`` gives
    from transmogrifai_tpu.impl import sweep_fragments
    from transmogrifai_tpu.obs import trace
    from transmogrifai_tpu.ops import trees as Tr

    X = np.random.default_rng(3).standard_normal((300, 5)).astype(np.float32)
    trace.disable()
    trace.reset()
    trace.enable(path=None)
    try:
        xbs = []
        assert sweep_fragments._xb_index(xbs, X, 8) == 0
        by_name, name_of = _spans_by_name(trace)
    finally:
        trace.disable()
        trace.reset()
    (quant,), (sketch,) = by_name["sweep.quantize"], by_name["sweep.sketch"]
    assert name_of[sketch["args"]["parent"]] == "sweep.quantize"
    for stat, want in (("rows", 300), ("width", 5), ("bins", 8)):
        assert sketch["args"][stat] == quant["args"][stat] == want
    (put,) = [e for e in by_name["devcache.put"] if e["args"]["tag"] == "bin"]
    assert name_of[put["args"]["parent"]] == "sweep.quantize"
    assert put["args"]["bytes"] == X.nbytes
    assert not sketch["ts"] <= put["ts"] <= sketch["ts"] + sketch["dur"]
    np.testing.assert_array_equal(np.asarray(xbs[0]), Tr.quantize(X, 8)[0])


def test_devcache_put_counts_host_bytes_and_passes_device_arrays():
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu import obs
    from transmogrifai_tpu.utils import devcache

    X = np.arange(60, dtype=np.float64).reshape(20, 3)
    before = obs.snapshot()["devcache"]
    a = devcache.put(X, np.float32, tag="X")
    first = obs.snapshot()["devcache"]
    assert isinstance(a, jax.Array) and a.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(a), X.astype(np.float32))
    # counted at the dtype it lands in: 20 x 3 float32; never cached
    assert first["put_bytes"] - before["put_bytes"] == 20 * 3 * 4
    assert first["puts"] - before["puts"] == 1
    assert first["h2d_bytes"] == before["h2d_bytes"]
    devcache.put(X, np.float32)
    again = obs.snapshot()["devcache"]
    assert again["put_bytes"] - first["put_bytes"] == 20 * 3 * 4
    # with no dtype, what ``jnp.asarray`` gives: float64 lands as float32
    assert devcache.put(X).dtype == jnp.asarray(X).dtype
    # a device array passes through and counts nothing
    assert devcache.put(a) is a
    assert devcache.put(a, jnp.float32) is a
    dev = jax.devices()[0]
    assert devcache.put(a, device=dev).devices() == {dev}
    assert obs.snapshot()["devcache"]["put_bytes"] \
        == again["put_bytes"] + 20 * 3 * 4
    assert obs.snapshot()["devcache"]["puts"] == again["puts"] + 1
    # onto a device: ``jax.device_put``'s placement
    b = devcache.put(X[:2], np.float32, device=dev)
    assert b.devices() == {dev} and b.dtype == jnp.float32


def test_sharded_fit_links_each_shard_to_its_launch(tracer):
    # the default mesh partitions the candidates over the virtual devices:
    # every sweep.shard (a pool or hedge thread) has the sweep.launch as its
    # parent and the fit's request, and its gather is under the shard
    sel, ds = _traced_selector(mesh="auto")
    sel.fit(ds)
    evs = [e for e in tracer.events() if e["ph"] == "X"]
    launch = [e for e in evs if e["name"] == "sweep.launch"]
    shards = [e for e in evs if e["name"] == "sweep.shard"]
    if not shards:
        pytest.skip("one device: nothing to shard")
    assert len(launch) == 1 and len(shards) >= 2
    req = next(e for e in evs if e["name"] == "selector.fit")["args"]["req"]
    for sh in shards:
        assert sh["args"]["parent"] == launch[0]["args"]["id"]
        assert sh["args"]["req"] == req
        assert sh["tid"] != launch[0]["tid"]
    shard_ids = {sh["args"]["id"] for sh in shards}
    gathers = [e for e in evs if e["name"] == "sweep.gather"]
    assert len(gathers) == len(shards)
    assert {g["args"]["parent"] for g in gathers} == shard_ids
