"""``DataCutter``'s training-sample cap (upstream ``SplitterParams``
``maxTrainingSample``): its default, where it is reported, and that the
selector's one cap code honours it as it does ``DataBalancer``'s."""
import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu import FeatureBuilder
from transmogrifai_tpu.columns import Dataset, NumericColumn, VectorColumn
from transmogrifai_tpu.evaluators import Evaluators
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.selector.factories import (
    MultiClassificationModelSelector)
from transmogrifai_tpu.impl.selector.model_selector import ModelSelectorSummary
from transmogrifai_tpu.impl.tuning.splitters import DataCutter
from transmogrifai_tpu.ops import sweep


def test_default_is_upstreams_and_the_default_splitter_passes_nothing_new():
    assert DataCutter().max_training_sample == 1_000_000
    default = MultiClassificationModelSelector._default_splitter()
    assert isinstance(default, DataCutter)
    assert default.max_training_sample == 1_000_000


def test_cap_is_in_params_beside_the_label_rules():
    params = DataCutter(max_label_categories=7, min_label_fraction=0.01,
                        max_training_sample=123)._params()
    assert params == {"reserveTestFraction": 0.1, "seed": 42,
                      "maxLabelCategories": 7, "minLabelFraction": 0.01,
                      "maxTrainingSample": 123}


def test_cap_changes_no_weight_and_no_kept_label():
    y = np.array([0.0] * 50 + [1.0] * 40 + [2.0] * 9 + [3.0])
    capped = DataCutter(max_label_categories=3, min_label_fraction=0.05,
                        max_training_sample=10)
    plain = DataCutter(max_label_categories=3, min_label_fraction=0.05)
    capped.pre_validation_prepare(y), plain.pre_validation_prepare(y)
    assert capped.labels_kept == plain.labels_kept == [0.0, 1.0, 2.0]
    assert np.array_equal(capped.prepare_weights(y), plain.prepare_weights(y))
    assert np.array_equal(capped.prepare_indices(y), plain.prepare_indices(y))


def _fit(cap, n=900, k=4):
    rng = np.random.default_rng(33)
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + 1.5), 0, k - 1).astype(np.float64)
    label = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
    vec = FeatureBuilder("features", T.OPVector).extract(field="features").as_predictor()
    ds = Dataset({"label": NumericColumn(T.RealNN, y, np.ones(n, bool)),
                  "features": VectorColumn(T.OPVector, X)})
    sel = MultiClassificationModelSelector.with_cross_validation(
        splitter=DataCutter(max_training_sample=cap), num_folds=3, seed=42,
        validation_metric=Evaluators.MultiClassification.error(),
        models_and_parameters=[(OpLogisticRegression(max_iter=20),
                                [{"reg_param": 0.01}, {"reg_param": 0.1}])])
    sel.set_input(label, vec)
    sel.validator.mesh = None  # one device, as on one chip: the counters' path
    sweep.reset_run_stats()
    return sel.fit(ds), y


@pytest.mark.parametrize("cap, swept", [(300, 300), (1_000_000, 810)])
def test_selector_sweeps_the_capped_rows(cap, swept):
    model, y = _fit(cap)
    stats = sweep.run_stats()
    (launch,) = stats["launches"]
    assert not stats["fallbacks"] and launch["candidates"] == 2
    # [F, C, n, k] float32: the launch's rows are the cap's
    assert stats["classes"] == 4
    assert stats["score_block_bytes"] == 4 * 3 * 2 * swept * 4
    assert model.summary.data_prep_parameters["maxTrainingSample"] == cap
    assert model.summary.data_prep_results["labelsDropped"] == []


def test_cap_survives_the_summarys_save_and_load():
    model, _ = _fit(300)
    saved = model.summary.to_json()
    assert saved["dataPrepParameters"]["maxTrainingSample"] == 300
    loaded = ModelSelectorSummary.from_json(saved)
    assert loaded.data_prep_parameters == model.summary.data_prep_parameters
