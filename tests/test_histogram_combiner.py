"""StreamingHistogram (Ben-Haim/Tom-Tov) + SelectedModelCombiner parity tests
on fixed small inputs (round-2 VERDICT #8).
"""
import numpy as np
import pytest

from transmogrifai_tpu.utils.histogram import StreamingHistogram


class TestStreamingHistogram:
    def test_exact_below_capacity(self):
        h = StreamingHistogram(max_bins=8)
        for v in [1.0, 2.0, 5.0, 2.0]:
            h.update(v)
        assert h.total == 4
        assert h.bins() == [(1.0, 1.0), (2.0, 2.0), (5.0, 1.0)]

    def test_paper_merge_example(self):
        """The BH-2010 paper's running example: points 23,19,10,16,36 at B=5,
        then inserting 2 and 9 forces the two closest-centroid merges the
        paper shows ((19,1),(16,1) -> (17.5,2))."""
        h = StreamingHistogram(max_bins=5)
        for v in [23, 19, 10, 16, 36]:
            h.update(v)
        h.update(2)   # -> merge 16 & 19 into (17.5, 2)
        assert (17.5, 2.0) in h.bins()
        h.update(9)   # -> merge 9 & 10 into (9.5, 2)
        assert (9.5, 2.0) in h.bins()
        assert h.total == 7
        assert len(h.bins()) == 5

    def test_sum_interpolation(self):
        # paper Algorithm 3 worked example structure: trapezoid estimate
        h = StreamingHistogram(max_bins=5)
        for v in [23, 19, 10, 16, 36, 2, 9]:
            h.update(v)
        s = h.sum_upto(15)
        # exact count <= 15 is 3 (2, 9, 10); the sketch estimate is close
        assert 2.0 <= s <= 4.5

    def test_batch_equals_sequential_when_exact(self):
        vals = [3.0, 1.0, 4.0, 1.0, 5.0]
        h1 = StreamingHistogram(max_bins=10)
        for v in vals:
            h1.update(v)
        h2 = StreamingHistogram(max_bins=10).update_all(vals)
        assert h1.bins() == h2.bins()

    def test_merge_conserves_mass(self):
        rng = np.random.default_rng(0)
        a = StreamingHistogram(32).update_all(rng.normal(size=500))
        b = StreamingHistogram(32).update_all(rng.normal(2.0, size=300))
        a.merge(b)
        assert a.total == pytest.approx(800)
        assert len(a.bins()) <= 32

    def test_quantiles_monotone_and_accurate(self):
        rng = np.random.default_rng(1)
        data = rng.normal(size=20000)
        h = StreamingHistogram(64).update_all(data)
        qs = [h.quantile(q) for q in (0.1, 0.25, 0.5, 0.75, 0.9)]
        assert all(a <= b for a, b in zip(qs, qs[1:]))
        exact = np.quantile(data, [0.1, 0.25, 0.5, 0.75, 0.9])
        np.testing.assert_allclose(qs, exact, atol=0.08)

    def test_cdf_and_density(self):
        h = StreamingHistogram(16).update_all(np.linspace(0, 10, 1000))
        assert h.cdf(10.5) == pytest.approx(1.0)
        assert h.cdf(-1) == 0.0
        dens = h.density([0.0, 5.0, 10.0])
        assert dens.sum() == pytest.approx(h.sum_upto(10.0) - h.sum_upto(0.0))
        assert dens[0] == pytest.approx(dens[1], rel=0.15)  # uniform data

    def test_json_roundtrip(self):
        h = StreamingHistogram(8).update_all([1, 2, 2, 3, 9])
        h2 = StreamingHistogram.from_json(h.to_json())
        assert h.bins() == h2.bins()
        assert h2.max_bins == 8


# ---------------------------------------------------------------------------
class TestSelectedModelCombiner:
    def _pred_col(self, probs, metric_value, metric="auPR", uid="ms_1",
                  problem="BinaryClassification"):
        from transmogrifai_tpu import types as T
        from transmogrifai_tpu.columns import PredictionColumn
        from transmogrifai_tpu.impl.selector.model_selector import (
            ModelSelectorSummary)

        probs = np.asarray(probs, np.float64)
        summary = ModelSelectorSummary(
            validation_type="OpCrossValidation", validation_parameters={},
            data_prep_parameters={}, data_prep_results=None,
            evaluation_metric=metric, problem_type=problem,
            best_model_uid=uid, best_model_name=f"name_{uid}",
            best_model_type="OpLogisticRegression", best_grid={},
            validation_results=[{"modelUID": uid, "metricValue": metric_value}],
            train_evaluation={metric: metric_value})
        return PredictionColumn(
            T.Prediction, probs.argmax(axis=1).astype(np.float64),
            raw_prediction=np.log(np.maximum(probs, 1e-9)), probability=probs,
            metadata={"model_selector_summary": summary.to_json()})

    def _fixture(self, strategy, m1=0.8, m2=0.6):
        from transmogrifai_tpu import types as T
        from transmogrifai_tpu import Dataset, FeatureBuilder
        from transmogrifai_tpu.columns import NumericColumn
        from transmogrifai_tpu.impl.selector.combiner import SelectedModelCombiner

        y = np.array([0, 1, 1, 0], np.float64)
        p1 = self._pred_col([[0.9, 0.1], [0.2, 0.8], [0.4, 0.6], [0.7, 0.3]],
                            m1, uid="ms_1")
        p2 = self._pred_col([[0.6, 0.4], [0.4, 0.6], [0.6, 0.4], [0.2, 0.8]],
                            m2, uid="ms_2")
        lbl = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
        f1 = FeatureBuilder("pred1", T.Prediction).extract(field="pred1").as_predictor()
        f2 = FeatureBuilder("pred2", T.Prediction).extract(field="pred2").as_predictor()
        ds = Dataset({"label": NumericColumn(T.RealNN, y, np.ones(4, bool)),
                      "pred1": p1, "pred2": p2})
        comb = SelectedModelCombiner(combination_strategy=strategy)
        comb.set_input(lbl, f1, f2)
        return comb, ds, p1, p2

    def test_best_picks_higher_metric(self):
        comb, ds, p1, _ = self._fixture("best")
        model = comb.fit(ds)
        assert model.weight1 == 1.0 and model.weight2 == 0.0
        out = model.transform_columns([ds["label"], ds["pred1"], ds["pred2"]])
        np.testing.assert_allclose(out.probability, p1.probability)
        md = model.metadata["model_selector_summary"]
        assert md["bestModelUID"] == "ms_1"

    def test_best_respects_smaller_is_better(self):
        from transmogrifai_tpu import Dataset

        comb, ds, _, _ = self._fixture("best")
        # rebuild with an error-style metric: smaller wins -> selector 2
        comb2, ds2, _, p2 = self._fixture("best")
        for name in ("pred1", "pred2"):
            md = ds2[name].metadata["model_selector_summary"]
            md["evaluationMetric"] = "Error"
            md["validationResults"][0]["metricValue"] = (
                0.4 if name == "pred1" else 0.2)
        model = comb2.fit(ds2)
        assert model.weight2 == 1.0

    def test_weighted_combination(self):
        comb, ds, p1, p2 = self._fixture("weighted", m1=0.6, m2=0.2)
        model = comb.fit(ds)
        assert model.weight1 == pytest.approx(0.75)
        out = model.transform_columns([ds["label"], ds["pred1"], ds["pred2"]])
        np.testing.assert_allclose(
            out.probability, 0.75 * p1.probability + 0.25 * p2.probability)
        # prediction is argmax of combined probability
        np.testing.assert_array_equal(out.prediction,
                                      out.probability.argmax(axis=1))
        md = model.metadata["model_selector_summary"]
        assert "ms_1 ms_2" == md["bestModelUID"]
        assert md["trainEvaluation"]  # re-evaluated on combined predictions

    def test_equal_combination(self):
        comb, ds, p1, p2 = self._fixture("equal")
        model = comb.fit(ds)
        assert model.weight1 == model.weight2 == 0.5

    def test_mismatched_problem_types_raise(self):
        comb, ds, _, _ = self._fixture("best")
        ds["pred2"].metadata["model_selector_summary"]["problemType"] = "Regression"
        with pytest.raises(ValueError, match="different problem types"):
            comb.fit(ds)

    def test_end_to_end_two_selectors_combined(self):
        """Full workflow: two ModelSelectors -> combiner -> Prediction."""
        from transmogrifai_tpu import types as T
        from transmogrifai_tpu import Dataset, FeatureBuilder, OpWorkflow
        from transmogrifai_tpu.columns import NumericColumn, VectorColumn
        from transmogrifai_tpu.features.metadata import (VectorColumnMetadata,
                                                         VectorMetadata)
        from transmogrifai_tpu.impl.selector.combiner import SelectedModelCombiner
        from transmogrifai_tpu.impl.selector.factories import (
            BinaryClassificationModelSelector)

        rng = np.random.default_rng(3)
        n, d = 300, 6
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X[:, 0] + 0.5 * rng.normal(size=n) > 0).astype(np.float64)
        meta = VectorMetadata("features", tuple(
            VectorColumnMetadata((f"f{i}",), ("Real",), index=i)
            for i in range(d)))
        ds = Dataset({"label": NumericColumn(T.RealNN, y, np.ones(n, bool)),
                      "features": VectorColumn(T.OPVector, X, meta)})
        lbl = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
        vec = FeatureBuilder("features", T.OPVector).extract(
            field="features").as_predictor()

        s1 = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=2, seed=1, model_types=["OpLogisticRegression"])
        # a forest selector over two shallow candidates: what is combined is
        # two selectors' winners, whatever grid each searched
        from transmogrifai_tpu.impl.classification.trees import (
            OpRandomForestClassifier)
        s2 = BinaryClassificationModelSelector.with_cross_validation(
            num_folds=2, seed=2, models_and_parameters=[
                (OpRandomForestClassifier(num_trees=10),
                 [{"max_depth": 3}, {"max_depth": 5}])])
        p1 = s1.set_input(lbl, vec).get_output()
        p2 = s2.set_input(lbl, vec).get_output()
        combined = SelectedModelCombiner(
            combination_strategy="weighted").set_input(lbl, p1, p2).get_output()
        model = OpWorkflow().set_result_features(combined).set_input_dataset(ds).train()
        out = model.train_data[combined.name]
        assert out.probability.shape == (n, 2)
        md = model.summary()
        assert any("bestModelUID" in str(v) for v in md.values())
