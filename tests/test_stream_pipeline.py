"""Streaming cross-layer transform pipeline (workflow/stream.py).

Exact-parity checks against the per-stage host path for numeric,
vector, and host-prep (categorical) stages at chunk sizes that divide
the row count evenly, exceed it (single chunk), and leave a remainder
(zero-padded, mask-aware tail).  Fill/concat/one-hot/gather stages are
bit-exact; scaler-type f32 arithmetic is compared at rtol 2e-6 /
atol 1e-6 — XLA fuses the multiply-add, numpy doesn't, so the last
1-2 ulp differ (same tolerance the fused-layer tests already use).

Also covers: padded-tail mask contract, multi-chunk + at-most-one
steady-state compile telemetry, liveness (device-only intermediates),
the model-selector device view, the jax_chunkable opt-out, the
too-few-stages fallback, and an end-to-end workflow train/score run
under forced-small chunk envs.
"""
import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu import Dataset, FeatureBuilder, OpWorkflow
from transmogrifai_tpu.columns import NumericColumn
from transmogrifai_tpu.workflow import stream


def _mkds(n, seed=0):
    rng = np.random.default_rng(seed)
    cols = {}
    for j in range(6):
        v = rng.normal(size=n)
        m = rng.random(n) > 0.1
        cols[f"x{j}"] = NumericColumn(T.Real, np.where(m, v, 0.0), m)
    cols["label"] = NumericColumn(T.RealNN, (rng.random(n) > 0.5).astype(float),
                                  np.ones(n, bool))
    return Dataset(cols)


def _features():
    label = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
    xs = [FeatureBuilder(f"x{j}", T.Real).extract(field=f"x{j}").as_predictor()
          for j in range(6)]
    return label, xs


def _pipeline(ds):
    """3 layers: fill + 2 vectorizers -> combiner -> standard scaler.
    Returns (layers, fitted-stage map by role) plus the host-path reference
    Dataset computed per stage."""
    from transmogrifai_tpu.impl.feature.transformers import FillMissingWithMean
    from transmogrifai_tpu.impl.feature.vectorizers import (
        RealVectorizer, StandardScalerVectorizer, VectorsCombiner)

    label, xs = _features()
    fm = FillMissingWithMean().set_input(xs[0]).fit(ds)
    m1 = RealVectorizer().set_input(*xs[:3]).fit(ds)
    m2 = RealVectorizer(fill_with_mean=False, fill_value=-1.0).set_input(*xs[3:]).fit(ds)
    comb = VectorsCombiner().set_input(m1.get_output(), m2.get_output())
    ref = ds
    for t in (fm, m1, m2, comb):
        ref = ref.with_column(t.get_output().name, t.transform_dataset(ref))
    sm = StandardScalerVectorizer().set_input(comb.get_output()).fit(ref)
    ref = ref.with_column(sm.get_output().name, sm.transform_dataset(ref))
    layers = [[fm, m1, m2], [comb], [sm]]
    return layers, {"fm": fm, "m1": m1, "m2": m2, "comb": comb, "sm": sm}, ref


def _out_name(t):
    return t.get_output().name


@pytest.mark.parametrize("n,chunk,n_chunks,pad", [
    (256, 64, 4, 0),     # chunk divides evenly
    (237, 64, 4, 19),    # remainder -> zero-padded masked tail
    (100, 256, 1, 156),  # chunk exceeds rows -> single padded chunk
])
def test_stream_parity_across_chunkings(monkeypatch, n, chunk, n_chunks, pad):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", str(chunk))
    ds = _mkds(n, seed=1)
    layers, st, ref = _pipeline(ds)

    stream.reset_stream_stats()
    out = stream.apply_streamed(ds, layers)
    assert out is not None

    # fill / vectorize / concat are bit-exact vs the host path
    fill = out[_out_name(st["fm"])]
    np.testing.assert_array_equal(fill.mask, ref[_out_name(st["fm"])].mask)
    np.testing.assert_allclose(fill.values, ref[_out_name(st["fm"])].values,
                               rtol=2e-6, atol=1e-6)
    for key in ("m1", "m2", "comb"):
        nm = _out_name(st[key])
        np.testing.assert_array_equal(out[nm].values, ref[nm].values)
        assert out[nm].metadata is not None
        assert len(out[nm]) == n  # tail padding sliced off
    # scaler: documented f32 fusion tolerance
    nm = _out_name(st["sm"])
    np.testing.assert_allclose(out[nm].values, ref[nm].values,
                               rtol=2e-6, atol=1e-6)

    s = stream.stream_stats()
    assert s["streams"] == 1
    assert s["chunks"] == n_chunks
    assert s["pad_rows"] == pad
    assert s["rows"] == n
    assert s["stages_fused"] == 5
    # one program per chip: a data mesh (TMOG_MESH / TMOG_STREAM_SHARDS)
    # specializes the same jit per committed device, never per chunk
    assert s["compiles"] <= min(max(1, s["shards"]), s["chunks"])
    assert np.isfinite(out[nm].values).all()


def test_steady_state_reuses_compiled_program(monkeypatch):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    ds = _mkds(200, seed=2)
    layers, _st, _ref = _pipeline(ds)

    stream.reset_stream_stats()
    assert stream.apply_streamed(ds, layers) is not None
    s0 = stream.stream_stats()
    first = s0["compiles"]
    assert first <= min(max(1, s0["shards"]), s0["chunks"])  # one per chip
    assert stream.apply_streamed(ds, layers) is not None
    s = stream.stream_stats()
    assert s["streams"] == 2
    assert s["compiles"] == first  # no recompile in steady state


def test_liveness_keeps_intermediates_device_only(monkeypatch):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    ds = _mkds(150, seed=3)
    layers, st, ref = _pipeline(ds)
    final = _out_name(st["sm"])

    stream.reset_stream_stats()
    out = stream.apply_streamed(ds, layers, live={final})
    assert out is not None
    np.testing.assert_allclose(out[final].values, ref[final].values,
                               rtol=2e-6, atol=1e-6)
    # everything upstream of the scaler stays device-resident
    for key in ("fm", "m1", "m2", "comb"):
        assert _out_name(st[key]) not in out.columns
    assert stream.stream_stats()["device_only"] == 4


def test_handoff_device_view_and_devcache_seed(monkeypatch):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    from transmogrifai_tpu import obs
    from transmogrifai_tpu.impl.selector import model_selector
    from transmogrifai_tpu.utils import devcache

    ds = _mkds(237, seed=4)
    layers, st, ref = _pipeline(ds)
    comb_nm = _out_name(st["comb"])

    stream.reset_stream_stats()
    stream.clear_views()
    out = stream.apply_streamed(ds, layers, handoff={comb_nm})
    X = out[comb_nm].values
    view = stream.device_view(X)
    assert view is not None
    np.testing.assert_array_equal(np.asarray(view), X)  # pad sliced off

    # the selector takes its training rows from the view on the device ...
    idx = np.arange(0, len(ds), 3)
    Xtr = model_selector._rows(view, idx, "train")
    np.testing.assert_array_equal(np.asarray(Xtr), X[idx])
    # ... and the sweep's upload calls take them as they are: no re-upload
    before = obs.snapshot()["devcache"]
    assert devcache.device_array(Xtr, np.float32) is Xtr
    assert devcache.put(Xtr, np.float32) is Xtr
    after = obs.snapshot()["devcache"]
    assert after["h2d_bytes"] == before["h2d_bytes"]
    assert after["put_bytes"] == before["put_bytes"]
    stream.clear_views()


def test_jax_chunkable_optout_runs_host_side(monkeypatch):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    from transmogrifai_tpu.impl.feature.transformers import FillMissingWithMean
    from transmogrifai_tpu.impl.feature.vectorizers import RealVectorizer

    ds = _mkds(200, seed=5)
    label, xs = _features()
    fm = FillMissingWithMean().set_input(xs[0]).fit(ds)
    m1 = RealVectorizer().set_input(*xs[:3]).fit(ds)
    m2 = RealVectorizer().set_input(*xs[3:]).fit(ds)
    m2.jax_chunkable = False  # opt out: must take the host path
    ref = {t: t.transform_dataset(ds) for t in (fm, m1, m2)}

    stream.reset_stream_stats()
    out = stream.apply_streamed(ds, [[fm, m1, m2]])
    assert out is not None
    np.testing.assert_array_equal(out[_out_name(m2)].values, ref[m2].values)
    np.testing.assert_array_equal(out[_out_name(m1)].values, ref[m1].values)
    s = stream.stream_stats()
    assert s["stages_fused"] == 2 and s["stages_host"] == 1


def test_fallback_when_too_few_fusable_stages(monkeypatch):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    from transmogrifai_tpu.impl.feature.vectorizers import RealVectorizer

    ds = _mkds(100, seed=6)
    _label, xs = _features()
    m1 = RealVectorizer().set_input(*xs[:3]).fit(ds)

    stream.reset_stream_stats()
    assert stream.apply_streamed(ds, [[m1]]) is None
    fb = stream.stream_stats()["fallbacks"]
    assert fb and fb[-1]["reason"] == "too_few_fusable_stages"


def test_onehot_host_prep_streams_bit_exact(monkeypatch):
    """Categorical pivot: per-chunk jax_host_prep (int32 targets) feeding the
    streamed one-hot expansion matches the host path exactly, including the
    padded tail chunk."""
    import pandas as pd

    from transmogrifai_tpu.features.builder import from_dataframe
    from transmogrifai_tpu.impl.feature.vectorizers import (
        OneHotVectorizer, RealVectorizer, VectorsCombiner)
    from transmogrifai_tpu.readers.base import CustomReader

    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "32")
    n = 120
    rng = np.random.default_rng(7)
    df = pd.DataFrame({
        "age": np.where(rng.random(n) < 0.2, np.nan, rng.uniform(1, 80, n)),
        "fare": rng.uniform(5, 500, n),
        "sex": rng.choice(["male", "female"], n),
        "embarked": rng.choice(["S", "C", "Q", None], n),
        "survived": rng.integers(0, 2, n),
    })
    feats, resp = from_dataframe(df, response="survived")
    by = {f.name: f for f in feats}
    ds = CustomReader(df).generate_dataset(list(by.values()) + [resp], {})

    cm = OneHotVectorizer(track_nulls=True).set_input(by["sex"], by["embarked"]).fit(ds)
    nm = RealVectorizer().set_input(by["age"], by["fare"]).fit(ds)
    comb = VectorsCombiner().set_input(cm.get_output(), nm.get_output())
    ref = ds
    for t in (cm, nm, comb):
        ref = ref.with_column(t.get_output().name, t.transform_dataset(ref))

    stream.reset_stream_stats()
    out = stream.apply_streamed(ds, [[cm, nm], [comb]])
    assert out is not None
    np.testing.assert_array_equal(out[_out_name(cm)].values,
                                  ref[_out_name(cm)].values)
    np.testing.assert_array_equal(out[_out_name(comb)].values,
                                  ref[_out_name(comb)].values)
    s = stream.stream_stats()
    assert s["chunks"] == 4 and s["pad_rows"] == 8
    assert s["compiles"] <= min(max(1, s["shards"]), s["chunks"])  # one per chip


def test_workflow_end_to_end_forced_streaming(monkeypatch):
    """Full train + score with the fuse cliff forced below the data size:
    the transform sub-DAG must stream (multiple chunks, >= 1 stream) and the
    model must come out healthy."""
    from transmogrifai_tpu.impl.feature.vectorizers import (RealVectorizer,
                                                            VectorsCombiner)
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)

    monkeypatch.setenv("TMOG_FUSE_MAX_ROWS", "32")
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    ds = _mkds(300, seed=8)
    label, xs = _features()
    va = RealVectorizer().set_input(*xs[:3]).get_output()
    vb = RealVectorizer().set_input(*xs[3:]).get_output()
    comb = VectorsCombiner().set_input(va, vb).get_output()
    pred = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, seed=0, model_types=["OpLogisticRegression"]
    ).set_input(label, comb).get_output()

    model = OpWorkflow().set_result_features(pred).set_input_dataset(ds).train()
    out = model.train_data[pred.name]
    assert np.isfinite(out.probability).all()
    s = stream.stream_stats()
    assert s["streams"] >= 1
    assert s["chunks"] >= 2  # genuinely multi-chunk
    assert s["transform_rows_per_sec"] > 0

    scores = model.score()
    assert np.isfinite(scores[pred.name].probability).all()
