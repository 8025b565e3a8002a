"""Mesh-sharded streaming transforms + sharded winner scoring.

Parity contract for the multi-device stream path (workflow/stream.py +
parallel/mesh.py stream routing): chunks round-robined over the data
devices must reproduce the single-device streamed output EXACTLY —
fill/concat/one-hot stages bit-exact, scaler-family f32 arithmetic at
rtol 2e-6 / atol 1e-6 (the documented XLA fusion tolerance) — across
divide/remainder/exceed chunkings at 2/4/8 data shards.

Also covers: the double-padding edge (chunk tail x shard tail both
zero-filled and mask-aware), a sharded device view as the selector's row
source, the overlap_efficiency floor on a multi-chunk prefetched run, winner
scoring routed through the sharded head with recorded (never raised)
fallbacks, Chan-merge sharded column moments vs numpy, and the
compiles <= n_shards telemetry contract (one program per chip).

Multi-device cases need forced devices BEFORE jax initializes:
    XLA_FLAGS=--xla_force_host_platform_device_count=8
(the tier1 forced-streaming matrix entry provides this); on a
single-device host they skip rather than fake it.
"""
import time

import numpy as np
import pytest

import jax

import transmogrifai_tpu.types as T
from transmogrifai_tpu import Dataset, FeatureBuilder, OpWorkflow
from transmogrifai_tpu.columns import NumericColumn
from transmogrifai_tpu.parallel import mesh as pmesh
from transmogrifai_tpu.workflow import stream

N_DEV = len(jax.devices())
multidev = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


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


def _run_streamed(ds, layers, **kw):
    stream.reset_stream_stats()
    out = stream.apply_streamed(ds, layers, **kw)
    assert out is not None
    return out, stream.stream_stats()


# ---------------------------------------------------------------------------
# sharded-vs-single parity
# ---------------------------------------------------------------------------

@multidev
@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("n,chunk", [
    (256, 64),    # chunk divides evenly
    (237, 64),    # remainder -> zero-padded masked chunk tail
    (100, 256),   # chunk exceeds rows -> single padded chunk, 1 device used
])
def test_sharded_parity_across_chunkings(monkeypatch, n, chunk, shards):
    if shards > N_DEV:
        pytest.skip(f"only {N_DEV} devices")
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", str(chunk))
    ds = _mkds(n, seed=1)
    layers, st, ref = _pipeline(ds)

    monkeypatch.setenv("TMOG_STREAM_ROUTE", "single")
    single, _ = _run_streamed(ds, layers)
    monkeypatch.delenv("TMOG_STREAM_ROUTE")
    monkeypatch.setenv("TMOG_STREAM_SHARDS", str(shards))
    out, s = _run_streamed(ds, layers)

    # fill/vectorize/concat: bit-exact vs BOTH the host path and the
    # single-device stream (the TMOG_MESH-unset contract)
    fm_nm = _out_name(st["fm"])
    np.testing.assert_array_equal(out[fm_nm].mask, ref[fm_nm].mask)
    for key in ("fm", "m1", "m2", "comb"):
        nm = _out_name(st[key])
        np.testing.assert_array_equal(out[nm].values, single[nm].values)
        assert len(out[nm]) == n
    np.testing.assert_array_equal(out[_out_name(st["comb"])].values,
                                  ref[_out_name(st["comb"])].values)
    # scaler: documented f32 fusion tolerance vs host, bit-exact vs the
    # single-device stream (same program, same chunking, same math)
    nm = _out_name(st["sm"])
    np.testing.assert_array_equal(out[nm].values, single[nm].values)
    np.testing.assert_allclose(out[nm].values, ref[nm].values,
                               rtol=2e-6, atol=1e-6)

    used = min(shards, N_DEV)
    assert s["shards"] == used
    assert s["compiles"] <= used      # one program per chip, not per chunk
    assert sum(d["chunks"] for d in s["by_device"].values()) == s["chunks"]
    assert len(s["by_device"]) == min(used, s["chunks"])


@multidev
def test_double_padding_edge(monkeypatch):
    """Chunk tail AND shard tail: 150 rows / 64-row chunks -> 3 chunks over
    2 devices, so the last device gets fewer chunks and the last chunk is
    zero-padded.  Both tails must stay mask-aware and slice off."""
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    monkeypatch.setenv("TMOG_STREAM_SHARDS", "2")
    ds = _mkds(150, seed=2)
    layers, st, ref = _pipeline(ds)
    out, s = _run_streamed(ds, layers)

    assert s["chunks"] == 3 and s["pad_rows"] == 42 and s["shards"] == 2
    by_chunks = sorted(d["chunks"] for d in s["by_device"].values())
    assert by_chunks == [1, 2]        # uneven shard tail
    fill = out[_out_name(st["fm"])]
    assert len(fill) == 150           # padding sliced off
    np.testing.assert_array_equal(fill.mask, ref[_out_name(st["fm"])].mask)
    for key in ("m1", "m2", "comb"):
        nm = _out_name(st[key])
        np.testing.assert_array_equal(out[nm].values, ref[nm].values)
    np.testing.assert_allclose(out[_out_name(st["sm"])].values,
                               ref[_out_name(st["sm"])].values,
                               rtol=2e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# handoff + devcache from a sharded stream
# ---------------------------------------------------------------------------

@multidev
def test_sharded_handoff_devcache_skips_reupload(monkeypatch):
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    monkeypatch.setenv("TMOG_STREAM_SHARDS", "4")
    from transmogrifai_tpu import obs
    from transmogrifai_tpu.impl.selector import model_selector
    from transmogrifai_tpu.utils import devcache

    ds = _mkds(237, seed=3)
    layers, st, _ref = _pipeline(ds)
    comb_nm = _out_name(st["comb"])

    stream.clear_views()
    out, s = _run_streamed(ds, layers, handoff={comb_nm})
    assert s["shards"] == min(4, N_DEV)
    X = out[comb_nm].values
    # the view gathers per-device chunks (row-ascending) onto one device
    view = stream.device_view(X)
    assert view is not None
    np.testing.assert_array_equal(np.asarray(view), X)

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


# ---------------------------------------------------------------------------
# overlap: host prep must hide behind device execution
# ---------------------------------------------------------------------------

def test_overlap_efficiency_floor_multi_chunk(monkeypatch):
    """>=4-chunk run with the prefetch worker on: only the first chunk's
    prep may block, so the hidden-prep share must clear the 0.3 floor (the
    old serialized loop sat at ~0.002).  Pinned to the single-device route:
    the subject is the prefetch pipeline itself, not the shard fan-out.
    ``prep_blocked_s`` is a wall-clock queue wait, so per-chunk prep must
    dwarf thread-scheduling jitter for the ratio to mean anything — with
    microsecond prep a single queue wakeup reads as 100% blocked.  Prep is
    therefore padded with a GIL-releasing sleep far above jitter but far
    below per-chunk device execution: the prefetch worker provably can
    hide it behind the in-flight window on any host, so only the first
    chunk's prep may block.  A warmup stream takes compilation out of the
    measured pass; the floor is asserted on the best of three attempts
    (one scheduler preemption on an oversubscribed CPU proxy can still
    sink a run)."""
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "16384")
    monkeypatch.setenv("TMOG_STREAM_PREFETCH", "2")
    monkeypatch.setenv("TMOG_STREAM_ROUTE", "single")
    ds = _mkds(131072, seed=4)     # 8 chunks, ~40ms device exec each
    layers, _st, _ref = _pipeline(ds)
    real_prep = stream._host_chunk_args

    def padded_prep(*a, **kw):
        out = real_prep(*a, **kw)
        time.sleep(0.003)
        return out

    monkeypatch.setattr(stream, "_host_chunk_args", padded_prep)
    assert stream.apply_streamed(ds, layers) is not None  # warmup: compile
    best = -1.0
    for _ in range(3):
        _out, s = _run_streamed(ds, layers)
        assert s["chunks"] == 8
        assert s["prep_s"] >= 8 * 0.003
        best = max(best, s["overlap_efficiency"])
        if best >= 0.3:
            break
    assert best >= 0.3


def test_inline_prep_reports_zero_overlap(monkeypatch):
    """TMOG_STREAM_PREFETCH=0 disables the worker: prep runs inline on the
    dispatch thread, nothing is hidden, and the metric must say so instead
    of flattering the serialized loop."""
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    monkeypatch.setenv("TMOG_STREAM_PREFETCH", "0")
    ds = _mkds(512, seed=5)
    layers, _st, _ref = _pipeline(ds)
    _out, s = _run_streamed(ds, layers)
    assert s["chunks"] == 8
    assert s["overlap_efficiency"] == 0.0


# ---------------------------------------------------------------------------
# winner scoring through the sharded head
# ---------------------------------------------------------------------------

def _trained_model(monkeypatch, n=300, seed=6):
    from transmogrifai_tpu.impl.feature.vectorizers import (RealVectorizer,
                                                            VectorsCombiner)
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)

    monkeypatch.setenv("TMOG_FUSE_MAX_ROWS", "32")
    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    ds = _mkds(n, seed=seed)
    label, xs = _features()
    va = RealVectorizer().set_input(*xs[:3]).get_output()
    vb = RealVectorizer().set_input(*xs[3:]).get_output()
    comb = VectorsCombiner().set_input(va, vb).get_output()
    pred = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, seed=0, model_types=["OpLogisticRegression"]
    ).set_input(label, comb).get_output()
    model = OpWorkflow().set_result_features(pred).set_input_dataset(ds).train()
    return model, ds, pred


@multidev
def test_winner_scoring_routes_sharded(monkeypatch):
    model, _ds, pred = _trained_model(monkeypatch)
    monkeypatch.setenv("TMOG_STREAM_ROUTE", "single")
    ref = model.score()
    monkeypatch.delenv("TMOG_STREAM_ROUTE")
    monkeypatch.setenv("TMOG_STREAM_SHARDS", str(min(4, N_DEV)))
    stream.reset_stream_stats()
    out = model.score()
    s = stream.stream_stats()
    assert s["score_stages"] >= 1          # the head went through the shards
    assert s["score_chunks"] >= 2
    np.testing.assert_allclose(out[pred.name].probability,
                               ref[pred.name].probability,
                               rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(out[pred.name].prediction,
                               ref[pred.name].prediction,
                               rtol=2e-6, atol=1e-6)
    # the SelectedModel metadata contract survives the sharded pass
    assert out[pred.name].metadata is not None
    assert "model_selector_summary" in out[pred.name].metadata


@multidev
def test_score_head_fallback_recorded_not_raised(monkeypatch):
    """A head without a pure-JAX predict_program must fall back to the
    generic transform with the reason recorded, never an error."""
    model, ds, pred = _trained_model(monkeypatch, n=200, seed=7)
    sel = next(st for st in model.stages
               if getattr(st, "predictor_class", None) is not None)

    class _NoProgram:
        __name__ = "NoProgram"

        @staticmethod
        def predict_program(params):
            raise NotImplementedError

    monkeypatch.setenv("TMOG_STREAM_SHARDS", str(min(4, N_DEV)))
    monkeypatch.setattr(sel, "predictor_class", _NoProgram)
    # training under an active mesh may already have cached this head's
    # real jitted program (keyed by stage identity) — drop it so the
    # monkeypatched program-less class is actually consulted
    with stream._HEAD_LOCK:
        stream._HEAD_JITS.clear()
    stream.reset_stream_stats()
    col = stream.maybe_score_sharded(sel, model.train_data)
    assert col is None
    fb = stream.stream_stats()["fallbacks"]
    assert any(f["reason"] == "score_head_no_program" for f in fb)


def test_maybe_score_sharded_declines_single_device(monkeypatch):
    """With one stream device the router must decline instantly (the
    single-chip path stays bit-identical with TMOG_MESH unset)."""
    model, _ds, _pred = _trained_model(monkeypatch, n=200, seed=8)
    sel = next(st for st in model.stages
               if getattr(st, "predictor_class", None) is not None)
    monkeypatch.setenv("TMOG_STREAM_ROUTE", "single")
    assert stream.maybe_score_sharded(sel, model.train_data) is None


# ---------------------------------------------------------------------------
# sharded fit statistics (Chan-merged per-device moments)
# ---------------------------------------------------------------------------

def test_sharded_column_moments_matches_numpy():
    from transmogrifai_tpu.parallel.stats import sharded_column_moments

    rng = np.random.default_rng(9)
    X = (rng.normal(3.0, 5.0, size=(4321, 7)) * 10).astype(np.float32)
    count, mean, std = sharded_column_moments(X, chunk_rows=1000)
    assert count == 4321
    np.testing.assert_allclose(mean, X.astype(np.float64).mean(axis=0),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(std, X.astype(np.float64).std(axis=0),
                               rtol=1e-6, atol=1e-6)


@multidev
def test_scaler_sharded_fit_parity(monkeypatch):
    """With the sharded-fit row gate lowered, the standard scaler's fit
    reduces per-device Chan partials — params must match the host fit."""
    from transmogrifai_tpu.impl.feature.vectorizers import (
        RealVectorizer, StandardScalerVectorizer)

    ds = _mkds(400, seed=10)
    _label, xs = _features()
    m1 = RealVectorizer().set_input(*xs).fit(ds)
    ref = ds.with_column(m1.get_output().name, m1.transform_dataset(ds))

    host = StandardScalerVectorizer().set_input(m1.get_output()).fit(ref)
    monkeypatch.setenv("TMOG_SHARDED_FIT_ROWS", "100")
    monkeypatch.setenv("TMOG_STREAM_SHARDS", str(min(4, N_DEV)))
    sharded = StandardScalerVectorizer().set_input(m1.get_output()).fit(ref)
    # the Chan merge runs in f64, the host fit in f32 numpy — both must sit
    # within a few f32 ulps of the exact f64 moments (and of each other)
    V = ref[m1.get_output().name].values.astype(np.float64)
    np.testing.assert_allclose(sharded.mean, V.mean(axis=0), rtol=5e-6, atol=1e-6)
    np.testing.assert_allclose(sharded.std, V.std(axis=0), rtol=5e-6, atol=1e-6)
    np.testing.assert_allclose(sharded.mean, host.mean, rtol=5e-6, atol=1e-6)
    np.testing.assert_allclose(sharded.std, host.std, rtol=5e-6, atol=1e-6)
    out_h = host.transform_dataset(ref)
    out_s = sharded.transform_dataset(ref)
    np.testing.assert_allclose(out_s.values, out_h.values,
                               rtol=5e-6, atol=1e-6)
