"""Perf-regression gate: comparison engine + CLI exit codes.

- obs.regress.compare: direction-aware verdicts with relative tolerance,
  zero-baseline handling, platform-mismatch skip;
- baseline discovery picks the newest BENCH round + STREAM_BENCH;
- tools/perfgate.py (subprocess): exit 0 when the baselines are checked
  against themselves, 1 on a synthetically regressed record, 0 under
  --warn-only, 2 with no baselines; JSONL records are extracted.

The repo commits no ``BENCH_r*.json`` any more (the old rounds' records went
with the transport they were taken through), so the baseline directory is a
fixture: two bench rounds in the ``BENCH_r*`` wrapper shape plus the
committed ``STREAM_BENCH.json``.
"""
import functools
import json
import os
import shutil
import subprocess
import sys

import pytest

from transmogrifai_tpu.obs import regress

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO, "tools", "perfgate.py")


def _base(**kw):
    rep = {"metric": "selector_sweep_models_per_sec", "value": 200.0,
           "warmup_s": 8.0, "steady_s": 0.4, "mfu": 0.011,
           "platform": "tpu"}
    rep.update(kw)
    return rep


@pytest.fixture
def baseline_dir(tmp_path):
    root = tmp_path / "baselines"
    root.mkdir()
    (root / "BENCH_r08.json").write_text(
        json.dumps({"rc": 0, "parsed": _base(value=12.0)}))
    (root / "BENCH_r09.json").write_text(
        json.dumps({"rc": 0, "parsed": _base()}))
    shutil.copy(os.path.join(REPO, "STREAM_BENCH.json"), root)
    return str(root)


def test_compare_ok_and_directions():
    v = regress.compare(_base(), _base(), tol=0.25)
    assert v["ok"] and not v["regressed"]
    # higher-better metric drops past tolerance -> regressed
    v = regress.compare(_base(value=100.0), _base(), tol=0.25)
    assert v["regressed"] == ["value"]
    # lower-better wall grows past tolerance -> regressed
    v = regress.compare(_base(steady_s=0.8), _base(), tol=0.25)
    assert "steady_s" in v["regressed"]
    # improvements are not failures
    v = regress.compare(_base(value=400.0, steady_s=0.2), _base(), tol=0.25)
    assert v["ok"]
    st = {r["key"]: r["status"] for r in v["results"]}
    assert st["value"] == "improved" and st["steady_s"] == "improved"


def test_compare_within_tolerance():
    v = regress.compare(_base(value=160.0), _base(), tol=0.25)  # -20%
    assert v["ok"]
    v = regress.compare(_base(value=140.0), _base(), tol=0.25)  # -30%
    assert not v["ok"]


def test_compare_zero_baseline_lower_better():
    b = {"metric": "transform_stream_speedup", "value": 3.0,
         "compiles_steady": 0, "platform": "cpu"}
    v = regress.compare(dict(b, compiles_steady=3), b)
    assert "compiles_steady" in v["regressed"]
    v = regress.compare(dict(b), b)
    assert v["ok"]


def test_compare_platform_mismatch_skips():
    v = regress.compare(_base(value=1.0, platform="cpu"), _base(), tol=0.25)
    assert v["ok"]
    assert all(r["status"] == "skipped_platform" for r in v["results"])


def test_compare_missing_keys_skip():
    v = regress.compare({"metric": "selector_sweep_models_per_sec",
                         "value": 210.0, "platform": "tpu"}, _base())
    assert v["ok"]
    st = {r["key"]: r["status"] for r in v["results"]}
    assert st["mfu"] == "skipped_missing"


def test_load_baselines_newest_round(baseline_dir):
    bl = regress.load_baselines(baseline_dir)
    assert "selector_sweep_models_per_sec" in bl
    assert "transform_stream_speedup" in bl
    name, rep = bl["selector_sweep_models_per_sec"]
    assert name == "BENCH_r09.json" and rep["value"] == 200.0


def test_extract_reports_jsonl(tmp_path):
    p = tmp_path / "telemetry.jsonl"
    rows = [
        {"schema": 3, "run": "x", "report": _base()},
        {"schema": 3, "run": "y"},          # no report: skipped
        {"parsed": _base(value=150.0)},      # BENCH wrapper shape
    ]
    p.write_text("\n".join(json.dumps(r) for r in rows) + "\nnot json\n")
    reps = regress.extract_reports(str(p))
    assert [r["value"] for r in reps] == [200.0, 150.0]


def _run_gate(*args, cwd=REPO):
    return subprocess.run([sys.executable, GATE, *args],
                          capture_output=True, text=True, cwd=cwd)


def _gate(baseline_dir, *args):
    return _run_gate("--baseline-dir", baseline_dir, *args)


def test_gate_self_check_passes(baseline_dir):
    """Baselines checked against themselves exit 0."""
    r = _gate(baseline_dir)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pass" in r.stdout


def test_gate_regressed_record_fails(tmp_path, baseline_dir):
    _run_gate = functools.partial(_gate, baseline_dir)
    bl = regress.load_baselines(baseline_dir)
    _, base = bl["selector_sweep_models_per_sec"]
    bad = dict(base, value=base["value"] * 0.5)
    p = tmp_path / "regressed.json"
    p.write_text(json.dumps(bad))
    r = _run_gate("--record", str(p))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESS" in r.stdout
    # --warn-only reports but never fails the build (the CPU-proxy CI step)
    r = _run_gate("--record", str(p), "--warn-only")
    assert r.returncode == 0
    assert "REGRESSION (warn-only)" in r.stdout


def test_gate_fresh_jsonl_and_unknown_metric(tmp_path, baseline_dir):
    _run_gate = functools.partial(_gate, baseline_dir)
    bl = regress.load_baselines(baseline_dir)
    _, base = bl["selector_sweep_models_per_sec"]
    p = tmp_path / "telemetry.jsonl"
    p.write_text(json.dumps({"report": dict(base)}) + "\n"
                 + json.dumps({"report": {"metric": "brand_new", "value": 1}})
                 + "\n")
    r = _run_gate("--record", str(p), "--json")
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout.splitlines()[-1])
    assert not doc["self_check"] and not doc["regressed"]
    skips = [v for v in doc["verdicts"] if v.get("skipped")]
    assert [v["metric"] for v in skips] == ["brand_new"]


def test_gate_tolerance_flag(tmp_path, baseline_dir):
    _run_gate = functools.partial(_gate, baseline_dir)
    bl = regress.load_baselines(baseline_dir)
    _, base = bl["selector_sweep_models_per_sec"]
    mild = dict(base, value=base["value"] * 0.9)  # -10%
    p = tmp_path / "mild.json"
    p.write_text(json.dumps(mild))
    assert _run_gate("--record", str(p), "--tol", "0.25").returncode == 0
    assert _run_gate("--record", str(p), "--tol", "0.05").returncode == 1


def test_gate_no_baselines(tmp_path):
    r = _run_gate("--baseline-dir", str(tmp_path))
    assert r.returncode == 2


def test_gate_env_tolerance(monkeypatch):
    monkeypatch.setenv("TMOG_PERFGATE_TOL", "0.1")
    assert regress.default_tolerance() == pytest.approx(0.1)
    monkeypatch.delenv("TMOG_PERFGATE_TOL")
    assert regress.default_tolerance() == pytest.approx(regress.DEFAULT_TOL)
