"""``correct`` of the ``scale-500-trees.sweep`` cell comes out true for a sound
run and false for the control and for two faults, and ``--rehearse-rows``
walks the cell.

The cell's own files (configuration, workload, reference, limits) go through
``benchmarks/run.py``'s ``run()`` — everything but the look for a chip — on a
table of 60 Real + 6 PickList x 4,000 rows with 3 trees a forest and 6
boosting rounds: the full grid at 4,000 rows takes the CPU 22 minutes a step
(walked once by hand, ``PERF.md`` PR 29), a test cannot.

- sound: the program as it stands;
- control: the plain reference with every histogram sum (and the logistic
  fit) in bfloat16, put in the program's place (``--control 1``);
- an answer altered where it is produced (every fold metric + 0.02);
- every bin edge moved under the program (the reference bins by the stated
  sketch).
"""
import argparse
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

CELL, SMALL = "scale-500-trees.sweep", "small-trees.sweep"


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A copy of ``benchmarks/`` with the cell's configuration cut small."""
    tmp = tmp_path_factory.mktemp("bench_trees")
    bench_dir = str(tmp / "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = bench_run.load_json(
        os.path.join(bench_dir, "configs", "scale-500-trees.json"))
    cfg.update(rows=4000, max_training_sample=2400, n_real=60, n_picklist=6)
    cfg["sanity_checker"]["sample_upper_limit"] = 2000
    cfg["grid"]["rf"]["fixed"]["num_trees"] = 3
    cfg["grid"]["xgb"]["fixed"]["num_round"] = 6
    with open(os.path.join(bench_dir, "configs", "small-trees.json"), "w") as f:
        json.dump(cfg, f)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    sweep = bench_run.load_json(os.path.join(bench_dir, "workloads", CELL + ".json"))
    sweep["config"] = "small-trees"
    # the cell's own limits but one: the CPU backend adds the histogram
    # GEMM's float32 products in another order than the chip does (the
    # program is the same: one grower since PR 31), a depth-10 tree has many
    # near-tied gains, and a boosted fold moves by 4e-4 to 1.4e-3 here
    # (PERF.md section 7: "it is not the scatter"); on the chip, where the
    # limit was set, it reads 1e-7
    assert sweep["correct"]["limits"]["xgb_fold_gap"] < 2e-3
    sweep["correct"]["limits"]["xgb_fold_gap"] = 2e-3
    with open(os.path.join(bench_dir, "workloads", SMALL + ".json"), "w") as f:
        json.dump(sweep, f)
    bench["workloads"].append({"name": SMALL, "config": "small-trees",
                               "traffic": "sweep", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(SMALL)
    path = str(tmp / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench_dir, path


def drive(small, capsys, control=0, rehearse_rows=None):
    bench_dir, path = small
    args = argparse.Namespace(workload=SMALL, seed=2147500529, seconds=0.01,
                              trace=0, rehearse_rows=rehearse_rows,
                              control=control)
    capsys.readouterr()
    rc = bench_run.run(args, bench_dir=bench_dir, benchmark_json=path,
                       look_for_chip=False)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    return rc, lines[-1], lines[:-1]


def test_sound_run_is_correct_and_the_control_is_not(small, capsys):
    rc, result, earlier = drive(small, capsys, control=1)
    assert rc == 0 and result["correct"] is True, result
    assert result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"fits_per_s", "setup_s"}
    assert set(result["compared"]) == {
        "vector_cells_differ", "winner_not_best", "lr_fold_gap", "rf_fold_gap",
        "xgb_fold_gap", "winner_cv_gap", "holdout_gap"}
    pairs = next(line for line in earlier if line.get("phase") == "pairs")
    sampled = [p["family"] for p in pairs["pairs"]]
    assert sampled.count("rf") >= 3 and "xgb" in sampled and "lr" in sampled
    control = next(line for line in earlier if line.get("phase") == "control")
    assert control["correct"] is False
    assert [k for k, v in control["compared"].items() if v["value"] > v["limit"]]


def test_an_altered_answer_is_not_correct(small, capsys, monkeypatch):
    from transmogrifai_tpu.impl.tuning import validators

    sweep = validators.OpValidator._sweep

    def altered(self, candidates, X, y, train_w, val_mask, summary):
        sweep(self, candidates, X, y, train_w, val_mask, summary)
        for r in summary.results:
            r.fold_metrics = [m + 0.02 for m in r.fold_metrics]
            r.metric_value += 0.02

    monkeypatch.setattr(validators.OpValidator, "_sweep", altered)
    rc, result, _ = drive(small, capsys)
    assert result["correct"] is False
    for name in ("lr_fold_gap", "rf_fold_gap", "xgb_fold_gap"):
        gap, limit = result["compared"][name]
        assert gap == pytest.approx(0.02, abs=4e-3) and gap > limit


def test_moved_bin_edges_are_not_correct(small, capsys, monkeypatch):
    from transmogrifai_tpu.ops import trees

    sketch = trees.sketch_edges

    def moved(X, n_bins, seed=0):  # the program bins by other edges
        return sketch(X, n_bins, seed=seed) + 0.3

    monkeypatch.setattr(trees, "sketch_edges", moved)
    rc, result, _ = drive(small, capsys)
    assert result["correct"] is False
    over = [k for k, (v, lim) in result["compared"].items() if v > lim]
    assert set(over) & {"rf_fold_gap", "xgb_fold_gap"}, result["compared"]


def test_rehearsal_walks_the_cell(small, capsys):
    rc, result, earlier = drive(small, capsys, rehearse_rows=1500)
    assert rc == 1 and result["rehearsal"] is True
    assert result["correct"] is False and result["metrics"] == {}
    assert result["attempted"] == 1 and result["failed"] == 0
    assert "rf_fold_gap" in result["compared"]
    window = next(line for line in earlier if line.get("phase") == "window")
    assert window["counts"]["sweep_launches"] >= 1 and window["failure"] is None
