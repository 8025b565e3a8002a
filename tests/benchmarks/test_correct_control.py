"""``correct`` comes out true for a sound run and false for the control and
for each fault the cell can have.

A small configuration (60 Real + 6 PickList, 4,000 rows) goes through
``benchmarks/run.py``'s own ``run()`` — everything but the look for a chip —
under the cells' own limits:

- sound: the program as it stands;
- control: the plain reference computed in bfloat16, put in the program's
  place (the line ``--control 1`` prints);
- an answer altered where it is produced (every fold metric + 0.02);
- a step served from a memo (the sweep's checkpoint store switched on);
- the vector altered before the sweep reads it.
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


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A copy of ``benchmarks/`` with one small configuration and the sweep
    cell on it, as a later PR would add them, holding the real cell's
    comparison and limits."""
    tmp = tmp_path_factory.mktemp("bench")
    bench_dir = str(tmp / "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = bench_run.load_json(os.path.join(bench_dir, "configs", "scale-500.json"))
    cfg.update(rows=4000, max_training_sample=2400, n_real=60, n_picklist=6)
    cfg["sanity_checker"]["sample_upper_limit"] = 2000
    with open(os.path.join(bench_dir, "configs", "small.json"), "w") as f:
        json.dump(cfg, f)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    sweep = bench_run.load_json(
        os.path.join(bench_dir, "workloads", "scale-500.sweep.json"))
    sweep["config"] = "small"
    with open(os.path.join(bench_dir, "workloads", "small.sweep.json"), "w") as f:
        json.dump(sweep, f)
    bench["workloads"].append({"name": "small.sweep", "config": "small",
                               "traffic": "sweep", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "scale-500.sweep" in m.get("workloads", []):
            m["workloads"].append("small.sweep")
    path = str(tmp / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench_dir, path


def drive(small, capsys, cell, control=0):
    """One run without the look for a chip: (result line, earlier lines)."""
    bench_dir, path = small
    args = argparse.Namespace(workload=cell, seed=11, seconds=0.01, trace=0,
                              rehearse_rows=None, control=control)
    capsys.readouterr()
    rc = bench_run.run(args, bench_dir=bench_dir, benchmark_json=path,
                       look_for_chip=False)
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith("{")]
    assert rc == 0
    return lines[-1], lines[:-1]


def test_sound_run_is_correct_and_the_control_is_not(small, capsys):
    result, earlier = drive(small, capsys, "small.sweep", control=1)
    assert result["correct"] is True, result
    assert result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"fits_per_s", "setup_s"}
    assert result["compared"]["vector_cells_differ"] == [0.0, 0]
    assert list(result)[-1] == "compared"
    control = next(line for line in earlier if line.get("phase") == "control")
    assert control["correct"] is False
    over = [k for k, v in control["compared"].items() if v["value"] > v["limit"]]
    assert over, control


def test_an_altered_answer_is_not_correct(small, capsys, monkeypatch):
    from transmogrifai_tpu.impl.tuning import validators

    sweep = validators.OpValidator._sweep

    def altered(self, candidates, X, y, train_w, val_mask, summary):
        sweep(self, candidates, X, y, train_w, val_mask, summary)
        for r in summary.results:
            r.fold_metrics = [m + 0.02 for m in r.fold_metrics]
            r.metric_value += 0.02

    monkeypatch.setattr(validators.OpValidator, "_sweep", altered)
    result, _ = drive(small, capsys, "small.sweep")
    assert result["correct"] is False
    gap, limit = result["compared"]["lr_fold_gap"]
    assert gap == pytest.approx(0.02, abs=2e-3) and gap > limit


def test_a_step_served_from_a_memo_is_not_correct(small, capsys, monkeypatch,
                                                  tmp_path):
    from transmogrifai_tpu.parallel import mesh

    # one device: the single-launch path is the one with the whole-launch memo
    monkeypatch.setattr(mesh, "auto_mesh", lambda: None)
    monkeypatch.setenv("TMOG_CHECKPOINT_DIR", str(tmp_path / "ckpt"))
    result, _ = drive(small, capsys, "small.sweep")
    assert result["correct"] is False and result["failed"] == 1
    assert "checkpoint" in result["failure"]
    assert result["metrics"] == {}


def test_an_altered_vector_is_not_correct(small, capsys, monkeypatch):
    from benchmarks import program

    to_dataset = program.to_dataset

    def altered(cols, table):  # the program reads a changed table
        changed = cols["num_7"].copy()
        changed[5] += 1.0
        return to_dataset(dict(cols, num_7=changed), table)

    monkeypatch.setattr(program, "to_dataset", altered)
    result, _ = drive(small, capsys, "small.sweep")
    assert result["correct"] is False
    assert result["compared"]["vector_cells_differ"][0] >= 1
