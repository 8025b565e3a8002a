"""``correct`` of the ``scale-500-multiclass.sweep`` cell comes out true for a
sound run and false for the control and for two faults.

The cell's own files (configuration, workload, entry, table maker, reference,
limits) go through ``benchmarks/run.py``'s ``run()`` — everything but the look
for a chip — on a table of 60 Real + 6 PickList x 4,000 rows, 10 classes at
the configuration's shares, with 3 trees a forest and a grid cut to 2 softmax
and 6 forest candidates (two of each depth).

- sound: the program as it stands;
- control: the plain reference in bfloat16, put in the program's place
  (``--control 1``);
- a forest trained on ONE channel under the 10-class label (a variance tree
  on the class index, its rounded mean leaf read as the class);
- a forest whose mean leaves out its last tree;
- one class's plane of the [F, C, n, k] score block zeroed before the metric
  pass.

``python tests/benchmarks/test_correct_control_multiclass.py <fault> [run.py's
arguments]`` runs the cell itself with a forest fault planted (``one_channel``,
``dropped_tree``): at the cell's size on the chip, where no forest of the grid
leaves the majority class, the Error gaps read 0 and ``rf_prob_gap`` alone has
to tell (``PERF.md`` section 2).
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

CELL, SMALL = "scale-500-multiclass.sweep", "small-multiclass.sweep"
COMPARED = {"vector_cells_differ", "winner_not_best", "softmax_fold_gap",
            "softmax_prob_gap", "rf_fold_gap", "rf_prob_gap", "holdout_gap",
            "holdout_prob_gap"}


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    """A copy of ``benchmarks/`` with the cell's configuration cut small."""
    tmp = tmp_path_factory.mktemp("bench_multiclass")
    bench_dir = str(tmp / "benchmarks")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = bench_run.load_json(
        os.path.join(bench_dir, "configs", "scale-500-multiclass.json"))
    cfg.update(rows=4000, max_training_sample=2400, n_real=60, n_picklist=6)
    cfg["sanity_checker"]["sample_upper_limit"] = 2000
    cfg["grid"]["lr"]["points"] = [[0.001, 0.1], [0.01, 0.5]]
    cfg["grid"]["rf"]["fixed"]["num_trees"] = 3
    cfg["grid"]["rf"]["points"] = [[d, g, 10] for d in (3, 6, 12)
                                   for g in (0.001, 0.01)]
    with open(os.path.join(bench_dir, "configs", "small-multiclass.json"), "w") as f:
        json.dump(cfg, f)
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    sweep = bench_run.load_json(os.path.join(bench_dir, "workloads", CELL + ".json"))
    sweep["config"] = "small-multiclass"
    sweep["correct"]["groups"]["lr"]["take"] = 1
    with open(os.path.join(bench_dir, "workloads", SMALL + ".json"), "w") as f:
        json.dump(sweep, f)
    bench["workloads"].append({"name": SMALL, "config": "small-multiclass",
                               "traffic": "sweep", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(SMALL)
    path = str(tmp / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench_dir, path


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    """A one-chip cell: the sweep goes through the single-device launcher, as
    on the chip, and not the eight-way partition that the tests' virtual CPU
    devices would give it."""
    from transmogrifai_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "auto_mesh", lambda *a, **kw: None)


def drive(small, capsys, control=0):
    bench_dir, path = small
    args = argparse.Namespace(workload=SMALL, seed=2147503301, seconds=0.01,
                              trace=0, rehearse_rows=None, control=control)
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
    assert set(result["compared"]) == COMPARED
    pairs = next(line for line in earlier if line.get("phase") == "pairs")
    sampled = [(p["family"], p["hp"][0]) for p in pairs["pairs"]]
    assert {("rf", 3), ("rf", 6), ("rf", 12)} <= set(sampled)
    assert "lr" in [fam for fam, _ in sampled]
    window = next(line for line in earlier if line.get("phase") == "window")
    assert window["counts"]["sweep_launches"] == 1 and window["failure"] is None
    control = next(line for line in earlier if line.get("phase") == "control")
    assert control["correct"] is False
    assert [k for k, v in control["compared"].items() if v["value"] > v["limit"]]


def plant_one_channel(setattr_):
    """A forest trained on one channel: the plan states c = 1 (another spec,
    so the sweep is traced anew) and the group's rounded mean leaf is read as
    the class."""
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.impl import sweep_fragments
    from transmogrifai_tpu.ops import sweep

    fragment, grown = sweep_fragments._forest_fragment, sweep._forest_group_scores

    def one_channel_plan(*args, n_classes=1, **kw):
        return fragment(*args, n_classes=1, **kw)

    def one_channel(group, xbs, y, train_w, blob, out_c, rs=None):
        assert out_c == 1
        mean = grown(group, xbs, y, train_w, blob, 1, rs=rs)[..., 0]
        return jax.nn.one_hot(jnp.round(mean).astype(jnp.int32), 10,
                              dtype=jnp.float32)

    setattr_(sweep_fragments, "_forest_fragment", one_channel_plan)
    setattr_(sweep, "_forest_group_scores", one_channel)


def plant_dropped_tree(setattr_):
    """Every forest's last tree gets no bootstrap weight: it grows nothing,
    reads 0 at every leaf, and the mean still divides by all the trees."""
    from transmogrifai_tpu.ops import trees

    drawn = trees.bootstrap_weights

    def all_but_the_last(*args, **kw):
        return drawn(*args, **kw).at[-1].set(0.0)

    setattr_(trees, "bootstrap_weights", all_but_the_last)


FAULTS = {"one_channel": plant_one_channel, "dropped_tree": plant_dropped_tree}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_forest_is_not_correct(small, capsys, monkeypatch, fault):
    import jax

    FAULTS[fault](monkeypatch.setattr)
    jax.clear_caches()      # the sound run's trace is not the faulty one's
    try:
        rc, result, _ = drive(small, capsys)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert result["correct"] is False and "failure" not in result
    gap, limit = result["compared"]["rf_prob_gap"]
    assert gap > 100 * limit


def test_a_zeroed_class_plane_is_not_correct(small, capsys, monkeypatch):
    import functools

    import jax

    from transmogrifai_tpu.ops import sweep

    @functools.partial(jax.jit, static_argnames=("spec",))
    def zeroed(spec, y, scores, val_w):
        return sweep._metrics_of(spec, y, scores.at[..., 0].set(0.0), val_w)

    # the two-program path hands the score block from one program to the next
    monkeypatch.setattr(sweep, "SPLIT_METRICS_ELEMS", 0)
    monkeypatch.setattr(sweep, "_run_metrics", zeroed)
    rc, result, _ = drive(small, capsys)
    assert result["correct"] is False and "failure" not in result
    over = [k for k, (v, lim) in result["compared"].items() if v > lim]
    assert set(over) & {"softmax_fold_gap", "rf_fold_gap"}, result["compared"]
    # the block the sweep kept is the one its training program made: whole
    assert not set(over) & {"softmax_prob_gap", "rf_prob_gap"}


if __name__ == "__main__":
    plant = FAULTS[sys.argv.pop(1)]
    plant(setattr)
    sys.exit(bench_run.main())
