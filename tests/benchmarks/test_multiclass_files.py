"""The files ``scale-500-multiclass`` brings, each on a case of known answer:
the table maker beside ``wide_tabular``, the required-operation counts of
``multiclass_ops_count`` on a hand-worked shape and beside
``trees_ops_count`` at one channel, the new readers on a trace without their
scopes, the entry's ``requires`` check."""
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import multiclass_ops_count as mc  # noqa: E402
from benchmarks import program, program_spans as ps  # noqa: E402
from benchmarks import run as bench_run, trace_reduce, trees_ops_count  # noqa: E402
from benchmarks.tables import wide_tabular, wide_tabular_multiclass  # noqa: E402

BENCH = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELL = "scale-500-multiclass.sweep"
#: the cells the benchmark had before it, in the order of ``workloads``
OLDER = ["scale-500.sweep", "scale-500-trees.sweep"]
CFG = bench_run.load_json(os.path.join(
    ROOT, "benchmarks", "configs", "scale-500-multiclass.json"))
NEW = ("softmax_scores_device_s", "multiclass_metrics_device_s",
       "score_block_bytes.multiclass", "mc_tree_hist_roofline",
       "mc_sweep_roofline", "mc_step_mfu")
APPENDED = ("selector_fit_s.sweep", "sweep_launches.sweep",
            "device_idle_pct.sweep", "forest_scores_device_s",
            "tree_hist_device_s", "tree_split_device_s",
            "sweep_quantize_idle_s.trees", "tree_level_builds.trees")


# ---- the table ---------------------------------------------------------------
@pytest.fixture(scope="module")
def small_cfg():
    return dict(CFG, rows=4000, n_real=52, n_picklist=11)


@pytest.mark.parametrize("seed", [0, 2147503301])
def test_predictors_are_wide_tabulars_bit_for_bit(small_cfg, seed):
    ours = wide_tabular_multiclass.make(small_cfg, seed)
    theirs = wide_tabular.make(small_cfg, seed)
    assert list(ours) == list(theirs)
    for name in ours:
        if name != wide_tabular.LABEL:
            assert np.array_equal(ours[name], theirs[name]), name
    assert wide_tabular_multiclass.features(small_cfg) == wide_tabular.features(small_cfg)


def test_class_shares_are_exact_and_the_seed_only_reorders(small_cfg):
    shares = small_cfg["table"]["class_shares"]
    want = np.diff(np.round(np.r_[0.0, shares] * 4000)).astype(int)
    a = wide_tabular_multiclass.make(small_cfg, 1)
    b = wide_tabular_multiclass.make(small_cfg, 2)
    for cols in (a, b):
        y = cols[wide_tabular.LABEL]
        assert y.dtype == np.float32 and set(np.unique(y)) == set(range(10))
        assert np.array_equal(np.bincount(y.astype(int)), want)
    assert want.min() == 60                          # the 1.5 % class
    # the same rows in another order: a row's label travels with its values
    key = lambda c: np.lexsort([c[f"num_{j}"] for j in (5, 4, 3, 0)])  # noqa: E731
    assert np.array_equal(a[wide_tabular.LABEL][key(a)], b[wide_tabular.LABEL][key(b)])
    assert not np.array_equal(a["num_0"], b["num_0"])


def test_label_is_the_rank_of_the_binary_labels_latent(small_cfg):
    """The binary label thresholds the same latent at 0: every class above
    the class that straddles 0 is all ones, every class below it all zeros."""
    y10 = wide_tabular_multiclass.make(small_cfg, 7)[wide_tabular.LABEL]
    y2 = wide_tabular.make(small_cfg, 7)[wide_tabular.LABEL]
    rate = np.array([y2[y10 == c].mean() for c in range(10)])
    mixed = np.flatnonzero((rate > 0) & (rate < 1))
    assert len(mixed) <= 1 and np.all(np.diff(rate) >= 0)
    assert rate[0] == 0.0 and rate[-1] == 1.0


# ---- required operations -----------------------------------------------------
def test_required_operations_of_a_hand_worked_shape():
    """Two softmax candidates (5 iterations) and one forest candidate (2
    trees, depth 3, frontier 4, 4 bins) on 30 sweep rows x 16 features, 3
    folds, 5 classes; an RF winner."""
    cfg = {"folds": 3, "classes": 5, "assumed_numbers": {"max_frontier": 4},
           "grid": {"lr": {"fixed": {"max_iter": 5}, "points": [[0.1, 0.5], [0.2, 0.5]]},
                    "rf": {"fixed": {"num_trees": 2, "max_bins": 4},
                           "keys": ["max_depth"], "points": [[3]]}}}
    w = mc.sweep_step(cfg, 30, 16, winner_family="rf", holdout_rows=6)
    n_tr, n_val, kept, planes = 20, 10, 4, 6             # sqrt(16); 5 + 1
    cells = (1 + 2 + 4) * kept * 4                       # open nodes x kept x bins
    assert w["hist_flops"] == 2 * 3 * planes * kept * (3 * n_tr + 30)
    assert w["split_flops"] == 6 * planes * 2 * cells * (3 + 1)
    lr = 2 * 3 * (5 * 4 * n_tr * 17 * 5 + 2 * n_val * 17 * 5)
    assert w["lr_flops"] == lr
    assert w["flops"] == w["hist_flops"] + w["split_flops"] + lr
    streams = 3 * 30 * 16 + 3 * (30 + 6) * 16
    writes = planes * 4 * 2 * cells * (3 + 1)
    assert w["hist_bytes"] == streams + writes
    assert w["bytes"] == w["hist_bytes"] + (5 + 1) * 2 * 30 * 16
    assert w["cv_fits"] == 9
    # an LR winner: the refit's iterations and its scoring pass instead
    w_lr = mc.sweep_step(cfg, 30, 16, winner_family="lr", holdout_rows=6)
    assert w_lr["lr_flops"] == lr + 5 * 4 * 30 * 17 * 5 + 2 * 36 * 17 * 5
    assert w_lr["hist_flops"] == 2 * 3 * planes * kept * 3 * n_tr


@pytest.mark.parametrize("depth", [3, 6, 12])
def test_one_channel_is_trees_ops_counts_tree(depth):
    s = {"trees": 50, "depth": depth, "kept": 28, "bins": 32, "frontier": 256,
         "chained": False}
    assert mc.tree_fit(s, 21845, 1) == trees_ops_count.tree_fit(s, 21845)
    assert mc.scan_flops(1) == trees_ops_count.SCAN_FLOPS
    ten = mc.tree_fit(s, 21845, 10)
    assert ten["hist_flops"] == 5.5 * mc.tree_fit(s, 21845, 1)["hist_flops"]
    assert ten["hist_bytes"] == 5.5 * mc.tree_fit(s, 21845, 1)["hist_bytes"]


def test_the_cells_step_counts_a_channel_term():
    w = mc.sweep_step(CFG, 32768, 760, "lr", 30000)
    assert w["cv_fits"] == 78
    # softmax LR: 24 fits x 50 iterations x 4 n (d+1) k, its scoring, a refit
    assert w["lr_flops"] == pytest.approx(
        24 * (50 * 4 * 21845 * 761 * 10 + 2 * 10923 * 761 * 10)
        + 50 * 4 * 32768 * 761 * 10 + 2 * 62768 * 761 * 10)
    binary = trees_ops_count.sweep_step(
        dict(CFG, grid={"rf": CFG["grid"]["rf"]}), 32768, 760, "rf", 0, refit=False)
    assert w["hist_flops"] == pytest.approx(5.5 * binary["hist_flops"])


# ---- readers -----------------------------------------------------------------
WINDOW = (0.0, 10.0)


def _run_like(ops, monkeypatch):
    r = types.SimpleNamespace(
        trace={"window": WINDOW, "modules": []}, cfg=CFG, n_steps=1,
        window_s=10.0, peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        shapes={"sweep_rows": 32768, "width": 760, "holdout_rows": 30000,
                "winner_family": "lr"})
    monkeypatch.setattr(ps, "read_xplane", lambda path: {
        "spans": [("bench.step", 0.0, 10.0, {})], "ops": {0: ops}})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.xplane.pb")
    return r


def test_new_readers_are_silent_on_a_trace_without_their_scopes(monkeypatch):
    """The parent's trace of another cell: no softmax, no multiclass metric
    pass, no tree scope, no ``_run`` module, no counter."""
    r = _run_like([("jit(_run)/scores.fista/dot_general", 1.0, 2.0),
                   ("jit(_run)/metrics.binary/sort", 2.0, 3.0)], monkeypatch)
    monkeypatch.setattr(program, "sweep_record", lambda: {"launches": []})
    silent = [m for m in NEW if m != "mc_step_mfu"]
    for m in silent:
        assert bench_run.load_module("layers", m).read(r) is None, m


def test_new_readers_read_their_scopes_and_stay_under_their_roofs(monkeypatch):
    ops = [("jit(_run_scores)/scores.softmax/while/body/dot_general", 0.0, 0.5),
           ("jit(_run_scores)/scores.forest/while/body/trees.hist/dot_general", 1.0, 5.0),
           ("jit(_run_metrics)/metrics.multiclass/vmap(argmax)", 6.0, 6.25)]
    r = _run_like(ops, monkeypatch)
    r.trace["modules"] = [("jit__run_scores(1)", 0.0, 5.5), ("jit__run_metrics(2)", 6.0, 6.3)]
    monkeypatch.setattr(program, "sweep_record",
                        lambda: {"score_block_bytes": 4 * 3 * 26 * 32768 * 10})
    read = {m: bench_run.load_module("layers", m).read(r) for m in NEW}
    assert read["softmax_scores_device_s"] == pytest.approx(0.5)
    assert read["multiclass_metrics_device_s"] == pytest.approx(0.25)
    assert read["score_block_bytes.multiclass"] == 102_236_160
    work = mc.sweep_step(CFG, 32768, 760, "lr", 30000)
    need = max(work["hist_flops"] / 197e12, work["hist_bytes"] / 819e9)
    assert read["mc_tree_hist_roofline"] == pytest.approx(100 * need / 4.0)
    whole = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert read["mc_sweep_roofline"] == pytest.approx(100 * whole / 5.8)
    assert read["mc_step_mfu"] == pytest.approx(100 * work["flops"] / 10.0 / 197e12)
    assert all(0 < read[m] < 100 for m in NEW[3:])


@pytest.mark.parametrize("name", NEW)
def test_new_entry_lists_this_cell_alone(name, bench):
    """Alone of the cells there were: a later multiclass cell joins after."""
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"][0] == CELL and entry["moves"] == "fits_per_s"
    assert not set(entry["workloads"]) & set(OLDER)
    assert callable(bench_run.load_module("layers", name).read)


@pytest.mark.parametrize("name", APPENDED + ("fits_per_s",))
def test_generic_reader_has_the_cell_appended_last(name, bench):
    """Last when PR 33 appended it: on the list, after none but the two cells
    older than it, those in their order; what follows it is a later PR's."""
    (entry,) = [m for m in bench["per_layer"] + bench["end_to_end"]
                if m["name"] == name]
    before = entry["workloads"][:entry["workloads"].index(CELL)]
    assert before == [c for c in OLDER if c in before]


# ---- the configuration and its entry -----------------------------------------
def test_configuration_states_its_source_and_cuts():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CFG["name"]]
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["reduced"] == CFG["reduced"] == ["rows", "max_training_sample"]
    assert CFG["published"] == {"rows": 10_000_000, "max_training_sample": 1_000_000}
    assert sum(len(g["points"]) for g in CFG["grid"].values()) \
        == CFG["expected_candidates"] == 26
    assert len(CFG["table"]["class_shares"]) == CFG["classes"] == 10
    binary = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "scale-500-trees.json"))
    for key in ("lr", "rf"):                             # the same grids, as data
        assert CFG["grid"][key] == binary["grid"][key]
    for key in ("n_real", "n_picklist", "picklist_categories", "transmogrifier",
                "sanity_checker", "rows", "max_training_sample"):
        assert CFG[key] == binary[key], key
    assert CFG["table"]["draw_seed"] == binary["table"]["draw_seed"]


def test_entry_names_what_a_program_lacks():
    entry = bench_run.load_module("entries", "selector_fit_multiclass")
    assert entry.missing(CFG["requires"]) == []
    lacks = ["transmogrifai_tpu.impl.tuning.splitters:DataCutter(no_such_parameter)",
             "transmogrifai_tpu.ops.trees:no_such_function",
             "transmogrifai_tpu.no_such_module:x"]
    assert entry.missing(CFG["requires"] + lacks) == lacks
    ctx = types.SimpleNamespace(cfg=dict(CFG, requires=lacks))
    with pytest.raises(SystemExit, match="lacks"):
        entry.setup(ctx)
