"""The benchmark's files agree with ``BENCHMARK.json``, later cells arrive
as files only, and the yardstick's arithmetic (required operations, window,
trace reduction, peaks) gives known answers.  CPU only, no training."""
import argparse
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH_DIR = os.path.join(ROOT, "benchmarks")

from benchmarks import ops_count, run as bench_run, trace_reduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def _names():
    out = [m["name"] for m in ALL_METRICS] + CELLS
    for c in BENCH["configs"]:
        out += [c["name"]] + list(c["reduced"])
    for w in BENCH["workloads"]:
        out += [w["config"], w["traffic"]]
    return sorted(set(out))


@pytest.mark.parametrize("name", _names())
def test_name_uses_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "layer", "moves", "workloads"}
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_names_files_that_exist(cell, bench):
    """Each committed cell, also where a later PR has appended to the lists
    (``conftest.appended``): what it reports and finds is the same."""
    assert cell in bench["workloads"]
    workload = bench_run.load_json(
        os.path.join(BENCH_DIR, "workloads", cell["name"] + ".json"))
    assert workload["config"] == cell["config"]
    assert workload["chips"] == cell["chips"]
    assert workload["traffic"]["name"] == cell["traffic"]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = bench_run.load_json(os.path.join(ROOT, cfg_entry["file"]))
    assert all(k in cfg for k in cfg_entry["reduced"])
    assert cfg["reduced"] == cfg_entry["reduced"]
    assert callable(bench_run.load_module("tables", cfg["table"]["maker"]).make)
    entry = bench_run.load_module("entries", workload["entry"])
    assert all(hasattr(entry, f) for f in
               ("setup", "step", "work", "answers", "shapes", "rehearsal_config"))
    e2e, layers = bench_run.metrics_for(bench, cell["name"])
    names = [m["name"] for m in e2e]
    assert "setup_s" in names and len(names) >= 2 and layers
    for kind, group in (("end_to_end", e2e), ("layers", layers)):
        for m in group:
            assert callable(bench_run.load_module(kind, m["name"]).read)
    check = bench_run.load_check(workload, cell["name"])
    assert callable(bench_run.load_module("references", check["reference"]).numbers)
    assert all(v is not None for v in check["limits"].values())


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_moves_is_reported_by_each_of_its_cells(name, bench):
    (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
    moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
    cells = [c["name"] for c in bench["workloads"]]
    for cell in metric.get("workloads", cells):
        assert cell in moved.get("workloads", cells)


def test_what_a_later_pr_appends_is_found_and_moves_nothing(later_pr):
    """``conftest.appended`` does what the driver lets a program PR do: every
    list of the committed file is the head of its list in the copy; the later
    cell reports ``fits_per_s`` and every reader whose list it joined, and
    its own; the cells that were there report what they reported."""
    bench, cell, metric = later_pr
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for old, new in zip(BENCH[key], bench[key]):
            if "workloads" in old:
                head = new["workloads"][:len(old["workloads"])]
                assert dict(new, workloads=head) == old
                assert new["workloads"][len(head):] == [cell]
            else:
                assert new == old
    assert [len(bench[k]) - len(BENCH[k]) for k in
            ("configs", "workloads", "end_to_end", "per_layer")] == [1, 1, 0, 1]
    e2e, layers = bench_run.metrics_for(bench, cell)
    assert [m["name"] for m in e2e] == ["fits_per_s", "setup_s"]
    joined = [m["name"] for m in BENCH["per_layer"] if "workloads" in m]
    assert [m["name"] for m in layers] == joined + [metric]
    for name in CELLS:
        assert [[m["name"] for m in g] for g in bench_run.metrics_for(bench, name)] \
            == [[m["name"] for m in g] for g in bench_run.metrics_for(BENCH, name)]


def test_a_metric_without_workloads_follows_what_it_moves():
    bench = {"end_to_end": [{"name": "rate", "workloads": ["a"]}, {"name": "setup_s"}],
             "per_layer": [{"name": "x", "moves": "rate"},
                           {"name": "y", "moves": "setup_s"},
                           {"name": "z", "moves": "rate", "workloads": ["b"]}]}
    names = lambda cell: [[m["name"] for m in g]  # noqa: E731
                          for g in bench_run.metrics_for(bench, cell)]
    assert names("a") == [["rate", "setup_s"], ["x", "y"]]
    assert names("b") == [["setup_s"], ["y", "z"]]


def test_a_cell_without_a_comparison_is_refused():
    with pytest.raises(ValueError, match="no cell runs without a comparison"):
        bench_run.load_check({"config": "x"}, "later.mix")


# ---- a later cell of another kind arrives as files only ----------------------
LATER_FILES = {
    "configs/later.json": json.dumps(
        {"table": {"maker": "later_rows"}, "rows": 40, "reduced": []}),
    "workloads/later.mix.json": json.dumps(
        {"config": "later", "entry": "later_entry", "chips": 1,
         "traffic": {"name": "mix", "batch": 8},
         "correct": {"reference": "later_reference", "limits": {"sum_gap": 0}}}),
    "tables/later_rows.py":
        "import numpy as np\n"
        "def make(cfg, seed):\n"
        "    return {'v': np.random.default_rng(seed).integers(0, 9, cfg['rows'])}\n",
    "entries/later_entry.py":
        "def rehearsal_config(cfg, rows): return dict(cfg, rows=rows)\n"
        "def setup(ctx): ctx.state['sums'] = []\n"
        "def step(ctx):\n"
        "    ctx.state['sums'].append(int(ctx.cols['v'].sum()) + ctx.cfg.get('fault', 0))\n"
        "    ctx.count('later_steps', 1)\n"
        "def work(ctx): return float(len(ctx.cols['v']))\n"
        "def answers(ctx): return {'sum': ctx.state['sums'][-1]}\n"
        "def shapes(ctx): return {'rows': len(ctx.cols['v'])}\n",
    "references/later_reference.py":
        "def numbers(answers, cols, cfg, check, seed, control=False, emit=None):\n"
        "    truth = sum(int(x) for x in cols['v'])\n"
        "    return {'sum_gap': float(abs(answers['sum'] - truth))}, None\n",
    "end_to_end/later_rate.py":
        "def read(r): return r.work_per_step * r.n_steps / r.window_s\n",
    "layers/later_layer.count.py":
        "def read(r): return r.counts['later_steps'] / r.n_steps\n",
}


def _later(tmp_path):
    bench_dir = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench_dir) for p in fs}
    for rel, text in LATER_FILES.items():
        os.makedirs(os.path.dirname(os.path.join(bench_dir, rel)), exist_ok=True)
        with open(os.path.join(bench_dir, rel), "w") as f:
            f.write(text)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "later", "source": "x", "why": "x",
                             "file": "benchmarks/configs/later.json", "reduced": []})
    bench["workloads"].append({"name": "later.mix", "config": "later",
                               "traffic": "mix", "chips": 1, "why": "x"})
    bench["end_to_end"].append({"name": "later_rate", "unit": "rows/s",
                                "better": "higher", "bound": 0.03,
                                "source": "host_clock", "workloads": ["later.mix"]})
    bench["per_layer"].append({"name": "later_layer.count", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "later", "moves": "later_rate",
                               "workloads": ["later.mix"]})
    path = str(tmp_path / "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return bench, bench_dir, path, before


def _drive(bench_dir, path, capsys, **over):
    args = argparse.Namespace(workload="later.mix", seed=2147484001, seconds=0.05,
                              trace=0, rehearse_rows=None, control=0)
    vars(args).update(over)
    capsys.readouterr()
    rc = bench_run.run(args, bench_dir=bench_dir, benchmark_json=path,
                       look_for_chip=False)
    out = capsys.readouterr().out.splitlines()
    return rc, json.loads(out[-1])


def test_a_later_cell_arrives_as_files_only(tmp_path, capsys):
    """A configuration with its own table, a cell, an entry, a reference and
    readers — none of this repo's kind — dropped beside the others are found
    by name and RUN, set-up to ``correct``; no existing file is edited."""
    bench, bench_dir, path, before = _later(tmp_path)
    rc, result = _drive(bench_dir, path, capsys)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"later_rate", "setup_s"}
    assert result["metrics"]["later_rate"]["value"] > 0
    assert result["compared"] == {"sum_gap": [0.0, 0]}
    # the same cell's rehearsal ends not correct, exit 1, with no metric
    rc, result = _drive(bench_dir, path, capsys, rehearse_rows=5)
    assert rc == 1 and result["correct"] is False and result["metrics"] == {}
    # the cells that were there report what they reported
    for name in CELLS:
        assert bench_run.metrics_for(bench, name) == bench_run.metrics_for(BENCH, name)
    assert all(open(p, "rb").read() == text for p, text in before.items())


def test_a_later_cell_with_a_wrong_answer_is_not_correct(tmp_path, capsys):
    _, bench_dir, path, _ = _later(tmp_path)
    cfg_path = os.path.join(bench_dir, "configs", "later.json")
    cfg = bench_run.load_json(cfg_path)
    with open(cfg_path, "w") as f:
        json.dump(dict(cfg, fault=1), f)
    rc, result = _drive(bench_dir, path, capsys)
    assert rc == 0 and result["correct"] is False
    assert result["compared"] == {"sum_gap": [1.0, 0]}


# ---- required operations, by hand -------------------------------------------
SMALL = {"folds": 2, "rows": 100,
         "grid": {"lr": {"fixed": {"max_iter": 10}, "points": [[0.1, 0.5]] * 3},
                  "svc": {"fixed": {"max_iter": 5}, "points": [[0.1]]},
                  "mlp": {"fixed": {"hidden_layers": [4], "max_iter": 2},
                          "points": [[0.1, 1]] * 2}}}


def test_ops_count_against_a_hand_worked_shape():
    # 40 sweep rows, width 3, 2 folds: 20 train + 20 validation rows per fit
    lr_fit = 10 * 4 * 20 * 4 + 2 * 20 * 4            # 3360
    svc_fit = 5 * 4 * 20 * 4 + 2 * 20 * 4            # 1760
    mlp_fit = 2 * (4 * 20 * 3 * 4 + 6 * 20 * 4 * 2) + 2 * 20 * (3 * 4 + 4 * 2)  # 4640
    cv = 2 * (3 * lr_fit + 1 * svc_fit + 2 * mlp_fit)
    refit = 10 * 4 * 40 * 4 + 2 * (40 + 7) * 4       # winner lr, 7 holdout rows
    got = ops_count.sweep_step(SMALL, 40, 3, "lr", 7)
    assert got["flops"] == cv + refit
    assert got["cv_fits"] == 12
    passes = (10 + 1) + (5 + 1) + (2 + 1) + (10 + 1)
    assert got["bytes"] == passes * 2 * 40 * 3 + 2 * 7 * 3
    with pytest.raises(KeyError, match="no formula"):
        ops_count.sweep_step({"folds": 2, "grid": {"trees": {}}}, 40, 3)


def test_roofline_names_the_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    r = ops_count.roofline_seconds({"flops": 1000.0, "bytes": 50.0}, peaks)
    assert r == {"seconds": 10.0, "bound": "flops", "flops_s": 10.0, "bytes_s": 5.0}
    assert ops_count.roofline_seconds({"flops": 10.0, "bytes": 50.0}, peaks)["bound"] == "bytes"


def test_peaks_refuse_an_unknown_device_kind():
    path = os.path.join(BENCH_DIR, "peaks.json")
    assert ops_count.load_peaks(path, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        ops_count.load_peaks(path, "TPU v9 imaginary")


# ---- window arithmetic -------------------------------------------------------
def _window(steps, window_s, work_per_step=320.0):
    r = bench_run.Run({}, None, {}, None, 51)
    r.steps, r.window_s, r.work_per_step = steps, window_s, work_per_step
    return r


def test_a_stall_in_the_window_lowers_the_rate():
    rate = bench_run.load_module("end_to_end", "fits_per_s").read
    steady = [(0.0, 10.0), (10.0, 20.0), (20.0, 30.0)]
    stalled = [(0.0, 10.0), (10.0, 20.0), (35.0, 45.0)]  # 15 s between steps
    r0, r1 = rate(_window(steady, 30.0)), rate(_window(stalled, 45.0))
    assert r0 == 32.0 and r1 == pytest.approx(320.0 * 3 / 45.0) and r1 < r0
    with pytest.raises(ZeroDivisionError):
        rate(_window([], 0.0))


def test_a_step_starts_only_while_the_window_is_open():
    assert bench_run.keep_going(0.0, 51) and bench_run.keep_going(50.9, 51)
    assert not bench_run.keep_going(51.0, 51)


# ---- trace arithmetic on synthetic intervals ---------------------------------
OPS = [("fusion.1", 1.0, 3.0), ("fusion.2", 2.0, 4.0), ("while.3", 6.0, 7.0),
       ("fusion.1", 9.5, 12.0)]
SPANS = [("bench.step", 0.0, 10.0), ("bench.inner.pull", 4.0, 6.0)]


def test_busy_union_idle_gaps_and_names():
    w = (0.0, 10.0)
    assert trace_reduce.busy_seconds(OPS, w) == pytest.approx(3.0 + 1.0 + 0.5)
    assert trace_reduce.gaps(OPS, w) == [(7.0, 9.5), (4.0, 6.0), (0.0, 1.0)]
    assert trace_reduce.name_gap((4.0, 6.0), SPANS) == "bench.inner.pull"
    assert trace_reduce.name_gap((20.0, 21.0), SPANS) == "(no span)"
    assert trace_reduce.top_ops(OPS, w, k=2) == [["fusion.1", 2.5], ["fusion.2", 2.0]]
    table = dict(map(tuple, trace_reduce.idle_gap_table(OPS, w, SPANS)))
    assert table == {"bench.step": pytest.approx(3.5), "bench.inner.pull": 2.0}


def test_the_programs_spans_name_the_gaps_and_move_no_number():
    """One trace read both ways: with the benchmark's spans alone (what
    ``SPAN_PREFIX`` kept before PR 36) and with the program's beside them.
    The window is still found by ``bench.step``; busy seconds, window and the
    idle share are the same to the digit; only the gaps' names differ."""
    program = [("stage.fit", 0.0, 10.0), ("selector.fit", 0.0, 9.9),
               ("selector.gather", 0.1, 0.9), ("sweep.plan", 0.9, 1.0),
               ("sweep.quantize", 0.92, 0.99), ("devcache.upload", 0.95, 0.98),
               ("sweep.gather", 1.0, 7.0), ("selector.evaluate", 7.2, 9.4),
               ("other.library", 0.0, 10.0)]
    assert all(n.startswith(trace_reduce.SPAN_PREFIX) for n, _, _ in program[:-1])
    assert not program[-1][0].startswith(trace_reduce.SPAN_PREFIX)
    kept = [s for s in SPANS + program if s[0].startswith(trace_reduce.SPAN_PREFIX)]
    read = lambda spans: {"devices": {0: {"ops": OPS, "modules": []}},  # noqa: E731
                          "host_spans": spans, "inventory": {}}
    before = trace_reduce.summarize(read(SPANS), "bench.step")
    after = trace_reduce.summarize(read(kept), "bench.step")
    for key in ("window", "window_s", "busy_s"):
        assert after[key] == before[key], key
    assert after["breakdown"]["device_ops"] == before["breakdown"]["device_ops"]
    idle = bench_run.load_module("layers", "device_idle_pct.sweep").read
    ns = lambda s: argparse.Namespace(trace=s)  # noqa: E731
    assert idle(ns(after)) == idle(ns(before)) == pytest.approx(55.0)
    named = lambda s: dict(map(tuple, s["breakdown"]["idle_gaps"]))  # noqa: E731
    assert named(before) == {"bench.step": pytest.approx(3.5), "bench.inner.pull": 2.0}
    # gaps [7, 9.5], [4, 6], [0, 1]: each to the innermost span over half of it
    assert named(after) == {"selector.evaluate": 2.5, "bench.inner.pull": 2.0,
                            "selector.gather": 1.0}
    assert sum(named(after).values()) == pytest.approx(sum(named(before).values()))


def test_gaps_between_span_edges_are_named_as_one_by_one():
    """``idle_gap_table`` names a stretch between two span edges once; the
    answer is ``name_gap``'s, gap by gap, on 2,000 ops under nested,
    overlapping and abutting spans (edges on op boundaries among them)."""
    rng = __import__("random").Random(36)
    t, ops = 0.0, []
    for k in range(2000):
        d, g = rng.choice((0.25, 0.5, 1.0)), rng.choice((0.0, 0.125, 0.5, 2.0))
        ops.append((f"op.{k % 7}", t, t + d))
        t += d + g
    w = (-1.0, t + 1.0)
    cut = lambda: rng.choice(ops)[rng.choice((1, 2))] + rng.choice((0.0, 0.0625))  # noqa: E731
    spans = [("bench.step", w[0], w[1]), ("selector.fit", 0.0, t)]
    for k in range(60):
        a, b = sorted((cut(), cut()))
        spans.append((f"sweep.s{k}", a, b + rng.choice((0.0, 40.0))))
    want = {}
    for g in trace_reduce.gaps(ops, w):
        n = trace_reduce.name_gap(g, spans)
        want[n] = want.get(n, 0.0) + g[1] - g[0]
    got = dict(map(tuple, trace_reduce.idle_gap_table(ops, w, spans, k=1000)))
    assert len(got) > 20 and got == pytest.approx(want)


def test_program_seconds_and_summary():
    modules = [("jit__run_scores(123)", 1.0, 4.0), ("jit__run_metrics(9)", 6.0, 7.0),
               ("jit_other(1)", 9.5, 12.0)]
    w = (0.0, 10.0)
    assert trace_reduce.program_seconds(modules, w, r"jit__run") == 4.0
    assert trace_reduce.program_seconds(modules, w, r"nothing") is None
    read = {"devices": {0: {"ops": OPS, "modules": modules}},
            "host_spans": SPANS, "inventory": {}}
    s = trace_reduce.summarize(read, "bench.step")
    assert s["window_s"] == 10.0 and s["busy_s"] == pytest.approx(4.5)
    assert len(s["breakdown"]["device_ops"]) <= 10
    with pytest.raises(ValueError):
        trace_reduce.summarize({"devices": {}, "host_spans": [], "inventory": {}},
                               "bench.step")
