"""``benchmarks/host_spans.py`` and the six readers of the selector's host
half: known answers on synthetic intervals, how they sit inside the three
accepted idle readers, their silence on a program that writes none of the
spans, and their entries.  CPU only, no training."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH_DIR = os.path.join(ROOT, "benchmarks")

from benchmarks import host_spans, program_spans as ps, run as bench_run, trace_reduce  # noqa: E402

#: the readers this file covers, with the cells each lists first
NEW = {"row_gather_idle_s.sweep": "scale-500.sweep",
       "winner_io_idle_s.sweep": "scale-500.sweep",
       "host_metrics_idle_s.sweep": "scale-500.sweep",
       "uncached_h2d_bytes.sweep": "scale-500.sweep",
       "row_gather_gbps.sweep": "scale-500.sweep",
       "sweep_sketch_idle_s.trees": "scale-500-trees.sweep"}
OLD = ("sweep_feed_idle_s.sweep", "refit_eval_idle_s.sweep",
       "unattributed_idle_s.sweep")
IDLE = ("row_gather_idle_s.sweep", "winner_io_idle_s.sweep",
        "host_metrics_idle_s.sweep", "sweep_sketch_idle_s.trees")

WINDOW = (0.0, 20.0)
# the device is busy on [3, 4], [8, 9] and [19, 19.5]: gaps [0,3] [4,8] [9,19]
# [19.5,20]
OPS = [("jit(_bin_chunk)/x", 3.0, 4.0), ("jit(_run)/scores.fista/dot", 8.0, 9.0),
       ("jit(predict)/dot", 19.0, 19.5)]


def span(name, a, b, **stats):
    return (name, float(a), float(b), stats)


#: one fit as the program lays out its spans (GB = 1e9 bytes)
FIT = [
    span("bench.step", 0, 20), span("selector.fit", 0, 19.9),
    span("selector.split", 0.0, 0.2),
    span("selector.gather", 0.2, 1.2),
    span("selector.rows", 0.2, 1.0, rows=10, bytes=1e9, what="train"),
    span("sweep.plan", 1.2, 5.0),
    span("sweep.quantize", 1.3, 4.8),
    span("sweep.sketch", 1.3, 2.5, rows=10, width=4, bins=32),
    span("devcache.put", 2.6, 2.9, bytes=400, tag="bin"),
    span("sweep.dispatch", 7.0, 7.5),
    span("devcache.put", 7.1, 7.2, bytes=40, tag="fold_w"),
    span("sweep.gather", 7.5, 9.0),
    span("selector.refit", 9.0, 13.0),
    span("selector.rows", 9.0, 10.0, rows=10, bytes=2e9, what="refit"),
    span("selector.winner_fit", 10.0, 12.5),
    span("devcache.put", 10.0, 10.5, bytes=4000, tag="X"),
    span("selector.evaluate", 13.0, 19.5),
    span("selector.rows", 13.0, 14.0, rows=10, bytes=1e9, what="evaluate"),
    span("selector.winner_predict", 14.0, 14.5, what="train"),
    span("devcache.put", 14.0, 14.2, bytes=4000, tag="X"),
    span("selector.metrics", 14.5, 15.5, what="train"),
    span("selector.rows", 15.5, 16.0, rows=5, bytes=5e8, what="holdout"),
    span("selector.winner_predict", 16.0, 17.0, what="holdout"),
    span("selector.metrics", 17.0, 18.0, what="holdout"),
    span("devcache.put", 30.0, 31.0, bytes=10**9, tag="X"),  # a later step
]


def _run_like(spans, ops, monkeypatch):
    """A ``Run`` whose trace reads as ``spans`` and ``ops``."""
    r = types.SimpleNamespace(trace={"window": WINDOW})
    monkeypatch.setattr(ps, "read_xplane", lambda path: {"spans": spans,
                                                         "ops": {0: ops}})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.xplane.pb")
    return r


def _read(r, names):
    return {m: bench_run.load_module("layers", m).read(r) for m in names}


def test_readers_give_known_answers(monkeypatch, capsys):
    read = _read(_run_like(FIT, OPS, monkeypatch), list(NEW) + list(OLD))
    # the rows gathers fall in the gaps whole: 0.8 +
    # 1.0 + 1.0 + 0.5 s
    assert read["row_gather_idle_s.sweep"] == pytest.approx(3.3)
    # winner_fit [10, 12.5] with its put inside, and both predicts
    assert read["winner_io_idle_s.sweep"] == pytest.approx(2.5 + 0.5 + 1.0)
    assert read["host_metrics_idle_s.sweep"] == pytest.approx(2.0)
    # the sketch [1.3, 2.5] inside the quantize span
    assert read["sweep_sketch_idle_s.trees"] == pytest.approx(1.2)
    # the window's four puts; the later step's is outside
    assert read["uncached_h2d_bytes.sweep"] == 400 + 40 + 4000 + 4000
    # 4.5e9 bytes in 0.8 + 1.0 + 1.0 + 0.5 s of gathering
    assert read["row_gather_gbps.sweep"] == pytest.approx(4.5 / 3.3)
    # the accepted three: the feed up to the plan's end and the dispatch,
    # the pull, refit and evaluation, the rest ([5, 7] and [19.5, 20])
    assert read["sweep_feed_idle_s.sweep"] == pytest.approx(3.0 + 1.0 + 0.5)
    assert read["refit_eval_idle_s.sweep"] == pytest.approx(0.5 + 10.0)
    assert read["unattributed_idle_s.sweep"] == pytest.approx(2.5)
    capsys.readouterr()


def test_new_idle_readers_sit_inside_the_accepted_three(monkeypatch, capsys):
    read = _read(_run_like(FIT, OPS, monkeypatch), IDLE + OLD)
    gaps = trace_reduce.length(trace_reduce.gaps(OPS, WINDOW))
    assert sum(read[m] for m in OLD) == pytest.approx(gaps)
    parts = sum(read[m] for m in IDLE if m != "sweep_sketch_idle_s.trees")
    assert parts <= read["sweep_feed_idle_s.sweep"] \
        + read["refit_eval_idle_s.sweep"] + 1e-9
    assert read["sweep_sketch_idle_s.trees"] \
        <= read["sweep_feed_idle_s.sweep"] + 1e-9
    # the sketch is a part of the quantize span's idle
    quant = bench_run.load_module("layers", "sweep_quantize_idle_s.trees").read
    assert read["sweep_sketch_idle_s.trees"] <= quant(
        types.SimpleNamespace(trace={"window": WINDOW})) + 1e-9
    capsys.readouterr()


@pytest.mark.parametrize("spans", [
    [span("bench.step", 0, 20)],
    [s for s in FIT if s[0] in ("bench.step", "selector.fit", "selector.gather",
                                "sweep.plan", "sweep.quantize", "sweep.dispatch",
                                "sweep.gather", "selector.refit",
                                "selector.evaluate", "devcache.upload")],
], ids=["no_spans", "parent_spans"])
def test_readers_are_silent_without_the_spans(spans, monkeypatch, capsys):
    # the parent of the PR that added the spans has the accepted phases
    # alone: the new readers read None there, the accepted ones still read
    r = _run_like(spans, OPS, monkeypatch)
    for m in NEW:
        assert bench_run.load_module("layers", m).read(r) is None, m
    capsys.readouterr()


def test_gather_rate_is_silent_on_spans_of_no_length(monkeypatch, capsys):
    r = _run_like([span("selector.rows", 1.0, 1.0, bytes=8)], OPS, monkeypatch)
    assert host_spans.gather_gbps(r) is None
    capsys.readouterr()


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_entry_has_its_reader_file(name, bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["workloads"][0] == NEW[name]
    assert set(entry["workloads"]) <= {c["name"] for c in bench["workloads"]}
    assert entry["source"] == "program_span"
    assert entry["layer"] == "fused sweep" and entry["moves"] == "fits_per_s"
    assert entry["better"] == ("higher" if name == "row_gather_gbps.sweep"
                               else "lower")
    names = [m["name"] for m in bench["per_layer"]]
    # appended after every metric that was there before them
    assert names.index(name) > names.index("tree_leaf_device_s")
    assert os.path.isfile(os.path.join(BENCH_DIR, "layers", name + ".py"))
    assert callable(bench_run.load_module("layers", name).read)
