"""``benchmarks/program_spans.py``: the arithmetic on synthetic intervals of
known answer, the name-path parsing, the silence on a program that writes no
span, and the new readers' files.  CPU only; a CPU trace has no device plane,
so the reading part is checked on the chip (PERF.md section 6, PR 27)."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH_DIR = os.path.join(ROOT, "benchmarks")

from benchmarks import program_spans as ps, run as bench_run, trace_reduce  # noqa: E402

#: the metrics whose readers go through program_spans
NEW = ("sweep_feed_idle_s.sweep", "refit_eval_idle_s.sweep",
       "unattributed_idle_s.sweep", "sweep_h2d_bytes.sweep",
       "metric_rank_device_s", "fista_scores_device_s",
       "svc_scores_device_s", "mlp_scores_device_s")


def span(name, a, b, **stats):
    return (name, float(a), float(b), stats)


# device busy on [2, 4] and [7, 8] of a [0, 10] window: gaps [0,2] [4,7] [8,10]
OPS = [("jit(f)/scores.fista/dot", 2.0, 4.0), ("jit(g)/metrics.rank/while", 7.0, 8.0)]
WINDOW = (0.0, 10.0)


def test_gap_is_split_across_two_spans():
    spans = [span("a", 0, 5), span("b", 5, 10)]
    idle = ps.idle_by_span(OPS, WINDOW, spans)
    # [0,2] -> a; [4,7] cut at 5 -> a 1, b 2; [8,10] -> b
    assert idle == pytest.approx({"a": 3.0, "b": 4.0})


def test_gap_under_no_span():
    idle = ps.idle_by_span(OPS, WINDOW, [span("a", 4.5, 6)])
    assert idle == pytest.approx({"a": 1.5, ps.NO_SPAN: 5.5})
    assert ps.idle_by_span(OPS, WINDOW, []) == pytest.approx({ps.NO_SPAN: 7.0})


def test_nested_spans_innermost_takes_the_piece():
    spans = [span("bench.step", 0, 10), span("selector.fit", 0, 9.5),
             span("sweep.plan", 0.5, 1.5), span("devcache.upload", 1.0, 1.4)]
    idle = ps.idle_by_span(OPS, WINDOW, spans)
    assert idle == pytest.approx({
        "devcache.upload": 0.4, "sweep.plan": 0.6,   # [0.5,1] + [1.4,1.5]
        "selector.fit": 0.5 + 0.5 + 3.0 + 1.5,       # the rest up to 9.5
        "bench.step": 0.5})


@pytest.mark.parametrize("spans", [
    [],
    [span("a", 0, 5), span("b", 5, 10)],
    [span("x", 1, 3), span("y", 2.5, 9), span("z", 6, 6.5), span("w", 0, 10)],
], ids=["none", "two", "overlapping"])
def test_idle_parts_sum_to_the_gaps_total(spans):
    total = trace_reduce.length(trace_reduce.gaps(OPS, WINDOW))
    assert total == pytest.approx(7.0)
    assert sum(ps.idle_by_span(OPS, WINDOW, spans).values()) == pytest.approx(total)
    assert sum(ps.idle_by_phase(OPS, WINDOW, spans, ("x", "z", "a")).values()) \
        == pytest.approx(total)


def test_idle_by_phase_gives_a_piece_to_the_innermost_phase():
    spans = [span("bench.step", 0, 10), span("sweep.plan", 0.5, 1.5),
             span("devcache.upload", 1.0, 1.4), span("selector.refit", 8.5, 9.5)]
    idle = ps.idle_by_phase(OPS, WINDOW, spans, ("sweep.plan", "selector.refit"))
    # the upload's 0.4 s count for the plan; bench.step is no phase
    assert idle == pytest.approx({"sweep.plan": 1.0, "selector.refit": 1.0,
                                 ps.NO_SPAN: 5.0})


def test_self_seconds_subtracts_what_children_cover():
    spans = [span("fit", 0, 10), span("plan", 1, 4), span("upload", 2, 3),
             span("upload", 3.5, 3.9), span("refit", 6, 9),
             span("dispatch", 2.5, 5)]          # another thread, overlapping
    got = ps.self_seconds(spans)
    assert got == pytest.approx({
        "fit": 10 - (4 + 3),            # [1,5] (plan U dispatch) and [6,9]
        "plan": 3 - 1.4, "upload": 1.4, "refit": 3.0,
        "dispatch": 2.5 - 0.4})     # holds the second upload alone
    # two spans on one interval: the later is the child, counted once
    twin = ps.self_seconds([span("stage.fit", 0, 2), span("selector.fit", 0, 2)])
    assert twin == pytest.approx({"stage.fit": 0.0, "selector.fit": 2.0})


def test_scope_seconds_unions_a_loop_with_its_body():
    ops = [("jit(m)/metrics.binary/metrics.rank/while", 0.0, 4.0),
           ("jit(m)/metrics.binary/metrics.rank/while/body/gather", 1.0, 2.0),
           ("jit(m)/metrics.binary/metrics.sort/sort", 4.0, 5.0),
           ("jit(m)/metrics.binary/reduce", 5.5, 6.0),
           ("jit(s)/scores.fista/dot_general", 8.0, 12.0)]
    win = (0.0, 10.0)
    assert ps.scope_seconds(ops, win, r"metrics\.rank\b") == pytest.approx(4.0)
    assert ps.scope_seconds(ops, win, r"metrics\.binary\b") == pytest.approx(5.5)
    assert ps.scope_seconds(ops, win, r"scores\.fista\b") == pytest.approx(2.0)  # clipped
    assert ps.scope_seconds(ops, win, r"scores\.mlp\b") is None
    tab = ps.table(ops, win, [span("bench.step", 0, 10)])
    assert tab["scopes"] == pytest.approx({
        "metrics.binary": 5.5, "metrics.rank": 4.0, "metrics.sort": 1.0,
        "scores.fista": 2.0})
    assert tab["spans"]["bench.step"] == pytest.approx(
        {"n": 1, "wall_s": 10.0, "self_s": 10.0, "device_idle_s": 2.5})


# ---- reading: the wire-format decoder ----------------------------------------
def _vi(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _f(num, value):
    """One protobuf field: an int as a varint, bytes length-delimited."""
    if isinstance(value, int):
        return _vi(num << 3) + _vi(value)
    if isinstance(value, str):
        value = value.encode()
    return _vi(num << 3 | 2) + _vi(len(value)) + value


def _device_xspace():
    """A device plane as a v5e trace lays it out: the name path is the
    ``tf_op`` stat of the EVENT METADATA (as a string, or as a reference to
    a stat-metadata name); the events carry offsets and durations alone."""
    stat_md = {1: "tf_op", 2: "flops",
               3: "jit(_run_scores)/scores.svc/dot_general:"}
    event_md = {
        10: ("%while.28 = while(...)", []),                       # no tf_op
        11: ("%fusion.49 = fusion(...)",
             [_f(1, 2) + _f(3, 7), _f(1, 1) + _f(
                 5, "jit(_run_metrics)/metrics.binary/vmap(vmap(metrics.rank))/gather:")]),
        12: ("%fusion.7 = fusion(...)", [_f(1, 1) + _f(7, 3)]),   # by reference
    }
    plane = _f(2, "/device:TPU:0")
    for k, name in stat_md.items():
        plane += _f(5, _f(1, k) + _f(2, _f(1, k) + _f(2, name)))
    for k, (name, stats) in event_md.items():
        md = _f(1, k) + _f(2, name) + b"".join(_f(5, st) for st in stats)
        plane += _f(4, _f(1, k) + _f(2, md))
    ev = lambda mid, off_ps, dur_ps: _f(4, _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps))  # noqa: E731
    ops_line = (_f(2, "XLA Ops") + _f(3, 5_000_000_000)            # t0 = 5 s
                + ev(10, 0, 4_000_000_000_000) + ev(11, 10**12, 10**12)
                + ev(12, 6 * 10**12, 5 * 10**11))
    other = _f(2, "XLA Modules") + _f(3, 5_000_000_000) + ev(10, 0, 10**12)
    plane += _f(3, ops_line) + _f(3, other)
    return _f(1, plane) + _f(2, "an error string the reader skips")


def test_read_xplane_takes_the_scope_path_from_the_event_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_device_xspace())
    got = ps.read_xplane(str(path))
    assert got["spans"] == []
    (ops,) = got["ops"].values()
    assert [(p, round(a, 9), round(b, 9)) for p, a, b in ops] == [
        ("", 5.0, 9.0),
        ("jit(_run_metrics)/metrics.binary/vmap(vmap(metrics.rank))/gather:", 6.0, 7.0),
        ("jit(_run_scores)/scores.svc/dot_general:", 11.0, 11.5)]
    win = (5.0, 12.0)
    assert ps.scope_seconds(ops, win, r"metrics\.rank\b") == pytest.approx(1.0)
    assert ps.table(ops, win, [])["scopes"] == pytest.approx(
        {"metrics.binary": 1.0, "metrics.rank": 1.0, "scores.svc": 0.5})


def test_read_xplane_agrees_with_profile_data_on_a_recorded_trace(tmp_path):
    # a real (CPU) capture: the host spans this decoder reads are the ones
    # jax's own reader shows — names, times, stats
    import jax
    from jax.profiler import ProfileData

    from transmogrifai_tpu.obs import trace

    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("selector.fit", rows=7, width=3):
            with trace.span("devcache.upload", bytes=2**40, tag="base"):
                pass
            with trace.span("not.a.root"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    want = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace_reduce.SPAN_PREFIX):
                        want[e.name] = (e.start_ns / 1e9, e.end_ns / 1e9,
                                        dict(e.stats))
    got = ps.read_xplane(path)
    assert got["ops"] == {}                      # a CPU trace has no device plane
    assert {s[0] for s in got["spans"]} == {"selector.fit", "devcache.upload"}
    for name, a, b, stats in got["spans"]:
        assert (a, b) == pytest.approx(want[name][:2], abs=1e-9)
        assert stats == want[name][2]
    (upload,) = [s for s in got["spans"] if s[0] == "devcache.upload"]
    assert upload[3] == {"bytes": 2**40, "tag": "base"}
    # trace_reduce's reader keeps the same spans (names and times alone), so
    # that ``breakdown.idle_gaps`` names what the program was doing
    kept = sorted(trace_reduce.read_xplane(path)["host_spans"])
    assert [s[0] for s in kept] == ["devcache.upload", "selector.fit"]
    for (_, a, b), s in zip(kept, sorted(got["spans"])):
        assert (a, b) == pytest.approx(s[1:3], abs=1e-9)


def _run_like(spans, ops, monkeypatch, capsys=None):
    """A ``Run`` with the pieces the readers use, its trace already read."""
    r = types.SimpleNamespace(trace={"window": WINDOW})
    monkeypatch.setattr(ps, "read_xplane", lambda path: {"spans": spans,
                                                         "ops": {0: ops}})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.xplane.pb")
    return r


def test_readers_partition_the_idle_time_and_print_one_fact_line(monkeypatch, capsys):
    spans = [span("bench.step", 0, 10), span("stage.fit", 0, 10),
             span("selector.fit", 0, 9.9), span("selector.gather", 0.2, 1.0),
             span("sweep.plan", 1.0, 1.9),
             span("devcache.upload", 1.1, 1.8, bytes=1000, tag="base"),
             span("devcache.upload", 1.8, 1.85, bytes=24, tag="base"),
             span("sweep.gather", 2.1, 8.2, d2h_bytes=96),
             span("selector.refit", 8.2, 9.0), span("selector.evaluate", 9.0, 9.8),
             span("selector.fit", 20, 30)]            # a later step: outside
    r = _run_like(spans, OPS, monkeypatch)
    read = {m: bench_run.load_module("layers", m).read(r) for m in NEW}
    feed, refit, rest = (read[m] for m in NEW[:3])
    assert feed == pytest.approx(0.8 + 0.9)
    assert refit == pytest.approx(3.0 + 0.2 + 0.8 + 0.8)
    assert rest == pytest.approx(7.0 - feed - refit)
    assert read["sweep_h2d_bytes.sweep"] == 1024
    assert read["metric_rank_device_s"] == pytest.approx(1.0)
    assert read["fista_scores_device_s"] == pytest.approx(2.0)
    assert read["svc_scores_device_s"] is None and read["mlp_scores_device_s"] is None
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert len(lines) == 1 and lines[0]["phase"] == "program_spans"  # parsed once
    assert lines[0]["spans"]["selector.fit"]["n"] == 1
    assert lines[0]["spans"]["devcache.upload"]["n"] == 2
    assert sum(s["device_idle_s"] for s in lines[0]["spans"].values()) \
        == pytest.approx(7.0)


def test_readers_are_silent_on_a_program_without_spans(monkeypatch, capsys):
    # the parent of the PR that added the spans: bench.step alone, plain ops
    r = _run_like([span("bench.step", 0, 10)],
                  [("%while.28 = while(...)", 2.0, 4.0)], monkeypatch)
    for m in NEW:
        assert bench_run.load_module("layers", m).read(r) is None, m
    # and with no trace on disk at all
    r2 = types.SimpleNamespace(trace={"window": WINDOW})
    monkeypatch.undo()
    monkeypatch.setattr(ps, "TRACE_DIR", os.path.join(BENCH_DIR, "no-such-dir"))
    for m in NEW:
        assert bench_run.load_module("layers", m).read(r2) is None, m


@pytest.mark.parametrize("name", NEW)
def test_new_entry_has_its_reader_file(name, bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    # the first cell they read in; a cell whose trace holds the spans or the
    # scope joins after it (the two tree cells did, PR 36)
    assert entry["workloads"][0] == "scale-500.sweep"
    assert entry["moves"] == "fits_per_s" and entry["better"] == "lower"
    assert entry["layer"] in ("fused sweep", "kernels")
    assert os.path.isfile(os.path.join(BENCH_DIR, "layers", name + ".py"))
    assert callable(bench_run.load_module("layers", name).read)
