"""The two readers PR 34 brings, ``tree_route_device_s`` (scope ``trees.route``,
in the program since PR 29) and ``tree_leaf_device_s`` (scope ``trees.leaves``,
``ops/trees.read_leaves``): their files and ``BENCHMARK.json`` entries, their
silence on a trace without the scope (the parent of PR 34 has ``trees.route``
and no ``trees.leaves``), and the seconds they read from a device plane laid
out as a v5e trace lays it (the scope path in the event metadata's ``tf_op``),
through ``program_spans``' own wire-format decoder.  CPU only."""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
BENCH_DIR = os.path.join(ROOT, "benchmarks")

from benchmarks import program_spans as ps, run as bench_run  # noqa: E402

READERS = {"tree_route_device_s": "trees.route",
           "tree_leaf_device_s": "trees.leaves"}
TREE_CELLS = ["scale-500-trees.sweep", "scale-500-multiclass.sweep"]
WINDOW = (0.0, 10.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_entry_and_file(name, bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert dict(entry, workloads=None) == {
        "name": name, "unit": "s", "better": "lower", "source": "device_trace",
        "layer": "kernels", "moves": "fits_per_s", "workloads": None}
    # the two tree cells first; a later tree cell joins after them
    assert entry["workloads"][:len(TREE_CELLS)] == TREE_CELLS
    # route directly before leaves, both after the last metric the benchmark
    # had before them; what a later PR appends after them is its own
    order = [m["name"] for m in bench["per_layer"]]
    route = order.index("tree_route_device_s")
    assert order[route + 1] == "tree_leaf_device_s"
    assert order.index("mc_step_mfu") < route
    assert os.path.isfile(os.path.join(BENCH_DIR, "layers", name + ".py"))
    assert callable(bench_run.load_module("layers", name).read)


# ---- a device plane on the wire ----------------------------------------------
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


def _device_xspace(paths):
    """``paths``: (tf_op path, start s, seconds) of each device op, the
    clock starting at the line's t0 = 5 s."""
    plane = _f(2, "/device:TPU:0") + _f(5, _f(1, 1) + _f(2, _f(1, 1) + _f(2, "tf_op")))
    line = _f(2, "XLA Ops") + _f(3, 5_000_000_000)
    for k, (path, start, secs) in enumerate(paths, start=10):
        md = _f(1, k) + _f(2, f"%fusion.{k} = fusion(...)") \
            + _f(5, _f(1, 1) + _f(5, path))
        plane += _f(4, _f(1, k) + _f(2, md))
        line += _f(4, _f(1, k) + _f(2, int(start * 10**12)) + _f(3, int(secs * 10**12)))
    return _f(1, plane + _f(3, line))


def _traced_run(tmp_path, monkeypatch, paths):
    """A ``Run`` whose traced step lies on disk where the readers look."""
    d = tmp_path / "plugins" / "profile" / "2026_10_03"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_device_xspace(paths))
    monkeypatch.setattr(ps, "TRACE_DIR", str(tmp_path))
    return types.SimpleNamespace(trace={"window": (5.0, 15.0)})


FOREST = "jit(_run_scores)/scores.forest/while/body/"


def test_both_read_their_scopes_from_a_device_plane(tmp_path, monkeypatch, capsys):
    """The change's trace: a level's route scan (a loop and an op of its
    body: a union, not a sum), the same under boosting, and the leaf read of
    a chunk; hist and split ops beside them are not counted."""
    r = _traced_run(tmp_path, monkeypatch, [
        (FOREST + "trees.hist/while/body/dot_general:", 0.0, 2.0),
        (FOREST + "trees.route/while", 2.0, 1.0),
        (FOREST + "trees.route/while/body/select_n:", 2.25, 0.5),
        (FOREST + "trees.leaves/while/body/dot_general:", 3.0, 0.25),
        (FOREST + "trees.leaves/while/body/reduce_sum:", 3.25, 0.125),
        ("jit(_run)/scores.gbt/while/body/trees.route/while/body/eq:", 6.0, 0.5),
        ("jit(_run)/scores.gbt/while/body/trees.leaves/dot_general:", 7.0, 0.0625)])
    read = {m: bench_run.load_module("layers", m).read(r) for m in READERS}
    assert read["tree_route_device_s"] == pytest.approx(1.5)
    assert read["tree_leaf_device_s"] == pytest.approx(0.4375)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["phase"] for ln in lines] == ["program_spans"]    # parsed once
    # the fact line lists the tree scopes beside the families' (``SCOPE``)
    assert lines[0]["scopes"] == pytest.approx({
        "scores.forest": 3.375, "scores.gbt": 0.5625, "trees.hist": 2.0,
        "trees.route": 1.5, "trees.leaves": 0.4375})


def test_the_parent_reads_its_route_and_no_leaves(tmp_path, monkeypatch):
    """PR 34's parent: ``trees.route`` since PR 29, the leaves gathered under
    no scope of their own."""
    r = _traced_run(tmp_path, monkeypatch, [
        (FOREST + "trees.route/while/body/select_n:", 1.0, 0.75),
        (FOREST + "gather:", 2.0, 3.0)])
    assert bench_run.load_module("layers", "tree_route_device_s").read(r) \
        == pytest.approx(0.75)
    assert bench_run.load_module("layers", "tree_leaf_device_s").read(r) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_silent_without_the_scope_and_without_a_trace(name, tmp_path, monkeypatch):
    """``scale-500.sweep`` grows no tree; and a run that left no trace."""
    r = _traced_run(tmp_path, monkeypatch, [
        ("jit(_run_scores)/scores.fista/dot_general:", 1.0, 2.0),
        ("", 3.0, 1.0)])
    assert bench_run.load_module("layers", name).read(r) is None
    monkeypatch.setattr(ps, "TRACE_DIR", os.path.join(str(tmp_path), "no-such-dir"))
    r2 = types.SimpleNamespace(trace={"window": WINDOW})
    assert bench_run.load_module("layers", name).read(r2) is None
