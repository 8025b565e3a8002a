"""``benchmarks/layers/device_row_share.sweep.py``: the share of the traced
step's ``selector.rows`` bytes taken on the device, on known answers, its
silence on a program whose spans carry no ``where`` (or hold no gather), and
its entry.  CPU only, no training."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program_spans as ps, run as bench_run, trace_reduce  # noqa: E402

NAME = "device_row_share.sweep"
CELLS = ("scale-500.sweep", "scale-500-trees.sweep", "scale-500-multiclass.sweep")
WINDOW = (0.0, 10.0)
OPS = [("jit(_run)/scores.fista/dot", 3.0, 4.0)]


def rows(a, b, nbytes, what, **where):
    return ("selector.rows", float(a), float(b),
            dict(rows=10, bytes=nbytes, what=what, **where))


def _read(spans, monkeypatch):
    r = types.SimpleNamespace(trace={"window": WINDOW})
    monkeypatch.setattr(ps, "read_xplane", lambda path: {"spans": spans,
                                                         "ops": {0: OPS}})
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x.xplane.pb")
    return bench_run.load_module("layers", NAME).read(r)


@pytest.mark.parametrize("spans,want", [
    ([rows(0, 1, 3e8, "train", where="device"),
      rows(5, 6, 1e8, "refit", where="device"),
      rows(6, 7, 1e8, "evaluate", where="device"),
      rows(7, 8, 5e7, "holdout", where="device")], 100.0),
    ([rows(0, 1, 3e8, "train", where="host"),
      rows(5, 6, 1e8, "refit", where="host")], 0.0),
    ([rows(0, 1, 3e8, "train", where="device"),
      rows(5, 6, 1e8, "refit", where="host")], 75.0),
    # a later step's gather, outside the window, is not read
    ([rows(0, 1, 1e8, "train", where="device"),
      rows(20, 21, 3e8, "train", where="host")], 100.0),
], ids=["all_device", "all_host", "mixed", "window"])
def test_share_of_bytes_taken_on_the_device(spans, want, monkeypatch, capsys):
    assert _read(spans, monkeypatch) == pytest.approx(want)
    capsys.readouterr()


@pytest.mark.parametrize("spans", [
    [("bench.step", 0.0, 10.0, {})],
    # the parent program's gathers say nothing of where
    [rows(0, 1, 3e8, "train"), rows(5, 6, 1e8, "refit")],
    [rows(0, 1, 0, "train", where="device")],
], ids=["no_gathers", "no_where", "no_bytes"])
def test_silent_where_there_is_nothing_to_read(spans, monkeypatch, capsys):
    assert _read(spans, monkeypatch) is None
    capsys.readouterr()


def test_entry_has_its_reader_file(bench):
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "program_span"
    assert entry["layer"] == "fused sweep" and entry["moves"] == "fits_per_s"
    for cell in CELLS:
        assert cell in entry["workloads"]
    assert entry["workloads"].index(CELLS[0]) \
        < entry["workloads"].index(CELLS[1]) \
        < entry["workloads"].index(CELLS[2])
    assert set(entry["workloads"]) <= {c["name"] for c in bench["workloads"]}
    names = [m["name"] for m in bench["per_layer"]]
    # appended after every metric that was there before it
    assert names.index(NAME) > names.index("sweep_sketch_idle_s.trees")
    assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layers",
                                       NAME + ".py"))
