"""``BENCHMARK.json`` as the tests of its lists see it: as committed, and as a
later PR that changes the program may leave it.  Such a PR adds entries at
the END of ``configs``, ``workloads``, ``per_layer`` and of a metric's
``workloads``, and nothing else (``benchmarks/README.md``, "What a later PR
may add"); a test that pins the tail or the whole of such a list refuses that
PR, as three did PR 35.  Every test that asserts where an entry stands takes
``bench`` and so runs on both."""
import copy
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LATER_CONFIG, LATER_CELL, LATER_METRIC = "later", "later.mix", "later_layer.count"


def committed():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def appended(bench):
    """A deep copy with exactly what the driver lets a program PR add: a
    configuration, a cell, that cell at the end of the ``workloads`` of every
    metric that has the key, and one per-layer metric."""
    out = copy.deepcopy(bench)
    out["configs"].append({"name": LATER_CONFIG, "source": "x", "why": "x",
                           "file": "benchmarks/configs/later.json", "reduced": []})
    out["workloads"].append({"name": LATER_CELL, "config": LATER_CONFIG,
                             "traffic": "mix", "chips": 1, "why": "x"})
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(LATER_CELL)
    out["per_layer"].append({"name": LATER_METRIC, "unit": "count",
                             "better": "lower", "source": "program_counter",
                             "layer": "later", "moves": "fits_per_s",
                             "workloads": [LATER_CELL]})
    return out


@pytest.fixture(params=["committed", "appended"])
def bench(request):
    return committed() if request.param == "committed" else appended(committed())


@pytest.fixture
def later_pr():
    """``(appended copy, the later cell's name, the later metric's name)``."""
    return appended(committed()), LATER_CELL, LATER_METRIC
