"""Test fixtures.

The reference tests distributed code against Spark local-mode
(TestSparkContext spins local[2], utils/.../test/TestSparkContext.scala:36).
Our analog: JAX on a virtual 8-device CPU mesh —
``--xla_force_host_platform_device_count=8`` (SURVEY §4 implication c).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The straggler-hedge layer is calibrated for production shards (minutes on
# real chips); on an oversubscribed CPU proxy, wall-clock noise reads as
# chip sickness — healthy devices get evicted and spurious hedges double
# FLOP accounting mid-suite.  Disarm it by default so every test sees the
# exact pre-hedge dispatch; tests/test_hedge.py opts back in per test.
os.environ.setdefault("TMOG_HEDGE", "0")

# obs/record.py defaults to ./telemetry.jsonl, so any test that drives a
# record-writing entry point (__graft_entry__ dryrun, bench helpers) would
# drop a stray file at repo root — the exact droppings the tier1 repo-
# hygiene step rejects.  Default the suite's telemetry out of the tree;
# CI entries that WANT the artifact set TMOG_TELEMETRY explicitly first.
os.environ.setdefault("TMOG_TELEMETRY", "/tmp/tmog_test_telemetry.jsonl")


import numpy as np
import pandas as pd
import pytest

TITANIC_CSV = "/root/reference/test-data/PassengerDataAllWithHeader.csv"


def pytest_sessionfinish(session, exitstatus):
    """CI telemetry: when TMOG_TELEMETRY names a path, snapshot every
    registry surface the run touched into one JSONL row (the tier1 artifact
    .github/workflows/tier1.yml uploads)."""
    if not os.environ.get("TMOG_TELEMETRY", "").strip():
        return
    try:
        from transmogrifai_tpu import obs

        obs.write_record("tier1", extra={"exitstatus": int(exitstatus)})
    except Exception:
        pass  # telemetry must never fail the suite


def _cut_binary_space(lr=3, rf_every=6, xgb=2, trees=10, rounds=20,
                      max_depth=12):
    """The default binary space (LR 8 + RF 18 + XGB 2) cut for a test that
    RUNS a sweep on a few hundred rows: the first ``lr`` logistic points,
    every ``rf_every``-th forest point (6: one of each depth 3 / 6 / 12; 3:
    two) up to ``max_depth``, the first ``xgb`` boosted points — every
    family, depth and fragment of the full grid — with ``trees`` trees a
    forest and ``rounds`` boosting rounds.  What launching, sharding,
    hedging or checkpointing does with a candidate does not depend on how
    long it trains; the whole grid is walked by ``chip_smoke.py``."""
    from transmogrifai_tpu.impl.selector.defaults import default_binary_space

    (lr_est, lr_grid), (rf_est, rf_grid), (xgb_est, xgb_grid) = \
        default_binary_space()
    return [(lr_est, lr_grid[:lr]),
            (rf_est, [dict(g, num_trees=trees) for g in rf_grid[::rf_every]
                      if g["max_depth"] <= max_depth]),
            (xgb_est, [dict(g, num_round=rounds) for g in xgb_grid[:xgb]])]


@pytest.fixture(scope="session")
def cut_binary_space():
    return _cut_binary_space


@pytest.fixture(scope="session")
def titanic_df():
    if os.path.exists(TITANIC_CSV):
        df = pd.read_csv(TITANIC_CSV)
        df.columns = [c.strip() for c in df.columns]
        return df
    # synthetic fallback with the same schema
    rng = np.random.default_rng(0)
    n = 800
    return pd.DataFrame({
        "PassengerId": np.arange(n),
        "Survived": rng.integers(0, 2, n),
        "Pclass": rng.integers(1, 4, n),
        "Name": [f"Person {i}" for i in range(n)],
        "Sex": rng.choice(["male", "female"], n),
        "Age": np.where(rng.random(n) < 0.2, np.nan, rng.uniform(1, 80, n)),
        "SibSp": rng.integers(0, 5, n),
        "Parch": rng.integers(0, 5, n),
        "Ticket": [f"T{i}" for i in range(n)],
        "Fare": rng.uniform(5, 500, n),
        "Cabin": np.where(rng.random(n) < 0.7, None, "C85"),
        "Embarked": rng.choice(["S", "C", "Q", None], n),
    })
