"""Kernel micro-profiler (dev tool): RF/GBT hot shapes, one entry point.

Consolidates the former ``profile_trees.py`` / ``profile_trees2.py`` /
``profile_trees3.py`` / ``profile_trace.py`` into subcommands:

- ``trees``        — the RF depth/frontier/chunk matrix + GBT batch cases at
  the Titanic hot shapes (n=891, d=24, 32 bins), mean-of-reps timing;
- ``trees-beam``   — the frontier-beam width variants at depth 12;
- ``trees-stats``  — min/median timing of the three sweep-representative RF
  cases + the GBT batch case (noise-robust numbers for before/after diffs);
- ``trace``        — one warmed depth-12 forest build under
  ``jax.profiler.trace`` (XLA-level, for TensorBoard);
- ``fused``        — per-fragment device-time profile of the fused Titanic
  sweep (the former ``profile_fused.py``): the full spec, each fragment
  kind alone, and each forest depth group alone;
- ``roofline``     — the launch ledger over the fused Titanic sweep:
  per-launch FLOPs + bytes-accessed vs the device peaks, per-family MFU
  decomposition and compute/memory/launch-bound labels
  (transmogrifai_tpu/obs/ledger.py; set TMOG_PEAK_FLOPS /
  TMOG_PEAK_HBM_GBPS to calibrate off-TPU).

``--trace out.json`` on any subcommand additionally records obs spans
(``profile.case`` per timed case) and exports Chrome trace-event JSON
loadable in Perfetto — the span tracer the rest of the repo shares
(transmogrifai_tpu/obs).  Every run appends a ``profile`` row to the
telemetry JSONL (TMOG_TELEMETRY or ./telemetry.jsonl).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from transmogrifai_tpu.utils.backend import device_summary

parser = argparse.ArgumentParser(description=__doc__)
parser.add_argument("cmd", nargs="?", default="trees",
                    choices=["trees", "trees-beam", "trees-stats", "trace",
                             "fused", "roofline"])
parser.add_argument("--reps", type=int, default=0,
                    help="timing repetitions (default: 3, trees-stats 6)")
parser.add_argument("--trace", default="",
                    help="record obs spans and export Chrome trace-event "
                         "JSON here (open in Perfetto)")
cli = parser.parse_args()

print("device:", device_summary(), file=sys.stderr)
import jax
import jax.numpy as jnp

from transmogrifai_tpu import obs
from transmogrifai_tpu.obs import trace as obs_trace
from transmogrifai_tpu.ops import trees as Tr

if cli.trace:
    obs_trace.enable(cli.trace)

# the Titanic hot shapes every sweep-kernel case below runs at
n, d = 891, 24
rng = np.random.default_rng(0)
X = rng.normal(size=(n, d)).astype(np.float32)
y = (rng.random(n) < 0.4).astype(np.float32)
Xb, _ = Tr.quantize(X, 32)
G = -y[:, None]
H = np.ones(n, np.float32)


def timed_mean(fn, label, reps):
    with obs_trace.span("profile.case", case=label, reps=reps):
        fn()  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        dt = (time.perf_counter() - t0) / reps
    print(f"{label:48s} {dt * 1e3:9.1f} ms")
    return dt


def timed_minmed(fn, label, reps):
    with obs_trace.span("profile.case", case=label, reps=reps):
        fn()  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
    print(f"{label:44s} min {min(ts) * 1e3:8.1f}  "
          f"med {float(np.median(ts)) * 1e3:8.1f} ms")
    return min(ts)


def rf_runner(TT, depth, frontier, chunk):
    wt = rng.poisson(1.0, size=(TT, n)).astype(np.float32)
    fm = (rng.random((TT, d)) < 0.3).astype(np.float32)
    mcw = np.full(TT, 10.0, np.float32)
    a = [jnp.asarray(v) for v in (Xb, G, H, wt, fm, mcw)]

    def run():
        return Tr.fit_forest_chunked(*a, max_depth=depth, n_bins=32,
                                     chunk=chunk, frontier=frontier)

    return run


def rf_case(timer, TT, depth, frontier, chunk, label, reps):
    return timer(rf_runner(TT, depth, frontier, chunk), label, reps)


def gbt_runner(n_rounds=200, max_depth=10, frontier=64, B=6):
    rw = np.ones((n_rounds, n), np.float32)
    fms = np.ones((n_rounds, d), np.float32)
    kw = dict(loss="logistic", n_rounds=n_rounds, max_depth=max_depth,
              n_bins=32, frontier=frontier,
              eta_b=jnp.full(B, 0.02), reg_lambda_b=jnp.full(B, 1.0),
              gamma_b=jnp.full(B, 0.8), min_child_weight_b=jnp.full(B, 1.0))
    a = [jnp.asarray(v) for v in (Xb, y, np.ones((B, n), np.float32),
                                  rw, fms)]

    def run():
        return Tr.fit_gbt_batch(a[0], a[1], a[2], a[3], a[4], **kw)

    return run


def cmd_trees(reps):
    """The sweep-representative RF matrix + GBT batch cases (means)."""
    from transmogrifai_tpu.ops.trees import forest_chunk_size

    for depth, frontier in ((3, 8), (6, 64), (12, 128)):
        cs = forest_chunk_size(depth, 32, d, 1, frontier)
        TT = 900
        chunk = min(cs, TT)
        TTp = TT + ((-TT) % chunk)
        rf_case(timed_mean, TTp, depth, frontier, chunk,
                f"RF d={depth} M={frontier} TT={TTp} chunk={chunk}", reps)
    rf_case(timed_mean, 900, 12, 128, 900, "RF d=12 M=128 one chunk of 900",
            reps)
    rf_case(timed_mean, 900, 12, 128, 300, "RF d=12 M=128 chunk=300", reps)
    rf_case(timed_mean, 896, 12, 128, 128, "RF d=12 M=128 chunk=128", reps)
    timed_mean(gbt_runner(n_rounds=200),
               "XGB batch=6 rounds=200 d=10 M=64", reps)
    timed_mean(gbt_runner(n_rounds=20),
               "XGB batch=6 rounds=20 d=10 M=64", reps)


def cmd_trees_beam(reps):
    """Frontier-beam width variants at depth 12."""
    rf_case(timed_mean, 900, 12, 128, 900, "RF d=12 M=128", reps)
    rf_case(timed_mean, 900, 12, 64, 900, "RF d=12 M=64 beam", reps)
    rf_case(timed_mean, 900, 12, 32, 900, "RF d=12 M=32 beam", reps)
    rf_case(timed_mean, 900, 8, 128, 900, "RF d=8 M=128", reps)
    rf_case(timed_mean, 112, 12, 128, 112, "RF d=12 M=128 TT=112", reps)


def cmd_trees_stats(reps):
    """min/median of the three sweep-representative cases (diff-stable)."""
    rf_case(timed_minmed, 900, 3, 8, 900, "RF d=3  M=8   TT=900", reps)
    rf_case(timed_minmed, 900, 6, 64, 900, "RF d=6  M=64  TT=900", reps)
    rf_case(timed_minmed, 900, 12, 128, 900, "RF d=12 M=128 TT=900", reps)
    timed_minmed(gbt_runner(n_rounds=200),
                 "XGB batch=6 rounds=200 d=10 M=64", reps)


def cmd_trace(reps):
    """One warmed depth-12 forest build under jax.profiler.trace."""
    run = rf_runner(900, 12, 128, 900)
    jax.block_until_ready(run())
    out = "/tmp/jaxtrace"
    with jax.profiler.trace(out):
        with obs_trace.span("profile.case", case="RF d=12 jax.profiler"):
            jax.block_until_ready(run())
    print(f"trace done -> {out}")


def cmd_fused(reps):
    """Per-fragment device time of the fused Titanic sweep (the folded-in
    ``profile_fused.py``): ALL, each fragment kind alone, each forest
    depth group alone — at the real selector shapes."""
    from bench import make_selector, titanic_arrays
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.ops.sweep import run_sweep

    Xt, yt = titanic_arrays()
    sel = make_selector()
    v = sel.validator
    train_w, val_mask = v.make_folds(len(yt), None)
    prep_w = sel.splitter.prepare_weights(yt)
    train_w = train_w * prep_w[None, :].astype(np.float32)
    val_mask = val_mask & (prep_w > 0)[None, :]
    plan = build_sweep_plan(sel.models, Xt, yt, train_w, v.evaluator)
    if plan is None:
        print("default grid did not build a fused plan; nothing to profile")
        return
    full = plan.spec

    def time_spec(name, frags):
        # keep the global candidate tuple: the metrics tensor stays sized by
        # the full spec; scores for absent candidates stay zero, harmless
        spec = (full[0], frags, full[2])
        with obs_trace.span("profile.case", case=name, reps=reps):
            t0 = time.perf_counter()
            m = run_sweep(spec, plan.X, plan.xbs, plan.y, train_w, val_mask,
                          plan.blob)
            np.asarray(m)
            warm = time.perf_counter() - t0
            t0 = time.perf_counter()
            for r in range(reps):
                tw = train_w * (1.0 + 1e-7 * r)  # new buffer: defeat memo
                m = run_sweep(spec, plan.X, plan.xbs, plan.y, tw, val_mask,
                              plan.blob)
                np.asarray(m)
            dt = (time.perf_counter() - t0) / reps
        print(f"{name:44s} warm={warm:7.2f}s steady={dt * 1e3:9.1f} ms")
        return dt

    frags = full[1]
    by_kind = {}
    for f in frags:
        by_kind.setdefault(f[0], []).append(f)
    time_spec("ALL", frags)
    for kind, fs in by_kind.items():
        time_spec(f"only:{kind}", tuple(fs))
    if "forest" in by_kind:
        groups = by_kind["forest"][0][2]
        for g in groups:
            frag = ("forest", by_kind["forest"][0][1], (g,))
            time_spec(f"forest depth={g[1]} frontier={g[9]} chunk={g[11]}",
                      (frag,))


def cmd_roofline(reps):
    """Launch ledger + roofline/MFU decomposition of the fused Titanic
    sweep: reps selector fits with FLOPs+bytes accounting and the launch
    ledger on, then the per-family report (obs/ledger.format_report)."""
    from bench import make_selector, titanic_arrays
    from transmogrifai_tpu.obs import ledger
    from transmogrifai_tpu.utils import flops

    Xt, yt = titanic_arrays()
    sel = make_selector()
    sel.find_best_estimator(Xt, yt)  # warmup: compile everything first
    flops.enable()
    flops.reset()
    ledger.enable()
    ledger.reset()
    trace_was_on = obs_trace.enabled()
    if not trace_was_on:
        obs_trace.enable(path=None)
    t0 = time.perf_counter()
    with obs_trace.span("profile.window", reps=reps):
        for r in range(reps):
            sel2 = make_selector(seed=100 + r)
            sel2.find_best_estimator(Xt, yt)
    wall = time.perf_counter() - t0
    if not trace_was_on:
        obs_trace.disable()
    flops.disable()
    try:
        roof = ledger.ledger_report(window_wall_s=wall,
                                    device_kind=jax.devices()[0].device_kind,
                                    platform=jax.devices()[0].platform,
                                    reps=reps)
    except ValueError:
        print("ledger is empty (cost_analysis unavailable?); no report")
        return None
    finally:
        ledger.disable()
    print(ledger.format_report(roof))
    return roof


_roof = None
if cli.cmd == "trees":
    cmd_trees(cli.reps or 3)
elif cli.cmd == "trees-beam":
    cmd_trees_beam(cli.reps or 3)
elif cli.cmd == "trees-stats":
    cmd_trees_stats(cli.reps or 6)
elif cli.cmd == "fused":
    cmd_fused(cli.reps or 5)
elif cli.cmd == "roofline":
    _roof = cmd_roofline(cli.reps or 3)
else:
    cmd_trace(cli.reps or 1)

if cli.trace:
    print(f"obs trace -> {obs_trace.export(cli.trace)}")
_extra = {"cmd": cli.cmd}
if _roof:
    _extra["roofline"] = _roof
    _extra["mfu_decomposition"] = _roof["mfu_decomposition"]
obs.write_record("profile", extra=_extra)
