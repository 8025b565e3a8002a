#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX.  It finds everything by name:
the cell in ``BENCHMARK.json``, its parameters in
``benchmarks/workloads/<cell>.json``, its configuration in
``benchmarks/configs/<config>.json``, the table maker that file names in
``benchmarks/tables/``, the step driver in ``benchmarks/entries/<entry>.py``,
each metric's reader in ``benchmarks/end_to_end/<metric>.py`` or
``benchmarks/layers/<metric>.py`` and the plain reference with its
comparison in ``benchmarks/references/<reference>.py``.  It holds no list of
cells, configurations, tables, model families or metrics, and knows nothing
of any of them: a later cell of any kind arrives as files.

Set-up (table from ``--seed``, workflow, warm-up of every program the window
uses) is timed as ``setup_s``.  The window is whole steps: a new step starts
while fewer than ``--seconds`` have elapsed, the window closes when the step
in flight ends, and every rate is all the window's work over that length.
A step that compiled, fell back, skipped a candidate or was served from a
checkpoint fails the run.  After the window: the peak device bytes are read,
the program's state is dropped, and the plain reference decides ``correct``.

Earlier stdout lines are JSON facts; the last is the result.  Without a TPU
(or with fewer chips than the cell asks for) it exits 2 and prints no
result.  ``--rehearse-rows N`` walks the same control flow at a tiny size on
any backend, prints no device metric, ends ``"correct": false`` and exits 1.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

STEP_SPAN = "bench.step"
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = HERE):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}: no file for {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                   f"{[c['name'] for c in bench['workloads']]}")


def metrics_for(bench: Dict[str, Any], cell: str
                ) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """(end-to-end, per-layer) metrics this cell reports: those that list it
    under ``workloads``; without the key (the contract's reading) every
    end-to-end metric, and every per-layer metric whose ``moves`` the cell
    reports."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"]
              if (cell in m["workloads"] if "workloads" in m
                  else m["moves"] in moved)]
    return e2e, layers


def load_check(workload: Dict[str, Any], cell: str) -> Dict[str, Any]:
    """The cell's ``correct`` block: every cell names a reference and the
    limit of each number it compares."""
    check = workload.get("correct") or {}
    if not check.get("reference") or not isinstance(check.get("limits"), dict):
        raise ValueError(
            f"workloads/{cell}.json needs \"correct\": {{\"reference\": "
            "<file under references/>, \"limits\": {number: limit}}: no "
            "cell runs without a comparison")
    return check


def keep_going(elapsed: float, seconds: float) -> bool:
    """A new step starts while fewer than ``seconds`` have elapsed."""
    return elapsed < seconds


# ---------------------------------------------------------------------------
# what entries and readers see
# ---------------------------------------------------------------------------
class Run:
    """One run's shared state: handed to the entry (``ctx``) and, after the
    window, to the metrics' readers (``r``)."""

    def __init__(self, cfg, table, cols, listener, seconds):
        self.cfg, self.table, self.cols = cfg, table, cols
        self.listener, self.seconds = listener, seconds
        self.state: Dict[str, Any] = {}
        self.counts: Dict[str, float] = {}
        # filled after set-up / after the window
        self.setup_s = 0.0
        self.steps: List[Tuple[float, float]] = []
        self.window_s = 0.0
        self.work_per_step = 0.0
        self.walls: Dict[str, float] = {}
        self.shapes: Dict[str, Any] = {}
        self.peaks: Dict[str, float] = {}
        self.trace: Optional[Dict[str, Any]] = None

    # -- for entries ---------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    def count(self, name: str, n: float) -> None:
        self.counts[name] = self.counts.get(name, 0.0) + float(n)

    # -- for readers ---------------------------------------------------------
    @property
    def n_steps(self) -> int:
        return len(self.steps)

    def stage_wall(self, *keys: str) -> Optional[float]:
        """Seconds per step of the stages named; None when the window
        recorded none of them."""
        hit = [v for k, v in self.walls.items() if k in keys]
        return sum(hit) / self.n_steps if hit and self.n_steps else None


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_PROCESS:.1f}s] {msg}",
          file=sys.stderr, flush=True)


def emit(**facts) -> None:
    print(json.dumps(facts, default=float), flush=True)


# ---------------------------------------------------------------------------
def run(args, bench_dir: str = HERE, benchmark_json: Optional[str] = None,
        look_for_chip: bool = True) -> int:
    """One run.  ``look_for_chip=False`` is for the tests under
    ``tests/benchmarks`` alone, which drive the rest of a run on the CPU;
    no command-line switch reaches it."""
    bench = load_json(benchmark_json or os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(bench, args.workload)
    workload = load_json(os.path.join(bench_dir, "workloads", cell["name"] + ".json"))
    check = load_check(workload, cell["name"])
    cfg = load_json(os.path.join(bench_dir, "configs", cell["config"] + ".json"))
    entry = load_module("entries", workload["entry"], bench_dir)
    rehearse = args.rehearse_rows is not None
    if rehearse:
        cfg = entry.rehearsal_config(cfg, args.rehearse_rows)
    table = load_module("tables", cfg["table"]["maker"], bench_dir)
    reference = load_module("references", check["reference"], bench_dir)
    e2e, layer_metrics = metrics_for(bench, cell["name"])

    # ---- the device --------------------------------------------------------
    import jax

    from benchmarks import compare, ops_count, program, trace_reduce

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu" and device["count"] >= int(cell["chips"])
    if not on_chip and look_for_chip and not rehearse:
        print(f"benchmarks/run.py: {cell['name']} needs {cell['chips']} TPU "
              f"chip(s), JAX selected {device}; pass --rehearse-rows N to "
              "walk the control flow", file=sys.stderr)
        return 2
    from transmogrifai_tpu.utils import backend
    from transmogrifai_tpu.utils.listener import OpListener

    cache_dir = backend.compile_cache_dir()
    compiles = program.CompileCounter()
    emit(phase="start", workload=cell["name"], config=cell["config"],
         seed=args.seed, device=device, jax_cache_dir=cache_dir,
         rehearse=rehearse)

    # ---- set-up ------------------------------------------------------------
    listener = OpListener(app_name="benchmark", collect_stage_metrics=True)
    with jax.profiler.TraceAnnotation("bench.setup.table"):
        cols = table.make(cfg, args.seed)
    ctx = Run(cfg, table, cols, listener, args.seconds)
    log("table made; set-up")
    with listener.install():
        entry.setup(ctx)
    setup_walls = program.stage_walls(listener)
    listener.metrics.stage_metrics.clear()
    ctx.counts.clear()
    setup_compiles = compiles.snapshot()
    gc.collect()
    ctx.setup_s = setup_s = time.perf_counter() - T_PROCESS
    emit(phase="setup", setup_s=setup_s, jax_compiles=setup_compiles,
         stage_walls_s=setup_walls)
    log(f"set-up {setup_s:.1f}s; window of {args.seconds}s")

    # ---- the window: whole steps -------------------------------------------
    tracing = bool(args.trace)
    trace_overhead = 0.0
    failure: Optional[str] = None
    steps: List[Tuple[float, float]] = []
    attempted = 0
    t0 = time.perf_counter()
    with listener.install():
        while keep_going(time.perf_counter() - t0 - trace_overhead, args.seconds):
            first = attempted == 0
            if tracing and first:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                t = time.perf_counter()
                jax.profiler.start_trace(
                    TRACE_DIR, profiler_options=_profile_options(jax))
                trace_overhead += time.perf_counter() - t
            attempted += 1
            s0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(STEP_SPAN):
                    entry.step(ctx)
                steps.append((s0 - t0, time.perf_counter() - t0))
            except Exception as e:  # noqa: BLE001 — a failed step is a result
                failure = f"step {attempted}: {type(e).__name__}: {e}"
                traceback.print_exc()
            finally:
                if tracing and first:
                    t = time.perf_counter()
                    jax.profiler.stop_trace()
                    trace_overhead += time.perf_counter() - t
            if failure:
                break
    window_s = time.perf_counter() - t0 - trace_overhead
    now = compiles.snapshot()
    in_window = {k: now[k] - setup_compiles[k] for k in now}
    # a program read back from the persistent cache is a stall the program
    # itself causes (train() re-jits its transform program for every new set
    # of fitted stages) and is reported; one that COMPILES fails the run.
    # With the persistent cache off (CPU rehearsal) the two cannot be told
    # apart, so the guard needs the cache.
    if in_window["compiled"] and cache_dir and not failure:
        failure = f"{in_window['compiled']} program(s) compiled inside the window"
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    ctx.steps, ctx.window_s = steps, window_s
    ctx.walls = program.stage_walls(listener)
    emit(phase="window", window_s=window_s, steps=steps, attempted=attempted,
         trace_overhead_s=trace_overhead, jax_compiles=in_window,
         counts=ctx.counts, stage_walls_s=ctx.walls,
         memory_peak_bytes=memory_peak, failure=failure)
    log(f"window {window_s:.1f}s, {len(steps)} step(s)")

    # ---- what the last step produced; then drop the program's state --------
    answers = None
    if steps:
        answers, ctx.work_per_step = entry.answers(ctx), entry.work(ctx)
        ctx.shapes = entry.shapes(ctx)
    ctx.state.clear()
    gc.collect()

    # ---- metrics -----------------------------------------------------------
    metrics: Dict[str, Dict[str, Any]] = {}
    device_out = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if steps and tracing:
        read = trace_reduce.read_xplane(trace_reduce.find_xplane(TRACE_DIR))
        emit(phase="trace", inventory=read["inventory"],
             host_spans=sorted({n for n, _, _ in read["host_spans"]}),
             modules=sorted({n for d in read["devices"].values()
                             for n, _, _ in d["modules"]})[:40])
    if steps and failure is None and not rehearse:
        if not tracing:
            for m in e2e:
                v = load_module("end_to_end", m["name"], bench_dir).read(ctx)
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            ctx.peaks = ops_count.load_peaks(os.path.join(bench_dir, "peaks.json"),
                                             device["kind"])
            ctx.trace = trace_reduce.summarize(
                read, STEP_SPAN, fallback_window_s=steps[0][1] - steps[0][0])
            device_out["busy_s"] = ctx.trace["busy_s"]
            device_out["window_s"] = ctx.trace["window_s"]
            breakdown = ctx.trace["breakdown"]
            for m in layer_metrics:
                v = load_module("layers", m["name"], bench_dir).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if tracing:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # ---- correct -----------------------------------------------------------
    compared: Dict[str, Any] = {}
    correct = False
    if answers is not None and failure is None:
        t = time.perf_counter()
        nums, ctl = reference.numbers(answers, cols, cfg, check, args.seed,
                                      control=bool(args.control), emit=emit)
        correct, compared = compare.judge(nums, check["limits"])
        if ctl is not None:
            ok_ctl, tab = compare.judge(ctl, check["limits"])
            emit(phase="control", correct=ok_ctl, compared=tab)
        emit(phase="compare", seconds=time.perf_counter() - t)
    if rehearse:
        correct = False

    out = {"correct": bool(correct), "attempted": attempted,
           "failed": attempted - len(steps), "metrics": metrics,
           "device": device_out}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if failure:
        out["failure"] = failure
    if rehearse:
        out["rehearsal"] = True
    out["compared"] = {k: [v["value"], v["limit"]] for k, v in compared.items()}
    sys.stdout.flush()
    if failure:
        print(f"run failed: {failure}", file=sys.stderr)
    for k, v in compared.items():
        print(f"compared {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(f"correct = {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out, default=float), flush=True)
    return 1 if rehearse else 0


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="walk the control flow at this many rows on any "
                         "backend; ends correct: false, exit 1")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the control (the reference in the next "
                         "lower precision, in the program's place) on a "
                         "'phase: control' line; the result is unchanged")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
