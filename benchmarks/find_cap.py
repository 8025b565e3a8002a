#!/usr/bin/env python3
"""How the configuration's ``max_training_sample`` (C) was chosen: step time
and the device memory the SWEEP ALONE holds, cap by cap.

    python3 benchmarks/find_cap.py --prepare .bench_columns.pkl --seed 7
    python3 benchmarks/find_cap.py --columns .bench_columns.pkl --caps 180224,196608

Two processes, because a process's peak never falls: the first streams the
transforms (whose chunk buffers set ITS peak) and writes the prepared label
and vector columns; the second loads them without streaming and reads
``peak_bytes_in_use`` after the selector fits at each cap, ascending — what
the timed window of the ``selector_fit`` entry holds on the device.  Per cap:
one fit that compiles, one that is timed.  Needs the TPU; prints one JSON
line per cap.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import pickle
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="scale-500")
    ap.add_argument("--prepare", help="write the prepared columns here and stop")
    ap.add_argument("--columns", help="prepared columns written by --prepare")
    ap.add_argument("--caps", default="", help="comma-separated, ascending")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import jax

    from benchmarks import program, run as bench_run
    from transmogrifai_tpu.columns import Dataset
    from transmogrifai_tpu.utils import backend

    dev = backend.require_tpu("find_cap")
    backend.compile_cache_dir()
    cfg = bench_run.load_json(
        os.path.join(ROOT, "benchmarks", "configs", args.config + ".json"))
    table = bench_run.load_module("tables", cfg["table"]["maker"])
    d0 = jax.devices()[0]

    def stats():
        s = d0.memory_stats() or {}
        return {"peak_bytes": s.get("peak_bytes_in_use"),
                "bytes_limit": s.get("bytes_limit"),
                "peak_share": s.get("peak_bytes_in_use", 0) / s["bytes_limit"]
                if s.get("bytes_limit") else None}

    if args.prepare:
        cols = table.make(cfg, args.seed)
        wf, _, label, vec = program.build_workflow(
            cfg, program.to_dataset(cols, table), table)
        data = wf.compute_data_up_to(vec, label)
        with open(args.prepare, "wb") as f:
            pickle.dump(Dataset({n: data[n] for n in (label.name, vec.name)}), f)
        print(json.dumps(dict(stats(), phase="prepared", device=dev)), flush=True)
        return 0

    # the workflow is built over a few rows only: the selector stage is all
    # this process uses of it, and nothing is streamed
    cols = table.make(dict(cfg, rows=1000), args.seed)
    _, sel, _, _ = program.build_workflow(
        cfg, program.to_dataset(cols, table), table)
    with open(args.columns, "rb") as f:
        data = pickle.load(f)
    n_candidates = sum(len(g) for _, g in sel.models)
    print(json.dumps(dict(stats(), phase="loaded", device=dev)), flush=True)
    for cap in sorted(int(c) for c in args.caps.split(",")):
        sel.splitter.max_training_sample = cap
        t0 = time.perf_counter()
        sel.fit(data)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        sel.fit(data)
        step = time.perf_counter() - t0
        launches = program.check_sweep_record(program.sweep_record(), n_candidates)
        print(json.dumps(dict(stats(), cap=cap, first_fit_s=first, step_s=step,
                              launches=launches)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
