"""100M-row multi-host scale proof: sharded ingestion feeding per-host
device-resident pipelines (PR 19), grown from scale10m.py.

The pipeline is scale10m's real product path unchanged (500 raw typed
features -> Transmogrifier defaults -> SanityChecker on the row-sharded
streaming stats path -> 64-candidate 5-fold selector).  What this harness
adds is the multi-host split:

- each host synthesizes/ingests ONLY its ``parallel.mesh.host_rows`` slice
  of the global row space (per-host rng seed — two hosts never produce the
  same rows), so 100M rows never exist on any single host;
- scaler/sanity-checker moments flow through the per-device -> per-host ->
  global merge tier in ``parallel/stats.py`` (Chan pairwise merges over
  ``process_allgather`` — nothing gathers raw rows to one host);
- the report carries PER-HOST phase walls and bytes ingested (gathered as a
  fixed-order f64 vector when ``host_count() > 1``; a plain single entry —
  zero collectives, zero overhead — when 1);
- a single-host run extrapolates the measured per-row cost to the 100M
  target under the linear-in-rows assumption the stats/stream tiers are
  built to satisfy, so one proxy host predicts the fleet wall it is sized
  against (``projected``, honestly labelled as an extrapolation).

Rows default to 100M; ``TMOG_SCALE_ROWS`` overrides (CI smoke uses ~10k).
Emits one schema-versioned JSON line on stdout, appends the same line to
``SCALE100M.jsonl`` (repo-hygiene CI refuses to let that artifact land in
git), and writes the standard obs run-record.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# scale10m reads the same envs at import; default THIS harness to 100M
os.environ.setdefault("TMOG_SCALE_ROWS", str(100_000_000))

import scale10m  # noqa: E402  (shares synthesize/build and the env knobs)

TARGET_ROWS = 100_000_000
N_ROWS = scale10m.N_ROWS
FOLDS = scale10m.FOLDS

#: bump when the JSONL row layout changes (consumers tolerate unknown keys)
RECORD_SCHEMA_VERSION = 1


def dataset_bytes(df) -> int:
    """Honest ingested-bytes estimate for one host's Dataset: exact array
    bytes for numeric columns, sampled mean string length x rows for object
    columns (an O(n) exact walk over 100M-row categorical columns would
    cost more than the ingest it measures)."""
    total = 0
    for col in df.columns.values():
        v = getattr(col, "values", None)
        if v is None:
            continue
        v = np.asarray(v)
        if v.dtype == object:
            n = v.shape[0]
            if n:
                k = min(n, 1024)
                idx = np.linspace(0, n - 1, k).astype(np.int64)
                mean_len = float(np.mean([len(str(v[i])) for i in idx]))
                total += int(mean_len * n)
        else:
            total += int(v.nbytes)
        m = getattr(col, "mask", None)
        if m is not None:
            total += int(np.asarray(m).nbytes)
    return total


def _gather_host_rows_f64(vec):
    """All hosts' copies of a fixed-order f64 vector (ordered by host
    index); the single-host fast path never touches a collective."""
    from transmogrifai_tpu.parallel import mesh

    if mesh.host_count() <= 1:
        return [np.asarray(vec, np.float64)]
    from transmogrifai_tpu.parallel import stats

    return stats._cross_host_gather(np.asarray(vec, np.float64),
                                    kind="scale100m_walls")


def main():
    from transmogrifai_tpu.utils.backend import compile_cache_dir, require_tpu

    dev = require_tpu("scale100m")
    compile_cache_dir()
    from transmogrifai_tpu.parallel import mesh
    from transmogrifai_tpu.utils.listener import OpListener

    H = mesh.host_count()
    h = mesh.host_index()
    lo, hi = mesh.host_rows(N_ROWS, index=h, count=H)
    n_local = hi - lo

    def log(msg):
        print(f"[scale100m h{h}/{H} +{time.perf_counter() - t_start:.0f}s] "
              f"{msg}", file=sys.stderr, flush=True)

    t_start = time.perf_counter()
    phases = {}
    log(f"platform={dev['platform']} kind={dev['kind']} "
        f"devices={dev['count']} rows={N_ROWS} local_rows={n_local} "
        f"range=[{lo},{hi})")

    t0 = time.perf_counter()
    # per-host seed: host h's slice is distinct but reproducible
    df = scale10m.synthesize(n_local, seed=[7, h])
    phases["generate_s"] = round(time.perf_counter() - t0, 2)
    bytes_ingested = dataset_bytes(df)
    log(f"synthesized {n_local} local rows "
        f"(~{bytes_ingested / 1e9:.2f} GB ingested)")

    t0 = time.perf_counter()
    wf, n_cands = scale10m.build(df)
    listener = OpListener(app_name="scale100m", collect_stage_metrics=True)
    with listener.install():
        model = wf.train()
    phases["train_s"] = round(time.perf_counter() - t0, 2)
    log("train done")

    stage_times = scale10m.stage_times(listener)
    best_model = None
    for st in model.stages:
        s = getattr(st, "summary", None)
        if s is not None and getattr(s, "best_model_name", None):
            best_model = s.best_model_name
    sweep_s = next((v for k, v in stage_times.items()
                    if "odelSelector" in k and k.endswith(".fit")), None)

    # per-host walls: one fixed-order vector per host, gathered when H > 1
    wall = time.perf_counter() - t_start
    gathered = _gather_host_rows_f64([
        float(h), float(n_local), float(bytes_ingested),
        phases["generate_s"], phases["train_s"], wall])
    per_host = {}
    for row in gathered:
        per_host[str(int(row[0]))] = {
            "rows": int(row[1]), "bytes_ingested": int(row[2]),
            "generate_s": round(float(row[3]), 2),
            "train_s": round(float(row[4]), 2),
            "wall_s": round(float(row[5]), 2),
        }

    metric = ("scale100m_train_wall_clock" if N_ROWS >= TARGET_ROWS
              else f"scale_smoke_{N_ROWS}_rows_train_wall_clock")
    out = {
        "schema_version": RECORD_SCHEMA_VERSION,
        "metric": metric,
        "value": phases["train_s"],
        "unit": "s",
        "rows": N_ROWS,
        "raw_features": scale10m.N_NUM + scale10m.N_CAT,
        "platform": dev["platform"], "device_kind": dev["kind"],
        "device_count": dev["count"],
        "host_count": H, "host_index": h,
        "host_rows": [lo, hi],
        "phases": phases,
        "per_host": per_host,
        "stage_times_s": stage_times,
        "sweep_candidates": n_cands, "folds": FOLDS,
        "models_trained": n_cands * FOLDS,
        "sweep_s": sweep_s,
        "best_model": best_model,
    }

    # single-host proxy runs predict the fleet: scale the measured per-row
    # train cost to the 100M target and divide across candidate host counts.
    # Labelled an EXTRAPOLATION — it assumes the row-linear phases dominate
    # (true of ingest/stats/stream; the fixed 64x5 sweep on the capped
    # training sample is a constant term, so the projection is pessimistic).
    if H == 1 and N_ROWS < TARGET_ROWS and N_ROWS > 0:
        per_row_s = phases["train_s"] / N_ROWS
        proj = per_row_s * TARGET_ROWS
        out["projected"] = {
            "kind": "linear_extrapolation",
            "target_rows": TARGET_ROWS,
            "measured_rows": N_ROWS,
            "measured_train_s": phases["train_s"],
            "projected_train_s_by_hosts": {
                str(n): round(proj / n, 1) for n in (1, 2, 4, 8, 16)},
        }

    line = json.dumps(out)
    print(line)
    # every host appends its own line (host-suffixed file under multi-host
    # so concurrent writers never interleave)
    suffix = "" if H == 1 else f".h{h}"
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"SCALE100M{suffix}.jsonl")
    with open(path, "a") as f:
        f.write(line + "\n")
    from transmogrifai_tpu import obs

    obs.write_record("scale", extra={"report": out})


if __name__ == "__main__":
    main()
