"""Roofline launch ledger — joins per-launch wall time with FLOPs + bytes.

``utils/flops.py`` counts FLOPs (and, since this module landed, bytes
accessed) per compiled program; the timeline (obs/timeline.py) attributes
wall time to bubble buckets.  Neither can say *why* a given launch is slow.
The ledger joins the two, one row per device launch:

    kernel family | shard | wall_s | flops | bytes | GFLOP/s | GB/s |
    arithmetic intensity | bound label

and classifies each row against the device roofline
(``utils/backend.device_peaks``):

* ``compute-bound`` — the compute roof ``flops/peak_flops`` dominates and
  the launch actually spends a meaningful fraction of its wall there;
* ``memory-bound``  — the HBM roof ``bytes/peak_bw`` dominates instead;
* ``launch-bound``  — both roofs are tiny next to the measured wall
  (``max(roof) < TMOG_LAUNCH_BOUND_FRAC x wall``, default 0.1): dispatch /
  host overhead dominates, the regime ROADMAP item 1 predicts for the
  sweep.  CPU hosts have no table entry and degrade to this label too —
  calibrate via ``TMOG_PEAK_FLOPS`` / ``TMOG_PEAK_HBM_GBPS`` to get real
  classification off-TPU.  A TPU kind missing from the table is an error,
  not this label.

On top of the rows, :func:`ledger_report` factors the headline MFU per
family as ``mfu_f = compute_fraction_f x achieved_f / peak`` where
``compute_fraction_f = wall_f / window_wall`` (on multi-shard launches the
per-family walls sum lane-seconds, so fractions can exceed 1.0 — that is
"average busy lanes", not an error) — so BENCH can finally say which lever
(pipelining, candidate packing, bf16) each family needs.

Disabled-path contract (same as obs/trace.py): :func:`get` returns a shared
no-op singleton when the ledger is off — one module-global boolean check
per hook, zero allocation, so production hot paths pay nothing.

No jax import at module level: the CLI (``python -m
transmogrifai_tpu.obs.ledger trace.json``) must run light over exported
files.
"""
from __future__ import annotations

import json
import threading
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from ..utils import env as _env
from ..utils.backend import device_peaks
from . import registry as _registry

SCHEMA = "tmog.launch_ledger"
SCHEMA_VERSION = 1

#: roof < frac x wall on BOTH axes => the launch is dominated by dispatch
#: overhead, not by the device.  Override via TMOG_LAUNCH_BOUND_FRAC.
LAUNCH_BOUND_FRAC = 0.1

BOUND_LABELS = ("compute-bound", "memory-bound", "launch-bound")

#: snapshot providers must stay bounded; keep the newest rows only
_SNAPSHOT_ROWS = 256


# --------------------------------------------------------------------------
# collection: live ledger + shared no-op singleton
# --------------------------------------------------------------------------

class _NullLedger:
    """Shared do-nothing ledger handed out while collection is disabled.

    Mirrors trace._NullSpan: no per-call allocation, ``enabled`` is a class
    attribute so hooks can guard extra work with one attribute load.
    """

    __slots__ = ()
    enabled = False

    def now(self) -> float:          # hooks call now() unconditionally;
        return 0.0                   # the null clock is free

    def launch(self, *args: Any, **kwargs: Any) -> None:
        return None

    def rows(self) -> List[Dict[str, Any]]:
        return []

    def reset(self) -> None:
        return None


_NULL = _NullLedger()


class LaunchLedger:
    """Thread-safe row collector: one row per device launch."""

    enabled = True

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._rows: List[Dict[str, Any]] = []

    def now(self) -> float:
        import time

        return time.perf_counter()

    def launch(self, kernel: str, wall_s: float = 0.0, flops: float = 0.0,
               bytes: float = 0.0, families: Optional[Dict[str, float]] = None,
               shard: Optional[int] = None, device: Optional[str] = None,
               **attrs: Any) -> None:
        """Record one launch.

        ``families`` maps family label (LR/RF/XGB/...) -> fraction of this
        launch's work; it is normalized here so downstream splits always sum
        exactly to the row totals.
        """
        fams = dict(families) if families else {"other": 1.0}
        tot = sum(v for v in fams.values() if v > 0)
        if tot <= 0:
            fams = {k: 1.0 / len(fams) for k in fams}
        else:
            fams = {k: max(v, 0.0) / tot for k, v in fams.items()}
        row = {"kernel": str(kernel), "wall_s": float(wall_s),
               "flops": float(flops), "bytes": float(bytes),
               "families": fams}
        if shard is not None:
            row["shard"] = shard
        if device is not None:
            row["device"] = str(device)
        if attrs:
            row.update(attrs)
        with self._lock:
            self._rows.append(row)

    def rows(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(r) for r in self._rows]

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()


_LIVE = LaunchLedger()
_enabled = bool(_env.env_flag("TMOG_LEDGER", False))


def get():
    """The one hook entry point: live ledger when enabled, else the shared
    no-op singleton.  One module-global boolean check, no allocation."""
    return _LIVE if _enabled else _NULL


def enable() -> None:
    """Turn on launch collection; also enables FLOPs/bytes accounting
    (utils/flops) since a ledger without cost data is just a stopwatch."""
    global _enabled
    _enabled = True
    try:
        from ..utils import flops as _flops

        _flops.enable()
    except Exception:  # keep the ledger usable even if accounting is broken
        pass


def disable() -> None:
    """Stop collecting.  Leaves utils/flops as-is (other consumers may be
    using it) and keeps collected rows until :func:`reset`."""
    global _enabled
    _enabled = False


def reset() -> None:
    _LIVE.reset()


def rows() -> List[Dict[str, Any]]:
    return _LIVE.rows()


# --------------------------------------------------------------------------
# roofline classification
# --------------------------------------------------------------------------

def _frac() -> float:
    return _env.env_float("TMOG_LAUNCH_BOUND_FRAC", LAUNCH_BOUND_FRAC)


def classify_launch(wall_s: float, flops: float, bytes: float,
                    peak_flops: Optional[float],
                    peak_hbm_gbps: Optional[float],
                    launch_bound_frac: Optional[float] = None
                    ) -> Tuple[str, float, float]:
    """Label one launch against the roofline.

    Returns ``(label, t_compute_s, t_memory_s)`` where the t_* are the
    idealized times at each roof.  Missing peaks give zero roofs, hence
    ``launch-bound`` — the honest answer when we have no roof to compare
    against (documented CPU-proxy behavior).
    """
    frac = _frac() if launch_bound_frac is None else launch_bound_frac
    t_c = flops / peak_flops if peak_flops else 0.0
    t_m = bytes / (peak_hbm_gbps * 1e9) if peak_hbm_gbps else 0.0
    roof = max(t_c, t_m)
    if wall_s <= 0.0 or roof < frac * wall_s:
        return "launch-bound", t_c, t_m
    if t_c >= t_m:
        return "compute-bound", t_c, t_m
    return "memory-bound", t_c, t_m


def _split_exact(total: float, fractions: Dict[str, float]) -> Dict[str, float]:
    """Split ``total`` by ``fractions`` with the last (sorted) family taking
    the remainder, so the shares sum back to ``total`` bit-exactly — the
    invariant the reconciliation tests (and the acceptance criteria) assert.
    """
    fams = sorted(fractions)
    out: Dict[str, float] = {}
    acc = 0.0
    for f in fams[:-1]:
        v = total * fractions[f]
        out[f] = v
        acc += v
    out[fams[-1]] = total - acc
    return out


# --------------------------------------------------------------------------
# report
# --------------------------------------------------------------------------

def ledger_report(rows: Optional[Sequence[Dict[str, Any]]] = None,
                  window_wall_s: Optional[float] = None,
                  device_kind: Optional[str] = None,
                  platform: Optional[str] = None,
                  peak_flops: Optional[float] = None,
                  peak_hbm_gbps: Optional[float] = None,
                  reps: int = 1) -> Dict[str, Any]:
    """Aggregate ledger rows into the roofline + MFU-decomposition report.

    ``rows`` defaults to the live ledger.  ``window_wall_s`` is the
    measurement window (e.g. the ``bench.window`` span); when omitted the
    per-launch walls are summed — correct for sequential launches, an
    overestimate for concurrent shards.  Explicit ``peak_flops`` /
    ``peak_hbm_gbps`` override the ``device_kind`` table lookup (tests
    inject synthetic peaks this way).
    """
    if rows is None:
        rows = _LIVE.rows()
    rows = list(rows)
    if not rows:
        raise ValueError("ledger is empty — nothing to report "
                         "(enable the ledger before the launches run)")
    peaks = device_peaks(device_kind, platform)
    if peak_flops is not None:
        peaks["peak_flops"] = peak_flops
    if peak_hbm_gbps is not None:
        peaks["peak_hbm_gbps"] = peak_hbm_gbps
    pf, bw = peaks["peak_flops"], peaks["peak_hbm_gbps"]

    launches: List[Dict[str, Any]] = []
    fam_agg: Dict[str, Dict[str, Any]] = {}
    bound_counts = {k: 0 for k in BOUND_LABELS}
    for r in rows:
        wall = float(r.get("wall_s", 0.0))
        fl = float(r.get("flops", 0.0))
        by = float(r.get("bytes", 0.0))
        label, t_c, t_m = classify_launch(wall, fl, by, pf, bw)
        bound_counts[label] += 1
        out = dict(r)
        out["gflops"] = fl / wall / 1e9 if wall > 0 else None
        out["gbps"] = by / wall / 1e9 if wall > 0 else None
        out["intensity"] = fl / by if by > 0 else None
        out["bound"] = label
        out["t_compute_s"] = t_c
        out["t_memory_s"] = t_m
        launches.append(out)
        fams = r.get("families") or {"other": 1.0}
        share_f = _split_exact(fl, fams)
        share_b = _split_exact(by, fams)
        share_w = _split_exact(wall, fams)
        for fam in share_f:
            agg = fam_agg.setdefault(fam, {"launches": 0, "wall_s": 0.0,
                                           "flops": 0.0, "bytes": 0.0,
                                           "bounds": {k: 0 for k in
                                                      BOUND_LABELS}})
            agg["launches"] += 1
            agg["wall_s"] += share_w[fam]
            agg["flops"] += share_f[fam]
            agg["bytes"] += share_b[fam]
            agg["bounds"][label] += 1

    total_wall = sum(float(r.get("wall_s", 0.0)) for r in rows)
    total_flops = sum(float(r.get("flops", 0.0)) for r in rows)
    total_bytes = sum(float(r.get("bytes", 0.0)) for r in rows)
    window = float(window_wall_s) if window_wall_s else total_wall

    by_family: Dict[str, Dict[str, Any]] = {}
    for fam in sorted(fam_agg):
        a = fam_agg[fam]
        w, fl, by = a["wall_s"], a["flops"], a["bytes"]
        dominant = max(a["bounds"], key=lambda k: (a["bounds"][k], k))
        by_family[fam] = {
            "launches": a["launches"], "wall_s": w, "flops": fl, "bytes": by,
            "gflops": fl / w / 1e9 if w > 0 else None,
            "gbps": by / w / 1e9 if w > 0 else None,
            "intensity": fl / by if by > 0 else None,
            "bound": dominant, "bounds": a["bounds"],
        }

    mfu_by_family: Dict[str, Dict[str, Any]] = {}
    for fam, a in by_family.items():
        w, fl = a["wall_s"], a["flops"]
        cf = w / window if window > 0 else 0.0
        achieved = fl / w if w > 0 else 0.0
        over_roof = achieved / pf if pf else None
        mfu_by_family[fam] = {
            "flops": fl, "wall_s": w,
            "compute_fraction": cf,
            "achieved_gflops": achieved / 1e9,
            "achieved_over_roof": over_roof,
            "mfu": cf * over_roof if over_roof is not None else None,
        }
    mfu = total_flops / window / pf if (pf and window > 0) else None

    n = len(rows)
    return {
        "schema": SCHEMA, "schema_version": SCHEMA_VERSION,
        "device_kind": device_kind, "platform": platform,
        "peak_flops": pf, "peak_hbm_gbps": bw,
        "launch_bound_frac": _frac(),
        "reps": reps,
        "launches": launches,
        "n_launches": n,
        "bound_counts": bound_counts,
        "launch_bound_fraction": bound_counts["launch-bound"] / n,
        "totals": {"wall_s": total_wall, "flops": total_flops,
                   "bytes": total_bytes,
                   "intensity": (total_flops / total_bytes
                                 if total_bytes > 0 else None)},
        "by_family": by_family,
        "mfu_decomposition": {
            "window_wall_s": window, "flops": total_flops,
            "peak_flops": pf, "mfu": mfu,
            "by_family": mfu_by_family,
            "residual_fraction": max(0.0, 1.0 - sum(
                v["compute_fraction"] for v in mfu_by_family.values())),
        },
    }


def _fmt(v: Optional[float], spec: str = "9.3f") -> str:
    return format(v, spec) if v is not None else " " * (int(spec.split(".")[0]) - 1) + "-"


def format_report(report: Dict[str, Any]) -> str:
    """Human-readable roofline table, by family, plus the MFU factoring."""
    lines: List[str] = []
    pf, bw = report.get("peak_flops"), report.get("peak_hbm_gbps")
    roof = (f"peak {pf / 1e12:.0f} TFLOP/s, {bw:.0f} GB/s" if pf and bw
            else "no roofline peaks for this device kind "
                 "(set TMOG_PEAK_FLOPS / TMOG_PEAK_HBM_GBPS)")
    lines.append(f"roofline ledger: {report['n_launches']} launches, {roof}")
    lines.append(f"{'family':>8} {'launches':>8} {'wall_s':>9} "
                 f"{'GFLOP/s':>9} {'GB/s':>9} {'flops/B':>9} bound")
    for fam, a in report["by_family"].items():
        lines.append(f"{fam:>8} {a['launches']:>8d} {a['wall_s']:>9.4f} "
                     f"{_fmt(a['gflops'])} {_fmt(a['gbps'])} "
                     f"{_fmt(a['intensity'])} {a['bound']}")
    bc = report["bound_counts"]
    lines.append("bounds: " + "  ".join(f"{k}={bc[k]}" for k in BOUND_LABELS)
                 + f"  launch_bound_fraction={report['launch_bound_fraction']:.2f}")
    dec = report["mfu_decomposition"]
    mfu = dec.get("mfu")
    head = (f"mfu={mfu * 100:.2f}%" if mfu is not None else "mfu=n/a (no peak)")
    lines.append(f"mfu decomposition over window {dec['window_wall_s']:.4f}s: "
                 f"{head}")
    for fam, v in dec["by_family"].items():
        tail = (f"x {v['achieved_over_roof'] * 100:.3f}% of roof "
                f"-> mfu {v['mfu'] * 100:.3f}%"
                if v["achieved_over_roof"] is not None
                else f"@ {v['achieved_gflops']:.2f} GFLOP/s (no roof)")
        lines.append(f"  {fam:>8}: compute_fraction {v['compute_fraction']:.3f} "
                     + tail)
    if dec["by_family"]:
        lines.append(f"  residual (idle/prep): "
                     f"{dec['residual_fraction'] * 100:.1f}% of window")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# offline join: rebuild rows from an exported Chrome trace (+ telemetry)
# --------------------------------------------------------------------------

def _complete(events: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [e for e in events
            if e.get("ph") == "X"
            and isinstance(e.get("ts"), (int, float))
            and isinstance(e.get("dur"), (int, float))]


def rows_from_trace(events: Iterable[Dict[str, Any]],
                    flops_totals: Optional[Dict[str, Any]] = None
                    ) -> List[Dict[str, Any]]:
    """Best-effort ledger rows from an exported trace.

    Pairs each ``sweep.dispatch`` span with the next ``sweep.gather`` on the
    same lane (wall = gather_end - dispatch_start: the full device round
    trip), and attributes FLOPs/bytes from the telemetry ``by_device``
    buckets when available (uniform per-launch split otherwise).  Offline
    rows carry family "sweep" — the per-candidate family split needs the
    live costmodel features and is only available in-process.  Stream pulls
    and serve batches become flops-free rows so their bytes traffic shows
    up on the memory axis.
    """
    evs = _complete(events)
    acct = flops_totals or {}
    by_dev = acct.get("by_device") or {}
    by_fn = acct.get("by_fn") or {}
    sweep_fl = sum(v.get("flops", 0.0) for k, v in by_fn.items()
                   if k.startswith("sweep.run"))
    sweep_by = sum(v.get("bytes", 0.0) for k, v in by_fn.items()
                   if k.startswith("sweep.run"))

    lanes: Dict[Any, List[Dict[str, Any]]] = {}
    for e in evs:
        lanes.setdefault((e.get("pid"), e.get("tid")), []).append(e)

    dispatches: List[Dict[str, Any]] = []
    rows: List[Dict[str, Any]] = []
    for lane in lanes.values():
        lane.sort(key=lambda e: e["ts"])
        gathers = [e for e in lane if e["name"] == "sweep.gather"]
        used: set = set()
        for e in lane:
            nm, a = e["name"], (e.get("args") or {})
            if nm == "sweep.dispatch":
                wall = e["dur"] / 1e6
                gbytes = 0.0
                for i, g in enumerate(gathers):
                    if i in used or g["ts"] < e["ts"]:
                        continue
                    used.add(i)
                    wall = (g["ts"] + g["dur"] - e["ts"]) / 1e6
                    gbytes = float((g.get("args") or {}).get("bytes", 0.0))
                    break
                dispatches.append({
                    "kernel": ("sweep.run_scores+metrics" if a.get("split")
                               else "sweep.run"),
                    "wall_s": wall, "gather_bytes": gbytes,
                    "shard": a.get("shard", a.get("column")),
                    "device": a.get("device"),
                })
            elif nm in ("stream.chunk.pull", "stream.chunk.upload"):
                rows.append({"kernel": nm, "wall_s": e["dur"] / 1e6,
                             "flops": 0.0,
                             "bytes": float(a.get("bytes", 0.0)),
                             "families": {"stream": 1.0}})
            elif nm == "serve.batch":
                rows.append({"kernel": nm, "wall_s": e["dur"] / 1e6,
                             "flops": 0.0, "bytes": 0.0,
                             "families": {"serve": 1.0}})

    if dispatches:
        # per-device attribution when the telemetry has per-device buckets,
        # else a uniform split of the sweep totals across launches
        ndev: Dict[Any, int] = {}
        for d in dispatches:
            ndev[d["device"]] = ndev.get(d["device"], 0) + 1
        for d in dispatches:
            dev = d["device"]
            bucket = by_dev.get(dev) if dev is not None else None
            if bucket:
                fl = bucket.get("flops", 0.0) / ndev[dev]
                by = bucket.get("bytes", 0.0) / ndev[dev]
            else:
                fl = sweep_fl / len(dispatches)
                by = sweep_by / len(dispatches)
            row = {"kernel": d["kernel"], "wall_s": d["wall_s"],
                   "flops": fl, "bytes": by or d["gather_bytes"],
                   "families": {"sweep": 1.0}}
            if d["shard"] is not None:
                row["shard"] = d["shard"]
            if d["device"] is not None:
                row["device"] = d["device"]
            rows.append(row)
    return rows


def _window_wall_s(evs: List[Dict[str, Any]],
                   window: Optional[str]) -> Optional[float]:
    names = [window] if window else ["bench.window", "profile.window"]
    for name in names:
        for e in reversed(evs):
            if e["name"] == name:
                return e["dur"] / 1e6
    if window:
        raise ValueError(f"window span {window!r} not found in trace")
    if not evs:
        return None
    t0 = min(e["ts"] for e in evs)
    t1 = max(e["ts"] + e["dur"] for e in evs)
    return (t1 - t0) / 1e6


def _latest_flops_totals(telemetry_path: str) -> Optional[Dict[str, Any]]:
    """Newest telemetry row carrying a flops snapshot with by_fn data."""
    best = None
    try:
        with open(telemetry_path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                snap = (row.get("snapshot") or {}).get("flops") or \
                    (row.get("extra") or {}).get("flops") or {}
                if snap.get("by_fn"):
                    best = snap
    except OSError:
        return None
    return best


def _main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m transmogrifai_tpu.obs.ledger",
        description="Render a roofline launch-ledger report from an "
                    "exported Chrome trace (+ optional telemetry JSONL "
                    "for the FLOPs/bytes join).")
    ap.add_argument("trace", help="trace JSON written by obs.trace.export")
    ap.add_argument("--telemetry", default="",
                    help="telemetry JSONL; the newest row with a flops "
                         "snapshot supplies the FLOPs/bytes buckets")
    ap.add_argument("--window", default=None,
                    help="span name bounding the window (default: "
                         "bench.window / profile.window, else event hull)")
    ap.add_argument("--device-kind", default=None,
                    help="device kind for the peak table (default: env "
                         "overrides only)")
    ap.add_argument("--out", default="",
                    help="also write the report dict as JSON here")
    args = ap.parse_args(argv)

    with open(args.trace) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    evs = _complete(events)
    totals = _latest_flops_totals(args.telemetry) if args.telemetry else None
    ledger_rows = rows_from_trace(evs, totals)
    if not ledger_rows:
        print("no launch spans (sweep.dispatch / stream.chunk.* / "
              "serve.batch) in trace — nothing to report")
        return 0
    report = ledger_report(rows=ledger_rows,
                           window_wall_s=_window_wall_s(evs, args.window),
                           device_kind=args.device_kind)
    print(format_report(report))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.out}")
    return 0


def _snapshot() -> Dict[str, Any]:
    r = _LIVE.rows()
    return {"enabled": _enabled, "n_rows": len(r),
            "rows": r[-_SNAPSHOT_ROWS:]}


_registry.register_provider("ledger", _snapshot)


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    raise SystemExit(_main())
