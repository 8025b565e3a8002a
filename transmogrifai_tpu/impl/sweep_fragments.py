"""Builders turning a selector candidate list into a fused sweep program.

The validator hands its ``candidates = [(estimator, grids), ...]`` list here;
``build_sweep_plan`` translates every family it understands into a static
spec fragment + dynamic f32 blob for ``ops/sweep.run_sweep`` — the
one-launch fold x grid sweep.  Families (or grids) outside the supported
surface return None and the validator keeps its legacy per-family path, so
custom estimators lose nothing.

Supported families (the full reference DEFAULT sweeps,
DefaultSelectorParams.scala:37-75) across all three problem types
(binary / multiclass / regression):

- OpLogisticRegression (binary sigmoid or multinomial softmax grids),
- OpLinearRegression (reg_param/elastic_net_param),
- OpLinearSVC (binary; reg_param) and
  OpMultilayerPerceptronClassifier (hidden_layers/max_iter/step_size/seed),
- OpRandomForestClassifier / OpDecisionTreeClassifier and the regressor
  twins — any grid over trees_common._FOREST_GRID_KEYS,
- OpGBTClassifier / OpXGBoostClassifier and the regressor twins — any grid
  over trees_common._DYNAMIC_BOOST_KEYS + static boosting shape.

Frontier sizing: with the bootstrap drawn on DEVICE the builder cannot read
the realized Poisson weight sums, so it bounds them: mean + 5 sigma of the
Poisson total on top of the fold-weight sum (P(exceed) < 3e-7 even per
group; on violation the kernel's count clamp would only trim the deepest
level's worst splits).  ``exact_cap`` is claimed only under that bound.
"""
from __future__ import annotations

import logging
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..ops import trees as Tr
from ..ops.metrics import (BINARY_METRICS, MULTICLASS_METRICS,
                           REGRESSION_METRICS)
from ..obs import trace
from ..utils import devcache
from .trees_common import (DEFAULT_MAX_FRONTIER, DEFAULT_MAX_FRONTIER_BOOSTED,
                           _DYNAMIC_BOOST_KEYS, _FOREST_GRID_KEYS,
                           effective_trees_per_round)

log = logging.getLogger(__name__)


class _Blob:
    """Append-only f32 parameter vector with static offsets."""

    def __init__(self):
        self.parts: List[np.ndarray] = []
        self.off = 0

    def add(self, values) -> int:
        arr = np.asarray(values, np.float32).ravel()
        off = self.off
        self.parts.append(arr)
        self.off += arr.size
        return off

    def pack(self) -> np.ndarray:
        if not self.parts:
            return np.zeros(1, np.float32)
        return np.concatenate(self.parts)


class SweepPlan:
    """A ready-to-run fused sweep: spec + arrays + metric bookkeeping.

    ``X_host`` / ``y_host`` / ``xb_bins`` keep the host-array identities and
    per-``xbs``-entry bin counts so the multi-chip path can place (and
    devcache) per-device copies; ``n_rows`` / ``n_features`` feed the static
    per-fragment cost model (``spec_units``).
    """

    def __init__(self, spec, X, xbs, y, blob, problem, X_host=None,
                 y_host=None, xb_bins=None):
        self.spec = spec
        self.X = X
        self.xbs = xbs
        self.y = y
        self.blob = blob
        self.problem = problem
        self.X_host = X_host
        self.y_host = y_host
        self.xb_bins = tuple(xb_bins) if xb_bins is not None else None
        self.n_rows = int(X_host.shape[0]) if X_host is not None else int(X.shape[0])
        self.n_features = int(X_host.shape[1]) if X_host is not None else int(X.shape[1])
        if problem == "binary":
            self.metric_names = BINARY_METRICS
        elif isinstance(problem, tuple):  # ("multiclass", k)
            self.metric_names = MULTICLASS_METRICS
        else:
            self.metric_names = REGRESSION_METRICS

    def units(self, n_folds: int) -> List["SweepUnit"]:
        """Per-fragment divisible cost units (the partitioner's input)."""
        return spec_units(self.spec, self.n_rows, self.n_features, n_folds)

    def run(self, train_w: np.ndarray, val_mask: np.ndarray) -> np.ndarray:
        """Execute; returns host metrics [F, C, M].  The launch is
        asynchronous: the one device pull, under ``sweep.gather``, is where
        the host waits for the device to finish."""
        from ..ops.sweep import run_sweep

        out = run_sweep(self.spec, self.X, self.xbs, self.y,
                        np.asarray(train_w, np.float32),
                        np.asarray(val_mask, np.float32), self.blob)
        with trace.span("sweep.gather", d2h_bytes=int(out.nbytes)):
            return np.asarray(out)

    def run_sharded(self, train_w: np.ndarray, val_mask: np.ndarray,
                    devices) -> np.ndarray:
        """Partition the spec over ``devices`` (cost-balanced), compile one
        program per device concurrently, dispatch them all asynchronously and
        gather the per-shard [F, C_s, M] metrics into the global candidate
        order.  Falls back to :meth:`run` on a single device.

        With the straggler layer armed (``TMOG_HEDGE``, default on), device
        health feeds the partition: chips past ``TMOG_DEVICE_EVICT_RATIO``
        (or with an open dispatch breaker) are excluded up front — the sweep
        degrades to N-1 chips with a recorded fallback — and persistently
        slow survivors get down-weighted LPT loads."""
        from ..ops.sweep import run_sweep_partitioned
        from ..parallel.spec_partition import partition_spec
        from ..resilience import health as _health
        from ..resilience import hedge as _hedge

        devices = list(devices)
        weights = None
        if _hedge.enabled() and len(devices) > 1:
            try:  # health feedback must never be able to kill a sweep
                tracker = _health.tracker()
                kept, evicted = tracker.filter_devices(devices)
                if evicted:
                    from ..obs.registry import record_fallback
                    record_fallback(
                        "sweep", "device_evicted",
                        devices=[str(d) for d in evicted],
                        slowdowns=[round(tracker.slowdown(d), 3)
                                   for d in evicted])
                    devices = kept
                ws = tracker.partition_weights(devices)
                if any(w != 1.0 for w in ws):
                    weights = ws
            except Exception:
                weights = None
        if len(devices) <= 1:
            return self.run(train_w, val_mask)
        from ..utils.env import env_flag
        if env_flag("TMOG_SWEEP_PACK", False):
            # candidate packing: cost-model-sized launch packs (possibly
            # several per device when the HBM / predicted-wall budgets
            # split a queue); every pack carries the slot it was balanced
            # for.  At the default budgets the packs ARE the LPT shards,
            # so the dispatched programs stay byte-identical — only the
            # launch-count telemetry is new.
            from ..ops.sweep import record_packs
            from ..parallel.spec_partition import launch_packs

            shards = launch_packs(self.spec, self.blob, len(devices),
                                  self.n_rows, self.n_features,
                                  int(train_w.shape[0]),
                                  device_weights=weights)
            if len(shards) <= 1:
                return self.run(train_w, val_mask)
            record_packs(len(shards), len(self.spec[2]))
            run_devices = [devices[s.slot if s.slot is not None else i]
                           for i, s in enumerate(shards)]
            return run_sweep_partitioned(
                shards, self.X, self.xbs, self.y,
                np.asarray(train_w, np.float32),
                np.asarray(val_mask, np.float32),
                len(self.spec[2]), run_devices,
                X_host=self.X_host, y_host=self.y_host,
                xb_bins=self.xb_bins)
        shards = partition_spec(self.spec, self.blob, len(devices),
                                self.n_rows, self.n_features,
                                int(train_w.shape[0]),
                                device_weights=weights)
        if len(shards) <= 1:
            return self.run(train_w, val_mask)
        if any(s.slot is not None for s in shards):
            # weighted partitions carry their slot: keep each shard on the
            # device it was balanced for even when empty shards dropped out
            run_devices = [devices[s.slot] if s.slot is not None
                           else devices[i] for i, s in enumerate(shards)]
        else:
            run_devices = devices[:len(shards)]
        return run_sweep_partitioned(
            shards, self.X, self.xbs, self.y,
            np.asarray(train_w, np.float32),
            np.asarray(val_mask, np.float32),
            len(self.spec[2]), run_devices,
            X_host=self.X_host, y_host=self.y_host, xb_bins=self.xb_bins)

    def run_rowsharded(self, train_w: np.ndarray, val_mask: np.ndarray,
                       mesh) -> np.ndarray:
        """Execute on a 2-D (data, model) mesh: the spec is cost-partitioned
        over the model axis exactly as :meth:`run_sharded` partitions it over
        devices, and each sub-spec program runs row-sharded over its model
        column's data-axis devices (one row shard per chip, psum'd
        reductions).  A 1-wide model axis degenerates to one row-sharded
        program over the whole spec."""
        from ..ops.sweep import run_sweep_rowsharded
        from ..parallel.mesh import MODEL_AXIS
        from ..parallel.spec_partition import partition_spec

        n_model = int(mesh.shape[MODEL_AXIS])
        shards = partition_spec(self.spec, self.blob, n_model,
                                self.n_rows, self.n_features,
                                int(train_w.shape[0]))
        return run_sweep_rowsharded(
            shards, self.X, self.xbs, self.y,
            np.asarray(train_w, np.float32),
            np.asarray(val_mask, np.float32),
            len(self.spec[2]), mesh,
            X_host=self.X_host, y_host=self.y_host, xb_bins=self.xb_bins)


# ---------------------------------------------------------------------------
# Per-fragment cost model + candidate-granular split(cis)
#
# The multi-chip partitioner (parallel/spec_partition.py) balances sub-specs
# across mesh ``model`` shards by predicted per-candidate cost.  The model is
# the analytic FLOP shape of each family kernel with constants CALIBRATED
# against XLA ``cost_analysis`` of the per-fragment programs on the default
# Titanic-scale sweep (n=891, d=20, F=3 — the same numbers utils/flops
# reports in the bench's ``flops_by_kernel``):
#
#   fista d3-group anchors:  3.73e5 /cand   (measured, 200 iters)
#   forest depth 3/6/12:     8.70e7 / 6.22e8 / 2.31e9 /cand
#   gbt 200x10:              9.03e7 /cand
#
# Caveat stated where it matters: cost_analysis counts a lax.scan body ONCE,
# so the boosting constant reflects that (the bench's accounting does too).
# The boosting ROUNDS CHAIN is sequential wall-clock that no partition can
# shrink — documented as a ROADMAP leftover, not modeled here.
# Unchecked at scale: no constant was fitted again at the 32,768 x 760 rows
# of the ``scale-500-trees`` cell (PERF.md, PR 29).
# ---------------------------------------------------------------------------
#: linear-family per-iteration constant: cost = F * iters * LIN_ITER_D2 * d^2
#: (FISTA precomputes the fold Gram; per-iter work is O(d^2) per candidate)
LIN_ITER_D2 = 1.6
#: Newton adds the d^3 solve per iteration (analytic; not in the default grid)
NEWTON_SOLVE = 0.35
#: MLP fwd+bwd constant per iteration per layer-pair matmul (analytic)
MLP_ITER = 6.0
#: tree level-sum terms (least-squares fit to the three forest anchors):
#: per tree = TREE_LEVEL_ND * depth * n * d
#:          + TREE_LEVEL_MB * sum_l min(2^l, frontier) * d * n_bins
TREE_LEVEL_ND = 26.0
TREE_LEVEL_MB = 20.0
#: boosting scale: scan body counted once + unrolled epilogue ~= 2 bodies at
#: the reference NumRound=200; linear in rounds to keep ordering monotone
GBT_ROUNDS_REF = 200.0


class SweepUnit:
    """One divisible partition unit: a linear/MLP fragment or a single
    forest/gbt group.  ``key`` identifies it for :func:`build_subspec`;
    ``cis`` are its GLOBAL candidate positions; ``per_cand`` the predicted
    cost of one candidate (folds included); ``kind`` the fragment kind —
    the learned cost model's family axis (costmodel.features.unit_family)."""

    __slots__ = ("key", "cis", "per_cand", "kind")

    def __init__(self, key: Tuple[int, Optional[int]], cis: Tuple[int, ...],
                 per_cand: float, kind: str = ""):
        self.key = key
        self.cis = tuple(cis)
        self.per_cand = float(per_cand)
        self.kind = kind

    @property
    def cost(self) -> float:
        return self.per_cand * len(self.cis)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"SweepUnit(key={self.key}, n={len(self.cis)}, "
                f"per_cand={self.per_cand:.3g})")


def _tree_level_sum(depth: int, frontier: int) -> float:
    return float(sum(min(1 << l, frontier) for l in range(depth)))


def _linear_unit_cost(kind: str, frag, n: int, d: int, F: int) -> float:
    if kind == "mlp":
        _, cis, layers, max_iter, _, _ = frag
        # layer-pair matmul work per iteration — the MLP analog of the
        # linear families' O(d^2)-per-iter convention
        pairs = sum(layers[i] * layers[i + 1] for i in range(len(layers) - 1))
        return F * max_iter * MLP_ITER * pairs
    max_iter = frag[2]
    cost = F * max_iter * LIN_ITER_D2 * d * d
    if kind == "newton":
        cost += F * max_iter * NEWTON_SOLVE * d ** 3
    return cost


def _forest_group_cost(group, n: int, d: int, F: int) -> float:
    _, depth, n_trees, _, n_bins, *_rest = group
    frontier = group[9]
    per_tree = (TREE_LEVEL_ND * depth * n * d
                + TREE_LEVEL_MB * _tree_level_sum(depth, frontier) * d * n_bins)
    return F * n_trees * per_tree


def _gbt_group_cost(group, n: int, d: int, F: int) -> float:
    _, rounds, depth, _, n_bins, *_rest = group
    frontier = group[8]
    k = max(int(group[11]), 1)
    # histogram subtraction builds only the light sibling below the root:
    # the matmul (MB) term halves for every level past the first
    level_sum = _tree_level_sum(depth, frontier)
    if Tr._hist_subtract() and depth > 1:
        level_sum = 1.0 + (level_sum - 1.0) * 0.5
    per_tree = (TREE_LEVEL_ND * depth * n * d
                + TREE_LEVEL_MB * level_sum * d * n_bins)
    # round-collapse: K trees per step, rounds / K sequential steps — the
    # per-launch constant term scales with the SHORTER chain while total
    # tree work (K * rounds / K) is unchanged
    return F * k * per_tree * (1.0 + (rounds / k) / GBT_ROUNDS_REF)


def spec_units(spec, n: int, d: int, F: int) -> List[SweepUnit]:
    """Decompose a spec into cost units splittable at candidate granularity.

    ``key`` = (fragment index, group index | None).  Every candidate of the
    spec appears in exactly one unit.
    """
    units: List[SweepUnit] = []
    for fi, frag in enumerate(spec[1]):
        kind = frag[0]
        if kind in ("fista", "newton", "svc", "mlp"):
            units.append(SweepUnit((fi, None), frag[1],
                                   _linear_unit_cost(kind, frag, n, d, F),
                                   kind=kind))
        elif kind == "forest":
            for gi, g in enumerate(frag[2]):
                units.append(SweepUnit(
                    (fi, gi), g[0],
                    _forest_group_cost(g, n, d, F) / max(len(g[0]), 1),
                    kind=kind))
        elif kind == "gbt":
            for gi, g in enumerate(frag[3]):
                units.append(SweepUnit(
                    (fi, gi), g[0],
                    _gbt_group_cost(g, n, d, F) / max(len(g[0]), 1),
                    kind=kind))
        else:  # pragma: no cover - grammar is closed
            raise ValueError(f"unknown sweep fragment {kind!r}")
    return units


def _split_linear_frag(frag, picks: List[int], local: Dict[int, int],
                       blob: np.ndarray, out_blob: "_Blob"):
    """split(cis) for a linear/MLP fragment: keep the picked candidates (by
    position within the fragment), re-pack their blob slices contiguously."""
    kind = frag[0]
    cis = frag[1]
    new_cis = tuple(local[cis[p]] for p in picks)
    G = len(cis)

    def sub(off):
        return out_blob.add(blob[[off + p for p in picks]])

    if kind == "fista":
        _, _, max_iter, fi, off_l1, off_l2 = frag
        return ("fista", new_cis, max_iter, fi, sub(off_l1), sub(off_l2))
    if kind == "newton":
        _, _, max_iter, fi, off_l2 = frag
        return ("newton", new_cis, max_iter, fi, sub(off_l2))
    if kind == "svc":
        _, _, max_iter, fi, off_l2 = frag
        return ("svc", new_cis, max_iter, fi, sub(off_l2))
    if kind == "mlp":
        _, _, layers, max_iter, off_lr, off_seed = frag
        return ("mlp", new_cis, layers, max_iter, sub(off_lr), sub(off_seed))
    raise ValueError(f"not a linear fragment: {kind!r}")  # pragma: no cover


def _split_forest_group(group, picks: List[int], local: Dict[int, int],
                        blob: np.ndarray, out_blob: "_Blob", F: int):
    (cis, depth, ntrees, xb_idx, n_bins, frac, rate, bootstrap, seed,
     frontier, exact_cap, chunk, off_mcw, off_mig) = group
    new_cis = tuple(local[cis[p]] for p in picks)
    # the (bootstrap, feature-mask) draw is keyed by (seed, n_trees) only, so
    # any candidate subset reuses the SAME per-tree draws — parity preserved.
    # chunk shrinks with the smaller tree population (same memory ceiling).
    new_chunk = Tr.balanced_chunk(F * len(picks) * ntrees, chunk, group=ntrees)
    return (new_cis, depth, ntrees, xb_idx, n_bins, frac, rate, bootstrap,
            seed, frontier, exact_cap, new_chunk,
            out_blob.add(blob[[off_mcw + p for p in picks]]),
            out_blob.add(blob[[off_mig + p for p in picks]]))


def _split_gbt_group(group, picks: List[int], local: Dict[int, int],
                     blob: np.ndarray, out_blob: "_Blob"):
    (cis, rounds, depth, xb_idx, n_bins, subsample, colsample, seed,
     frontier, exact_cap, fold_base, trees_per_round, off_eta, off_lam,
     off_gam, off_mcw, off_mig) = group
    new_cis = tuple(local[cis[p]] for p in picks)
    return (new_cis, rounds, depth, xb_idx, n_bins, subsample, colsample,
            seed, frontier, exact_cap, fold_base, trees_per_round,
            out_blob.add(blob[[off_eta + p for p in picks]]),
            out_blob.add(blob[[off_lam + p for p in picks]]),
            out_blob.add(blob[[off_gam + p for p in picks]]),
            out_blob.add(blob[[off_mcw + p for p in picks]]),
            out_blob.add(blob[[off_mig + p for p in picks]]))


def build_subspec(spec, blob: np.ndarray, picks: Dict[Tuple[int, Optional[int]],
                                                      List[int]],
                  F: int) -> Tuple[tuple, np.ndarray, Tuple[int, ...]]:
    """Materialize ONE shard's sub-spec from a unit->positions selection.

    ``picks`` maps a :class:`SweepUnit` key to the picked positions WITHIN
    that unit's ``cis`` tuple.  Returns ``(sub_spec, sub_blob, global_cis)``
    where ``global_cis[j]`` is the global candidate index of the sub-spec's
    local candidate ``j`` (ascending).  Offsets in the sub-spec index the
    freshly packed ``sub_blob``, so any candidate subset — not just
    contiguous ranges — is expressible.
    """
    problem, frags, strict = spec
    global_cis: List[int] = []
    for (fi, gi), pos in picks.items():
        frag = frags[fi]
        cis = frag[1] if gi is None else (
            frag[2][gi][0] if frag[0] == "forest" else frag[3][gi][0])
        global_cis.extend(cis[p] for p in pos)
    global_cis = sorted(global_cis)
    local = {ci: j for j, ci in enumerate(global_cis)}
    out_blob = _Blob()
    out_frags: List[tuple] = []
    for fi, frag in enumerate(frags):
        kind = frag[0]
        if kind in ("fista", "newton", "svc", "mlp"):
            pos = sorted(picks.get((fi, None), ()))
            if pos:
                out_frags.append(_split_linear_frag(frag, pos, local, blob,
                                                    out_blob))
        elif kind == "forest":
            groups = []
            for gi, g in enumerate(frag[2]):
                pos = sorted(picks.get((fi, gi), ()))
                if pos:
                    groups.append(_split_forest_group(g, pos, local, blob,
                                                      out_blob, F))
            if groups:
                out_frags.append(("forest", frag[1], tuple(groups)))
        elif kind == "gbt":
            groups = []
            for gi, g in enumerate(frag[3]):
                pos = sorted(picks.get((fi, gi), ()))
                if pos:
                    groups.append(_split_gbt_group(g, pos, local, blob,
                                                   out_blob))
            if groups:
                out_frags.append(("gbt", frag[1], frag[2], tuple(groups)))
    sub_strict = tuple(strict[ci] for ci in global_cis)
    sub_spec = (problem, tuple(out_frags), sub_strict)
    return sub_spec, out_blob.pack(), tuple(global_cis)


def _poisson_bound(fold_sum: float, rate: float, max_w: float) -> float:
    """Upper bound on a Poisson(rate)-bootstrapped fold weight sum: mean +
    5 sigma, with sigma^2 = rate * sum_i w_i^2 <= rate * max_w * sum_w using
    the ACTUAL max row weight (DataBalancer can up-weight far past any
    constant heuristic).  P(exceed 5 sigma) < 3e-7 per group."""
    mean = rate * fold_sum
    sigma = math.sqrt(max(rate * fold_sum * max(max_w, 1.0), 1.0))
    return mean + 5.0 * sigma + 5.0 * max(max_w, 1.0)


def _xb_index(xbs: List, X: np.ndarray, n_bins: int) -> int:
    """Pre-binned matrix index for ``n_bins`` (cached per X identity)."""
    def binned():
        # host quantile sketch + device binning + the pull and upload back
        with trace.span("sweep.quantize", rows=int(X.shape[0]),
                        width=int(X.shape[1]), bins=int(n_bins)):
            return devcache.device_array(Tr.quantize(X, n_bins)[0],
                                         tag=f"xb{n_bins}")

    dev = devcache.derived(X, ("xb", n_bins), binned)
    for i, a in enumerate(xbs):
        if a is dev:
            return i
    xbs.append(dev)
    return len(xbs) - 1


def _spec_xb_bins(spec, n_xbs: int) -> Tuple[int, ...]:
    """Recover each ``xbs`` entry's bin count from the spec's tree groups."""
    bins = [0] * n_xbs
    for frag in spec[1]:
        if frag[0] == "forest":
            for g in frag[2]:
                bins[g[3]] = g[4]
        elif frag[0] == "gbt":
            for g in frag[3]:
                bins[g[3]] = g[4]
    return tuple(bins)


def _lr_fragments(est, grids, pos: int, blob: _Blob, y) -> Optional[List]:
    base_mi = int(est.get_param("max_iter", 100))
    base_fi = bool(est.get_param("fit_intercept", True))
    family = est.get_param("family", "auto")
    num_classes = int(np.max(np.asarray(y))) + 1 if len(y) else 2
    if family == "multinomial" or (family == "auto" and num_classes > 2):
        return None  # softmax not fused yet
    for g in grids:
        for k in g:
            if k not in ("reg_param", "elastic_net_param"):
                return None
    reg = np.array([float(g.get("reg_param", est.get_param("reg_param", 0.0)))
                    for g in grids], np.float32)
    alpha = np.array([float(g.get("elastic_net_param",
                                  est.get_param("elastic_net_param", 0.0)))
                      for g in grids], np.float32)
    l1 = reg * alpha
    l2 = reg * (1.0 - alpha)
    frags = []
    newton = tuple(int(pos + i) for i in np.where(l1 == 0.0)[0])
    fista = tuple(int(pos + i) for i in np.where(l1 != 0.0)[0])
    if newton:
        idx = [c - pos for c in newton]
        off_l2 = blob.add(l2[idx])
        frags.append(("newton", newton,
                      min(max(base_mi // 4, 10), 50), base_fi, off_l2))
    if fista:
        idx = [c - pos for c in fista]
        off_l1 = blob.add(l1[idx])
        off_l2 = blob.add(l2[idx])
        frags.append(("fista", fista, max(base_mi, 200), base_fi,
                      off_l1, off_l2))
    return frags


def _linreg_fragments(est, grids, pos: int, blob: _Blob) -> Optional[List]:
    base_mi = int(est.get_param("max_iter", 100))
    base_fi = bool(est.get_param("fit_intercept", True))
    for g in grids:
        for k in g:
            if k not in ("reg_param", "elastic_net_param"):
                return None
    reg = np.array([float(g.get("reg_param", est.get_param("reg_param", 0.0)))
                    for g in grids], np.float32)
    alpha = np.array([float(g.get("elastic_net_param",
                                  est.get_param("elastic_net_param", 0.0)))
                      for g in grids], np.float32)
    cis = tuple(range(pos, pos + len(grids)))
    off_l1 = blob.add(reg * alpha)
    off_l2 = blob.add(reg * (1.0 - alpha))
    return [("fista", cis, max(base_mi, 300), base_fi, off_l1, off_l2)]


def _svc_fragments(est, grids, pos: int, blob: _Blob) -> Optional[List]:
    for g in grids:
        for k in g:
            if k != "reg_param":
                return None
    l2 = [float(g.get("reg_param", est.get_param("reg_param", 0.0)))
          for g in grids]
    cis = tuple(range(pos, pos + len(grids)))
    return [("svc", cis, max(int(est.get_param("max_iter", 100)), 200),
             bool(est.get_param("fit_intercept", True)), blob.add(l2))]


def _mlp_fragments(est, grids, pos: int, blob: _Blob, d: int,
                   n_classes: int = 2) -> Optional[List]:
    allowed = ("hidden_layers", "max_iter", "step_size", "seed")
    for g in grids:
        for k in g:
            if k not in allowed:
                return None
    cands = [est.copy_with_params(dict(g)) for g in grids]
    groups: Dict[tuple, List[int]] = {}
    for i, c in enumerate(cands):
        hl = tuple(int(h) for h in c.get_param("hidden_layers", (10,)))
        groups.setdefault((hl, int(c.get_param("max_iter", 200))), []).append(i)
    frags = []
    for (hl, mi), idxs in groups.items():
        layers = (d,) + hl + (n_classes,)
        lrs = [float(cands[i].get_param("step_size", 0.03)) for i in idxs]
        seeds = [float(int(cands[i].get_param("seed", 42))) for i in idxs]
        frags.append(("mlp", tuple(int(pos + i) for i in idxs), layers, mi,
                      blob.add(lrs), blob.add(seeds)))
    return frags


def _forest_fragment(est, grids, pos: int, blob: _Blob, xbs, X, train_w,
                     classification: bool, n_classes: int = 1) -> Optional[List]:
    for g in grids:
        for k in g:
            if k not in _FOREST_GRID_KEYS:
                return None
    n, d = X.shape
    cands = [est.copy_with_params(dict(g)) for g in grids]
    groups: Dict[tuple, List[int]] = {}
    for i, c in enumerate(cands):
        key = (int(c.get_param("max_depth", 5)),
               int(c.get_param("num_trees", 20)),
               int(c.get_param("max_bins", 32)),
               float(c._subset_frac(d)),
               float(c.get_param("subsampling_rate", 1.0)),
               bool(getattr(c, "_grid_bootstrap", True)),
               int(c.get_param("seed", 42)))
        groups.setdefault(key, []).append(i)
    tw = np.asarray(train_w, np.float32)
    fold_sum = float(tw.sum(axis=1).max())
    max_w = float(tw.max()) if tw.size else 1.0
    out_groups = []
    # 1-channel leaves for binary AND k=2-multiclass (the variance kernel's
    # splits are gini-identical and match the legacy path bit-for-bit; the
    # interpreter expands p -> [1-p, p] for the k=2 score buffer); true
    # multiclass gets class-distribution leaves
    c = n_classes if (classification and n_classes > 2) else 1
    for (depth, ntrees, n_bins, frac, rate, bag, seed), idxs in groups.items():
        mcw = [float(cands[i].get_param("min_instances_per_node", 1))
               for i in idxs]
        mig = [float(cands[i].get_param("min_info_gain", 0.0)) for i in idxs]
        bound = _poisson_bound(fold_sum, rate, max_w) if bag else fold_sum
        mcw_min = min(mcw)
        frontier = Tr.frontier_cap(
            n, depth, mcw_min, h_max=1.0,
            max_frontier=int(est.get_param("max_frontier",
                                           DEFAULT_MAX_FRONTIER)),
            total_weight=bound)
        exact = Tr.frontier_is_exact(n, depth, mcw_min, 1.0, frontier,
                                     total_weight=bound)
        F = train_w.shape[0]
        TT = F * len(idxs) * ntrees
        chunk = Tr.balanced_chunk(
            TT, Tr.forest_chunk_size(depth, n_bins, d, c, frontier, n_rows=n,
                                     n_kept=Tr.n_kept(d, frac)), group=ntrees)
        out_groups.append((
            tuple(int(pos + i) for i in idxs), depth, ntrees,
            _xb_index(xbs, X, n_bins), n_bins, frac,
            rate if bag else 1.0, bag, seed, frontier, exact, chunk,
            blob.add(mcw), blob.add(mig)))
    return [("forest", c, tuple(out_groups))]


def _softmax_fragments(est, grids, pos: int, blob: _Blob) -> Optional[List]:
    """Multinomial LR: every grid goes through the softmax kernel (matches
    logistic.fit_grid_folds' multinomial branch)."""
    base_mi = int(est.get_param("max_iter", 100))
    base_fi = bool(est.get_param("fit_intercept", True))
    for g in grids:
        for k in g:
            if k not in ("reg_param", "elastic_net_param"):
                return None
    reg = np.array([float(g.get("reg_param", est.get_param("reg_param", 0.0)))
                    for g in grids], np.float32)
    alpha = np.array([float(g.get("elastic_net_param",
                                  est.get_param("elastic_net_param", 0.0)))
                      for g in grids], np.float32)
    cis = tuple(range(pos, pos + len(grids)))
    off_l1 = blob.add(reg * alpha)
    off_l2 = blob.add(reg * (1.0 - alpha))
    return [("fista", cis, base_mi, base_fi, off_l1, off_l2)]


def _gbt_fragment(est, grids, pos: int, blob: _Blob, xbs, X, train_w,
                  loss: str, n_classes: int = 2) -> Optional[List]:
    static_keys = ("num_round", "max_iter", "max_depth", "max_bins",
                   "subsample", "subsampling_rate", "colsample_bytree",
                   "trees_per_round")
    for g in grids:
        for k in g:
            if k not in _DYNAMIC_BOOST_KEYS and k not in static_keys:
                return None
    n, d = X.shape
    cands = [est.copy_with_params(dict(g)) for g in grids]
    bps = [c._boost_params() for c in cands]
    groups: Dict[tuple, List[int]] = {}
    for i, bp in enumerate(bps):
        k_req = int(bp.get("trees_per_round", 1))
        k_eff = effective_trees_per_round(k_req, bp["n_rounds"])
        if k_req > 1 and k_eff == 1:
            # declined round-collapse for this candidate (K must divide
            # rounds) — audit-trail it like the other graceful degradations
            from ..ops import sweep as sweep_ops
            sweep_ops.record_fallback(
                "gbt_rounds_not_collapsible", requested=k_req,
                n_rounds=int(bp["n_rounds"]))
        key = (bp["n_rounds"], bp["max_depth"], bp["n_bins"],
               float(bp["subsample"]), float(bp["colsample"]),
               int(cands[i].get_param("seed", 42)), k_eff)
        groups.setdefault(key, []).append(i)
    fold_sum = float(np.asarray(train_w, np.float32).sum(axis=1).max())
    h_max = 0.25 if loss in ("logistic", "softmax") else 1.0
    fold_base = loss == "squared"
    out_groups = []
    for (rounds, depth, n_bins, subsample, colsample, seed,
         k_eff), idxs in groups.items():
        mcw_min = min(bps[i]["min_child_weight"] for i in idxs)
        frontier = Tr.frontier_cap(
            n, depth, mcw_min, h_max=h_max,
            max_frontier=int(est.get_param("max_frontier",
                                           DEFAULT_MAX_FRONTIER_BOOSTED)),
            total_weight=fold_sum)
        exact = Tr.frontier_is_exact(n, depth, mcw_min, h_max, frontier,
                                     total_weight=fold_sum)
        out_groups.append((
            tuple(int(pos + i) for i in idxs), rounds, depth,
            _xb_index(xbs, X, n_bins), n_bins, subsample, colsample, seed,
            frontier, exact, fold_base, k_eff,
            blob.add([bps[i]["eta"] for i in idxs]),
            blob.add([bps[i]["reg_lambda"] for i in idxs]),
            blob.add([bps[i]["gamma"] for i in idxs]),
            blob.add([bps[i]["min_child_weight"] for i in idxs]),
            blob.add([bps[i].get("min_info_gain", 0.0) for i in idxs])))
    out_c = n_classes if loss == "softmax" else 1
    return [("gbt", loss, out_c, tuple(out_groups))]


def build_sweep_plan(candidates: Sequence[Tuple[Any, Sequence[Dict[str, Any]]]],
                     X: np.ndarray, y: np.ndarray, train_w: np.ndarray,
                     evaluator) -> Optional[SweepPlan]:
    """Translate the candidate list into a fused program, or None.

    Requires: every family supported, a device-computable default metric,
    and (for classification) a binary 0/1 label.
    """
    from .classification.logistic import OpLogisticRegression
    from .classification.mlp import OpMultilayerPerceptronClassifier
    from .classification.svc import OpLinearSVC
    from .classification.trees import (OpDecisionTreeClassifier,
                                       OpGBTClassifier,
                                       OpRandomForestClassifier,
                                       OpXGBoostClassifier)
    from .regression.linear import OpLinearRegression
    from .regression.trees import (OpDecisionTreeRegressor, OpGBTRegressor,
                                   OpRandomForestRegressor,
                                   OpXGBoostRegressor)

    # exact estimator types only (mirrors the evaluator check below): an
    # unknown SUBCLASS may override fit/predict semantics, and fusing it
    # would silently train the base family's kernel instead — the legacy
    # per-family path keeps such estimators' own code paths (and their
    # failure modes; tests rely on per-candidate error tolerance there)
    fusable = (OpLogisticRegression, OpMultilayerPerceptronClassifier,
               OpLinearSVC, OpRandomForestClassifier,
               OpDecisionTreeClassifier, OpGBTClassifier,
               OpXGBoostClassifier, OpLinearRegression,
               OpRandomForestRegressor, OpDecisionTreeRegressor,
               OpGBTRegressor, OpXGBoostRegressor)
    if any(type(est) not in fusable for est, _ in candidates):
        return None

    from ..evaluators import _SingleMetric
    from ..evaluators.classification import (OpBinaryClassificationEvaluator,
                                             OpMultiClassificationEvaluator)
    from ..evaluators.regression import OpRegressionEvaluator

    yv = np.asarray(y)
    binary = bool(np.isin(yv, (0.0, 1.0)).all()) and len(np.unique(yv)) == 2
    # exact types only: a subclass may override evaluate_arrays, and the
    # device program must compute the SAME number the host path would.
    # _SingleMetric (the Evaluators.* factory wrapper) delegates verbatim to
    # its inner evaluator, so unwrap it and honor its chosen default metric.
    inner = evaluator.inner if type(evaluator) is _SingleMetric else evaluator
    n_classes = 2
    if type(inner) is OpBinaryClassificationEvaluator and binary:
        problem = "binary"
        if evaluator.default_metric not in BINARY_METRICS:
            return None
    elif type(inner) is OpMultiClassificationEvaluator:
        if len(yv) == 0 or not np.isin(yv, np.arange(64)).all():
            return None
        n_classes = max(int(yv.max()) + 1, 2)
        problem = ("multiclass", n_classes)
        if evaluator.default_metric not in MULTICLASS_METRICS:
            return None
        # the [F, C, n, k] probability tensor must stay HBM-friendly
        n_cand = sum(max(len(list(g) or [{}]), 1) for _, g in candidates)
        if 8 * n_cand * len(yv) * n_classes * 4 > 2e9:
            return None
    elif type(inner) is OpRegressionEvaluator:
        problem = "regression"
        if evaluator.default_metric not in REGRESSION_METRICS:
            return None
    else:
        return None

    X = np.ascontiguousarray(np.asarray(X, np.float32))
    blob = _Blob()
    xbs: List = []
    frags: List = []
    strict: List[int] = []
    pos = 0
    for est, grids in candidates:
        grids = [dict(g) for g in (list(grids) or [{}])]
        G = len(grids)
        # k=2 under the multiclass evaluator trains the SAME binary models
        # the legacy path does (family=auto resolves to binomial at 2
        # classes); the interpreter expands p1 -> [1-p1, p1] score planes
        if problem == "binary" or (isinstance(problem, tuple)
                                   and problem[1] == 2):
            if isinstance(est, OpLogisticRegression):
                fr = _lr_fragments(est, grids, pos, blob, yv)
                s = 0
            elif isinstance(est, OpRandomForestClassifier):  # covers DT subclass
                fr = _forest_fragment(est, grids, pos, blob, xbs, X, train_w,
                                      classification=True)
                s = 1  # argmax([1-p, p]) ties to class 0 => p > 0.5
            elif isinstance(est, (OpGBTClassifier, OpXGBoostClassifier)):
                fr = _gbt_fragment(est, grids, pos, blob, xbs, X, train_w,
                                   loss="logistic")
                s = 0  # _margins_to_preds uses p >= 0.5
            elif isinstance(est, OpLinearSVC):
                fr = _svc_fragments(est, grids, pos, blob)
                s = 0  # 0/1 score; >= 0.5 picks exactly z >= 0
            elif isinstance(est, OpMultilayerPerceptronClassifier):
                fr = _mlp_fragments(est, grids, pos, blob, X.shape[1])
                s = 1  # argmax(prob) ties to class 0
            else:
                fr = None
                s = 0
        elif isinstance(problem, tuple):  # multiclass, k > 2
            s = 0  # argmax semantics; strict flags unused
            if isinstance(est, OpLogisticRegression):
                fr = _softmax_fragments(est, grids, pos, blob)
            elif isinstance(est, OpRandomForestClassifier):
                fr = _forest_fragment(est, grids, pos, blob, xbs, X, train_w,
                                      classification=True,
                                      n_classes=n_classes)
            elif isinstance(est, (OpGBTClassifier, OpXGBoostClassifier)):
                fr = _gbt_fragment(est, grids, pos, blob, xbs, X, train_w,
                                   loss="softmax", n_classes=n_classes)
            elif isinstance(est, OpMultilayerPerceptronClassifier):
                fr = _mlp_fragments(est, grids, pos, blob, X.shape[1],
                                    n_classes=n_classes)
            else:
                fr = None
        else:
            if isinstance(est, OpLinearRegression):
                fr = _linreg_fragments(est, grids, pos, blob)
            elif isinstance(est, OpRandomForestRegressor):
                fr = _forest_fragment(est, grids, pos, blob, xbs, X, train_w,
                                      classification=False)
            elif isinstance(est, (OpGBTRegressor, OpXGBoostRegressor)):
                fr = _gbt_fragment(est, grids, pos, blob, xbs, X, train_w,
                                   loss="squared")
            else:
                fr = None
            s = 0
        if fr is None:
            log.debug("fused sweep: unsupported family %s; falling back",
                      type(est).__name__)
            return None
        frags.extend(fr)
        strict.extend([s] * G)
        pos += G

    spec = (problem, tuple(frags), tuple(strict))
    Xd = devcache.device_array(X, np.float32)
    y_host = np.ascontiguousarray(np.asarray(yv, np.float32))
    yd = devcache.device_array(y_host, np.float32)
    return SweepPlan(spec, Xd, tuple(xbs), yd, blob.pack(), problem,
                     X_host=X, y_host=y_host,
                     xb_bins=_spec_xb_bins(spec, len(xbs)))
