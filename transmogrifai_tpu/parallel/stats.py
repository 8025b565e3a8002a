"""Row-sharded streaming statistics — SURVEY §2.7 axis 1 and §5.7.

The reference computes column moments and correlations with Spark
``Statistics.colStats`` / ``Statistics.corr`` — treeAggregate reductions over
executor row partitions (SanityChecker.scala:406-470).  The O(p²)
feature×feature correlation is its "long axis" (SURVEY §5.7).  TPU-native
formulation:

- rows arrive in CHUNKS (the dataset may exceed HBM: 10M x 500 f32 = 20 GB
  vs 16 GB on a v5e chip); each chunk is placed sharded over the mesh
  ``data`` axis and reduced on device — XLA inserts the psum collectives
  from the sharding annotations (the scaling-book recipe),
- pass 1 accumulates count / sum / sum-of-squares / min / max per column,
- pass 2 accumulates the CENTERED Gram Z^T Z (+ Z^T z_y) — one MXU matmul
  per chunk — from which the full p x p Pearson matrix and the label
  correlations fall out.  Centering first keeps f32 accumulation accurate
  (raw second moments over 10M rows would not be),
- accumulators live on device replicated; one tiny d2h at finalize.

Spearman needs a GLOBAL rank transform first (Spark Statistics.corr
"spearman" sorts each column cluster-wide, SanityChecker.scala:406-466);
here ``rank_transform`` computes per-column midranks on device in column
blocks (sort + two searchsorteds — ties averaged exactly like
utils/stats._rank_data), then the SAME streaming Pearson passes run over
the ranks, whose mean is exactly (n+1)/2.  Sampled Spearman stays available
via utils/stats.correlations_with_label.
"""
from __future__ import annotations

from typing import Iterable, Iterator, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .mesh import DATA_AXIS
from ..utils.stats import ColStats


def _data_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(DATA_AXIS))


@jax.jit
def _moments_step(carry, X, m):
    """carry: (n, s1, s2, mn, mx); X f32[rows, d] (sharded over data), m
    f32[rows] validity mask (0 for padding rows)."""
    n, s1, s2, mn, mx = carry
    Xm = X * m[:, None]
    n = n + m.sum()
    s1 = s1 + Xm.sum(axis=0)
    s2 = s2 + (X * Xm).sum(axis=0)
    mn = jnp.minimum(mn, jnp.where(m[:, None] > 0, X, jnp.inf).min(axis=0))
    mx = jnp.maximum(mx, jnp.where(m[:, None] > 0, X, -jnp.inf).max(axis=0))
    return n, s1, s2, mn, mx


@jax.jit
def _gram_step(carry, X, yv, m, mean, y_mean):
    """carry: (G [d,d], gy [d], yy, n); accumulates the centered Gram."""
    G, gy, yy, n = carry
    Z = (X - mean[None, :]) * m[:, None]
    zy = (yv - y_mean) * m
    G = G + Z.T @ Z
    gy = gy + Z.T @ zy
    yy = yy + (zy * zy).sum()
    n = n + m.sum()
    return G, gy, yy, n


class DataShardedStats:
    """Two-pass streaming moments + correlations over row chunks.

    ``mesh=None`` runs single-device (same code path; XLA elides the
    collectives) — the Spark local-mode analog.  Chunks may be any row
    count; they are padded to the data-shard multiple with masked rows.
    """

    def __init__(self, d: int, mesh=None):
        self.d = d
        self.mesh = mesh
        self.n_shards = int(mesh.shape[DATA_AXIS]) if mesh is not None else 1

    def _place(self, arr: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(jnp.asarray(arr), _data_sharding(self.mesh))

    def _chunks_masked(self, chunks: Iterable[np.ndarray]
                       ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        for X in chunks:
            X = np.ascontiguousarray(np.asarray(X, np.float32))
            rows = X.shape[0]
            pad = (-rows) % self.n_shards
            m = np.ones(rows, np.float32)
            if pad:
                X = np.concatenate([X, np.zeros((pad, X.shape[1]), np.float32)])
                m = np.concatenate([m, np.zeros(pad, np.float32)])
            yield X, m

    # ---- pass 1 ------------------------------------------------------------
    def moments(self, chunks: Iterable[np.ndarray]) -> ColStats:
        d = self.d
        carry = (jnp.zeros(()), jnp.zeros(d), jnp.zeros(d),
                 jnp.full(d, jnp.inf), jnp.full(d, -jnp.inf))
        for X, m in self._chunks_masked(chunks):
            carry = _moments_step(carry, self._place(X), self._place(m))
        n, s1, s2, mn, mx = (np.asarray(c, np.float64) for c in carry)
        # cross-host tier: raw sums add, min/max lattice-merge (identity
        # single-process)
        packed = host_sum_reduce(np.concatenate([[float(n)], s1, s2]),
                                 "moments_raw")
        n, s1, s2 = packed[0], packed[1:1 + d], packed[1 + d:]
        mn, mx = host_merge_minmax(mn, mx)
        n = float(n)
        mean = s1 / max(n, 1.0)
        var = np.maximum(s2 / max(n, 1.0) - mean * mean, 0.0) * (
            n / max(n - 1.0, 1.0))  # sample variance (Spark colStats)
        return ColStats(count=int(n), mean=mean, variance=var, min=mn, max=mx)

    # ---- pass 2 ------------------------------------------------------------
    def correlations_from(self, chunks_factory, mean: np.ndarray, y_mean: float,
                          with_corr_matrix: bool = True
                          ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """``chunks_factory()`` yields (X_chunk [rows, d], y_chunk [rows])
        pairs.  Returns (corr_with_label [d], corr_matrix [d,d] | None)."""
        d = self.d
        meand = jnp.asarray(mean, jnp.float32)
        ymd = jnp.asarray(np.float32(y_mean))
        carry = (jnp.zeros((d, d)), jnp.zeros(d), jnp.zeros(()), jnp.zeros(()))
        for X, y in chunks_factory():
            X = np.ascontiguousarray(np.asarray(X, np.float32))
            y = np.asarray(y, np.float32)
            rows = X.shape[0]
            pad = (-rows) % self.n_shards
            m = np.ones(rows, np.float32)
            if pad:
                X = np.concatenate([X, np.zeros((pad, d), np.float32)])
                y = np.concatenate([y, np.zeros(pad, np.float32)])
                m = np.concatenate([m, np.zeros(pad, np.float32)])
            carry = _gram_step(carry, self._place(X), self._place(y),
                               self._place(m), meand, ymd)
        G, gy, yy, n = (np.asarray(c, np.float64) for c in carry)
        # cross-host tier: every host's Gram is centered at the SAME global
        # mean (pass 1 already merged), so the carries are plain sums
        packed = host_sum_reduce(
            np.concatenate([[float(n), float(yy)], gy, G.reshape(-1)]),
            "gram")
        n, yy = packed[0], packed[1]
        gy = packed[2:2 + d]
        G = packed[2 + d:].reshape(d, d)
        diag = np.diag(G).copy()
        zero = diag <= 0.0
        denom = np.sqrt(np.maximum(diag, 1e-300))
        with np.errstate(invalid="ignore", divide="ignore"):
            corr_label = gy / (denom * np.sqrt(max(float(yy), 1e-300)))
        corr_label[zero] = np.nan
        corr_matrix = None
        if with_corr_matrix:
            corr_matrix = G / np.outer(denom, denom)
            np.fill_diagonal(corr_matrix, 1.0)
            corr_matrix[zero, :] = np.nan
            corr_matrix[:, zero] = np.nan
        return corr_label, corr_matrix


def chunked(X: np.ndarray, y: Optional[np.ndarray] = None,
            chunk_rows: int = 1 << 18):
    """Row-chunk an in-memory array (factory usable for both passes)."""
    n = X.shape[0]

    def gen_x():
        for lo in range(0, n, chunk_rows):
            yield X[lo:lo + chunk_rows]

    if y is None:
        return gen_x

    def gen_xy():
        for lo in range(0, n, chunk_rows):
            yield X[lo:lo + chunk_rows], y[lo:lo + chunk_rows]

    return gen_xy


@jax.jit
def _fused_stats_step(carry, X, yv, m):
    """ONE-pass moments + mean-centered Gram via Chan's pairwise merge.

    carry: (n, mean[d], y_mean, mn, mx, G[d,d], gy[d], yy) where G/gy/yy are
    centered at the CARRY means.  Each chunk is centered at its OWN means
    and merged with the exact pairwise-update cross terms
    (f = n0*nc/(n0+nc); G += Gc + f dx dx^T; gy += gyc + f dx dy;
    yy += yyc + f dy^2), so no large-offset cancellation ever enters the
    f32 accumulators — a constant-center scheme would cancel catastrophically
    on row-ordered data whose mean drifts.  ONE pass means each chunk
    uploads once: the second upload of the matrix was the single largest
    cost of the two-pass scheme.
    """
    n0, mean0, ym0, mn, mx, G, gy, yy = carry
    nc = m.sum()
    ncs = jnp.maximum(nc, 1.0)
    mc = (X * m[:, None]).sum(axis=0) / ncs
    yc = (yv * m).sum() / ncs
    Z = (X - mc[None, :]) * m[:, None]
    zy = (yv - yc) * m
    Gc = Z.T @ Z
    gyc = Z.T @ zy
    yyc = (zy * zy).sum()
    nt = n0 + nc
    f = jnp.where(nt > 0, n0 * nc / jnp.maximum(nt, 1.0), 0.0)
    dx = mc - mean0
    dy = yc - ym0
    G = G + Gc + f * jnp.outer(dx, dx)
    gy = gy + gyc + f * dx * dy
    yy = yy + yyc + f * dy * dy
    w = nc / jnp.maximum(nt, 1.0)
    mean = mean0 + dx * w
    ym = ym0 + dy * w
    mn = jnp.minimum(mn, jnp.where(m[:, None] > 0, X, jnp.inf).min(axis=0))
    mx = jnp.maximum(mx, jnp.where(m[:, None] > 0, X, -jnp.inf).max(axis=0))
    return nt, mean, ym, mn, mx, G, gy, yy


@jax.jit
def _chan_moments_step(carry, X, m):
    """One Chan pairwise-merge step of streaming column moments.

    carry: (n, mean[d], M2[d]) with M2 the CENTERED sum of squares.  The
    chunk is centered at its OWN mean and merged with the exact pairwise
    cross term (the _fused_stats_step recipe minus the Gram), so no raw
    second moments enter the f32 accumulator.  m masks padding rows."""
    n0, mean0, M2 = carry
    nc = m.sum()
    ncs = jnp.maximum(nc, 1.0)
    mc = (X * m[:, None]).sum(axis=0) / ncs
    Z = (X - mc[None, :]) * m[:, None]
    M2c = (Z * Z).sum(axis=0)
    nt = n0 + nc
    f = jnp.where(nt > 0, n0 * nc / jnp.maximum(nt, 1.0), 0.0)
    dx = mc - mean0
    M2 = M2 + M2c + f * dx * dx
    mean = mean0 + dx * (nc / jnp.maximum(nt, 1.0))
    return nt, mean, M2


def _merge_moment_carries(carries):
    """Chan-merge per-device (n, mean, M2) partials host-side in f64 — the
    cross-device half of the reduction (ROADMAP item 4's per-host merge
    pattern, applied across the stream devices of one host)."""
    n_t: float = 0.0
    mean_t = M2_t = None
    for c in carries:
        n_c, mean_c, M2_c = (np.asarray(x, np.float64) for x in c)
        n_c = float(n_c)
        if n_c <= 0:
            continue
        if mean_t is None:
            n_t, mean_t, M2_t = n_c, mean_c, M2_c
            continue
        nt = n_t + n_c
        dx = mean_c - mean_t
        M2_t = M2_t + M2_c + (n_t * n_c / nt) * dx * dx
        mean_t = mean_t + dx * (n_c / nt)
        n_t = nt
    return n_t, mean_t, M2_t


# ---------------------------------------------------------------------------
# Host-level merge tier — the cross-host (DCN) half of the fit statistics.
#
# Per-device Chan partials merge on each host (``_merge_moment_carries``);
# under ``jax.distributed`` the per-host results then cross the host boundary
# ONCE as a tiny f64 carry (O(d) floats, never row data) via
# ``process_allgather``, and every host merges the SAME ordered list in f64 —
# deterministic and bit-identical across hosts.  Single-process runs skip all
# of it (``jax.process_count() == 1`` → the carry passes through untouched),
# so the one-host path stays byte-identical.
# ---------------------------------------------------------------------------


#: per-kind monotone sequence for the coordination-service transport: every
#: host performs the SAME gathers in the SAME order (an all-gather invariant
#: already), so the counter names each round's keys identically everywhere
_KV_SEQ: dict = {}


def _kv_gather(raw: np.ndarray, kind: str):
    """All-gather raw bytes through the jax.distributed coordination-service
    key-value store (pure gRPC — no XLA computation involved).

    This is the CPU-proxy transport: XLA:CPU refuses multiprocess
    computations outright ("Multiprocess computations aren't implemented on
    the CPU backend"), so the two-process CI topology exchanges its moment
    carries host->coordinator->host instead.  Payloads are per-host moment
    carries (KBs), not row data — the store is never a data plane."""
    from jax._src import distributed

    client = distributed.global_state.client
    seq = _KV_SEQ.get(kind, 0)
    _KV_SEQ[kind] = seq + 1
    me = int(jax.process_index())
    client.key_value_set_bytes(f"tmog_gather/{kind}/{seq}/{me}",
                               raw.tobytes())
    out = []
    for h in range(int(jax.process_count())):
        buf = client.blocking_key_value_get_bytes(
            f"tmog_gather/{kind}/{seq}/{h}", 120_000)
        out.append(np.frombuffer(bytes(buf), np.uint8))
    return out


def _cross_host_gather(vec64: np.ndarray, kind: str):
    """All-gather one f64 vector across processes -> list of per-host rows.

    The payload crosses DCN as raw bytes (uint8 view), so the f64 carries
    survive even with jax x64 disabled.  Each gather is counted in the
    ``host`` obs scope (kind, payload bytes) — the cross-host analog of the
    ``mesh_psum`` trace telemetry."""
    from ..obs.registry import scope as _scope

    raw = np.ascontiguousarray(np.asarray(vec64, np.float64)).view(np.uint8)
    sc = _scope("host")
    sc.inc("collectives")
    sc.inc("collective_bytes", float(raw.nbytes))
    sc.append("events", {"kind": kind, "bytes": int(raw.nbytes)})
    if jax.default_backend() == "cpu":
        rows8 = _kv_gather(raw, kind)
    else:
        from jax.experimental import multihost_utils

        gathered = np.asarray(multihost_utils.process_allgather(raw))
        rows8 = [np.ascontiguousarray(gathered[i])
                 for i in range(gathered.shape[0])]
    return [row.view(np.float64) for row in rows8]


def _multi_host() -> bool:
    try:
        return int(jax.process_count()) > 1
    except Exception:
        return False


def host_merge_moments(carry, d: int):
    """Merge one host's (n, mean[d], M2[d]) Chan carry into the GLOBAL carry.

    A host with an empty row range contributes an exact zero carry (its
    ``mean`` may be None).  Single-process: identity."""
    n, mean, M2 = carry
    if not _multi_host():
        return carry
    if mean is None:
        n, mean, M2 = 0.0, np.zeros(d), np.zeros(d)
    packed = np.concatenate([[float(n)], np.asarray(mean, np.float64),
                             np.asarray(M2, np.float64)])
    rows = _cross_host_gather(packed, "moments")
    return _merge_moment_carries(
        [(r[0], r[1:1 + d], r[1 + d:]) for r in rows])


def host_sum_reduce(parts, kind: str = "sum"):
    """Element-wise sum of a flat f64 vector across hosts (for carries
    already centered at a GLOBAL reference — raw sums, common-mean Grams).
    min/max components must not ride through this; see
    ``host_merge_minmax``.  Single-process: identity."""
    parts = np.asarray(parts, np.float64)
    if not _multi_host():
        return parts
    rows = _cross_host_gather(parts, kind)
    return np.sum(np.stack(rows, axis=0), axis=0)


def host_merge_minmax(mn, mx):
    """Global element-wise column min/max across hosts (empty-range hosts
    hold ±inf identities).  Single-process: identity."""
    mn = np.asarray(mn, np.float64)
    mx = np.asarray(mx, np.float64)
    if not _multi_host():
        return mn, mx
    d = mn.shape[0]
    rows = _cross_host_gather(np.concatenate([mn, mx]), "minmax")
    stacked = np.stack(rows, axis=0)
    return stacked[:, :d].min(axis=0), stacked[:, d:].max(axis=0)


def host_merge_fused_carry(carry, d: int):
    """Chan-merge the fused one-pass carry (n, mean, ym, mn, mx, G, gy, yy)
    across hosts in f64 — exact pairwise cross terms for the Gram, so the
    global correlations match a single-host pass to f32-accumulation noise.
    Single-process: identity."""
    if not _multi_host():
        return carry
    n, mean, ym, mn, mx, G, gy, yy = (np.asarray(c, np.float64)
                                      for c in carry)
    packed = np.concatenate([[float(n), float(ym), float(yy)], mean, mn, mx,
                             gy, G.reshape(-1)])
    rows = _cross_host_gather(packed, "fused_stats")
    nt = 0.0
    mean_t = ym_t = G_t = gy_t = yy_t = None
    mn_t = np.full(d, np.inf)
    mx_t = np.full(d, -np.inf)
    for r in rows:
        n_c, ym_c, yy_c = r[0], r[1], r[2]
        o = 3
        mean_c = r[o:o + d]; o += d
        mn_c = r[o:o + d]; o += d
        mx_c = r[o:o + d]; o += d
        gy_c = r[o:o + d]; o += d
        G_c = r[o:].reshape(d, d)
        mn_t = np.minimum(mn_t, mn_c)
        mx_t = np.maximum(mx_t, mx_c)
        if n_c <= 0:
            continue
        if mean_t is None:
            nt, mean_t, ym_t = n_c, mean_c, ym_c
            G_t, gy_t, yy_t = G_c, gy_c, yy_c
            continue
        ns = nt + n_c
        f = nt * n_c / ns
        dx = mean_c - mean_t
        dy = ym_c - ym_t
        G_t = G_t + G_c + f * np.outer(dx, dx)
        gy_t = gy_t + gy_c + f * dx * dy
        yy_t = yy_t + yy_c + f * dy * dy
        w = n_c / ns
        mean_t = mean_t + dx * w
        ym_t = ym_t + dy * w
        nt = ns
    if mean_t is None:
        z = np.zeros(d)
        return 0.0, z, 0.0, mn_t, mx_t, np.zeros((d, d)), z.copy(), 0.0
    return nt, mean_t, ym_t, mn_t, mx_t, G_t, gy_t, yy_t


def sharded_column_moments(X: np.ndarray, chunk_rows: int = 1 << 18,
                           devices: Optional[list] = None
                           ) -> Tuple[float, np.ndarray, np.ndarray]:
    """Column mean and POPULATION std of ``X [n, d]`` via per-device
    round-robin Chan partials.

    Chunk i accumulates into device i-mod-D's carry, so each device runs an
    independent async accumulation pipeline (no per-chunk lockstep
    collective, unlike the mesh-placed ``DataShardedStats``), and the D
    partial carries merge exactly at the end.  This is what the streamed
    scaler fit reduces through when the transform stream is sharded — fit
    and transform ride the same devices.  Returns ``(count, mean, std)``
    f64; ``devices=None``/single runs the identical math on the default
    device."""
    X = np.asarray(X)
    n = X.shape[0]
    d = X.shape[1] if X.ndim > 1 else 1
    X = X.reshape(n, d)
    devices = list(devices) if devices else [None]
    D = len(devices)
    carries: list = [None] * D
    for k, lo in enumerate(range(0, n, chunk_rows)):
        chunk = np.ascontiguousarray(X[lo:lo + chunk_rows], np.float32)
        rows = chunk.shape[0]
        m = np.ones(rows, np.float32)
        if rows < chunk_rows:  # constant chunk shape: one compile per device
            chunk = np.concatenate(
                [chunk, np.zeros((chunk_rows - rows, d), np.float32)])
            m = np.concatenate([m, np.zeros(chunk_rows - rows, np.float32)])
        di = k % D
        dev = devices[di]
        if carries[di] is None:
            z = (jnp.zeros(()), jnp.zeros(d), jnp.zeros(d))
            carries[di] = jax.device_put(z, dev) if dev is not None else z
        xa = jax.device_put(chunk, dev) if dev is not None \
            else jnp.asarray(chunk)
        ma = jax.device_put(m, dev) if dev is not None else jnp.asarray(m)
        carries[di] = _chan_moments_step(carries[di], xa, ma)
    n_t, mean, M2 = host_merge_moments(_merge_moment_carries(
        [c for c in carries if c is not None]), d)
    if not n_t or mean is None:
        z = np.zeros(d)
        return 0.0, z, z.copy()
    return n_t, mean, np.sqrt(np.maximum(M2, 0.0) / n_t)


@jax.jit
def _midrank_cols(Xb):
    """Per-column average-tie midranks (1-based): f32[n, k] -> f32[n, k]."""

    def one(col):
        order = jnp.argsort(col)
        ss = col[order]
        lo = jnp.searchsorted(ss, ss, side="left")
        hi = jnp.searchsorted(ss, ss, side="right")
        mid = (lo + hi + 1).astype(jnp.float32) * 0.5
        return jnp.zeros_like(mid).at[order].set(mid)

    return jax.vmap(one, in_axes=1, out_axes=1)(Xb)


def rank_transform(X: np.ndarray, block_cols: int = 128) -> np.ndarray:
    """Global average-tie ranks per column, computed on device in column
    blocks (the Spearman prep; parity with utils/stats._rank_data)."""
    X = np.asarray(X, np.float32)
    if X.ndim == 1:
        return rank_transform(X[:, None], block_cols)[:, 0]
    n, d = X.shape
    out = np.empty((n, d), np.float32)
    for lo in range(0, d, block_cols):
        blk = np.ascontiguousarray(X[:, lo:lo + block_cols])
        out[:, lo:lo + block_cols] = np.asarray(_midrank_cols(jnp.asarray(blk)))
    return out


def fused_moments_and_correlations(chunks_factory, d: int, mesh=None,
                                   with_corr_matrix: bool = True
                                   ) -> Tuple[ColStats, np.ndarray,
                                              Optional[np.ndarray]]:
    """ONE streaming pass: column moments AND label/feature correlations.

    ``chunks_factory()`` yields (X_chunk [rows, d], y_chunk [rows]) pairs —
    each chunk uploads ONCE (the two-pass scheme re-uploaded the whole
    matrix for the Gram pass, and the host->device feed dominates).  Gram,
    mean, and variance accumulate with Chan's numerically-stable pairwise
    merge (see _fused_stats_step); variance falls out of the centered
    Gram's diagonal.
    """
    acc = DataShardedStats(d, mesh=mesh)
    carry = None
    for X, y in chunks_factory():
        X = np.ascontiguousarray(np.asarray(X, np.float32))
        y = np.asarray(y, np.float32)
        rows = X.shape[0]
        pad = (-rows) % acc.n_shards
        m = np.ones(rows, np.float32)
        if pad:
            X = np.concatenate([X, np.zeros((pad, d), np.float32)])
            y = np.concatenate([y, np.zeros(pad, np.float32)])
            m = np.concatenate([m, np.zeros(pad, np.float32)])
        if carry is None:
            carry = (jnp.zeros(()), jnp.zeros(d), jnp.zeros(()),
                     jnp.full(d, jnp.inf), jnp.full(d, -jnp.inf),
                     jnp.zeros((d, d)), jnp.zeros(d), jnp.zeros(()))
        carry = _fused_stats_step(carry, acc._place(X), acc._place(y),
                                  acc._place(m))
    if carry is None:
        if _multi_host():
            # an empty-range host still joins the cross-host merge with an
            # exact zero carry — the other hosts' allgather must not hang
            carry = (jnp.zeros(()), jnp.zeros(d), jnp.zeros(()),
                     jnp.full(d, jnp.inf), jnp.full(d, -jnp.inf),
                     jnp.zeros((d, d)), jnp.zeros(d), jnp.zeros(()))
        else:
            z = np.zeros(d)
            return ColStats(0, z, z.copy(), z.copy(), z.copy()), \
                np.full(d, np.nan), None
    carry = host_merge_fused_carry(carry, d)
    n_, mean, _ym, mn, mx, G, gy, yy = (np.asarray(c, np.float64)
                                        for c in carry)
    n = float(n_)
    yy = float(yy)
    # sample variance straight off the centered Gram's diagonal
    var = np.maximum(np.diag(G), 0.0) / max(n - 1.0, 1.0)
    stats = ColStats(count=int(n), mean=mean, variance=var, min=mn, max=mx)
    diag = np.diag(G).copy()
    zero = diag <= 0.0
    denom = np.sqrt(np.maximum(diag, 1e-300))
    with np.errstate(invalid="ignore", divide="ignore"):
        corr_label = gy / (denom * np.sqrt(max(yy, 1e-300)))
    corr_label[zero] = np.nan
    corr_matrix = None
    if with_corr_matrix:
        corr_matrix = G / np.outer(denom, denom)
        np.fill_diagonal(corr_matrix, 1.0)
        corr_matrix[zero, :] = np.nan
        corr_matrix[:, zero] = np.nan
    return stats, corr_label, corr_matrix


def sharded_correlations(X: np.ndarray, y: np.ndarray, mesh=None,
                         with_corr_matrix: bool = True,
                         chunk_rows: int = 1 << 18, method: str = "pearson"
                         ) -> Tuple[ColStats, np.ndarray, Optional[np.ndarray]]:
    """Drop-in large-data correlation path for SanityChecker: two sharded
    streaming passes over row chunks.  ``method`` "spearman" rank-transforms
    every column on device first (one extra [n, d] f32 materialization) and
    streams Pearson over the ranks; column stats are always raw-space.
    Returns (col_stats, corr_with_label, corr_matrix|None) matching
    utils/stats.correlations_with_label."""
    n = X.shape[0]
    acc = DataShardedStats(X.shape[1], mesh=mesh)
    stats = acc.moments(chunked(X, chunk_rows=chunk_rows)())
    if method == "spearman":
        Xc = rank_transform(X)
        yc = rank_transform(np.asarray(y, np.float32))
        mean_c = np.full(X.shape[1], (n + 1) / 2.0)  # midrank mean, exact
        y_mean = (n + 1) / 2.0
    else:
        Xc, yc = X, y
        mean_c = stats.mean
        y64 = np.asarray(y, np.float64)
        y_mean = float(y64.mean()) if len(y64) else 0.0
    corr_label, corr_matrix = acc.correlations_from(
        chunked(Xc, yc, chunk_rows=chunk_rows), mean_c, y_mean,
        with_corr_matrix=with_corr_matrix)
    return stats, corr_label, corr_matrix
