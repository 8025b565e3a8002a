"""Ask the v5e's compiler first: the jitted programs of the main path, at the
shapes ``chip_smoke.py`` launches, compiled for a chip that is described and
not attached (on-chip-measurement guide, section 2).

What a pass means: the TPU compiler accepts the program and it fits one
chip's 16 GB.  Nothing runs — no result, no time.

The topology is described inside a module-scoped fixture, never at import:
only the test worker that is handed this file loads the TPU library, and it
compiles in its own process with jax's persistent cache switched off (an
entry compiled for an absent chip cannot be read back).  ``jax.devices()``
is still the CPU here; the tree kernels have one formulation for every
backend, so what is lowered here is what a TPU traces.

Time: about two and a half minutes in all on this sandbox's 8 cores, not the
one minute asked for — the programs of this repo are whole sweeps, not
two-second kernels (the boosted rounds at the trees cell's shape, whose
compiled layouts the last test reads, are ~30 s of it).  Whole programs are kept where one takes under a
minute: phase A's 28-candidate sweep is the slowest (45-60 s, most of it
the three forest depth groups), the streamed chunk program ~20 s.  Two are
cut to their dominant kernels, and say so where they are cut: phase B's
sweep compiles its family training kernels (``_run_scores``) and not the
metric kernel again, which costs ~30 s at any shape and is inside phase A's
program; the row-sharded program keeps the linear and boosting fragments of
a model column (a full column of the default grid compiles for ~50 s) — the
same ``mesh_psum`` / ``mesh_all_gather`` call sites, the histogram psum
inside ``_grow_level_batch`` included — and leaves the forests to phase A.
"""
import os
import re
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (shared shape constants; imports no jax)
import scale10m  # noqa: E402

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of ``tree``'s leaves, placed by ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _rows(tree, n):
    """The same leaves with the leading (row) axis set to ``n``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + tuple(a.shape[1:]), a.dtype,
                                       sharding=a.sharding), tree)


def _fits(compiled) -> float:
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one v5e"
    return total


_ARRAY = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]"
                    r"\{([\d,]*)(?::T\((\d+),(\d+)\))?[^}]*\} ([\w\-]+)\(")
_ITEM = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "s8": 1, "u8": 1, "pred": 1}


def _buffers(text: str):
    """The arrays a compiled TPU program materialises (instructions outside
    fused computations): dicts of computation, name, op, dims in minor-to-
    major order, and logical / tiled physical bytes — a (t2, t1) tile pads
    the minor axis to t1 and the one above it to t2."""
    comp, out = "", []
    for line in text.splitlines():
        if line[:1] not in (" ", "}", "") and "{" in line:
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            continue
        m = _ARRAY.match(line)
        if not m or comp.lstrip("%").startswith("fused_computation"):
            continue
        name, dt, dims, order, t2, t1, op = m.groups()
        dims = [int(x) for x in dims.split(",")] if dims else []
        order = [int(x) for x in order.split(",")] if order else []
        phys = [dims[i] for i in order]                   # minor first
        n = int(np.prod(dims)) if dims else 1
        if t1 and phys:
            phys[0] = -(-phys[0] // int(t1)) * int(t1)
            if len(phys) > 1:
                phys[1] = -(-phys[1] // int(t2)) * int(t2)
        item = _ITEM.get(dt, 4)
        out.append({"comp": comp, "name": name, "op": op, "n": n,
                    "minor": [dims[i] for i in order], "logical": n * item,
                    "physical": (int(np.prod(phys)) if phys else 1) * item})
    return out


def _relayout_bytes(buffers, at_least: float = 30e6):
    """{computation: tiled bytes its ``copy`` / ``reshape`` / ``transpose``
    instructions write} — ops that compute nothing (a reshape that is a
    bitcast is printed as ``bitcast``, not ``reshape``)."""
    by = {}
    for b in buffers:
        if b["op"] in ("copy", "reshape", "transpose") \
                and b["physical"] >= at_least:
            by[b["comp"]] = by.get(b["comp"], 0) + b["physical"]
    return by


# ---------------------------------------------------------------------------
# Phase A: the Titanic app's selector sweep and its serving program
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def titanic():
    """helloworld's workflow on its seeded frame: the selector, the arrays
    its sweep sees and the fused plan of the full default grid."""
    from helloworld.titanic import build_workflow, titanic_data
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.readers import DataReaders

    wf, pred = build_workflow()
    sel = pred.origin_stage
    label_f, vec_f = sel.inputs
    wf.set_reader(DataReaders.Simple.custom(titanic_data(),
                                            key="PassengerId"))
    data = wf.compute_data_up_to(vec_f, label_f)
    X = np.asarray(data[vec_f.name].values, np.float32)
    y = np.asarray(data[label_f.name].values, np.float32)
    train_w, val_mask = sel.validator.make_folds(len(y), None)
    return {"wf": wf, "sel": sel, "X": X, "y": y,
            "train_w": np.asarray(train_w, np.float32),
            "val_w": np.asarray(val_mask, np.float32),
            "plan": lambda models: build_sweep_plan(
                models, X, y, train_w, sel.validator.evaluator)}


def test_phase_a_fused_sweep(titanic, one_chip):
    from transmogrifai_tpu.ops import sweep

    sel = titanic["sel"]
    assert sum(len(g) for _, g in sel.models) == chip_smoke.TITANIC_CANDIDATES
    assert titanic["train_w"].shape[0] == chip_smoke.TITANIC_FOLDS
    plan = titanic["plan"](sel.models)
    args = _on(one_chip, (plan.X, tuple(plan.xbs), plan.y, titanic["train_w"],
                          titanic["val_w"], plan.blob))
    compiled = sweep._run.lower(plan.spec, *args).compile()
    _fits(compiled)
    # the one-hot formulation: histograms ride dot ops, not segment scatters
    assert "segment" not in compiled.as_text()


def test_serve_largest_bucket_program(titanic, one_chip):
    """``BucketScorer``'s program is the fitted transform DAG fused for one
    bucket; the prediction head runs outside it, so it does not depend on
    which candidate won and a one-candidate selector fits the DAG here."""
    from transmogrifai_tpu.serve.aot import BucketScorer
    from transmogrifai_tpu.serve.registry import shape_buckets

    wf, sel = titanic["wf"], titanic["sel"]
    full = sel.models
    est, grids = full[0]
    sel.models = [(est, grids[:1])]
    try:
        model = wf.train()
    finally:
        sel.models = full
    buckets = shape_buckets(chip_smoke.SERVE_MAX_BATCH)
    assert buckets[-1] == max(chip_smoke.SERVE_REQUEST_ROWS)
    scorer = BucketScorer(model, buckets, jax.devices()[0])
    args = _on(one_chip, scorer._template_args(buckets[-1]))
    _fits(scorer._jitted.lower(args).compile())


def test_rowsharded_program_on_2x2(titanic, topo):
    """One model column of a 2x2 mesh: ``_run_rs`` over the column's two
    chips; every collective stays inside that data-axis pair."""
    from transmogrifai_tpu.impl.classification.trees import (
        OpRandomForestClassifier)
    from transmogrifai_tpu.ops import sweep
    from transmogrifai_tpu.parallel import mesh as mesh_mod
    from transmogrifai_tpu.parallel.spec_partition import partition_spec

    models = [(e, g) for e, g in titanic["sel"].models
              if not isinstance(e, OpRandomForestClassifier)]
    plan = titanic["plan"](models)
    F = titanic["train_w"].shape[0]
    grid = mesh_mod.make_mesh(n_data=2, n_model=2, devices=topo.devices)
    shard = partition_spec(plan.spec, plan.blob, 2, plan.n_rows,
                           plan.n_features, F)[0]
    column = Mesh(np.asarray(grid.devices)[:, 0], (mesh_mod.DATA_AXIS,))
    rows = NamedSharding(column, P(mesh_mod.DATA_AXIS))
    folds = NamedSharding(column, P(None, mesh_mod.DATA_AXIS))
    n = plan.n_rows
    n_pad = -(-n // 2) * 2
    args = (_rows(_on(rows, (plan.X, tuple(plan.xbs), plan.y)), n_pad)
            + (jax.ShapeDtypeStruct((F, n_pad), np.float32, sharding=folds),)
            * 2
            + (_on(NamedSharding(column, P()), shard.blob),))
    compiled = sweep._run_rs.lower(shard.spec, column, n, *args).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" in text
    groups = set(re.findall(r"replica_groups=\{(\{[^}]*\}(?:,\{[^}]*\})*)\}",
                            text))
    assert groups == {"{0,1}"}, groups  # the column's data pair, nothing else


# ---------------------------------------------------------------------------
# Phase B: the scale10m pipeline at chip_smoke's rows x 500 features
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def scale_features():
    """scale10m's feature DAG (500 raw features -> transmogrify ->
    SanityChecker) fitted on a small seeded sample: the fitted stages fix
    the vector width and the streamed program; rows are set at lowering."""
    from transmogrifai_tpu import OpWorkflow

    width = dict(n_num=scale10m.FULL_NUM, n_cat=scale10m.FULL_CAT)
    df = scale10m.synthesize(1024, seed=7, **width)
    label, checked = scale10m.features(**width)
    model = (OpWorkflow().set_result_features(checked).set_input_dataset(df)
             .train())
    return {"df": df, "model": model, "checked": checked.name,
            "width": int(model.train_data[checked.name].values.shape[1])}


def test_phase_b_fused_sweep_fits_one_chip(scale_features, one_chip):
    """The 64-candidate LR + SVC + MLP plan at phase B's sweep rows x vector
    width, chunked as the validator chunks it.  Each chunk compiles as
    ``_run_scores``: the family training kernels, which hold X.  The metric
    kernel that follows (inside ``_run``, or as ``_run_metrics`` past
    ``SPLIT_METRICS_ELEMS``) is the one phase A's program already contains;
    it costs ~30 s to compile at any shape and holds the [F, C, n] scores
    that ``TMOG_FUSED_SCORES_BYTES`` bounds."""
    from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.impl.tuning.validators import _chunk_candidates
    from transmogrifai_tpu.ops import sweep
    from transmogrifai_tpu.utils.env import env_float

    n = chip_smoke.phase_b_sweep_rows(chip_smoke.PHASE_B_ROWS)
    d, F = scale_features["width"], scale10m.FOLDS
    grid = scale10m.candidates()
    assert sum(len(g) for _, g in grid) == chip_smoke.PHASE_B_CANDIDATES
    budget = env_float("TMOG_FUSED_SCORES_BYTES", 3e8)
    chunks = _chunk_candidates(grid, max(int(budget // (F * n * 4.0)), 1))
    # the plan needs data only for its label check: tiny rows, real width
    rng = np.random.default_rng(0)
    Xs = rng.normal(size=(64, d)).astype(np.float32)
    ys = (np.arange(64) % 2).astype(np.float32)
    rows = jax.ShapeDtypeStruct((n,), np.float32, sharding=one_chip)
    folds = jax.ShapeDtypeStruct((F, n), np.float32, sharding=one_chip)
    X = jax.ShapeDtypeStruct((n, d), np.float32, sharding=one_chip)
    specs = set()  # chunks with the same fragments share one program
    for chunk in chunks:
        plan = build_sweep_plan(chunk, Xs, ys, np.ones((F, 64), np.float32),
                                OpBinaryClassificationEvaluator())
        assert plan is not None and plan.xbs == ()
        if plan.spec in specs:
            continue
        specs.add(plan.spec)
        scores_bytes = F * len(plan.spec[2]) * n * 4
        assert scores_bytes <= budget
        held = _fits(sweep._run_scores.lower(
            plan.spec, X, (), rows, folds, _on(one_chip, plan.blob)).compile())
        assert held + 16 * scores_bytes < HBM_BYTES  # room for the metric sort


def test_binary_metric_program_at_the_sweep_shape(one_chip):
    """``_run_metrics`` over the ``scale-500`` cell's ``[5, 64, 180224]``
    score block: no gather in the compiled program (the argsort's three
    permutation gathers and the AuPR curve's indexed read were 3.5 s of a
    7.3 s step on the v5e), and temporaries no larger than the gather form's
    1,777,358,848 B plus the one ``y`` broadcast to the block that the sort
    carries.  ~40 s to compile on an 8-core host; the same pass written as
    ``lax.associative_scan`` took the compiler six minutes there."""
    from transmogrifai_tpu.ops import sweep

    F, C, n = 5, 64, 180224
    spec = ("binary", (), (0.0,) * C)
    shape = lambda *s: jax.ShapeDtypeStruct(s, np.float32,  # noqa: E731
                                            sharding=one_chip)
    compiled = sweep._run_metrics.lower(
        spec, shape(n), shape(F, C, n), shape(F, n)).compile()
    _fits(compiled)
    assert not re.search(r"\sgather\(", compiled.as_text())
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1_777_358_848 + F * C * n * 4, temp


def test_streamed_chunk_program(scale_features, one_chip):
    """One chunk of the streamed transform program: every fusable stage of
    the fitted DAG, ``chunk_rows`` x 500 raw features in."""
    from transmogrifai_tpu.workflow import stream

    df, model = scale_features["df"], scale_features["model"]
    plan = stream.build_plan(df, model.dag, live={scale_features["checked"]})
    assert plan is not None and plan.n_stream >= 2
    host_args, _ = stream.chunk_args(plan, df, 0, len(df), len(df))
    C = min(stream.chunk_rows(), chip_smoke.PHASE_B_ROWS)
    assert C > len(df)
    _fits(stream.program_for(plan).lower(
        _rows(_on(one_chip, host_args), C)).compile())


# ---------------------------------------------------------------------------
# The level-histogram build of ops/trees.py, both layouts
# ---------------------------------------------------------------------------
N_BINS, CHANNELS = 32, 2  # 32 = the MLlib maxBins default


def test_histogram_blocked_at_the_trees_cell_rows(scale_features, one_chip):
    """The row-blocked one-hot GEMM (the one path there is, for every n) at
    the ``scale-500-trees`` cell's 32,768 sweep rows and phase B's width:
    three levels of a 17-tree chunk, several row blocks a level."""
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import trees as Tr

    d = scale_features["width"]
    n, T = 32768, 17
    assert Tr.hist_blocks(n, T * 2, CHANNELS * d * N_BINS)[0] > 1

    def grow(Xb, y, w, fm):
        ones = jnp.ones((T,), jnp.float32)
        tree, node = Tr.grow_forest(
            Xb, -y[:, None], jnp.ones_like(y), w, fm, 3, N_BINS, 256,
            reg_lambda_t=1e-6 * ones, gamma_t=0 * ones, mcw_t=10 * ones,
            mig_t=0 * ones, return_row_node=True)
        return tree.leaf_val, node

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(grow).lower(
        S((n, d), np.int8), S((n,), np.float32), S((T, n), np.float32),
        S((T, d), np.float32)).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "trees.hist" in text and "trees.route" in text
    assert "segment" not in text  # histograms ride dot ops


def test_histogram_compacted_at_the_trees_cell_rows(scale_features, one_chip):
    """A forest chunk on its kept features (28 of the vector's columns a
    tree, as ``kept_features`` draws them): 300 trees at the trees
    cell's 32,768 rows — the column gather, the tree-batched GEMM over row
    blocks and the k-wide routing, three levels of them."""
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import trees as Tr

    d = scale_features["width"]
    n, T = 32768, 300
    k = Tr.n_kept(d, np.sqrt(d) / d)
    assert k < d and Tr.hist_blocks(n, T * 2 * CHANNELS, T * k * N_BINS)[0] > 1

    def grow(Xb, y, w, kept):
        ones = jnp.ones((T,), jnp.float32)
        tree, node = Tr.grow_forest(
            Xb, -y[:, None], jnp.ones_like(y), w, kept, 3, N_BINS, 256,
            reg_lambda_t=1e-6 * ones, gamma_t=0 * ones, mcw_t=10 * ones,
            mig_t=0 * ones, return_row_node=True)
        return tree.split_feat, tree.leaf_val, node

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(grow).lower(
        S((n, d), np.int8), S((n,), np.float32), S((T, n), np.float32),
        S((T, k), np.int32)).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "trees.hist" in text and "trees.route" in text
    assert "segment" not in text  # histograms ride dot ops
    assert f"s8[{T},{k}," in text  # the kept columns stay one byte a cell
    # relayout copies of 30 MB and more, all computations together: no more
    # than PR 31's program wrote (these three levels are narrow: what is
    # copied is the gathered columns and the row blocks, not level tensors)
    assert sum(_relayout_bytes(_buffers(text)).values()) <= 1_102_069_760


def test_boosted_levels_keep_the_bins_off_the_lanes(scale_features, one_chip):
    """Two boosting rounds at the ``scale-500-trees`` cell's shape (6 trees x
    32,768 rows x the vector's width, 32 bins, depth 10, frontier 256: levels
    8-9 are the beam loop), read from the program the v5e's compiler makes.

    No level tensor has the 32 bins as its minor axis, where the (8, 128)
    tiles store it four times over; and what is left of relayout copies in
    any one computation — a beam level's body, or a round's eight unrolled
    levels together — is under 0.6 GB (PR 31's program: 3.29 GB a beam
    level, 3.43 GB the unrolled levels, plus 2.4 GB of padded fusion outputs
    a beam level), and no array of 1e7 elements or more is stored above 1.1
    times its size.  What stays, pinned here: the GEMM's accumulator
    [1536, 24320] is cut to [6, 2, 128, 32, 760] and turned slots-minor, two
    ops, 0.30 GB a beam level."""
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import trees as Tr

    d = scale_features["width"]
    n, T, rounds, depth, frontier = 32768, 6, 2, 10, 256

    def run(Xb, y, w, rw, fm, eta, lam, gam, mcw):
        return Tr._gbt_batch_impl(Xb, y, w, rw, fm, "logistic", rounds, depth,
                                  N_BINS, frontier, eta, lam, gam, mcw)

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(run).lower(
        S((n, d), np.int8), S((n,), np.float32), S((T, n), np.float32),
        S((rounds, n), np.float32), S((rounds, d), np.float32),
        *[S((T,), np.float32)] * 4).compile()
    _fits(compiled)
    text = compiled.as_text()
    assert "trees.hist" in text and "trees.split" in text
    buffers = _buffers(text)
    big = [b for b in buffers if b["n"] >= 1e7]
    # the beam's level tensors are there: 6 x 2 x 32 x 256 x d
    assert any(sorted(b["minor"]) == sorted([T, 2, N_BINS, frontier, d])
               for b in big)
    assert not [b for b in big if b["minor"][0] == N_BINS]
    padded = [b for b in big if b["physical"] > 1.1 * b["logical"]]
    assert not padded, padded[:3]
    relayout = _relayout_bytes(buffers)
    assert relayout and max(relayout.values()) < 0.6e9, relayout


def test_forest_chunk_reads_its_leaves_without_a_gather(one_chip, monkeypatch):
    """One depth-12 chunk of the ``scale-500-multiclass`` cell, cut to the
    chunk: one 50-tree forest x 32,768 rows x 10 classes on its 28 kept
    columns, through ``ops/sweep._forest_group_scores`` under its
    ``scores.forest`` scope.  The program the v5e's compiler makes reads the
    chunk's leaves under ``trees.leaves`` and holds no gather that hands back
    a [chunk, rows] plane (the parent's ten ``take_along_axis`` a chunk, 8.85e8
    lookups a step: PERF.md, PR 34) — the one gather left takes whole rows of
    the transposed matrix, each tree's kept columns; and the row block's
    one-hot and product cost the chunk no memory: its temporaries are no
    larger than with the parent's read in the helper's place (measured here:
    1,743,680,000 B against 1,744,292,864 B)."""
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import sweep, trees as Tr

    n, d, c, T, depth = 32768, 760, 10, 50, 12
    group = ((0,), depth, T, 0, N_BINS, np.sqrt(d) / d, 1.0, True, 42, 256,
             False, Tr.balanced_chunk(T, Tr.forest_chunk_size(
                 depth, N_BINS, d, c, 256, n_rows=n,
                 n_kept=Tr.n_kept(d, np.sqrt(d) / d)), group=T), 0, 1)
    assert group[11] == T          # the plan's own cut: a forest a chunk

    def gathered(leaf_val, row_node):
        return jnp.stack([jnp.take_along_axis(leaf_val[:, :, j], row_node, axis=1)
                          for j in range(leaf_val.shape[-1])], axis=1)

    def compiled_chunk():
        def chunk(Xb, y, train_w, blob):
            with jax.named_scope("scores.forest"):
                return sweep._forest_group_scores(group, (Xb,), y, train_w,
                                                  blob, c)

        S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
        return jax.jit(chunk).lower(
            S((n, d), np.int8), S((n,), np.float32), S((1, n), np.float32),
            S((2,), np.float32)).compile()

    # all of this program is the chunk, under ``scores.forest``
    planes = re.compile(rf"= \w+\[{T},{n}\]\S* gather\(")
    ours = compiled_chunk()
    _fits(ours)
    text = ours.as_text()
    assert "trees.leaves" in text
    assert not planes.search(text)
    monkeypatch.setattr(Tr, "read_leaves", gathered)
    parents = compiled_chunk()
    assert len(planes.findall(parents.as_text())) == c     # the pattern finds them
    assert ours.memory_analysis().temp_size_in_bytes \
        <= parents.memory_analysis().temp_size_in_bytes
