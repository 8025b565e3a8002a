"""Step driver: the selector stage's ``fit`` on the prepared label and vector
columns, as ``OpWorkflow.train()`` calls it — DataBalancer preparation, the
fused fold x grid sweep, the metric pull, the winner's refit and its train
and holdout evaluation.  Ingest, stats and the streamed transforms run once,
in set-up.
"""
from __future__ import annotations

import numpy as np

from benchmarks import program


rehearsal_config = program.rehearsal_config


def setup(ctx) -> None:
    """Build the workflow, prepare the selector's input columns (the sub-DAG
    up to the checked vector) and run one fit: the warm-up of exactly the
    programs the window drives."""
    cfg = ctx.cfg
    wf, sel, label, vec = program.build_workflow(
        cfg, program.to_dataset(ctx.cols, ctx.table), ctx.table)
    with ctx.span("bench.setup.prepare_columns"):
        data = wf.compute_data_up_to(vec, label)
    ctx.state.update(sel=sel, data=data, vec_name=vec.name,
                     n_candidates=sum(len(g) for _, g in sel.models))
    with ctx.span("bench.setup.warm_fit"):
        step(ctx)


def step(ctx) -> None:
    s = ctx.state
    sel, data = s["sel"], s["data"]
    with ctx.listener.time_stage(sel, "fit", len(data)):
        s["last"] = sel.fit(data)
    ctx.count("sweep_launches", program.check_sweep_record(
        program.sweep_record(), s["n_candidates"]))


def work(ctx) -> float:
    """CV fits one step completes."""
    return float(ctx.state["n_candidates"] * int(ctx.cfg["folds"]))


def answers(ctx) -> dict:
    s = ctx.state
    out = program.answers_of(s["last"])
    out["vector"] = np.asarray(s["data"][s["vec_name"]].values)
    return out


def shapes(ctx) -> dict:
    """What the readers need once the program's state is dropped."""
    s = ctx.state
    X = s["data"][s["vec_name"]].values
    sweep_rows, holdout_rows = program.split_rows(ctx.cfg)
    return {"width": int(X.shape[1]), "rows": int(X.shape[0]),
            "sweep_rows": sweep_rows, "holdout_rows": holdout_rows,
            "winner_family": program.family_of(
                ctx.cfg, s["last"].summary.best_model_type)}
