"""Step driver ``selector_fit`` for a configuration that states what it
requires of the program: ``cfg["requires"]`` lists ``module:attribute`` names,
and set-up refuses at once (exit 1, one line on stderr) where the program
lacks one — before a table is transformed or a program compiled.

Why a configuration needs it: a selector whose fused launch fails does not
stop, it falls back to its per-family path.  On the default grid at 32,768 x
760 rows a program without the row-blocked histogram build
(``ops/trees.hist_blocks``) has its fused launch refused for memory, then
every forest candidate, and had not ended one fit after 10 minutes
(``PERF.md``, PR 29): the guard of ``selector_fit`` would say so only after
that fit.  A program that cannot run the configuration fails cleanly and
soon instead.  Everything else is ``selector_fit``'s.
"""
from __future__ import annotations

import importlib

from benchmarks.entries import selector_fit as base

rehearsal_config = base.rehearsal_config
step, work, answers, shapes = base.step, base.work, base.answers, base.shapes


def missing(requires) -> list:
    """The required ``module:attribute`` names the program does not have."""
    out = []
    for name in requires:
        module, attr = name.split(":")
        try:
            getattr(importlib.import_module(module), attr)
        except (ImportError, AttributeError):
            out.append(name)
    return out


def setup(ctx) -> None:
    lacks = missing(ctx.cfg.get("requires", ()))
    if lacks:
        raise SystemExit(f"{ctx.cfg['name']}: the program lacks {lacks}, which "
                         "this configuration requires; it cannot be run here")
    base.setup(ctx)
