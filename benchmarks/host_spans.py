"""The host half of one selector fit, span by span: the arithmetic behind
the readers of the row gathers (``selector.rows``), the winner's device
calls (``selector.winner_fit`` / ``.winner_predict``), the host evaluators
(``selector.metrics``), the quantile sketch (``sweep.sketch``) and the
uploads that bypass the device cache (``devcache.put``).

Each of these spans sits inside one of ``program_spans.FEED`` /
``REFIT_EVAL``, so the three accepted idle readers keep cutting the step
the same three ways; the readers here cut finer inside them.  A program
without the spans reads None.
"""
from __future__ import annotations

from typing import Optional, Sequence

from benchmarks import program_spans

ROWS = "selector.rows"


def idle_under(r, names: Sequence[str]) -> Optional[float]:
    """Idle seconds of the traced step under any span in ``names``, each
    gap cut at the edges of those spans (what nests inside them counts for
    them); None when the step holds none of them."""
    p = program_spans.load(r)
    if not any(s[0] in names for s in p["spans"]):
        return None
    idle = program_spans.idle_by_phase(p["ops"], p["window"], p["spans"],
                                       tuple(names))
    return sum(idle.get(n, 0.0) for n in names)


def gather_gbps(r) -> Optional[float]:
    """GB/s of the step's host row gathers: the ``bytes`` of its
    ``selector.rows`` spans over their summed wall; None without them."""
    rows = [s for s in program_spans.load(r)["spans"] if s[0] == ROWS]
    wall = sum(b - a for _, a, b, _ in rows)
    if not rows or wall <= 0:
        return None
    return sum(s[3].get("bytes", 0) for s in rows) / wall / 1e9
