"""The comparison that decides ``correct``: every number a cell's reference
compares (``benchmarks/references/<reference>.py``: ``numbers(...)``) held
against its limit from the cell's workload file.  The readings each limit
was set from are in ``PERF.md``."""
from __future__ import annotations

import math
from typing import Dict, Tuple


def judge(nums: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}).  A number without a limit, or
    one that is not finite, fails; so does a comparison of nothing."""
    table, ok = {}, bool(nums)
    for name, v in nums.items():
        lim = limits.get(name)
        table[name] = {"value": v, "limit": lim}
        ok = ok and lim is not None and math.isfinite(v) and v <= lim
    return bool(ok), table
