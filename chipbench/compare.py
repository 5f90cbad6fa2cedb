"""The comparisons that decide ``correct``: each yields a check — a
name, the number compared, its limit, and whether it holds."""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, Optional


def check(name: str, value: float, limit: float, at: str = "") -> Dict:
    """``at`` names where the value was read (the worst leaf), for the log."""
    ok = math.isfinite(value) and value <= limit
    return {"name": name, "value": float(value), "limit": float(limit),
            "ok": bool(ok), "at": at}


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              only: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """Per leaf, the gap between the program's norm and the reference's
    (not the norm of a difference), measured against the reference's norm
    of that leaf or of the median leaf, whichever is larger — some leaves'
    norms are all but zero. A gap that is not a number reads infinite."""
    names = list(reference if only is None else only)
    floor = statistics.median(reference[n] for n in names)
    gaps = {n: abs(program[n] - reference[n]) / max(reference[n], floor)
            for n in names}
    return {n: g if math.isfinite(g) else math.inf for n, g in gaps.items()}


def check_worst_leaf(name: str, gaps: Dict[str, float], limit: float) -> Dict:
    """The check of the largest of ``gaps``, naming its leaf."""
    leaf = max(gaps, key=gaps.get)
    return check(name, gaps[leaf], limit, at=leaf)
