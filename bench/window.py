"""Metric arithmetic over one measured window, from the feeder's stamps.

Times are host seconds.  A request's TTFT runs from its due time to the
stamp of its first token; a request due in the window that has no first
token when the run ends ranks above every served one.  Inter-token gaps
are all gaps between consecutive tokens of a request whose later token
falls in the window, pooled over every request.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of sorted order."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ranked_percentile(served: Sequence[float], unserved: Sequence[float],
                      q: float) -> float:
    """Percentile where each unserved value ranks above every served one:
    its value is its wait so far, but never below the largest served."""
    top = max(served, default=0.0)
    ranked = sorted(served) + sorted(max(u, top) for u in unserved)
    return percentile(ranked, q)


def in_window(t: float, w0: float, w1: float) -> bool:
    return w0 <= t < w1


def out_tok_s(stamp_lists: Iterable[Sequence[float]], w0: float,
              w1: float) -> float:
    n = sum(1 for st in stamp_lists for t in st if in_window(t, w0, w1))
    return n / (w1 - w0)


def ttft_s(recs, w0: float, w1: float, end: float, q: float) -> Optional[float]:
    """``q``-th percentile TTFT over every request due in the window;
    ``end`` is when the run stopped watching (the unserved waited until
    then).  None when no request was due."""
    due = [r for r in recs if in_window(r.due, w0, w1)]
    if not due:
        return None
    served = [r.stamps[0] - r.due for r in due if r.stamps]
    unserved = [end - r.due for r in due if not r.stamps]
    return ranked_percentile(served, unserved, q)


def gaps_in_window(stamp_lists: Iterable[Sequence[float]], w0: float,
                   w1: float) -> List[float]:
    out = []
    for st in stamp_lists:
        out.extend(b - a for a, b in zip(st, st[1:]) if in_window(b, w0, w1))
    return out
