"""Client-side latencies of the requests due in the window.

Every time here is the host clock at which the client saw a token: when
``Replica.step`` returned it, counted from when the request was due.
"""
from __future__ import annotations

import numpy as np


def window_requests(run) -> list:
    return [r for r in run.recs if 0.0 <= r.due < run.seconds]


def ttft_s(rec, run) -> float:
    """Due to first token; a request that never got one counts as having
    waited until the drain cap."""
    if rec.deliveries:
        return rec.deliveries[0][0] - rec.due
    return run.seconds + float(run.spec["cell"]["drain_cap_s"]) - rec.due


def tpot_ms(rec, run, window_only: bool = False) -> float | None:
    """(last delivery - first delivery) / tokens delivered after the first
    delivery, in ms; None with fewer than two deliveries."""
    d = rec.deliveries
    if window_only:
        d = [x for x in d if 0.0 <= x[0] < run.seconds]
    if len(d) < 2:
        return None
    return 1e3 * (d[-1][0] - d[0][0]) / sum(k for _, k in d[1:])


def closed(run) -> bool:
    return run.spec["mix"]["loop"] == "closed"


def percentile(values, q: float) -> float | None:
    v = [x for x in values if x is not None]
    return float(np.percentile(np.asarray(v, np.float64), q)) if v else None


__all__ = ["closed", "percentile", "tpot_ms", "ttft_s", "window_requests"]
