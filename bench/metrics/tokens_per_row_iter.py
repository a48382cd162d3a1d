"""Tokens emitted per live row per loop iteration over the profiled steps
(the engine's speculation counters): above 1 where drafts are accepted."""


def read(run):
    if not run.counters or run.counters[1] is None:
        return None
    a, b = run.counters
    rows = b["live_iters"] - a["live_iters"]
    return (b["emitted"] - a["emitted"]) / rows if rows else None
