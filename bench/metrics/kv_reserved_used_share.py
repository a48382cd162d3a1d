"""Share of the KV pages reserved for the served slots that their committed
KV fills, over the profiled steps: 100 x the engine's
Δ``kv_committed_page_iters`` / Δ``kv_reserved_page_iters``
(``speculation_stats``; each counts pages times loop iterations at every
sync).  A program without those counters gives nothing."""

KEYS = ("kv_committed_page_iters", "kv_reserved_page_iters")


def read(run):
    if not run.counters or run.counters[1] is None:
        return None
    a, b = run.counters
    if not all(k in a and k in b for k in KEYS):
        return None
    used, reserved = (b[k] - a[k] for k in KEYS)
    return 100.0 * used / reserved if reserved else None
