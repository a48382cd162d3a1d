"""Median time per output token (printed on an earlier line)."""
from bench.clientside import closed, percentile, tpot_ms, window_requests


def read(run):
    if closed(run):
        return percentile([tpot_ms(r, run, True) for r in run.recs], 50)
    return percentile([tpot_ms(r, run) for r in window_requests(run)], 50)
