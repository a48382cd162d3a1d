"""Median time to first token (printed on an earlier line)."""
from bench.clientside import percentile, ttft_s, window_requests


def read(run):
    return percentile([ttft_s(r, run) for r in window_requests(run)], 50)
