"""90th percentile of time to first token over the requests due in the
window, from when each was due to when the client got its first token."""
from bench.clientside import percentile, ttft_s, window_requests


def read(run):
    return percentile([ttft_s(r, run) for r in window_requests(run)], 90)
