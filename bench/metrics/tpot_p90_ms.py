"""90th percentile over requests of the time per output token after the
first.  Open loop: the requests due in the window.  Closed loop: every
request, counting only deliveries inside the window."""
from bench.clientside import closed, percentile, tpot_ms, window_requests


def read(run):
    if closed(run):
        return percentile([tpot_ms(r, run, True) for r in run.recs], 90)
    return percentile([tpot_ms(r, run) for r in window_requests(run)], 90)
