"""Share of the requests due in the window that finished and met both the
cell's limit on time to first token and its limit on time per output
token.  A failed request misses."""
from bench.clientside import tpot_ms, ttft_s, window_requests


def read(run):
    cell = run.spec["cell"]
    reqs = window_requests(run)
    if not reqs:
        return None
    ok = 0
    for r in reqs:
        tpot = tpot_ms(r, run) or 0.0
        ok += (not r.failed and ttft_s(r, run) <= cell["ttft_limit_s"]
               and tpot <= cell["tpot_limit_ms"])
    return ok / len(reqs)
