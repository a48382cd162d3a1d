"""Output tokens the clients received inside the window, over the window."""


def read(run):
    n = sum(k for r in run.recs for t, k in r.deliveries
            if 0.0 <= t < run.seconds)
    return n / run.seconds
