"""90th percentile of the front door's wait: from when a request was due to
the start of the first engine step that held it in a slot.  Read over the
requests due before the profile starts, which the profiler does not slow."""
from bench.clientside import percentile


def read(run):
    waits = [r.admit - r.due for r in run.recs
             if 0.0 <= r.due < run.profile[0] and r.admit is not None]
    return percentile(waits, 90)
