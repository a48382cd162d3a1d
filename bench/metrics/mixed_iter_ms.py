"""Device time of the mixed-step executable per loop iteration, in ms, over
the profiled steps."""

MODULE = "_mixed_step_fn"


def read(run):
    iters = sum(s.iters for s in run.steps)
    t = sum(v for k, v in run.trace.module_s.items() if MODULE in k)
    if not iters or not t:
        return None
    return 1e3 * t / iters
