"""Share of the positions the mixed loop computes that it commits, in the
profiled steps: committed KV positions (prompt tokens prefilled plus tokens
emitted) over max_batch x span x loop iterations."""


def read(run):
    iters = sum(s.iters for s in run.steps)
    if not iters:
        return None
    useful = sum(max(end - p0, 0) for s in run.steps for p0, _, end in s.rows)
    return 100.0 * useful / (run.max_batch * run.span * iters)
