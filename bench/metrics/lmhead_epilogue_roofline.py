"""Roofline share of the fused lm-head epilogue (``lmhead_epilogue_fwd``):
one call per loop iteration over max_batch x span rows; the least time of
each call (the architecture's ``lmhead_call``: the (rows, d) x (d, V)
product, the weight read once) over the kernel's device time in the
trace."""
from bench.costs import roofline_seconds
from bench.peaks import peaks

KERNEL = "lmhead_epilogue"


def read(run):
    ops = [n for n, k in run.kernel_ops.items() if k == KERNEL]
    t = sum(run.trace.op_s.get(n, 0.0) for n in ops)
    iters = sum(s.iters for s in run.steps)
    if not t or not iters:
        return None
    least = iters * roofline_seconds(
        *run.costs.lmhead_call(run.max_batch * run.span),
        peaks(run.device_kind))
    return 100.0 * least / t
