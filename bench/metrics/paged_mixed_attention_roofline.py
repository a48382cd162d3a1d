"""Roofline share of the paged mixed attention kernel
(``paged_mixed_attention_fwd``): one call per layer per loop iteration.
Each call's least time (the architecture's ``attention_call``) comes from
the rows that hold a request, at a lower bound of each row's position in
that iteration (a prompt row advances by at most the span per iteration, a
decoding row by at least one, and neither passes where the step left it),
so the share errs low, never high."""
import numpy as np

from bench.costs import roofline_seconds
from bench.peaks import peaks

KERNEL = "paged_mixed_attention"


def read(run):
    ops = [n for n, k in run.kernel_ops.items() if k == KERNEL]
    t = sum(run.trace.op_s.get(n, 0.0) for n in ops)
    if not t or not run.steps:
        return None
    c = run.costs
    peak = peaks(run.device_kind)
    T = run.span
    least = 0.0
    for st in run.steps:
        if not st.rows:
            continue
        p0 = np.array([r[0] for r in st.rows], np.int64)
        e0 = np.array([r[1] for r in st.rows], np.int64)
        end = np.array([r[2] for r in st.rows], np.int64)
        prompt = p0 < e0 - 1
        for i in range(st.iters):
            lb = np.where(prompt, np.minimum(p0 + i * T, e0 - 1), p0 + i)
            lb = np.minimum(lb, np.maximum(end, p0))
            least += c.n_layers * roofline_seconds(
                *c.attention_call(lb, T), peak)
    return 100.0 * least / t
