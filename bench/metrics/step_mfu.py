"""The whole step's share of the chip's bf16 peak: operations of the
committed positions of the profiled steps (the architecture's
``positions_flops``: block and head matmuls, and attention over each
position's context) over the traced window."""
from bench.peaks import peaks


def read(run):
    start = [p0 for st in run.steps for p0, _, _ in st.rows]
    end = [e for st in run.steps for _, _, e in st.rows]
    if not start or not run.trace.window_s:
        return None
    flops = run.costs.positions_flops(start, end)
    peak = peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (run.trace.window_s * peak)
