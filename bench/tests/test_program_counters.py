"""The program's mixed-loop counters against the benchmark's own reckoning,
on the CPU at a small size, through the real router, replica, engine and
load loop: over the same steps, committed positions over computed positions
(``speculation_stats``) equals ``span_useful_share``, which the benchmark
reckons from its snapshots of ``eng.pos`` (and, for rows that finished,
from the request's length)."""
from types import SimpleNamespace

import pytest

from bench import run
from bench.tests.test_correct import CELL, MIX, SMALL

SEED = 2**33 + 5


def test_committed_share_equals_span_useful_share():
    spec = {"name": "small", "chips": 1, "config": SMALL, "mix": MIX,
            "cell": CELL, "end_to_end": [], "per_layer": []}
    _, eng, rep, _, router = run.setup_engine(spec, SEED, cache=False)
    loop = run.LoadLoop(spec, router, rep, SEED, 3.0, trace=False)
    loop.profiling = True                  # record every step
    first = dict(eng.speculation_stats)
    loop._generate(float("inf"))           # every arrival at once
    while not loop._idle():
        router.dispatch(0.0)
        loop._step(lambda: 0.0)
    last = eng.speculation_stats
    done = [r for r in loop.recs.values() if r.done is not None]
    assert len(done) == len(loop.recs) > eng.cfg.max_batch   # rows finished
    delta = {k: last[k] - first[k] for k in last}
    iters = sum(s.iters for s in loop.steps)
    assert delta["computed_positions"] == eng.cfg.max_batch * eng.span * iters
    assert delta["committed_positions"] == sum(
        max(end - p0, 0) for s in loop.steps for p0, _, end in s.rows)
    view = SimpleNamespace(steps=loop.steps, max_batch=eng.cfg.max_batch,
                           span=eng.span)
    share = 100.0 * delta["committed_positions"] / delta["computed_positions"]
    assert share == pytest.approx(run.reader("span_useful_share")(view),
                                  rel=1e-12)
    assert 0 < delta["kv_committed_page_iters"] < delta["kv_reserved_page_iters"]
