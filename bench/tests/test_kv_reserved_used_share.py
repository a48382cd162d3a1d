"""``kv_reserved_used_share``: the engine's KV page counters, passed through
``LoadLoop._counters``, read against a value worked out by hand, and on the
CPU at a small size through the real router, replica, engine and load
loop."""
from types import SimpleNamespace

import pytest

from bench import run
from bench.tests.test_correct import CELL, MIX, SMALL

SEED = 2**33 + 17


def _read(counters):
    return run.reader("kv_reserved_used_share")(
        SimpleNamespace(counters=counters))


def test_by_hand():
    a = {"kv_committed_page_iters": 100.0, "kv_reserved_page_iters": 400.0}
    b = {"kv_committed_page_iters": 950.0, "kv_reserved_page_iters": 1400.0}
    # (950 - 100) / (1400 - 400) pages x iterations
    assert _read([a, b]) == pytest.approx(85.0)


@pytest.mark.parametrize("counters", [
    None,
    [{"kv_committed_page_iters": 1.0, "kv_reserved_page_iters": 2.0}, None],
    [{"emitted": 1.0}, {"emitted": 2.0}],
    [{"kv_committed_page_iters": 1.0, "kv_reserved_page_iters": 2.0}] * 2,
], ids=["no_profile", "unfinished", "no_kv_counters", "no_iterations"])
def test_nothing_to_read_gives_nothing(counters):
    assert _read(counters) is None


def test_reads_the_engine_through_the_load_loop():
    spec = {"name": "small", "chips": 1, "config": SMALL, "mix": MIX,
            "cell": CELL, "end_to_end": [], "per_layer": []}
    _, eng, rep, _, router = run.setup_engine(spec, SEED, cache=False)
    loop = run.LoadLoop(spec, router, rep, SEED, 3.0, trace=False)
    loop.counters = [loop._counters(), None]
    assert set(loop.counters[0]) == set(eng.speculation_stats)
    loop._generate(float("inf"))           # every arrival at once
    while not loop._idle():
        router.dispatch(0.0)
        loop._step(lambda: 0.0)
    loop.counters[1] = loop._counters()
    share = _read(loop.counters)
    assert 0.0 < share < 100.0
    assert loop.reserved_peak >= loop.pages_peak > 0
