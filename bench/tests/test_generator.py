"""Traffic: seeded, repeatable, inside its stated ranges, and the same work
for every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.generator import (BLOCK, arrival_gaps, arrival_times, closed_loop,
                             lengths, open_loop)

TRAFFIC = Path(__file__).parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
CELL = {"rate_rps": 3.0, "ramp_s": 5.0, "clients": 4, "requests_per_client": 6}


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def _draw(name, seed, seconds=40.0):
    return open_loop(_mix(name), CELL, seed, seconds, 1000)


@pytest.mark.parametrize("name", MIXES)
def test_repeatable_and_in_range(name):
    mix = _mix(name)
    a, b = _draw(name, 2**31 + 17), _draw(name, 2**31 + 17)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for x in a:
        assert p["min"] <= len(x.prompt) <= p["max"]
        assert o["min"] <= x.max_new_tokens <= o["max"]
        assert x.prompt.min() >= 0 and x.prompt.max() < 1000


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work(name):
    a, b = _draw(name, 1), _draw(name, 2)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert (sorted(x.max_new_tokens for x in a)
            == sorted(x.max_new_tokens for x in b))
    assert [x.due_s for x in a] != [x.due_s for x in b] or \
        [len(x.prompt) for x in a] != [len(x.prompt) for x in b]


def test_lognormal_quantiles_have_the_stated_median():
    x = lengths({"dist": "lognormal", "median": 300, "sigma": 1.0,
                 "min": 16, "max": 1536}, 1001)
    assert np.median(x) == 300
    assert x.min() >= 16 and x.max() == 1536


@pytest.mark.parametrize("name", ["chat", "rag"])
def test_poisson_counts_follow_the_rate(name):
    arr = _draw(name, 5, seconds=40.0)
    in_window = [x for x in arr if 0 <= x.due_s < 40.0]
    assert len(in_window) == 120                      # 3 req/s x 40 s
    assert len(arr) - len(in_window) == 15            # the 5 s ramp
    assert all(-5.0 <= x.due_s < 40.0 for x in arr)


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**40 + 9])
def test_balanced_arrivals_hold_no_clusters(seed):
    """Each block of consecutive gaps holds one gap from each exponential
    stratum, so at most two of its gaps are under a quarter of the mean;
    each arrival sits at the middle of its gap."""
    gaps = arrival_gaps(400, np.random.default_rng(seed))
    assert np.all(gaps > 0)
    short = gaps < 0.25 * gaps.mean()
    for b in range(0, len(gaps) - BLOCK + 1, BLOCK):
        assert short[b:b + BLOCK].sum() <= 2
    # over the run the gaps are the exponential's: about 22% under a quarter
    assert 0.18 < short.mean() < 0.26
    t = arrival_times(_mix("chat"), 2.0, 0.0, 200.0, np.random.default_rng(seed))
    g = gaps * 200.0 / gaps.sum()
    assert len(t) == 400 and t[0] == pytest.approx(g[0] / 2)
    np.testing.assert_allclose(np.diff(t), (g[:-1] + g[1:]) / 2, rtol=1e-9)


def test_unknown_arrival_process_is_refused():
    mix = {**_mix("chat"), "arrivals": {"process": "poisson"}}
    with pytest.raises(ValueError, match="poisson"):
        open_loop(mix, CELL, 1, 10.0, 1000)


@pytest.mark.parametrize("name", MIXES)
def test_one_client_closed_loop_takes_the_mix_in_turn(name):
    """The unloaded run of ``calibrate.py``: one client, the mix's lengths."""
    mix = {**_mix(name), "loop": "closed"}
    cell = {"clients": 1, "requests_per_client": 16}
    (reqs,) = closed_loop(mix, cell, 2**31 + 3, 1000)
    again = closed_loop(mix, cell, 2**31 + 3, 1000)[0]
    assert len(reqs) == 16
    assert [len(x.prompt) for x in reqs] == [len(x.prompt) for x in again]
    assert (sorted(len(x.prompt) for x in reqs)
            == sorted(lengths(mix["prompt_tokens"], 16)))


def test_balanced_blocks_take_one_value_per_stratum():
    from bench.generator import balanced
    v = np.arange(40)
    out = balanced(v, np.random.default_rng(3), block=8)
    assert sorted(out) == list(v)
    # 5 blocks of 8; stratum j holds values 5j..5j+4, one in each block
    for b in range(5):
        blk = sorted(out[8 * b:8 * b + 8])
        assert [x // 5 for x in blk] == list(range(8))
    assert not np.array_equal(out, balanced(v, np.random.default_rng(4),
                                            block=8))
