"""The serving program's own spans and counters, on the CPU with the smoke
model: ``FleetRouter.dispatch`` and one ``ServingEngine.step`` under a
profiler session leave their ``serve.*`` spans, nested and in order, in the
trace; the mixed loop's position and KV page counters equal counts made by
hand from ``eng.pos``, ``kv.worst`` and the loop iterations."""
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build_model
from repro.serving import Request, ServeConfig, ServingEngine
from repro.serving.fleet import FleetRouter, Replica, ReplicaPool
from repro.serving.kvcache import PagedKVCache

CFG = ServeConfig(max_batch=4, max_len=128, page_size=8, chunk_size=8,
                  draft_len=3, decode_steps=4)
STEP_CHILDREN = ["serve.fill", "serve.pack", "serve.launch", "serve.sync",
                 "serve.fold", "serve.rollback"]


@pytest.fixture(scope="module")
def smol():
    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    return cfg, model, model.init_params(jax.random.key(0))


def _requests(cfg, n, plen, budget, rid0=0):
    rng = np.random.default_rng(rid0)
    return [Request(rid=rid0 + i, max_new_tokens=budget,
                    prompt=rng.integers(1, cfg.vocab, plen).astype(np.int32))
            for i in range(n)]


@pytest.fixture(scope="module")
def traced(smol, tmp_path_factory):
    """One dispatch and one step under a profiler session, after a warm-up
    wave has compiled the mixed loop; the trace's serve.* events in start
    order, as (name, start ns, end ns, args)."""
    from jax.profiler import ProfileData
    cfg, model, params = smol
    eng = ServingEngine(model, params, CFG)
    eng.submit(_requests(cfg, 1, 4, 2, rid0=99)[0])
    eng.run_until_drained()
    pool = ReplicaPool(model, None, CFG)
    pool.serving.append(Replica(0, eng, 0.0))
    router = FleetRouter(pool)
    for req in _requests(cfg, 3, 20, 30):
        router.submit(req)
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        router.dispatch(0.0)
        eng.step(0.0, decode_steps=4)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{out}/**/*.xplane.pb", recursive=True))[-1]
    found = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("serve.")]
    return sorted(found, key=lambda e: (e[1], -e[2]))


def test_step_holds_its_spans_in_order_and_nested(traced):
    steps = [e for e in traced if e[0] == "serve.step"]
    assert len(steps) == 1
    _, a, b, args = steps[0]
    assert args == {"k": 4, "rows": 3}
    inner = [e for e in traced if a <= e[1] and e[2] <= b
             and e[0] != "serve.step"]
    assert [e[0] for e in inner] == STEP_CHILDREN
    # siblings, one after another
    assert all(x[2] <= y[1] for x, y in zip(inner, inner[1:]))
    # only the step carries arguments
    assert all(not e[3] for e in traced if e[0] != "serve.step")


def test_dispatch_is_its_own_span_before_the_step(traced):
    disp = [e for e in traced if e[0] == "serve.dispatch"]
    assert len(disp) == 1
    step = next(e for e in traced if e[0] == "serve.step")
    assert disp[0][2] <= step[1]


def test_mixed_counters_equal_hand_counts(smol):
    cfg, model, params = smol
    eng = ServingEngine(model, params, CFG)
    ps = eng.kv.page_size
    waves = [_requests(cfg, 3, 20, 90), [], _requests(cfg, 1, 13, 90, 10),
             []]
    hand = dict.fromkeys(("computed_positions", "committed_positions",
                          "kv_reserved_page_iters",
                          "kv_committed_page_iters"), 0)
    first = dict(eng.speculation_stats)
    for wave in waves:
        for req in wave:
            eng.submit(req)
        before = {s: int(eng.pos[s]) for s in eng.active}
        it0 = eng.step_count
        eng.step(0.0, decode_steps=4)
        iters = eng.step_count - it0
        # budgets outlast these steps: every slot the loop served is active
        served = sorted(eng.active)
        assert len(served) == len(before) + len(wave)
        hand["computed_positions"] += CFG.max_batch * eng.span * iters
        hand["committed_positions"] += sum(int(eng.pos[s]) - before.get(s, 0)
                                           for s in served)
        hand["kv_reserved_page_iters"] += iters * sum(
            int(eng.kv.worst[s]) for s in served)
        hand["kv_committed_page_iters"] += iters * sum(
            -(-int(eng.pos[s]) // ps) for s in served)
    last = eng.speculation_stats
    for key, want in hand.items():
        assert last[key] - first[key] == want, key
    assert 0 < hand["committed_positions"] < hand["computed_positions"]
    assert 0 < hand["kv_committed_page_iters"] < hand["kv_reserved_page_iters"]


def test_n_reserved_is_held_plus_outstanding():
    kv = PagedKVCache(lambda b, n: {"k": jax.numpy.zeros((1, b, n, 2))},
                      max_batch=3, max_len=32, page_size=8)
    kv.reserve(0, 20)                       # 3 pages, none held yet
    kv.alloc_prefill(1, 9, 30, 2)           # 2 held of 4
    kv.ensure_writable_span(0, 0, 8)        # 1 held of 3
    assert kv.n_reserved == 7
    assert kv.n_reserved == int(kv.held.sum()) + kv._outstanding
    kv.release(1)
    assert kv.n_reserved == 3
