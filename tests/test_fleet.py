"""Replica fleet: single-replica behavioral equivalence (pinned), drain
migration conservation, measured provisioning delay, SLA-aware routing, and
convergence-plane healing of killed replicas (see repro.serving.fleet)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.autoscaler.base import Decision, Policy  # noqa: E402
from repro.core.scaling import CapacityPlan, Sla, UnitPool
from repro.serving import Request, ServeConfig, ServingEngine
from repro.serving.fleet import (
    FLEET_POOL,
    FleetBackend,
    FleetExecutor,
    FleetRouter,
    ReplicaPool,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")

@pytest.fixture(scope="module")
def fleet_env(tmp_path_factory):
    """One model + checkpoint shared by every spawn in this module."""
    import jax

    from repro.checkpoint import CheckpointManager
    from repro.configs import get_smoke_config
    from repro.models import build_model

    cfg = get_smoke_config("smollm-135m")
    model = build_model(cfg)
    params = model.init_params(jax.random.key(0))
    ckpt_dir = tmp_path_factory.mktemp("fleet-ckpt")
    mgr = CheckpointManager(str(ckpt_dir), keep=2, async_save=False)
    mgr.save(params, step=1)
    return cfg, model, mgr


def _make_pool(fleet_env, n_replicas, **cfg_kw):
    cfg, model, mgr = fleet_env
    serve_cfg = ServeConfig(max_batch=cfg_kw.pop("max_batch", 4),
                            max_len=cfg_kw.pop("max_len", 128),
                            decode_steps=4, **cfg_kw)
    pool = ReplicaPool(model, mgr, serve_cfg)
    for _ in range(n_replicas):
        rep, _ = pool.spawn()
        pool.serving.append(rep)
    return cfg, pool


def _requests(cfg, rng, n, *, arrival=lambda i: 0.0, decode=lambda i: 6):
    return [Request(rid=i, arrival_s=arrival(i),
                    prompt=rng.integers(0, cfg.vocab,
                                        8 + (i % 3) * 8).astype(np.int32),
                    max_new_tokens=decode(i)) for i in range(n)]


class _Hold(Policy):
    """Votes zero delta forever: the desired state is whatever the fleet
    started at, so the only scaling activity left is fault healing."""

    name = "hold"

    def reset(self):
        pass

    def decide(self, obs):
        return Decision(0, "hold")

    def describe(self):
        return "hold"


def test_single_replica_fleet_matches_bare_engine(fleet_env):
    """Pinned equivalence: the router + one replica admits and emits exactly
    what the bare engine does under the same virtual-time stepping -- fleet
    mode at size 1 is today's engine, not a different scheduler."""
    cfg, pool = _make_pool(fleet_env, 1)
    bare = ServingEngine(pool.model, pool.serving[0].eng.params,
                         pool.serve_cfg)
    rng = np.random.default_rng(7)
    reqs_fleet = _requests(cfg, rng, 10, arrival=lambda i: float(i // 3),
                           decode=lambda i: 4 + i % 5)
    rng = np.random.default_rng(7)
    reqs_bare = _requests(cfg, rng, 10, arrival=lambda i: float(i // 3),
                          decode=lambda i: 4 + i % 5)

    router = FleetRouter(pool)
    replica = pool.serving[0]
    heads = [0, 0]
    for t in range(200):
        while heads[0] < len(reqs_fleet) and \
                reqs_fleet[heads[0]].arrival_s <= t:
            router.submit(reqs_fleet[heads[0]])
            heads[0] += 1
        router.dispatch(float(t))
        replica.step(float(t), decode_steps=2)
        while heads[1] < len(reqs_bare) and reqs_bare[heads[1]].arrival_s <= t:
            bare.submit(reqs_bare[heads[1]])
            heads[1] += 1
        bare.step(now=float(t), decode_steps=2)
        if not router.backlog and not replica.eng.n_in_system \
                and not bare.n_in_system:
            break
    else:
        raise AssertionError("fleet or bare engine failed to drain")

    fleet_done = {r.rid: (list(r.output), r.done_s)
                  for r in replica.eng.completed}
    bare_done = {r.rid: (list(r.output), r.done_s) for r in bare.completed}
    assert fleet_done == bare_done
    replica.eng.kv.check_invariants()


def test_drain_migration_bit_identical_and_conserves_pages(fleet_env):
    """Mid-decode drain: every in-flight request resumes on the survivor
    with bit-identical tokens, and page free-lists conserve on BOTH sides."""
    cfg, pool = _make_pool(fleet_env, 2)
    rng = np.random.default_rng(3)
    reqs = _requests(cfg, rng, 8, decode=lambda i: 6 + i % 4)
    rng = np.random.default_rng(3)
    ref_reqs = _requests(cfg, rng, 8, decode=lambda i: 6 + i % 4)

    # reference: same params, no migration
    ref = ServingEngine(pool.model, pool.serving[0].eng.params,
                        pool.serve_cfg)
    for r in ref_reqs:
        ref.submit(r)
    ref.run_until_drained()
    reference = {r.rid: list(r.output) for r in ref.completed}

    router = FleetRouter(pool)
    for r in reqs:
        router.submit(r)
    for t in range(3):
        router.dispatch(float(t))
        for rep in pool.serving:
            rep.step(float(t), decode_steps=2)
    victim = pool.serving[-1]
    assert victim.eng.active, "nothing mid-decode: the drill is vacuous"
    free_before = int(victim.eng.kv.n_free)
    held_before = int(victim.eng.kv.held.sum())
    pool.drain(victim)
    # drained side: every held page is back on the free list
    assert int(victim.eng.kv.held.sum()) == 0
    assert int(victim.eng.kv.worst.sum()) == 0
    assert victim.eng.kv.n_free == free_before + held_before
    victim.eng.kv.check_invariants()

    for t in range(3, 300):
        router.dispatch(float(t))
        for rep in pool.serving:
            rep.step(float(t), decode_steps=2)
        if not router.backlog and not any(r.eng.n_in_system
                                          for r in pool.serving):
            break
    survivor = pool.serving[0]
    survivor.eng.kv.check_invariants()   # survivor side conserves too
    done = {r.rid: list(r.output)
            for rep in pool.serving + pool.retired
            for r in rep.eng.completed}
    assert done == reference


def test_measured_delay_lands_in_run_report(fleet_env):
    """The RunReport's provisioning delay is measured at spawn, not the
    configured guess."""
    cfg, pool = _make_pool(fleet_env, 0)
    rng = np.random.default_rng(5)
    reqs = _requests(cfg, rng, 8, arrival=lambda i: float(i // 4),
                     decode=lambda i: 4)
    be = FleetBackend(pool, reqs, sla_s=30.0, horizon_s=10.0,
                      starting_replicas=1, max_replicas=2,
                      provision_delay_s=123.0, adapt_period_s=2.0,
                      app_window_s=4.0, decode_steps=2)
    rep = be.run()
    assert rep.n_done == len(reqs)
    measured = rep.pool_provision_delay_s.get(FLEET_POOL)
    assert measured is not None and 0.0 < measured < 123.0
    assert rep.summary()["measured_delay_s.replica"] == measured


def test_router_sheds_cheapest_class_first(fleet_env):
    """Under pressure the queue serves strictest absolute deadline first, so
    the cheapest class (longest deadline) is the one that waits."""
    cfg, pool = _make_pool(fleet_env, 1, max_batch=2)
    sla = Sla(default_s=100.0, per_class={"p32d16": 5.0})
    router = FleetRouter(pool, sla=sla)
    rng = np.random.default_rng(9)
    # two blockers fill both slots: one finishes quickly, one runs long
    blockers = [
        Request(rid=0, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                max_new_tokens=2),
        Request(rid=1, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                max_new_tokens=40),
    ]
    for b in blockers:
        router.submit(b)
    router.dispatch(0.0)
    pool.serving[0].step(0.0, decode_steps=1)
    assert len(pool.serving[0].eng.active) == 2
    # cheap (p16 -> 100 s deadline) arrives BEFORE premium (p32 -> 5 s):
    # FIFO would admit cheap first; deadline order must not
    cheap = Request(rid=2, arrival_s=1.0,
                    prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new_tokens=4)
    premium = Request(rid=3, arrival_s=1.0,
                      prompt=rng.integers(0, cfg.vocab, 24).astype(np.int32),
                      max_new_tokens=4)
    router.submit(cheap)
    router.submit(premium)
    router.dispatch(1.0)
    assert [r.rid for r in router.queue] == [3, 2], \
        "queue is not deadline-ordered"
    for t in range(2, 20):     # rid 0 finishes, freeing exactly one slot
        pool.serving[0].step(float(t), decode_steps=2)
        if 0 in {r.rid for r in pool.serving[0].eng.completed}:
            break
    router.dispatch(float(t))
    pool.serving[0].step(float(t), decode_steps=1)
    active_rids = {r.rid for r in pool.serving[0].eng.active.values()}
    assert 3 in active_rids, "premium class did not preempt the cheap one"
    assert [r.rid for r in router.queue] == [2], "cheap class should shed"


def test_kill_requeues_at_original_deadline(fleet_env):
    """A killed replica's restarted requests re-enter the deadline queue at
    their ORIGINAL deadline (arrival survives the kill) -- re-admission must
    not jump a premium request that arrived later with a tighter absolute
    deadline.  Regression: the migrated backlog used to bypass the queue via
    direct placement, so a crash laundered cheap work past premium."""
    cfg, pool = _make_pool(fleet_env, 2, max_batch=1)
    sla = Sla(default_s=100.0, per_class={"p32d16": 5.0})
    router = FleetRouter(pool, sla=sla)
    rng = np.random.default_rng(13)
    # one blocker per replica: rid 0 runs long on A, cheap rid 1 sits on B
    blocker = Request(rid=0, prompt=rng.integers(0, cfg.vocab,
                                                 8).astype(np.int32),
                      max_new_tokens=40)
    cheap = Request(rid=1, prompt=rng.integers(0, cfg.vocab,
                                               8).astype(np.int32),
                    max_new_tokens=16)           # p16d16 -> 100 s deadline
    router.submit(blocker)
    router.submit(cheap)
    router.dispatch(0.0)
    for rep in pool.serving:
        rep.step(0.0, decode_steps=1)
    victim = next(r for r in pool.serving
                  if 1 in {q.rid for q in r.eng.active.values()})
    pool.kill(victim)                            # cheap restarts from scratch
    assert pool.migrated and pool.migrated[0].req.rid == 1
    # premium arrives AFTER the kill with a tighter absolute deadline
    premium = Request(rid=2, arrival_s=1.0,
                      prompt=rng.integers(0, cfg.vocab, 24).astype(np.int32),
                      max_new_tokens=16)         # p32d16 -> deadline 6 s
    router.submit(premium)
    router.dispatch(1.0)
    # the restarted cheap request folded into the queue BEHIND premium
    assert not pool.migrated
    assert [r.rid for r in router.queue] == [2, 1]
    for t in range(2, 60):                       # blocker frees the only slot
        pool.serving[0].step(float(t), decode_steps=2)
        if 0 in {r.rid for r in pool.serving[0].eng.completed}:
            break
    router.dispatch(float(t))
    pool.serving[0].step(float(t), decode_steps=1)
    active_rids = {r.rid for r in pool.serving[0].eng.active.values()}
    assert 2 in active_rids, "crash restart outranked the premium class"
    assert [r.rid for r in router.queue] == [1]


def test_converger_heals_killed_replica(fleet_env):
    """Abrupt replica loss mid-run: the plan records a measured unit loss
    and the converger heals it with a REAL respawn; every request (including
    the killed replica's restarted in-flights) still completes."""
    cfg, pool = _make_pool(fleet_env, 0)
    rng = np.random.default_rng(11)
    reqs = _requests(cfg, rng, 14, arrival=lambda i: float(i // 2),
                     decode=lambda i: 5 + i % 4)
    killed = []

    def kill_once(be, t):
        if t == 3.0 and not killed:
            victim = be.pool.serving[-1]
            killed.append(victim.rix)
            be.kill_replica(victim, t)

    be = FleetBackend(pool, reqs, sla_s=60.0, horizon_s=10.0,
                      policy=_Hold(), starting_replicas=2, max_replicas=3,
                      adapt_period_s=2.0, app_window_s=4.0, decode_steps=2,
                      on_step=kill_once)
    rep = be.run()
    assert killed, "the drill never fired"
    assert rep.n_done == len(reqs)
    assert len(pool.serving) == 2, "fleet did not heal back to desired size"
    assert pool._next_rix >= 3, "healing never spawned a replacement"
    # the loss is on the books as a measured fault, not silent
    meters = be.controller.plan.meters()[FLEET_POOL]
    assert meters.lost == 1
    for r in pool.serving:
        r.eng.kv.check_invariants()


def test_executor_books_stuck_spawn_and_cancels_it_first(fleet_env):
    """A spawn that raises becomes a measured stuck build; cancel takes the
    stuck book entry before discarding healthy provisioning replicas."""
    cfg, pool = _make_pool(fleet_env, 0)
    outcomes = iter([True, False])      # first spawn fails, second succeeds
    pool.spawn_fault = lambda: next(outcomes, False)
    plan = CapacityPlan((UnitPool(FLEET_POOL, provision_delay_s=5.0,
                                  max_units=4),), starting_units=0)
    ex = FleetExecutor(pool, plan)
    applied = ex.launch(FLEET_POOL, 2, now=0.0)
    assert applied == 2
    assert ex._stuck == 1 and len(pool.provisioning) == 1
    # measured delay was calibrated from the successful spawn
    assert plan.report_kwargs()["pool_provision_delay_s"][FLEET_POOL] > 0.0
    # cancel one: the stuck build goes first, the real replica survives
    assert ex.cancel_pending(FLEET_POOL, 1, now=1.0) == 1
    assert ex._stuck == 0 and len(pool.provisioning) == 1
    # cancel the other: now the provisioning replica is discarded
    assert ex.cancel_pending(FLEET_POOL, 1, now=2.0) == 1
    assert not pool.provisioning and len(pool.retired) == 1


def test_executor_lets_real_spawn_errors_propagate(fleet_env):
    """Only an injected SpawnFault is a stuck build.  A real failure -- a
    kernel the chip's compiler refuses, a replica that does not fit (JAX
    raises both as RuntimeError subclasses) -- must surface, not be booked
    as a stuck build while the run exits 0."""
    cfg, pool = _make_pool(fleet_env, 0)

    class CompileError(RuntimeError):
        pass

    def refuse():
        raise CompileError("Mosaic failed to compile TPU kernel")

    pool.spawn = refuse
    plan = CapacityPlan((UnitPool(FLEET_POOL, provision_delay_s=5.0,
                                  max_units=4),), starting_units=0)
    ex = FleetExecutor(pool, plan)
    with pytest.raises(CompileError):
        ex.launch(FLEET_POOL, 1, now=0.0)
    assert ex._stuck == 0


def test_replicas_own_one_device_each_and_drain_across_devices():
    """On a four-device host every replica's params, KV pool and steps sit
    on its own device, and a mid-run drain moves committed KV to another
    device with outputs bit-identical to a one-replica run (subprocess:
    four forced host devices)."""
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(ROOT, "src")}
    code = textwrap.dedent("""
        import jax, numpy as np, os, tempfile
        from repro.checkpoint import save_checkpoint
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.serving import Request, ServeConfig
        from repro.serving.fleet import FleetRouter, ReplicaPool

        cfg = get_smoke_config('smollm-135m')
        model = build_model(cfg)
        ckpt = save_checkpoint(os.path.join(tempfile.mkdtemp(), 'c.npz'),
                               model.init_params(jax.random.key(0)))
        serve_cfg = ServeConfig(max_batch=4, max_len=128, decode_steps=4)

        def run(n, drain):
            pool = ReplicaPool(model, ckpt, serve_cfg)
            for _ in range(n):
                pool.serving.append(pool.spawn()[0])
            rng = np.random.default_rng(3)
            reqs = [Request(rid=i, prompt=rng.integers(
                        0, cfg.vocab, 8 + 4 * i).astype(np.int32),
                        max_new_tokens=24) for i in range(8)]
            router = FleetRouter(pool)
            for r in reqs:
                router.submit(r)
            moved = None
            for t in range(200):
                router.dispatch(float(t))
                for rep in pool.serving:
                    rep.step(float(t), decode_steps=2)
                if drain and t == 0:
                    victim = pool.serving[0]
                    kv = {r.rid for s, r in victim.eng.active.items()
                          if victim.eng.pos[s] > 0}
                    pool.drain(victim)
                    moved = (kv, victim.eng.device,
                             {r.eng.device for r in pool.serving
                              for q in r.eng.active.values() if q.rid in kv})
                if not router.backlog and not any(
                        r.eng.n_in_system for r in pool.serving):
                    break
            for rep in pool.serving + pool.retired:
                rep.eng.kv.check_invariants()
            return pool, {r.rid: r.output for r in reqs}, moved

        _, ref, _ = run(1, False)
        pool, out, (kv, left, landed) = run(4, True)
        reps = pool.serving + pool.retired
        assert len({r.eng.device for r in reps}) == 4
        for r in reps:
            held = {d for leaf in jax.tree.leaves((r.eng.params, r.eng.kv.pages))
                    for d in leaf.devices()}
            assert held == {r.eng.device}, (held, r.eng.device)
        assert kv and landed and left not in landed, (kv, left, landed)
        assert out == ref
        print('PLACEMENT_OK')
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "PLACEMENT_OK" in p.stdout


def test_chaos_drill_kill_under_load_is_observationally_equivalent(
        fleet_env, tmp_path):
    """End-to-end ChaosDrill over REAL engines: a replica killed under
    burst load heals, and the whole invariant battery -- exactly-once,
    bit-identical outputs vs the fault-free reference, KV page
    conservation, sealed audit replay -- comes back green."""
    from repro.core.chaos import ChaosAction, ChaosDrill, ChaosScript

    def make_backend(*, on_step, audit_path):
        cfg, pool = _make_pool(fleet_env, 0)
        rng = np.random.default_rng(21)
        reqs = _requests(cfg, rng, 10, arrival=lambda i: float(i // 2),
                         decode=lambda i: 4 + i % 3)
        return FleetBackend(pool, reqs, sla_s=60.0, horizon_s=8.0,
                            policy=_Hold(), starting_replicas=2,
                            max_replicas=3, adapt_period_s=2.0,
                            app_window_s=4.0, decode_steps=2,
                            calibrate=False, on_step=on_step,
                            audit_path=audit_path)

    script = ChaosScript([ChaosAction(3.0, "kill", count=1)], seed=5)
    drill = ChaosDrill("kill-under-load", make_backend, script,
                       audit_path=str(tmp_path / "drill.jsonl"))
    report = drill.run()
    assert report.fired and report.fired[0]["kind"] == "kill"
    assert report.n_completed == 10 == report.n_reference
    assert report.ok, report.summary()
