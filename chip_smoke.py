"""Chip smoke test: serve qwen2.5-3b at its published widths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four one-chip replicas (needs 4 chips)

One chip: the weights are made on the chip from ``--seed`` and written to a
checkpoint; one replica is spawned from it twice through
``ReplicaPool.spawn`` (cold, then warm); that replica's ``ServingEngine``
serves 16 requests through ``launch/serve.py``'s ``ServeBackend`` on the
default mixed loop with the Pallas kernels on.  The script then checks that
every request completed, every KV page is back on the free list, the
compiled mixed step holds Pallas kernels (``tpu_custom_call``), and a few
requests agree with a float32 reference forward pass
(``repro.models.reference``, tie-aware).

``--chips 4`` runs only the fleet phase: four one-chip replicas behind
``FleetRouter``, with one replica drained mid-run so its requests' KV moves
to another chip, against a one-replica run of the same requests.  Outputs
must be bit-identical and each replica's arrays must sit on its own chip.

Everything runs in this one process: a process that has touched JAX holds
the chip.  The script exits non-zero, and prints no result, when JAX finds
no TPU.  Its last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2.5-3b"
N_REQUESTS = 16
PROMPT_LEN = (128, 768)          # inclusive range of prompt lengths
NEW_TOKENS = (32, 64)            # inclusive range of new tokens per request
MAX_BATCH = 8
MAX_LEN = 1024
N_REFERENCE = 3                  # requests checked against the f32 reference
CKPT_DIR = ROOT / ".smoke_ckpt"  # gitignored; removed when the script ends


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_requests(vocab: int, seed: int):
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, int(rng.integers(
                        PROMPT_LEN[0], PROMPT_LEN[1] + 1))).astype(np.int32),
                    max_new_tokens=int(rng.integers(NEW_TOKENS[0],
                                                    NEW_TOKENS[1] + 1)))
            for i in range(N_REQUESTS)]


def write_checkpoint(model, seed: int) -> str:
    """Make the weights on the chip from ``seed`` and save them; the device
    copy is dropped before any replica loads its own."""
    from repro.checkpoint import save_checkpoint
    t0 = time.perf_counter()
    params = jax.jit(model.init_params)(jax.random.key(seed))
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    path = save_checkpoint(str(CKPT_DIR / "ckpt_00000001.npz"), params)
    del params
    gc.collect()
    log(f"params made on chip in {t1 - t0:.2f}s, checkpoint written in "
        f"{time.perf_counter() - t1:.2f}s")
    return path


def mixed_step_args(eng):
    """Arguments of one mixed-step call at the engine's fixed width."""
    na, H = eng.cfg.max_batch, eng.cfg.max_len + 1
    zeros = lambda *s: np.zeros(s, np.int32)
    return (eng.params, eng.kv.pages, zeros(na, H), zeros(na), zeros(na),
            zeros(na), np.zeros((na,), bool), zeros(na, eng.kv.pages_per_slot),
            np.int32(1))


def compile_seconds(model, serve_cfg) -> float:
    """Compile the mixed step once from shapes alone, with the persistent
    cache off, so the time is a real compile whatever the cache holds."""
    from repro.serving import ServingEngine
    eng = ServingEngine(model, None, serve_cfg)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    shape = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=one)
    args = (jax.tree.map(shape, model.abstract_params()),
            *jax.tree.map(shape, mixed_step_args(eng)[1:]))
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        t0 = time.perf_counter()
        eng._mixed_jit.lower(*args).compile()
        return time.perf_counter() - t0
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


def check_reference(model, eng, reqs) -> None:
    from repro.models.reference import (TOL_STD, greedy_agreement,
                                        reference_logits)
    for r in reqs[:N_REFERENCE]:
        logits = reference_logits(model, eng.params,
                                  np.concatenate([r.prompt, r.output]))
        gap, ref_lp, scale = greedy_agreement(logits, len(r.prompt), r.output)
        log(f"request {r.rid}: prompt {len(r.prompt)}, {len(r.output)} "
            f"tokens; worst logit gap {gap:.6g}, score {r.score:.6g} vs "
            f"reference {ref_lp:.6g} (tolerance {TOL_STD} x logit std "
            f"{scale:.6g} = {TOL_STD * scale:.6g})")
        check(gap <= TOL_STD * scale, f"request {r.rid} token off reference")
        check(abs(r.score - ref_lp) <= TOL_STD * scale,
              f"request {r.rid} score off reference")


def check_pages_free(eng) -> None:
    eng.kv.check_invariants()
    check(eng.kv.n_free == eng.kv.num_pages - 1, "KV pages leaked")


def one_chip(model, serve_cfg, ckpt: str, seed: int, dev: str) -> None:
    from repro.launch.serve import ServeBackend
    from repro.serving.fleet import ReplicaPool

    log(f"{dev}: mixed-step compile {compile_seconds(model, serve_cfg):.2f}s "
        f"(persistent cache off)")
    pool = ReplicaPool(model, ckpt, serve_cfg)
    rep, cold_s = pool.spawn()
    del rep
    gc.collect()
    rep, warm_s = pool.spawn()
    log(f"{dev}: spawn cold {cold_s:.2f}s, warm {warm_s:.2f}s "
        f"(checkpoint load + engine build + two probe waves)")
    eng = rep.eng
    reqs = make_requests(model.cfg.vocab, seed)
    backend = ServeBackend(eng, reqs, sla_s=60.0, horizon_s=60.0,
                           starting_slots=MAX_BATCH,
                           decode_steps=serve_cfg.decode_steps)
    t0 = time.perf_counter()
    report = backend.run()
    wall = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in reqs)
    check(report.n_done == N_REQUESTS, "not every request completed")
    check(all(len(r.output) == r.max_new_tokens for r in reqs),
          "a request stopped short of its budget")
    check_pages_free(eng)
    log(f"{dev}: {N_REQUESTS} requests, {tokens} tokens in {wall:.3f}s "
        f"through ServeBackend: warm {tokens / wall:.1f} tokens/s "
        f"(prefill included)")
    hlo = eng._mixed_jit.lower(*mixed_step_args(eng)).compile().as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"{dev}: compiled mixed step holds {n_kernels} tpu_custom_call ops")
    check(n_kernels >= 2, "mixed step runs without its Pallas kernels")
    check_reference(model, eng, reqs)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"{dev}: peak device bytes {stats.get('peak_bytes_in_use')}")


def drive(pool, router, *, drain_at: int | None = None, decode_steps: int):
    """Step the fleet until every request is done; optionally drain the
    fullest replica at step ``drain_at``.  Returns the drained request ids
    that had committed KV, and the devices they left and landed on."""
    moved = None
    for t in range(100_000):
        if not router.backlog and not any(r.eng.n_in_system
                                          for r in pool.serving):
            return moved
        router.dispatch(float(t))
        for rep in pool.serving:
            rep.step(float(t), decode_steps=decode_steps)
        if t == drain_at:
            victim = max(pool.serving, key=lambda r: len(r.eng.active))
            kv_rids = {r.rid for s, r in victim.eng.active.items()
                       if victim.eng.pos[s] > 0}
            pool.drain(victim)
            landed = {r.eng.device for r in pool.serving
                      for q in r.eng.active.values() if q.rid in kv_rids}
            moved = (kv_rids, victim.eng.device, landed)
    raise RuntimeError("fleet failed to drain")


def four_chips(model, serve_cfg, ckpt: str, seed: int, dev: str) -> None:
    from repro.serving.fleet import FleetRouter, ReplicaPool

    def run(n_replicas: int, drain_at: int | None):
        pool = ReplicaPool(model, ckpt, serve_cfg)
        for _ in range(n_replicas):
            rep, spawn_s = pool.spawn()
            pool.serving.append(rep)
            log(f"{dev}: replica {rep.rix} on {rep.eng.device} up in "
                f"{spawn_s:.2f}s")
        reqs = make_requests(model.cfg.vocab, seed)
        router = FleetRouter(pool)
        for r in reqs:
            router.submit(r)
        t0 = time.perf_counter()
        moved = drive(pool, router, drain_at=drain_at,
                      decode_steps=serve_cfg.decode_steps)
        log(f"{dev}: {n_replicas} replica(s) served {N_REQUESTS} requests in "
            f"{time.perf_counter() - t0:.3f}s")
        for rep in pool.serving + pool.retired:
            check_pages_free(rep.eng)
        return pool, {r.rid: list(r.output) for r in reqs}, moved

    pool, reference, _ = run(1, None)
    del pool
    gc.collect()
    pool, outputs, moved = run(4, drain_at=0)
    replicas = pool.serving + pool.retired
    devices = [rep.eng.device for rep in replicas]
    check(len(set(devices)) == 4, f"replicas share chips: {devices}")
    for rep in replicas:
        held = {d for leaf in jax.tree.leaves((rep.eng.params, rep.eng.kv.pages))
                for d in leaf.devices()}
        check(held == {rep.eng.device},
              f"replica {rep.rix} arrays on {held}, not {rep.eng.device}")
    kv_rids, left, landed = moved
    check(bool(kv_rids), "the drain moved no committed KV")
    check(bool(landed) and left not in landed,
          f"drained KV did not move to another chip: {left} -> {landed}")
    log(f"{dev}: drain moved KV of requests {sorted(kv_rids)} from {left} "
        f"to {sorted(str(d) for d in landed)}")
    check(outputs == reference,
          "four-replica outputs differ from the one-replica run")
    log(f"{dev}: all {N_REQUESTS} outputs bit-identical to the one-replica "
        f"run, across the cross-chip drain")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    info = device_info()
    dev = f"{info['platform']} {info['kind']} x{info['count']}"
    log(f"device: {dev}")
    if info["platform"] != "tpu":
        print("[chip_smoke] no TPU: this check runs on the chip only",
              file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} chips, "
              f"found {info['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServeConfig
    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    model = build_model(get_config(ARCH))
    check(model.use_kernel, "the Pallas kernels are off on this platform")
    serve_cfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN)
    try:
        ckpt = write_checkpoint(model, args.seed)
        if args.chips == 4:
            four_chips(model, serve_cfg, ckpt, args.seed, dev)
        else:
            one_chip(model, serve_cfg, ckpt, args.seed, dev)
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
