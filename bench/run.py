"""Run one benchmark cell once, on the chip, and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); ``bench/cells/<cell>.json`` holds the
numbers fixed for that cell on the chip (rate, limits, ramp, drain cap and
the correctness limits).  The configuration's ``bench_model`` key names its
architecture, ``bench/models/<bench_model>.py``: the served model's
configuration, the reference, the costs and any kernels of its own (the
interface is in ``bench/models/dense.py``).  Each metric is read by
``bench/metrics/<name>.py``.

Set-up (``setup_s``, from process start): the compile cache, the model,
its weights made on the chip from ``--seed`` in one jitted call, one
``ServingEngine`` wrapped as replica 0 of a ``ReplicaPool`` behind a
``FleetRouter``, and two warm-up waves of the fixed-width mixed step (fresh
pages, then committed ones).  Then a ramp of load, then the measured window
of ``--seconds``: the requests due in it go through ``FleetRouter.submit``
-> ``dispatch`` -> ``Replica.step`` in this one process, and the client side
stamps each token when ``step`` returns it.  Requests due in the window are
followed to completion, up to the cell's drain cap.

``--trace 1`` is a run of its own: it profiles a few seconds inside the
window and prints the per-layer metrics and a breakdown instead of the
end-to-end ones.

Afterwards, with the program's state freed, a sample of the finished
requests is checked against the architecture's reference; ``correct``
holds when every compared number is within its limit and every KV page is
back.

The run exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"          # profiler output (ignored by git)
MODELS = BENCH / "models"          # one file per architecture
# the benchmark's modules are imported as ``bench.<name>``: run as a script,
# the checkout root takes this directory's place at the head of the path
if sys.path and Path(sys.path[0] or ".").resolve() == BENCH:
    sys.path[0] = str(ROOT)
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(1, _p)

TRACE_AT = 0.5          # the profile starts at this share of the window
TRACE_SECONDS = 4.0     # and lasts this long (at most a quarter of it)
# the kernels every model runs; an architecture's ``KERNELS`` adds its own
KERNELS = {"paged_mixed_attention": "_paged_mixed_kernel",
           "lmhead_epilogue": "_lmhead_epilogue_kernel"}


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# -- the cell -------------------------------------------------------------------

def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(name: str) -> dict:
    """Everything one cell needs, found by name from ``BENCHMARK.json``."""
    bench = _json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {"name": name, "chips": int(w["chips"]),
            "config": _json(BENCH / "configs" / f"{w['config']}.json"),
            "mix": _json(BENCH / "traffic" / f"{w['traffic']}.json"),
            "cell": _json(BENCH / "cells" / f"{name}.json"),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def bench_model(cfg: dict, models: Path | None = None):
    """The architecture module a configuration names by its ``bench_model``
    key: ``<models>/<bench_model>.py``, ``bench/models/`` by default."""
    models = Path(models or MODELS)
    known = sorted(p.stem for p in models.glob("*.py"))
    name = cfg.get("bench_model")
    if name not in known:
        raise SystemExit(f"configuration {cfg.get('source', '?')!r}: "
                         f"bench_model {name!r} is not a module in "
                         f"{models}; known: {known}")
    path = (models / f"{name}.py").resolve()
    key = f"bench_model_{name}"
    mod = sys.modules.get(key)
    if mod is None or Path(mod.__file__) != path:
        mod_spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[key] = mod
        mod_spec.loader.exec_module(mod)
    return mod


def build_model(cfg: dict):
    """The served model, built from the config file's numbers by its
    architecture module."""
    from repro.models import build_model as build
    return build(bench_model(cfg).model_config(cfg))


def kernel_names(arch) -> dict:
    """{kernel name: Pallas function} of the kernels a model of ``arch``
    runs: the shared ones and its own."""
    return {**KERNELS, **arch.KERNELS}


# -- client-side records ----------------------------------------------------------

@dataclass
class Rec:
    """What the client saw of one request."""

    rid: int
    due: float
    prompt_len: int
    budget: int
    client: int = -1
    admit: float | None = None        # start of the first step holding it
    deliveries: list = field(default_factory=list)   # [(time, tokens)]
    n_tok: int = 0
    done: float | None = None
    failed: bool = False


@dataclass
class StepRec:
    """One engine step inside the profiled interval: its loop iterations and,
    per request it served, committed KV before and after."""

    iters: int
    rows: list = field(default_factory=list)   # [(pos0, ell0, pos_end)]


class Compiles:
    """Counts executables built or loaded (JAX's backend-compile event) and
    functions traced (its jaxpr-trace event)."""

    n = 0
    traced = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        from jax import monitoring
        if cls._installed:
            return
        cls._installed = True

        def listen(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.n += 1
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                cls.traced += 1
        monitoring.register_event_duration_secs_listener(listen)


# -- the window -------------------------------------------------------------------

class LoadLoop:
    """Open- or closed-loop client over one replica behind ``FleetRouter``."""

    def __init__(self, spec, router, rep, seed: int, seconds: float,
                 trace: bool):
        from bench.generator import closed_loop, open_loop
        self.spec, self.router, self.rep = spec, router, rep
        self.eng = rep.eng
        self.seconds = float(seconds)
        self.cell = spec["cell"]
        self.closed = spec["mix"]["loop"] == "closed"
        vocab = int(spec["config"]["vocab_size"])
        self.ramp = float(self.cell["ramp_s"])
        self.cap_end = self.seconds + float(self.cell["drain_cap_s"])
        if self.closed:
            self.clients = closed_loop(spec["mix"], self.cell, seed, vocab)
            self.next_of = [0] * len(self.clients)
            self.sched = []
        else:
            self.sched = open_loop(spec["mix"], self.cell, seed, seconds, vocab)
        self.recs: dict[int, Rec] = {}
        self.reqs: dict = {}
        self.pending: list = []            # (due, client) closed-loop starts
        self.lag: list = []                # generator lateness, seconds
        self.trace = trace
        self.profiling = False             # inside the profiled interval
        self.profile = (TRACE_AT * self.seconds,
                        TRACE_AT * self.seconds
                        + min(TRACE_SECONDS, 0.25 * self.seconds))
        self.steps: list[StepRec] = []
        self.backlog: list = []            # (time, requests not yet in a slot)
        self.counters = None
        self._seen_done = 0
        self.pages_peak = 0                # KV pages held, most at once
        self.reserved_peak = 0             # held + promised to admitted rows
        self.longest_step = (0.0, 0.0)     # (wall seconds, when it began)

    # submitting
    def _submit(self, arr, due: float, client: int, now: float) -> None:
        from repro.serving import Request
        rid = len(self.recs)
        req = Request(rid=rid, prompt=arr.prompt,
                      max_new_tokens=arr.max_new_tokens, arrival_s=due)
        self.recs[rid] = Rec(rid, due, len(arr.prompt), arr.max_new_tokens,
                             client)
        self.reqs[rid] = req
        self.router.submit(req)
        self.lag.append(now - due)

    def _generate(self, now: float) -> None:
        if self.closed:
            if not self.recs:                       # every client starts
                for c in range(len(self.clients)):
                    self.pending.append((-self.ramp, c))
            while self.pending and self.pending[0][0] <= now:
                due, c = self.pending.pop(0)
                if due >= self.seconds or self.next_of[c] >= len(self.clients[c]):
                    continue
                arr = self.clients[c][self.next_of[c]]
                self.next_of[c] += 1
                self._submit(arr, due, c, now)
            return
        while self.sched and self.sched[0].due_s <= now:
            arr = self.sched.pop(0)
            self._submit(arr, arr.due_s, -1, now)

    def _next_due(self) -> float | None:
        if self.closed:
            return self.pending[0][0] if self.pending else None
        return self.sched[0].due_s if self.sched else None

    def _idle(self) -> bool:
        return not self.router.backlog and not self.eng.n_in_system

    # stepping
    def _snapshot(self) -> dict:
        eng = self.eng
        return {r.rid: (int(eng.pos[s]), len(r.prompt) + len(r.output))
                for s, r in eng.active.items()}

    def _step(self, clock) -> None:
        eng = self.eng
        before = self._snapshot() if self.profiling else None
        it0 = eng.step_count
        ts = clock()
        self.rep.step(ts, decode_steps=eng.decode_steps)
        t1 = clock()
        self.longest_step = max(self.longest_step, (t1 - ts, ts))
        held = eng.kv.num_pages - 1 - eng.kv.n_free
        self.pages_peak = max(self.pages_peak, held)
        self.reserved_peak = max(self.reserved_peak, eng.kv.n_reserved)
        served = list(eng.active.items())
        fresh = eng.completed[self._seen_done:]
        self._seen_done = len(eng.completed)
        for req in [r for _, r in served] + list(fresh):
            if req.rid < 0:
                continue
            rec = self.recs[req.rid]
            if rec.admit is None:
                rec.admit = ts
            n = len(req.output)
            if n > rec.n_tok:
                rec.deliveries.append((t1, n - rec.n_tok))
                rec.n_tok = n
        for req in fresh:
            if req.rid < 0:
                continue
            rec = self.recs[req.rid]
            rec.done = t1
            if self.closed and rec.client >= 0:
                self.pending.append((t1, rec.client))
        if self.closed:
            self.pending.sort()
        self.backlog.append((t1, self.router.backlog + len(eng.queue)))
        if before is not None:
            rows = []
            for s, req in served:
                p0, e0 = before.get(req.rid, (0, len(req.prompt)))
                rows.append((p0, e0, int(eng.pos[s])))
            for req in fresh:
                if req.rid < 0:
                    continue
                p0, e0 = before.get(req.rid, (0, len(req.prompt)))
                rows.append((p0, e0, len(req.prompt) + len(req.output) - 1))
            self.steps.append(StepRec(eng.step_count - it0, rows))

    def _counters(self) -> dict:
        return dict(self.eng.speculation_stats)

    def _stop_profile(self, span) -> None:
        import jax
        self.counters[1] = self._counters()
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.profiling = False

    def run(self) -> None:
        """The ramp, the window and the drain, with Python's garbage
        collector held off: what set-up built is frozen out of its reach,
        and a collection runs once the drain has ended."""
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            self._run()
        finally:
            gc.enable()
            gc.unfreeze()

    def _run(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter() + self.ramp

        def clock():
            return time.perf_counter() - t0

        span = None
        while True:
            now = clock()
            if self.trace and self.counters is None and now >= self.profile[0]:
                shutil.rmtree(OUT / "trace", ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(OUT / "trace"),
                                         profiler_options=opts)
                span = TraceAnnotation("bench.window")
                span.__enter__()
                self.profiling = True
                self.counters = [self._counters(), None]
            with TraceAnnotation("bench.generator"):
                self._generate(now)
            nxt = self._next_due()
            if self._idle():
                if nxt is None or now > self.cap_end:
                    break
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt - clock(), 0.01)))
                continue
            if now > self.cap_end:
                break
            with TraceAnnotation("bench.dispatch"):
                self.router.dispatch(now)
            with TraceAnnotation("bench.step"):
                self._step(clock)
            if self.profiling and clock() >= self.profile[1]:
                self._stop_profile(span)
        if self.profiling:                      # the run ended inside it
            self._stop_profile(span)
        for rec in self.recs.values():
            if rec.done is None:
                rec.failed = True

    def mixed_hlo(self) -> str:
        """The compiled mixed step's text (its Pallas calls' names)."""
        eng = self.eng
        na, H = eng.cfg.max_batch, eng.cfg.max_len + 1
        z = lambda *s: np.zeros(s, np.int32)  # noqa: E731
        args = (eng.params, eng.kv.pages, z(na, H), z(na), z(na), z(na),
                np.zeros((na,), bool), z(na, eng.kv.pages_per_slot),
                np.int32(1))
        return eng._mixed_jit.lower(*args).compile().as_text()


# -- correctness -----------------------------------------------------------------

def sample_for_check(loop, seed: int) -> list:
    """Finished requests due in the window: the longest, then others drawn
    from the seed, until the cell's served-token target is met."""
    cell = loop.cell
    done = [r for r in loop.recs.values()
            if 0 <= r.due < loop.seconds and not r.failed]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.prompt_len + r.n_tok, -r.rid))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    rest = [done[i] for i in rng.permutation(len(done))
            if done[i] is not longest]
    picked, tokens = [longest], longest.n_tok
    for r in rest:
        if (tokens >= int(cell["check_tokens"])
                or len(picked) >= int(cell["check_requests"])):
            break
        picked.append(r)
        tokens += r.n_tok
    return picked


def check(spec, loop, seed: int, control: bool) -> dict:
    """Compare the sampled requests with the reference: the compared numbers
    with their limits.  With ``control`` the reference's control (float8
    for a bfloat16 model) takes the program's place in them (the program's
    own readings go on a line of their own), so a control run has to come
    out not correct."""
    picked = sample_for_check(loop, seed)
    seqs = [(np.asarray(loop.reqs[r.rid].prompt),
             np.asarray(loop.reqs[r.rid].output)) for r in picked]
    t0 = time.perf_counter()
    res = bench_model(spec["config"]).check_sequences(
        spec["config"], seed, seqs, control=control)
    gaps = [x["max_gap"] for x in res]
    score = [abs(loop.reqs[r.rid].score - x["mean_lp"])
             for r, x in zip(picked, res)]
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(s) for _, s in seqs)} served tokens, "
        f"{sum(len(p) + len(s) - 1 for p, s in seqs)} positions, "
        f"{time.perf_counter() - t0:.2f}s")
    limits = spec["cell"]["correct"]
    if control and res:
        log(f"program: max_logit_gap {max(gaps)!r}, "
            f"max_score_gap {max(score)!r}")
        gaps = [x["ctrl_max_gap"] for x in res]
        score = [abs(x["ctrl_mean_lp"] - x["mean_lp"]) for x in res]
    return {"max_logit_gap": {"value": max(gaps) if gaps else float("nan"),
                              "limit": float(limits["max_logit_gap"])},
            "max_score_gap": {"value": max(score) if score else float("nan"),
                              "limit": float(limits["max_score_gap"])}}


# -- metrics ----------------------------------------------------------------------

def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@dataclass
class RunView:
    """What a metric reader may look at."""

    spec: dict
    seconds: float
    setup_s: float
    recs: list
    steps: list
    counters: list | None
    trace: object | None
    kernel_ops: dict
    max_batch: int
    span: int
    device_kind: str
    profile: tuple
    costs: object = None       # the architecture's ``costs(cfg)``


def breakdown(summary, kernel_ops: dict) -> dict:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
    def label(n):
        kind = summary.op_kind.get(n, "")
        return f"{kernel_ops[n]} ({n})" if n in kernel_ops else f"{n} ({kind})"

    return {"device_ops": [[label(n), s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}


# -- the run -----------------------------------------------------------------------

def setup_engine(spec: dict, seed: int, cache: bool = True):
    """The compile cache, the model, its weights made on the chip from the
    seed, one engine as replica 0 of a pool behind a router, and two warm-up
    waves of the fixed-width mixed step (fresh pages, then committed ones,
    as ``ReplicaPool.spawn`` warms a replica)."""
    import jax

    from repro.serving import Request, ServeConfig, ServingEngine
    from repro.serving.fleet import FleetRouter, Replica, ReplicaPool
    from repro.utils.compile_cache import enable_compile_cache

    if cache:
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    Compiles.install()
    model = build_model(spec["config"])
    params = jax.jit(model.init_params)(
        bench_model(spec["config"]).params_key(seed))
    jax.block_until_ready(params)
    serve = spec["config"]["serve"]
    serve_cfg = ServeConfig(max_batch=int(serve["max_batch"]),
                            max_len=int(serve["max_len"]))
    eng = ServingEngine(model, params, serve_cfg)
    del params
    rep = Replica(0, eng, 0.0)
    pool = ReplicaPool(model, None, serve_cfg)
    pool.serving.append(rep)
    router = FleetRouter(pool)
    for wave in range(2):
        eng.submit(Request(rid=-1 - wave, prompt=np.ones(4, np.int32),
                           max_new_tokens=2))
        eng.run_until_drained()
    eng.completed.clear()
    return model, eng, rep, pool, router


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, control: bool = False,
             cache: bool = True, t_process: float = T_PROCESS) -> int:
    import jax
    info = device_info()
    log(f"device: {info['platform']} {info['kind']} x{info['count']}")
    if require_tpu and info["platform"] != "tpu":
        print("[bench] no TPU: the benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    if info["count"] < spec["chips"]:
        print(f"[bench] the cell needs {spec['chips']} chips, found "
              f"{info['count']}", file=sys.stderr)
        return 2

    arch = bench_model(spec["config"])
    model, eng, rep, pool, router = setup_engine(spec, seed, cache)
    loop = LoadLoop(spec, router, rep, seed, seconds, trace)
    setup_s = time.perf_counter() - t_process
    rep.spawn_s = setup_s
    log(f"setup {setup_s:.4f}s (mixed-step variants {eng.mixed_trace_count}, "
        f"executables built or loaded {Compiles.n}, traced {Compiles.traced})")
    if not loop.closed:
        in_win = sum(0 <= a.due_s < seconds for a in loop.sched)
        log(f"schedule: {len(loop.sched)} requests, {in_win} due in the "
            f"window, ramp {loop.ramp}s")

    n_before, traced_before = Compiles.n, Compiles.traced
    loop.run()
    log(f"compilations inside the window and drain: {Compiles.n - n_before} "
        f"(functions traced {Compiles.traced - traced_before})")
    lag = np.asarray(loop.lag) if loop.lag else np.zeros(1)
    log(f"generator lateness: p50 {np.percentile(lag, 50):.6f}s, "
        f"max {lag.max():.6f}s; longest step {loop.longest_step[0]:.6f}s "
        f"at {loop.longest_step[1]:.3f}s")
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.kv.pages))
    log(f"KV pages held at most {loop.pages_peak} of {eng.kv.num_pages - 1} "
        f"({loop.pages_peak * pool_bytes // eng.kv.num_pages} of "
        f"{pool_bytes} pool bytes), reserved at most {loop.reserved_peak}")

    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"peak device bytes {peak}")
    leaked = 0
    try:
        for s in list(eng.active):
            eng._reset_slot(s)
        eng.kv.check_invariants()
        leaked = (eng.kv.num_pages - 1) - eng.kv.n_free
    except (AssertionError, RuntimeError, ValueError) as e:
        # a broken page invariant is a wrong result, not a crash
        log(f"KV invariants broken: {e!r}")
        leaked = -1
    summary, kernel_ops = None, {}
    if trace:
        from bench.trace import find_xplane, kernel_ops as find_kernels, load, summarize
        summary = summarize(load(find_xplane(str(OUT / "trace"))))
        kernel_ops = find_kernels(loop.mixed_hlo(), kernel_names(arch))
        log(f"trace: window {summary.window_s:.6f}s, busy {summary.busy_s:.6f}s, "
            f"kernels {kernel_ops}")
    max_batch, span = eng.cfg.max_batch, eng.span

    # free the program's state before the reference runs
    eng.kv.pages = None
    eng.params = None
    del eng, rep, pool, router, model
    loop.eng = loop.rep = loop.router = None
    gc.collect()

    nums = check(spec, loop, seed, control)
    nums["pages_leaked"] = {"value": leaked, "limit": 0}
    correct = all(v["value"] <= v["limit"] for v in nums.values()) and leaked >= 0

    window = [r for r in loop.recs.values() if 0 <= r.due < seconds]
    view = RunView(spec=spec, seconds=float(seconds), setup_s=setup_s,
                   recs=list(loop.recs.values()), steps=loop.steps,
                   counters=loop.counters, trace=summary,
                   kernel_ops=kernel_ops, max_batch=max_batch, span=span,
                   device_kind=info["kind"], profile=loop.profile,
                   costs=arch.costs(spec["config"]))
    metrics = {}
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if not trace:
        for name in ("ttft_p50_s", "tpot_p50_ms"):
            v = reader(name)(view)
            if v is not None:
                log(f"{name} {v!r}")
    device = {**info, "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(window),
           "failed": sum(r.failed for r in window),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = breakdown(summary, kernel_ops)
    out["checks"] = nums
    for k, v in nums.items():
        print(f"[bench] check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the float8 control in the program's place in "
                         "the comparison (calibration only; comes out not "
                         "correct)")
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    return run_cell(spec, args.seed, args.seconds, bool(args.trace),
                    control=bool(args.control))


if __name__ == "__main__":
    sys.exit(main())
