"""Fix a cell's limits and rate on the chip, once, when the cell is made.

    python3 bench/calibrate.py --workload <cell> --seed <n> \\
        --unloaded-seconds 60 --seconds 30 --rates 2,3,4

One process, one warm engine.  First an unloaded run: one request at a
time (a closed loop of one client) over the cell's own lengths; the limits
are twice its p90 time to first token and twice its p90 time per output
token (DistServe's SLO scale of 2).  Then one open-loop window per rate: the
share of requests that met both limits, the tails, and the backlog at the
middle and at the end of the window.  The knee is the highest rate with at
least 90% attainment and no backlog growing over the window; the cell runs
at four fifths of it.  Each reading is one JSON line on standard output.
The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

sys.path[0] = str(Path(__file__).resolve().parents[1])

from bench import run  # noqa: E402


def _round2(x: float) -> float:
    """Two significant digits."""
    return float(f"{x:.2g}")


def measure(spec, parts, seed: int, seconds: float) -> dict:
    model, eng, rep, pool, router = parts
    loop = run.LoadLoop(spec, router, rep, seed, seconds, False)
    t0 = time.perf_counter()
    loop.run()
    wall = time.perf_counter() - t0
    view = run.RunView(spec=spec, seconds=seconds, setup_s=0.0,
                       recs=list(loop.recs.values()), steps=[], counters=None,
                       trace=None, kernel_ops={}, max_batch=eng.cfg.max_batch,
                       span=eng.span, device_kind="", profile=(0.0, 0.0))
    out = {name: run.reader(name)(view) for name in
           ("ttft_p50_s", "ttft_p90_s", "tpot_p50_ms", "tpot_p90_ms",
            "output_tokens_per_s")}
    window = [r for r in loop.recs.values() if 0 <= r.due < seconds]
    mid = [b for t, b in loop.backlog if t <= seconds / 2]
    end = [b for t, b in loop.backlog if t <= seconds]
    out.update(attempted=len(window), failed=sum(r.failed for r in window),
               backlog_mid=mid[-1] if mid else 0,
               backlog_end=end[-1] if end else 0, wall_s=wall)
    if "ttft_limit_s" in spec["cell"]:
        out["slo_attainment"] = run.reader("slo_attainment")(view)
    # leave the engine empty for the next window
    router.queue.clear()
    eng.queue.clear()
    for s in list(eng.active):
        eng.active.pop(s)
        eng._reset_slot(s)
    eng.completed.clear()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--unloaded-seconds", type=float, default=60.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--ttft-limit", type=float, default=None)
    ap.add_argument("--tpot-limit", type=float, default=None)
    args = ap.parse_args()
    spec = run.load_spec(args.workload)
    info = run.device_info()
    if info["platform"] != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    parts = run.setup_engine(spec, args.seed)

    def emit(rec):
        print(json.dumps({"workload": args.workload, "device": info, **rec}),
              flush=True)

    ttft_lim, tpot_lim = args.ttft_limit, args.tpot_limit
    if ttft_lim is None or tpot_lim is None:
        un = copy.deepcopy(spec)
        un["mix"]["loop"] = "closed"
        un["cell"].update(clients=1, requests_per_client=1000, ramp_s=0.0,
                          drain_cap_s=120.0)
        un["cell"].pop("ttft_limit_s", None)
        r = measure(un, parts, args.seed, args.unloaded_seconds)
        ttft_lim = _round2(2 * r["ttft_p90_s"])
        tpot_lim = _round2(2 * r["tpot_p90_ms"])
        emit({"phase": "unloaded", **r, "ttft_limit_s": ttft_lim,
              "tpot_limit_ms": tpot_lim})
    for rate in [float(x) for x in args.rates.split(",") if x]:
        sp = copy.deepcopy(spec)
        sp["cell"].update(rate_rps=rate, ttft_limit_s=ttft_lim,
                          tpot_limit_ms=tpot_lim)
        r = measure(sp, parts, args.seed, args.seconds)
        emit({"phase": "rate", "rate_rps": rate, **r})
    return 0


if __name__ == "__main__":
    sys.exit(main())
