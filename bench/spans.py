"""The host's spans in a profiler trace, on the device planes' clock.

The serving program records its own spans (``serve.*``, see
``ServingEngine.step`` and ``FleetRouter.dispatch``) inside the benchmark's
(``bench.*``) on the thread that runs them.  This module reads them from the
same trace ``bench/trace.py`` reduces, inside the window span:

* ``idle_by_span``: each second of the window in which no operation ran on
  a chip, put down to the innermost span covering it (the one begun last),
  averaged over the chips, so the seconds add up to the window's idle time;
* ``host_ms_per_sync``: over the ``serve.step`` spans wholly inside the
  window that hold a ``serve.sync``, the mean of each step's time outside
  that sync (the host's share of every step, when the chip waits on it);
* ``span_ms``: each span's count and mean duration.

    python3 bench/spans.py <profiler output directory>

prints these for the newest trace under the directory, as one JSON line.
A trace of a program that records no ``serve.*`` spans gives no
``host_ms_per_sync``.
"""
from __future__ import annotations

import json
import sys
from dataclasses import dataclass, field
from pathlib import Path

if __name__ == "__main__":              # run as a script: import as bench.*
    sys.path[0] = str(Path(__file__).resolve().parent.parent)

from bench.trace import (  # noqa: E402
    CONTAINERS, DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, _clip, _union,
    find_xplane, load, op_name)

PREFIXES = ("bench.", "serve.")
NO_SPAN = "(no bench span)"
STEP, SYNC = "serve.step", "serve.sync"


@dataclass(frozen=True)
class Span:
    name: str
    start: float                        # ns
    end: float
    args: dict = field(default_factory=dict)


@dataclass
class ProgramSpans:
    window_s: float
    idle_s: float
    spans: list                         # Span, overlapping the window
    idle_by_span: dict                  # innermost span -> seconds
    host_ms_per_sync: float | None
    span_ms: dict                       # name -> [count, mean ms]


def host_spans(profile) -> tuple[tuple[float, float], list[Span]]:
    """The window span's (start, end) and the other spans overlapping it."""
    found = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    found.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
    windows = [s for s in found if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    return (lo, hi), sorted((s for s in found if s.name != WINDOW_SPAN
                             and s.start < hi and s.end > lo),
                            key=lambda s: (s.start, -s.end))


def device_gaps(profile, lo: float, hi: float) -> list[list]:
    """Per chip, the stretches of [lo, hi) with no leaf operation running."""
    out = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        busy = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if op_name(ev.name)[1] in CONTAINERS:
                    continue
                a, b = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if b > a:
                    busy.append((a, b))
        gaps, t = [], lo
        for a, b in _union(busy):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        out.append(gaps)
    if not out:
        raise ValueError("the trace holds no TPU device plane")
    return out


def innermost(spans: list[Span], lo: float, hi: float) -> list[tuple]:
    """Cut [lo, hi) at every span's ends into pieces ``(a, b, name)``, each
    named by the innermost span covering it (begun last; of two begun
    together, the one that ends first), or ``NO_SPAN``."""
    cuts = sorted({lo, hi} | {min(max(t, lo), hi)
                              for s in spans for t in (s.start, s.end)})
    out, held, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j].start <= a:
            held.append(spans[j])
            j += 1
        held = [s for s in held if s.end > a]
        out.append((a, b, max(held, key=lambda s: (s.start, -s.end)).name
                    if held else NO_SPAN))
    return out


def idle_by_span(gaps_per_chip: list[list], pieces: list[tuple]) -> dict:
    """Seconds of the gaps under each piece's name, averaged over chips."""
    idle: dict = {}
    for gaps in gaps_per_chip:
        i = 0
        for ga, gb in gaps:
            while pieces[i][1] <= ga:
                i += 1
            k = i
            while k < len(pieces) and pieces[k][0] < gb:
                a, b, name = pieces[k]
                ov = min(b, gb) - max(a, ga)
                if ov > 0:
                    idle[name] = idle.get(name, 0.0) + ov * 1e-9
                k += 1
    n = len(gaps_per_chip)
    return {k: v / n for k, v in idle.items()}


def host_ms_per_sync(spans: list[Span], lo: float, hi: float) -> float | None:
    """Mean over the ``serve.step`` spans wholly inside [lo, hi] that hold a
    ``serve.sync`` of the step's duration less that sync's, in ms."""
    syncs = [s for s in spans if s.name == SYNC]
    host = []
    for st in spans:
        if st.name != STEP or st.start < lo or st.end > hi:
            continue
        inner = [s for s in syncs if st.start <= s.start and s.end <= st.end]
        if inner:
            host.append((st.end - st.start)
                        - sum(s.end - s.start for s in inner))
    return 1e-6 * sum(host) / len(host) if host else None


def summarize(profile) -> ProgramSpans:
    (lo, hi), spans = host_spans(profile)
    gaps = device_gaps(profile, lo, hi)
    idle = idle_by_span(gaps, innermost(spans, lo, hi))
    span_ms: dict = {}
    for s in spans:
        n, t = span_ms.get(s.name, (0, 0.0))
        span_ms[s.name] = (n + 1, t + (s.end - s.start) * 1e-6)
    return ProgramSpans(
        window_s=(hi - lo) * 1e-9,
        idle_s=sum((b - a) for g in gaps for a, b in g) * 1e-9 / len(gaps),
        spans=spans, idle_by_span=idle,
        host_ms_per_sync=host_ms_per_sync(spans, lo, hi),
        span_ms={k: [n, t / n] for k, (n, t) in span_ms.items()})


__all__ = ["NO_SPAN", "ProgramSpans", "Span", "device_gaps",
           "host_ms_per_sync", "host_spans", "idle_by_span", "innermost",
           "summarize"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 bench/spans.py <profiler output directory>",
              file=sys.stderr)
        return 2
    ps = summarize(load(find_xplane(argv[0])))
    print(json.dumps({
        "window_s": ps.window_s, "idle_s": ps.idle_s,
        "idle_by_span": dict(sorted(ps.idle_by_span.items(),
                                    key=lambda kv: -kv[1])),
        "host_ms_per_sync": ps.host_ms_per_sync,
        "span_ms": dict(sorted(ps.span_ms.items()))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

