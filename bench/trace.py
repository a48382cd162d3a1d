"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) with a line of
XLA operations and a line of XLA modules (whole executables), and a host
plane whose threads carry the benchmark's own spans
(``jax.profiler.TraceAnnotation``).  The benchmark wraps the traced part of
its window in one span, ``WINDOW_SPAN``; everything is measured inside it.

* busy: the union of the intervals in which an operation ran on a chip,
  averaged over the chips;
* per-operation and per-module time: summed durations, averaged over chips;
* idle gaps: the stretches of the window with no operation on the chip,
  each second put down to the benchmark span the host was in then.
"""
from __future__ import annotations

import base64
import glob
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that only hold others (a loop, a branch, a call): their
#: intervals are covered by the operations inside them
CONTAINERS = {"while", "conditional", "call"}
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def op_name(event_name: str) -> tuple[str, str]:
    """(instruction name, opcode) of an operation event.  On a TPU an event
    is named by its whole HLO instruction, ``%fusion.3 = bf16[8] fusion(..)``;
    a bare name is taken as it is."""
    if not event_name.startswith("%"):
        return event_name, ""
    head, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return head[1:], m.group(1) if m else ""


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_chips: int
    op_s: dict = field(default_factory=dict)        # instruction -> seconds
    op_kind: dict = field(default_factory=dict)     # instruction -> opcode
    module_s: dict = field(default_factory=dict)    # module name -> seconds
    idle_by_span: dict = field(default_factory=dict)  # host span -> seconds


def _union(intervals):
    """Merge ``(start, end)`` intervals into disjoint ones, in order."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _overlap(a, b, c, d):
    return max(0.0, min(b, d) - max(a, c))


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(directory: str) -> str:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def summarize(profile) -> TraceSummary:
    """Reduce a loaded trace (``jax.profiler.ProfileData``) to a summary of
    the window span.  Times in the trace are nanoseconds."""
    planes = list(profile.planes)
    host_spans = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    host_spans.append((ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns))
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    spans = [s for s in host_spans if s[0] != WINDOW_SPAN
             and _overlap(s[1], s[2], lo, hi) > 0]
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy = 0.0
    op_s: dict = {}
    op_kind: dict = {}
    module_s: dict = {}
    idle: dict = {}
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                a, b = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if b <= a:
                    continue
                if line.name == MODULES_LINE:
                    module_s[ev.name] = module_s.get(ev.name, 0.0) + (b - a) * 1e-9
                    continue
                name, kind = op_name(ev.name)
                if kind in CONTAINERS:
                    continue
                op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9
                op_kind[name] = kind
                intervals.append((a, b))
        merged = _union(intervals)
        busy += sum(b - a for a, b in merged) * 1e-9
        gaps, t = [], lo
        for a, b in merged:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        for ga, gb in gaps:
            covered = 0.0
            for name, a, b in spans:
                ov = _overlap(ga, gb, a, b)
                if ov:
                    idle[name] = idle.get(name, 0.0) + ov * 1e-9
                    covered += ov
            rest = (gb - ga) - covered
            if rest > 0:
                idle["(no bench span)"] = idle.get("(no bench span)", 0.0) \
                    + rest * 1e-9
    n = len(devices)
    scale = 1.0 / n
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy * scale, n_chips=n,
        op_s={k: v * scale for k, v in op_s.items()}, op_kind=op_kind,
        module_s={k: v * scale for k, v in module_s.items()},
        idle_by_span={k: v * scale for k, v in idle.items()})


def kernel_ops(hlo_text: str, kernels: dict) -> dict:
    """Map the compiled program's Pallas calls to kernel names.

    ``kernels``: {kernel name: name of the Pallas kernel function}.  Each
    ``tpu_custom_call`` instruction carries its Mosaic body, which names the
    kernel function; the instruction's name is what the trace calls the
    operation.  Returns {instruction name: kernel name}."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        instr = line.strip().split(" ", 1)[0].lstrip("%")
        m = re.search(r'"body":"([^"]*)"', line)
        if not m:
            continue
        try:
            body = base64.b64decode(m.group(1))
        except ValueError:
            body = m.group(1).encode()
        for name, fn in kernels.items():
            if fn.encode() in body:
                out[instr] = name
    return out


__all__ = ["MODULES_LINE", "OPS_LINE", "TraceSummary", "WINDOW_SPAN",
           "find_xplane", "kernel_ops", "load", "summarize"]
