"""Reduction of a JAX profiler trace (.xplane.pb) to the numbers the
per-layer metrics read.

Device planes are `/device:TPU:<n>`; their "XLA Ops" line holds one event
per executed HLO instruction, named by its HLO text ("%hbfp_matmul_fwd.12
= ..."), with loops ("%while.3 = ...") spanning their bodies, and their
"XLA Modules" line one event per executable run ("jit_train_step(...)").
The host plane `/host:CPU` holds the benchmark's own spans
(`TraceAnnotation("bench.*")`), on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Tuple

CONTAINERS = ("while", "conditional", "call")
_INSTR = re.compile(r"^%?([\w.\-]+)")


def instr_name(event_name: str) -> str:
    """'%hbfp_matmul_fwd.12 = f32[...] custom-call(...)' -> the
    instruction's name, 'hbfp_matmul_fwd.12'."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def base_name(instr: str) -> str:
    """'hbfp_matmul_fwd.12' -> 'hbfp_matmul_fwd'."""
    return re.sub(r"\.\d+$", "", instr)


@dataclasses.dataclass
class Trace:
    """Events as (name, start_ns, end_ns), on one clock."""
    ops: Dict[str, List[Tuple[str, float, float]]]      # per device plane
    modules: Dict[str, List[Tuple[str, float, float]]]  # per device plane
    spans: List[Tuple[str, float, float]]               # bench.* host spans

    def window(self) -> Tuple[float, float]:
        """The traced window: the outermost bench.window span."""
        w = [s for s in self.spans if s[0] == "bench.window"]
        if not w:
            raise ValueError("trace has no bench.window span")
        return min(s[1] for s in w), max(s[2] for s in w)


def load(trace_dir: str) -> Trace:
    """Read the newest .xplane.pb under trace_dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]))


def from_profile(pd) -> Trace:
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith("bench.")]
    return Trace(ops=ops, modules=modules, spans=spans)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Nanoseconds in [lo, hi] in which some operation ran on the device,
    averaged over the device planes."""
    if not tr.ops:
        return 0.0
    tot = 0.0
    for evs in tr.ops.values():
        tot += sum(b - a for a, b in union(clip([(s, e) for _, s, e in evs],
                                                lo, hi)))
    return tot / len(tr.ops)


def op_seconds(tr: Trace, prefix: str) -> float:
    """Summed device seconds of the ops whose instruction name starts
    with `prefix`, over all device planes."""
    return sum(e - s for evs in tr.ops.values() for n, s, e in evs
               if instr_name(n).startswith(prefix)) / 1e9


def module_events(tr: Trace, fragment: str):
    """(start, end) of every run of the executables whose name contains
    `fragment`, over all device planes, sorted."""
    return sorted((s, e) for evs in tr.modules.values() for n, s, e in evs
                  if fragment in n)


def top_ops(tr: Trace, n: int = 10):
    """The device instructions that took most time, loops left out (they
    span their bodies): [[name, seconds], ...]."""
    tot: Dict[str, float] = {}
    for evs in tr.ops.values():
        for name, s, e in evs:
            i = instr_name(name)
            if base_name(i) in CONTAINERS:
                continue
            tot[i] = tot.get(i, 0.0) + (e - s)
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def _label(tr: Trace, a: float, b: float) -> str:
    """What the host was doing in [a, b]: the innermost bench span at its
    middle."""
    mid = (a + b) / 2
    cover = [ev for ev in tr.spans if ev[1] <= mid <= ev[2]]
    return min(cover, key=lambda ev: ev[2] - ev[1])[0] if cover else "-"


def idle_gaps(tr: Trace, n: int = 10):
    """The longest stretches of the window with nothing running on the
    first device, each named by what the host was doing:
    [[label, seconds], ...]."""
    lo, hi = tr.window()
    if not tr.ops:
        return []
    evs = next(iter(tr.ops.values()))
    busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_label(tr, a, b), (b - a) / 1e9] for a, b in gaps[:n]]
