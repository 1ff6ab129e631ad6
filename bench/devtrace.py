"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain lists of ``(name, start_ns, duration_ns)``:
the device's operations and its XLA module executions, the host spans
that the harness writes with ``jax.profiler.TraceAnnotation``, and the
program's own spans (``repro.telemetry``) on the same clock.  Everything
after ``load`` is arithmetic on those lists, so the tests can build a
trace by hand.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start ns, duration ns
Interval = Tuple[str, float, float]       # name, start ns, end ns

HOST_SPANS = ("window", "ingest", "step", "ckpt_save", "restore")
PROGRAM_PREFIXES = ("ckpt.", "ingest.")


class NoDeviceTrace(RuntimeError):
    """The trace holds no accelerator plane: there is nothing to read."""


@dataclass
class Trace:
    ops: List[List[Event]]                # per device plane
    modules: List[List[Event]]            # per device plane
    spans: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)
    program: List[Interval] = field(default_factory=list)

    @property
    def window(self) -> Tuple[float, float]:
        (w,) = self.spans["window"]
        return w


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    ops, modules = [], []
    spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    program: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines or "XLA Modules" not in lines:
                raise NoDeviceTrace(
                    f"{plane.name} has lines {sorted(lines)}; "
                    "expected 'XLA Ops' and 'XLA Modules'")
            ops.append([(_short(e.name), e.start_ns, e.duration_ns)
                        for e in lines["XLA Ops"].events])
            modules.append([(e.name, e.start_ns, e.duration_ns)
                            for e in lines["XLA Modules"].events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIXES):
                        program.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    if not ops:
        raise NoDeviceTrace(
            "no TPU plane in the trace: planes are "
            f"{[p.name for p in data.planes]}")
    return Trace(ops, modules, dict(spans), program)


def _short(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event: a loop's or a call's own
    event spans the operations run inside it."""
    parents, stack = set(), []
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    for i in order:
        _, s, d = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack and events[stack[-1]][1] + events[stack[-1]][2] >= s + d:
            parents.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(events) if i not in parents]


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the interval [lo, hi]; those outside it dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of the events inside [lo, hi]."""
    return sum(b - a for a, b in union(
        [(s, s + d) for _, s, d in clip(events, lo, hi)]))


def idle_gaps(events: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no event runs."""
    gaps, t = [], lo
    for a, b in union([(s, s + d) for _, s, d in clip(events, lo, hi)]):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def name_gap(gap: Tuple[float, float],
             spans: Dict[str, List[Tuple[float, float]]],
             program: Iterable[Interval] = ()) -> str:
    """The host span (other than the window) that covers most of a gap,
    or ``"other"`` where none covers any of it; followed by ``/`` and
    the innermost program span that covers more than half of it, where
    one does."""
    base, best_cover = "other", 0.0
    a, b = gap
    for name, ivs in spans.items():
        if name == "window":
            continue
        cover = sum(max(0.0, min(b, e) - max(a, s)) for s, e in ivs)
        if cover > best_cover:
            base, best_cover = name, cover
    inner: Optional[Tuple[float, str]] = None
    for name, s, e in program:
        if 2 * (min(b, e) - max(a, s)) > b - a:
            if inner is None or e - s < inner[0]:
                inner = (e - s, name)
    return base if inner is None else f"{base}/{inner[1]}"


def module_time(modules: Sequence[Event], key: str, lo: float, hi: float
                ) -> Tuple[float, int]:
    """Total ns and count of the module executions whose name holds
    ``key`` and that start inside [lo, hi]."""
    hits = [d for name, s, d in modules if key in name and lo <= s <= hi]
    return float(sum(hits)), len(hits)


@dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the device planes
    step_ns: float                # total train-step module time, all planes
    step_count: int
    breakdown: Dict[str, list]


def summarize(trace: Trace, step_key: str = "train_step",
              top: int = 10) -> Summary:
    lo, hi = trace.window
    busy, step_ns, step_n = [], 0.0, 0
    per_op: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for ops, modules in zip(trace.ops, trace.modules):
        inside = clip(ops, lo, hi)
        busy.append(busy_ns(inside, lo, hi))
        for name, _, d in leaves(inside):
            per_op[name] += d
        t, n = module_time(modules, step_key, lo, hi)
        step_ns, step_n = step_ns + t, step_n + n
        gaps += [(name_gap(g, trace.spans, trace.program), g[1] - g[0])
                 for g in idle_gaps(inside, lo, hi)]
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(gaps, key=lambda kv: -kv[1])[:top]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / len(busy) / 1e9,
        step_ns=step_ns, step_count=step_n,
        breakdown={"device_ops": [[n, d / 1e9] for n, d in ops_top],
                   "idle_gaps": [[n, d / 1e9] for n, d in gaps_top]})
