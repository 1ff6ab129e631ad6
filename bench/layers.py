"""The program's own spans, counters and scopes, reduced to layer numbers.

The program marks its layers itself (``repro.telemetry``): spans in the
checkpoint path (``ckpt.save`` and its ``serialize``, ``write`` and
``publish``; ``ckpt.restore`` and its ``manifest``, ``read`` and
``assemble``) and in ingest (``ingest.batch``), counters of the bytes a
restore copies from the device and of the samples and queries ingest
makes, and ``jax.named_scope``s in the train step (``forward``,
``optimizer``, ``attention``; the backward pass carries ``transpose(``).
This module turns a recorder and a profiler trace into the numbers of
each layer:

* ``host_numbers``: each checkpoint span's mean seconds, the device bytes
  copied per restore, the queries per ingested sample;
* ``step_split``: the train step's device time by part, per step;
* ``name_gaps``: the device's idle gaps, each named by the harness span
  and the innermost program span that covers most of it.

The benchmark's own runs do not read these yet: the reader files of the
per-layer metrics call them once ``Job.window`` records and
``devtrace`` loads the program's spans.
"""

from __future__ import annotations

import re
from bisect import bisect_left
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

from bench import devtrace as tr

# Program span -> the number it gives, in seconds per span.
SPAN_NUMBERS = {
    "save_s": "ckpt.save",
    "save_serialize_s": "ckpt.save.serialize",
    "save_write_s": "ckpt.save.write",
    "save_publish_s": "ckpt.save.publish",
    "restore_s": "ckpt.restore",
    "restore_manifest_s": "ckpt.restore.manifest",
    "restore_read_s": "ckpt.restore.read",
    "restore_assemble_s": "ckpt.restore.assemble",
}
PROGRAM_PREFIXES = ("ckpt.", "ingest.")
PARTS = ("forward", "backward", "optimizer")


# ---------------------------------------------------------------- host
def host_numbers(spans: Sequence, counters: Dict[str, int]) -> Dict[str, float]:
    """The checkpoint spans' mean seconds, ``restore_d2h_gb`` (device
    bytes copied to the host per restore) and
    ``ingest_queries_per_sample``, from a recorder's ``spans`` and
    ``counters``; a number with nothing to read is left out."""
    secs: Dict[str, List[float]] = defaultdict(list)
    for s in spans:
        secs[s.name].append((s.end_ns - s.start_ns) / 1e9)
    out = {k: sum(secs[n]) / len(secs[n])
           for k, n in SPAN_NUMBERS.items() if secs.get(n)}
    restores = len(secs.get("ckpt.restore", ()))
    if restores:
        out["restore_d2h_gb"] = (counters.get("ckpt.restore.d2h_bytes", 0)
                                 / restores / 1e9)
    if counters.get("ingest.samples"):
        out["ingest_queries_per_sample"] = (counters["ingest.queries"]
                                            / counters["ingest.samples"])
    return out


# ---------------------------------------------------------- op scopes
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(calls|body|condition|to_apply)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def hlo_scopes(text: str) -> Dict[str, str]:
    """Each instruction of an optimized HLO module (``compile().as_text()``)
    with the name stack it runs under: its own ``op_name``; for a fusion
    without one, its root's; else that of the loop, call or fusion whose
    computation holds it.  (A TPU trace's op events carry no name stack of
    their own, so the trace's op names are looked up here.)"""
    own: Dict[str, str] = {}
    home: Dict[str, str] = {}          # instruction -> its computation
    root: Dict[str, str] = {}          # computation -> its root
    fused: Dict[str, str] = {}         # fusion instruction -> computation
    caller: Dict[str, str] = {}        # computation -> calling instruction
    comp = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c:
                comp = c.group(1)
            continue
        name = m.group(2)
        home[name] = comp
        if m.group(1):
            root[comp] = name
        o = _OP_NAME.search(line)
        if o:
            own[name] = o.group(1)
        for kind, callee in _CALLS.findall(line):
            caller.setdefault(callee, name)
            if kind == "calls":
                fused[name] = callee
        for b in _BRANCHES.findall(line):
            for callee in re.findall(r"%([\w.\-]+)", b):
                caller.setdefault(callee, name)

    out: Dict[str, str] = {}

    def scope(name: str) -> Optional[str]:
        if name in out:
            return out[name]
        s = own.get(name)
        if s is None and name in fused:
            s = own.get(root.get(fused[name], ""))
        if s is None and home.get(name) in caller:
            s = scope(caller[home[name]])
        if s is not None:
            out[name] = s
        return s

    for name in home:
        scope(name)
    return out


def step_part(op_name: Optional[str]) -> Optional[str]:
    """``forward``, ``backward`` or ``optimizer`` by an op's name stack
    (the scopes, not the primitive at its end); None for glue."""
    if not op_name:
        return None
    if "transpose(" in op_name:
        return "backward"
    words = set(re.findall(r"[\w.\-]+", op_name.rsplit("/", 1)[0]))
    for part in ("forward", "optimizer"):
        if part in words:
            return part
    return None


def in_attention(op_name: Optional[str]) -> bool:
    return bool(op_name) and "attention" in re.findall(
        r"[\w.\-]+", op_name.rsplit("/", 1)[0])


def step_split(trace: tr.Trace, scope_of: Callable[[str], Optional[str]],
               step_key: str = "train_step") -> Dict[str, float]:
    """Device ms per train step by part: ``forward`` (not transposed),
    ``backward`` (under ``transpose(``, remat recompute included),
    ``optimizer``, ``unscoped``, and ``attention`` (forward and
    backward, also counted in those), over the innermost operations that
    start inside a train-step module that starts in the window;
    ``module`` is the modules' own time, as ``step_device_ms`` reads it."""
    lo, hi = trace.window
    ns: Dict[str, float] = defaultdict(float)
    steps = 0
    for ops, modules in zip(trace.ops, trace.modules):
        runs = sorted((s, s + d) for name, s, d in modules
                      if step_key in name and lo <= s <= hi)
        steps += len(runs)
        ns["module"] += sum(b - a for a, b in runs)
        ops = sorted(ops, key=lambda e: e[1])
        starts = [e[1] for e in ops]
        for a, b in runs:
            inside = ops[bisect_left(starts, a):bisect_left(starts, b)]
            for name, _, d in tr.leaves(inside):
                op_name = scope_of(name)
                ns[step_part(op_name) or "unscoped"] += d
                if in_attention(op_name):
                    ns["attention"] += d
    if not steps:
        return {}
    return {k: ns.get(k, 0.0) / steps / 1e6
            for k in ("module", *PARTS, "unscoped", "attention")}


# ------------------------------------------------------------- gaps
Interval = Tuple[str, float, float]      # name, start ns, end ns


def name_gap(gap: Tuple[float, float],
             spans: Dict[str, List[Tuple[float, float]]],
             program: Iterable[Interval]) -> str:
    """The harness's name of a gap (``devtrace.name_gap``), followed by
    ``/`` and the innermost program span that covers more than half of
    it, where one does."""
    a, b = gap
    best: Optional[Tuple[float, str]] = None
    for name, s, e in program:
        if 2 * (min(b, e) - max(a, s)) > b - a:
            if best is None or e - s < best[0]:
                best = (e - s, name)
    base = tr.name_gap(gap, spans)
    return base if best is None else f"{base}/{best[1]}"


def name_gaps(trace: tr.Trace, program: Sequence[Interval]
              ) -> List[Tuple[str, float]]:
    """Every idle gap of the window on every device plane, named, with
    its length in s, longest first."""
    lo, hi = trace.window
    out = []
    for ops in trace.ops:
        inside = tr.clip(ops, lo, hi)
        out += [(name_gap(g, trace.spans, program), (g[1] - g[0]) / 1e9)
                for g in tr.idle_gaps(inside, lo, hi)]
    return sorted(out, key=lambda kv: -kv[1])


# ------------------------------------------------------------- trace
def load_program(log_dir: str) -> List[Interval]:
    """The program's spans on the host plane of the one ``.xplane.pb``
    under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_PREFIXES)]
