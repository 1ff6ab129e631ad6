"""The program's own spans, counters and scopes, reduced to layer numbers.

The program marks its layers itself (``repro.telemetry``): spans in the
checkpoint path (``ckpt.save`` and its ``serialize``, ``write`` and
``publish``; ``ckpt.restore`` and its ``manifest``, ``read`` and
``assemble``) and in ingest (``ingest.batch``), counters of the bytes a
restore copies into its leaves and of the samples and queries ingest
makes, and ``jax.named_scope``s in the train step (``forward``,
``optimizer``, ``attention``; the backward pass carries ``transpose(``).
This module turns a recorder and a profiler trace into the numbers of
each layer:

* ``host_numbers``: each checkpoint span's mean seconds, the bytes a
  restore copies on the host, the queries per ingested sample;
* ``step_scopes``: the train step's device time under each named scope
  and by part (forward, backward, optimizer), per step.

(``devtrace`` names the device's idle gaps by the program's spans.)

``bench/run.py`` hands a traced run's recorder to the per-layer readers
as ``ctx.telemetry``, and ``step_scopes`` of the window, by the compiled
step's HLO (``hlo_scopes``), as ``ctx.scopes``.
"""

from __future__ import annotations

import re
from bisect import bisect_left
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

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
PARTS = ("forward", "backward", "optimizer")


# ---------------------------------------------------------------- host
def host_numbers(spans: Sequence, counters: Dict[str, int]) -> Dict[str, float]:
    """The checkpoint spans' mean seconds, ``restore_host_copy_gb``
    (bytes a restore copies into its leaves on the host, per restore)
    and ``ingest_queries_per_sample``, from a recorder's ``spans`` and
    ``counters``; a number with nothing to read is left out."""
    secs: Dict[str, List[float]] = defaultdict(list)
    for s in spans:
        secs[s.name].append((s.end_ns - s.start_ns) / 1e9)
    out = {k: sum(secs[n]) / len(secs[n])
           for k, n in SPAN_NUMBERS.items() if secs.get(n)}
    restores = len(secs.get("ckpt.restore", ()))
    if restores:
        out["restore_host_copy_gb"] = (
            counters.get("ckpt.restore.host_copy_bytes", 0) / restores / 1e9)
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


def _scope_words(op_name: Optional[str]) -> set:
    """The words of an op's name stack without the primitive at its end:
    the named scopes (and transforms) it runs under."""
    if not op_name:
        return set()
    return set(re.findall(r"[\w.\-]+", op_name.rsplit("/", 1)[0]))


def step_part(op_name: Optional[str]) -> Optional[str]:
    """``forward``, ``backward`` or ``optimizer`` by an op's name stack
    (the scopes, not the primitive at its end); None for glue."""
    if not op_name:
        return None
    if "transpose(" in op_name:
        return "backward"
    words = _scope_words(op_name)
    for part in ("forward", "optimizer"):
        if part in words:
            return part
    return None


def step_scopes(trace: tr.Trace, scope_of: Callable[[str], Optional[str]],
                step_key: str = "train_step") -> Dict[str, float]:
    """Device ms per train step, over the innermost operations that start
    inside a train-step module that starts in the window: under each
    word of their name stacks (each ``jax.named_scope`` by its name:
    ``attention`` forward, backward and remat recompute together), and
    by part, which takes the place of a same-named scope: ``forward``
    (not transposed), ``backward`` (under ``transpose(``, remat recompute
    included), ``optimizer``, ``unscoped``; ``module`` is the modules'
    own time, as ``step_device_ms`` reads it."""
    lo, hi = trace.window
    words: Dict[str, float] = defaultdict(float)
    parts: Dict[str, float] = defaultdict(float)
    steps = 0
    for ops, modules in zip(trace.ops, trace.modules):
        runs = sorted((s, s + d) for name, s, d in modules
                      if step_key in name and lo <= s <= hi)
        steps += len(runs)
        parts["module"] += sum(b - a for a, b in runs)
        ops = sorted(ops, key=lambda e: e[1])
        starts = [e[1] for e in ops]
        for a, b in runs:
            inside = ops[bisect_left(starts, a):bisect_left(starts, b)]
            for name, _, d in tr.leaves(inside):
                op_name = scope_of(name)
                parts[step_part(op_name) or "unscoped"] += d
                for word in _scope_words(op_name):
                    words[word] += d
    if not steps:
        return {}
    ns = {**words, **{k: parts[k] for k in ("module", *PARTS, "unscoped")}}
    return {k: v / steps / 1e6 for k, v in ns.items()}

