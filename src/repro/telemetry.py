"""Program spans and counters, on the profiler's clock.

``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation``, so a
profiler trace shows the span, with ``attrs`` as its event stats, on the
host plane beside the device's operations.  While ``recording()`` is
active, each span is also kept in memory as a :class:`Span` and each
``count(name, n)`` adds to a counter; outside it nothing is kept and
``count`` costs one check.  Neither ever waits for the device.

Spans nest by the order they open in one thread: ``parent`` is the index
of the span that was open when this one opened.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, NamedTuple, Optional

import jax


class Span(NamedTuple):
    name: str
    parent: Optional[int]       # index in ``Recorder.spans``
    start_ns: int               # ``time.perf_counter_ns``
    end_ns: int
    attrs: Dict[str, Any]


@dataclass
class Recorder:
    spans: List[Span] = field(default_factory=list)
    counters: Dict[str, int] = field(default_factory=dict)
    _open: List[int] = field(default_factory=list)


_active: Optional[Recorder] = None


@contextlib.contextmanager
def span(name: str, **attrs: Any) -> Iterator[None]:
    rec = _active
    with jax.profiler.TraceAnnotation(name, **attrs):
        if rec is None:
            yield
            return
        i = len(rec.spans)
        rec.spans.append(Span(name, rec._open[-1] if rec._open else None,
                              time.perf_counter_ns(), 0, attrs))
        rec._open.append(i)
        try:
            yield
        finally:
            rec._open.pop()
            rec.spans[i] = rec.spans[i]._replace(
                end_ns=time.perf_counter_ns())


def count(name: str, n: int = 1) -> None:
    rec = _active
    if rec is not None:
        rec.counters[name] = rec.counters.get(name, 0) + n


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Keep every span and count made inside the block."""
    global _active
    prev, rec = _active, Recorder()
    _active = rec
    try:
        yield rec
    finally:
        _active = prev
