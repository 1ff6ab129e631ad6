"""Program spans and counters (``repro.telemetry``)."""

import glob
import os

import jax
import jax.numpy as jnp

from repro import telemetry


def test_spans_nest_with_parent_indices_and_attrs():
    with telemetry.recording() as rec:
        with telemetry.span("a", step=3):
            with telemetry.span("a.b"):
                pass
            with telemetry.span("a.c", bytes=10):
                with telemetry.span("a.c.d"):
                    pass
        with telemetry.span("e"):
            pass
    assert [(s.name, s.parent, s.attrs) for s in rec.spans] == [
        ("a", None, {"step": 3}), ("a.b", 0, {}), ("a.c", 0, {"bytes": 10}),
        ("a.c.d", 2, {}), ("e", None, {})]
    for s in rec.spans:
        assert 0 < s.start_ns <= s.end_ns
        if s.parent is not None:
            p = rec.spans[s.parent]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns


def test_counters_add_up():
    with telemetry.recording() as rec:
        telemetry.count("x")
        telemetry.count("x", 4)
        telemetry.count("y", 0)
    assert rec.counters == {"x": 5, "y": 0}


def test_a_span_that_raises_is_closed():
    with telemetry.recording() as rec:
        try:
            with telemetry.span("outer"):
                raise KeyError("k")
        except KeyError:
            pass
        with telemetry.span("next"):
            pass
    assert [(s.name, s.parent) for s in rec.spans] == [
        ("outer", None), ("next", None)]
    assert rec.spans[0].end_ns > 0


def test_nothing_is_kept_outside_recording():
    with telemetry.recording() as rec:
        pass
    with telemetry.span("late"):
        telemetry.count("late")
    assert rec.spans == [] and rec.counters == {}
    assert telemetry._active is None


def test_a_span_lands_on_the_host_plane_with_its_attrs(tmp_path):
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((8,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with telemetry.span("ckpt.save", step=7, bytes=4096):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    found = [dict(e.stats)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name == "ckpt.save"]
    assert found == [{"step": 7, "bytes": 4096}]
