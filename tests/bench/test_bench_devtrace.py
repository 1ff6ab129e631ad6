"""The reduction from a profiler trace to the benchmark's device numbers."""

import pytest

from bench import devtrace as tr

MS = 1e6  # ns


def _trace():
    # Window 0..100 ms.  Device ops: 0-10, 5-20 (overlapping), 40-60,
    # 60-70 (abutting), 90-110 (runs past the window's end).  Idle
    # gaps: 20-40 under "ingest", 70-90 under "ckpt_save".
    ops = [("fusion.1", 0, 10 * MS), ("fusion.2", 5 * MS, 15 * MS),
           ("dot.3", 40 * MS, 20 * MS), ("fusion.1", 60 * MS, 10 * MS),
           ("copy.4", 90 * MS, 20 * MS)]
    modules = [("jit_train_step(7)", 0, 20 * MS),
               ("jit_train_step(7)", 40 * MS, 30 * MS),
               ("jit_norms(9)", 90 * MS, 5 * MS),
               ("jit_train_step(7)", 150 * MS, 10 * MS)]   # after the window
    spans = {"window": [(0, 100 * MS)],
             "ingest": [(18 * MS, 41 * MS)],
             "step": [(0, 18 * MS), (41 * MS, 45 * MS)],
             "ckpt_save": [(69 * MS, 95 * MS)]}
    return tr.Trace([ops], [modules], spans)


def test_busy_union_merges_overlaps_and_clips_to_window():
    ops = _trace().ops[0]
    assert tr.busy_ns(ops, 0, 100 * MS) == pytest.approx(60 * MS)
    assert tr.union([(0, 10), (5, 20), (20, 25), (30, 31)]) == [
        (0, 25), (30, 31)]


def test_idle_gaps_and_their_host_spans():
    t = _trace()
    gaps = tr.idle_gaps(t.ops[0], 0, 100 * MS)
    assert gaps == [(20 * MS, 40 * MS), (70 * MS, 90 * MS)]
    assert [tr.name_gap(g, t.spans) for g in gaps] == ["ingest", "ckpt_save"]
    assert tr.name_gap((200 * MS, 210 * MS), t.spans) == "other"


def test_step_device_time_counts_train_step_modules_in_window():
    t = _trace()
    assert tr.module_time(t.modules[0], "train_step", 0, 100 * MS) == (
        50 * MS, 2)


def test_summary_and_breakdown():
    s = tr.summarize(_trace())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.06)
    assert (s.step_ns, s.step_count) == (50 * MS, 2)
    ops = dict(s.breakdown["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.02)
    assert ops["copy.4"] == pytest.approx(0.01)      # clipped at 100 ms
    assert s.breakdown["idle_gaps"] == [["ingest", pytest.approx(0.02)],
                                        ["ckpt_save", pytest.approx(0.02)]]


def test_a_cpu_trace_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(tr.NoDeviceTrace):
        tr.load(str(tmp_path))


def test_loop_events_are_left_out_of_the_top_operations():
    ops = [("while.1", 0, 10), ("fusion.2", 1, 3), ("fusion.3", 5, 4),
           ("copy.4", 12, 2)]
    assert tr.leaves(ops) == [("fusion.2", 1, 3), ("fusion.3", 5, 4),
                              ("copy.4", 12, 2)]
    assert tr._short("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == (
        "fusion.12")


def test_breakdown_names_gaps_by_the_program_s_spans():
    t = _trace()
    t.program = [("ckpt.save", 69 * MS, 94 * MS),
                 ("ckpt.save.write", 72 * MS, 90 * MS),
                 ("ingest.batch", 30 * MS, 31 * MS)]
    s = tr.summarize(t)
    assert s.breakdown["idle_gaps"] == [
        ["ingest", pytest.approx(0.02)],       # no program span has half
        ["ckpt_save/ckpt.save.write", pytest.approx(0.02)]]
