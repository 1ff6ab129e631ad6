"""The program's spans, counters and scopes reduced to layer numbers."""

import json
import statistics

import jax
import jax.numpy as jnp
import pytest
from bench_tiny import ROOT, TINY_JOB, TINY_MODEL

from bench import devtrace as tr
from bench import layers as L

MS = 1e6  # ns

FWD = "jit(train_step)/jvp(forward)/while/body/dot_general"
FWD_ATTN = "jit(train_step)/jvp(forward)/while/body/attention/jit(_flash_chunked)/exp"
BWD_ATTN = "jit(train_step)/transpose(jvp(forward))/while/body/attention/mul"
BWD = "jit(train_step)/transpose(jvp(forward))/while/body/dot_general"
OPT = "jit(train_step)/optimizer/add"
FWD_TRANSPOSE_PRIMITIVE = "jit(train_step)/jvp(forward)/transpose"


def _trace():
    # Two train steps, 0-40 ms and 50-90 ms, in a 0-100 ms window; a loop
    # event spans each step's backward ops; one op from another module
    # runs between the steps.
    scopes = {"fusion.1": FWD, "fusion.2": FWD_ATTN, "fusion.3": BWD_ATTN,
              "fusion.4": BWD, "fusion.5": OPT, "copy.6": None,
              "transpose.7": FWD_TRANSPOSE_PRIMITIVE, "while.8": BWD,
              "fusion.9": FWD}
    ops = []
    for t0 in (0, 50 * MS):
        ops += [("fusion.1", t0, 4 * MS), ("fusion.2", t0 + 4 * MS, 6 * MS),
                ("transpose.7", t0 + 10 * MS, 2 * MS),
                ("while.8", t0 + 12 * MS, 20 * MS),
                ("fusion.3", t0 + 12 * MS, 8 * MS),
                ("fusion.4", t0 + 20 * MS, 12 * MS),
                ("fusion.5", t0 + 32 * MS, 5 * MS),
                ("copy.6", t0 + 37 * MS, 3 * MS)]
    ops.append(("fusion.9", 42 * MS, 5 * MS))
    modules = [("jit_train_step(3)", 0, 40 * MS),
               ("jit_norms(4)", 42 * MS, 5 * MS),
               ("jit_train_step(3)", 50 * MS, 40 * MS)]
    return tr.Trace([ops], [modules], {"window": [(0, 100 * MS)]}), scopes


def test_step_split_by_scope():
    trace, scopes = _trace()
    got = L.step_scopes(trace, scopes.get)
    split = {k: got[k] for k in ("module", *L.PARTS, "unscoped", "attention")}
    assert split == {"module": 40.0, "forward": 12.0, "backward": 20.0,
                     "optimizer": 5.0, "unscoped": 3.0, "attention": 14.0}
    assert sum(split[k] for k in L.PARTS) + split["unscoped"] == 40.0


def test_step_scopes_by_every_scope_word():
    trace, scopes = _trace()
    got = L.step_scopes(trace, scopes.get)
    assert got["attention"] == 14.0               # forward and backward
    assert got["_flash_chunked"] == 6.0
    assert got["train_step"] == 37.0              # every scoped op
    assert "add" not in got and "exp" not in got  # primitives
    assert got["forward"] == 12.0                 # the part, not the scope
    assert L.step_scopes(tr.Trace([[]], [[]], {"window": [(0, 1)]}),
                         scopes.get) == {}


def test_step_part_reads_the_scopes_not_the_primitive():
    assert L.step_part(FWD) == "forward"
    assert L.step_part(FWD_TRANSPOSE_PRIMITIVE) == "forward"
    assert L.step_part(BWD) == "backward"
    assert L.step_part(OPT) == "optimizer"
    assert L.step_part("jit(train_step)/add") is None
    assert L.step_part(None) is None
    attention = L.step_scopes(*_one_op_trace(FWD_ATTN)).get("attention")
    assert attention == L.step_scopes(*_one_op_trace(BWD_ATTN))["attention"]
    assert "attention" not in L.step_scopes(
        *_one_op_trace("jit(train_step)/jvp(forward)/attention"))


def _one_op_trace(op_name):
    """One 1 ms step of one op whose name stack is ``op_name``."""
    trace = tr.Trace([[("fusion.1", 0, MS)]], [[("jit_train_step(1)", 0, MS)]],
                     {"window": [(0, MS)]})
    return trace, {"fusion.1": op_name}.get


HLO = """HloModule jit_step

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/optimizer/mul"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%t), index=1
  %copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%gte.1)
  %copy-done.1 = f32[4]{0} copy-done(%copy-start.1)
  %gte.0 = s32[] get-tuple-element(%t), index=0
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.0, %copy-done.1)
}

%cond (t.1: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%t.1), index=0
  %c.1 = s32[] constant(3)
  ROOT %lt.1 = pred[] compare(%gte.2, %c.1), direction=LT, metadata={op_name="jit(step)/transpose(jvp(forward))/while/cond/lt"}
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %c.0 = s32[] constant(0)
  %tuple.0 = (s32[], f32[4]{0}) tuple(%c.0, %fusion.1)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/transpose(jvp(forward))/while"}
  ROOT %gte.9 = f32[4]{0} get-tuple-element(%while.1), index=1
}
"""


def test_hlo_scopes_own_fusion_root_and_enclosing_loop():
    s = L.hlo_scopes(HLO)
    assert s["fusion.1"] == "jit(step)/optimizer/mul"          # fusion root
    assert s["copy-done.1"] == "jit(step)/transpose(jvp(forward))/while"
    assert s["lt.1"] == "jit(step)/transpose(jvp(forward))/while/cond/lt"
    assert s["x"] == "x"
    assert "c.0" not in s and "tuple.0" not in s               # glue


def test_hlo_scopes_of_a_compiled_step():
    """The program's scopes survive into a compiled module's metadata."""
    def loss(w, x):
        with jax.named_scope("forward"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

    def step(w, x):
        g = jax.grad(loss)(w, x)
        with jax.named_scope("optimizer"):
            return w - 0.1 * g

    w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
    s = L.hlo_scopes(jax.jit(step).lower(w, x).compile().as_text())
    parts = {L.step_part(v) for v in s.values()}
    assert {"forward", "backward", "optimizer"} <= parts


def _gap_spans():
    spans = {"window": [(0, 100 * MS)], "ckpt_save": [(10 * MS, 60 * MS)],
             "restore": [(60 * MS, 95 * MS)]}
    program = [("ckpt.save", 10 * MS, 58 * MS),
               ("ckpt.save.serialize", 12 * MS, 40 * MS),
               ("ckpt.save.write", 40 * MS, 55 * MS),
               ("ckpt.restore", 61 * MS, 90 * MS),
               ("ckpt.restore.read", 62 * MS, 85 * MS)]
    return spans, program


@pytest.mark.parametrize("gap, name", [
    ((10 * MS, 60 * MS), "ckpt_save/ckpt.save.serialize"),
    ((42 * MS, 54 * MS), "ckpt_save/ckpt.save.write"),
    ((30 * MS, 50 * MS), "ckpt_save/ckpt.save"),      # no child has half
    ((62 * MS, 88 * MS), "restore/ckpt.restore.read"),
    ((90 * MS, 95 * MS), "restore"),                  # device_put, no span
    ((96 * MS, 99 * MS), "other"),
])
def test_gaps_take_the_innermost_program_span(gap, name):
    spans, program = _gap_spans()
    assert tr.name_gap(gap, spans, program) == name


def test_gap_names_stay_as_today_without_program_spans():
    spans, program = _gap_spans()
    ops = [("fusion.1", 0, 10 * MS), ("fusion.2", 60 * MS, 2 * MS),
           ("fusion.3", 99 * MS, 1 * MS)]
    trace = tr.Trace([ops], [[]], spans)
    today = [[tr.name_gap(g, spans), (g[1] - g[0]) / 1e9]
             for g in tr.idle_gaps(ops, 0, 100 * MS)]
    gaps = tr.summarize(trace).breakdown["idle_gaps"]
    assert sorted(gaps) == sorted(today)
    trace.program = program
    assert tr.summarize(trace).breakdown["idle_gaps"][0] == [
        "ckpt_save/ckpt.save.serialize", pytest.approx(0.05)]


def test_host_numbers_from_a_recorder():
    from repro import telemetry

    with telemetry.recording() as rec:
        for _ in range(2):
            with telemetry.span("ckpt.save"):
                with telemetry.span("ckpt.save.serialize"):
                    pass
        with telemetry.span("ckpt.restore"):
            telemetry.count("ckpt.restore.host_copy_bytes", 3_000_000_000)
        telemetry.count("ingest.samples", 8)
        telemetry.count("ingest.queries", 4)
    got = L.host_numbers(rec.spans, rec.counters)
    assert set(got) == {"save_s", "save_serialize_s", "restore_s",
                        "restore_host_copy_gb", "ingest_queries_per_sample"}
    assert got["restore_host_copy_gb"] == 3.0
    assert got["ingest_queries_per_sample"] == 0.5
    assert L.host_numbers([], {}) == {}


def test_a_tiny_ckpt_restart_window_gives_every_checkpoint_number():
    from bench.job import Job
    from bench.run import load_module
    from repro import telemetry

    config = json.loads(
        (ROOT / "bench" / "configs" / "gpt2-small-commit.json").read_text())
    config["model"].update({k: v for k, v in TINY_MODEL.items()
                            if k in config["model"]})
    config["job"].update(TINY_JOB)
    config["storage"]["samples_per_host"] = 8
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / "ckpt-restart.json").read_text())
    ref = load_module(ROOT / "bench" / "reference" / "gpt2.py")
    job = Job(config, traffic, ref, 2**31 + 11)
    job.first_steps()
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(job.state))
    with telemetry.recording() as rec:
        w = job.window(1.0)
    got = L.host_numbers(rec.spans, rec.counters)
    assert len(w.save_s) == 3 and len(w.resume_s) == 1
    for k in ("save_serialize_s", "save_write_s", "save_publish_s",
              "restore_manifest_s", "restore_read_s", "restore_assemble_s"):
        assert got[k] > 0, k
    assert got["save_s"] <= statistics.fmean(w.save_s)
    assert got["restore_host_copy_gb"] == state_bytes / 1e9
    assert got["ingest_queries_per_sample"] == 1.0
    assert job.restore_check() == 0


def test_the_step_s_hlo_text_compiles_nothing():
    """A traced run takes the HLO text of the step the window ran, from
    the step's own cache: no compilation after the window."""
    from bench.job import Job
    from bench.run import load_module

    config = json.loads(
        (ROOT / "bench" / "configs" / "gpt2-small-commit.json").read_text())
    config["model"].update({k: v for k, v in TINY_MODEL.items()
                            if k in config["model"]})
    config["job"].update(TINY_JOB)
    config["storage"]["samples_per_host"] = 8
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / "train.json").read_text())
    ref = load_module(ROOT / "bench" / "reference" / "gpt2.py")
    job = Job(config, traffic, ref, 2**31 + 13)
    job.first_steps()
    job.window(0.5)
    compiles = []

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        text = job.step_hlo()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []
    parts = {L.step_part(v) for v in L.hlo_scopes(text).values()}
    assert {"forward", "backward", "optimizer"} <= parts
