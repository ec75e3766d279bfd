"""The program's own spans (``serve.*``, ``legion.*``) on the trace's clock:
their reduction on a synthetic trace whose answers are worked out by hand,
every reader that was there before reading the same with them, the five
readers that need them, and traces recorded here of the engine, the Legion
executor and a whole traced run."""
import time

import numpy as np
import pytest

from bench import harness, program_trace, serving
from bench import trace_reduce as tr
from tiny_cells import off_chip, tiny

E = tr.Event
PEAK = harness.peak_of("TPU v5 lite")
BENCH_SPANS = ("bench.window", "bench.step", "bench.pass")
NEW = ("decode_program_ms", "engine_idle_ms", "legion_check_share",
       "legion_execute_share", "legion_tile_host_us")
BEFORE = ("decode_step_ms", "decode_roofline", "decode_batch_mean",
          "serve_mfu", "device_idle_share", "bitlinear_roofline",
          "legion_mfu")


def synthetic(program: bool = True) -> program_trace.Trace:
    """Window 0..200.  An engine step 10..60 holds the engine's phases, the
    sample 27..50 its sync 30..50; a pass 80..180 holds one stage 80..170
    (plan, prepare, execute 90..130 with two tiles, each ending in a sync;
    check 130..160, validate 160..165) and the pipeline 170..175.  Device:
    the decode program's operations 22..40 (its module 22..41), an argmax
    45..49, two kernels 97..99 and 112..114.  ``program=False`` keeps the
    benchmark's spans alone, and the decode program unnamed."""
    device = [E("fusion.1", 22, 40), E("argmax", 45, 49),
              E("bitlinear_matmul.1", 97, 99),
              E("bitlinear_matmul.1", 112, 114)]
    modules = [E("jit_serve_decode(7)", 22, 41), E("jit_argmax(3)", 45, 49)]
    spans = [E("bench.window", 0, 200), E("bench.step", 10, 60),
             E("serve.admit", 10, 14), E("serve.chunks", 14, 20),
             E("serve.prefill_chunk", 15, 19), E("serve.ensure_kv", 20, 21),
             E("serve.decode", 21, 25), E("serve.observers", 25, 27),
             E("serve.sample", 27, 50), E("serve.sync", 30, 50),
             E("serve.finish", 50, 55),
             E("bench.pass", 80, 180), E("legion.stage", 80, 170),
             E("legion.plan", 80, 85), E("legion.prepare", 85, 90),
             E("legion.execute", 90, 130),
             E("legion.tile", 95, 105), E("legion.sync", 100, 105),
             E("legion.tile", 110, 120), E("legion.sync", 115, 120),
             E("legion.check", 130, 160), E("legion.validate", 160, 165),
             E("legion.pipeline", 170, 175)]
    if not program:     # as the program before its spans: anonymous jits
        spans = [s for s in spans if s.name in BENCH_SPANS]
        modules = [E("jit__lambda(7)", 22, 41), E("jit_argmax(3)", 45, 49)]
    return program_trace.Trace({"/device:TPU:0": device}, spans,
                               {"/device:TPU:0": modules})


def observed() -> dict:
    """What the drivers hand the readers, for one decode step of two slots
    and one pass of two tile calls."""
    config = harness.load_json(
        harness.BENCH / "configs" / "bitnet-1.58b-kv.json")
    step = {"t0": 0, "t1": 1, "chunks": [], "positions": [5, 9]}
    return {"steps": [step], "traced_steps": [step],
            "model": serving.model_sizes(config),
            "tile_calls": [(128, 256, 64, 2)] * 2,
            "passes": [{"t0": 0.0, "t1": 1e-7}], "program_ops": 1e6}


def view(reduced) -> harness.View:
    return harness.View(observed(), reduced, PEAK)


def read(name: str, reduced):
    return harness.load_module(harness.metric_file(name)).read(view(reduced))


def test_idle_named_by_innermost_span():
    r = program_trace.Reduced(synthetic(), chips=1)
    # busy 22..40, 45..49, 97..99, 112..114; gaps 0..22 (mid 11: the
    # admit, inside the step), 40..45 (42.5: the sync, inside the sample),
    # 49..97 (73: no span), 99..112 (105.5: execute, between the tiles),
    # 114..200 (157: the check)
    assert r.idle_by_span() == {
        "serve.admit": pytest.approx(22e-9),
        "serve.sync": pytest.approx(5e-9), "host": pytest.approx(48e-9),
        "legion.execute": pytest.approx(13e-9),
        "legion.check": pytest.approx(86e-9)}
    assert program_trace.Reduced(synthetic(program=False), 1).idle_by_span() \
        == tr.Reduced(synthetic(program=False), 1).idle_by_span() == {
            "bench.step": pytest.approx(27e-9), "host": pytest.approx(48e-9),
            "bench.pass": pytest.approx(99e-9)}


def test_self_and_module_seconds():
    r = program_trace.Reduced(synthetic(), chips=1)
    assert r.self_seconds("bench.step") == pytest.approx(5e-9)   # 55..60
    assert r.self_seconds("serve.sample") == pytest.approx(3e-9)  # 27..30
    assert r.self_seconds("serve.chunks") == pytest.approx(2e-9)
    assert r.self_seconds("legion.execute") == pytest.approx(20e-9)
    assert r.self_seconds("legion.tile") == pytest.approx(10e-9)  # 2 x 5
    assert r.self_seconds("legion.stage") == pytest.approx(5e-9)
    assert r.self_seconds("bench.pass") == pytest.approx(5e-9)
    assert r.span_seconds("legion.tile") == pytest.approx(20e-9)
    assert r.module_seconds("serve_decode") == {
        "jit_serve_decode(7)": pytest.approx(19e-9)}
    assert r.module_seconds() == {"jit_serve_decode(7)": pytest.approx(19e-9),
                                  "jit_argmax(3)": pytest.approx(4e-9)}
    assert len(r.module_runs("serve_decode")) == 1
    assert r.busy_in_each(r.spans_named("legion.tile")) == pytest.approx(
        [2e-9, 2e-9])


def test_new_readers_on_known_values():
    r = program_trace.Reduced(synthetic(), chips=1)
    # busy 18 ns of the module's 19
    assert read("decode_program_ms", r) == pytest.approx(18e-6)
    # admit 4, chunks 6, ensure_kv 1, decode 4, finish 5 ns in one step
    assert read("engine_idle_ms", r) == pytest.approx(20e-6)
    assert read("legion_check_share", r) == pytest.approx(35.0)
    assert read("legion_execute_share", r) == pytest.approx(40.0)
    # each tile 10 ns long, 2 ns of it busy
    assert read("legion_tile_host_us", r) == pytest.approx(8e-3)


def test_engine_idle_read_in_decode_only_steps():
    """A step that also carried a prompt chunk keeps the device busy with
    it, so it is left out, as ``decode_step_ms`` leaves it out."""
    v = view(program_trace.Reduced(synthetic(), chips=1))
    v.observed["traced_steps"][0]["chunks"] = [(64, 0, False)]
    reader = harness.load_module(harness.metric_file("engine_idle_ms"))
    assert reader.read(v) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_program_spans(name):
    """A program that opens none of these spans reads nothing (None), and
    does not raise, under the harness's own reduction too."""
    assert read(name, program_trace.Reduced(synthetic(False), 1)) is None
    assert read(name, tr.Reduced(synthetic(False), 1)) is None


@pytest.mark.parametrize("name", BEFORE)
def test_earlier_readers_read_the_same_with_program_spans(name):
    before = read(name, tr.Reduced(synthetic(program=False), chips=1))
    assert before is not None
    assert read(name, program_trace.Reduced(synthetic(), chips=1)) == before
    assert read(name, tr.Reduced(synthetic(), chips=1)) == before


def test_harness_readings_the_same_with_program_spans():
    a = tr.Reduced(synthetic(program=False), chips=1)
    b = program_trace.Reduced(synthetic(), chips=1)
    assert b.busy_s == a.busy_s and b.window_s == a.window_s
    assert b.spans_named("bench.step") == a.spans_named("bench.step")
    assert b.op_seconds() == a.op_seconds()
    steps = observed()["traced_steps"]
    assert tr.paired(b, "bench.step", steps) == tr.paired(a, "bench.step",
                                                          steps)
    assert b.breakdown()["device_ops"] == a.breakdown()["device_ops"]


# --------------------------------------------------------------------------- #
# Traces recorded here (the CPU: host spans, no device plane)
# --------------------------------------------------------------------------- #

ENGINE_PHASES = ("serve.admit", "serve.chunks", "serve.ensure_kv",
                 "serve.decode", "serve.observers", "serve.sample",
                 "serve.sync", "serve.finish")


def recorded(tmp_path, body) -> program_trace.Reduced:
    """``body()`` run inside a profiler trace and its ``bench.window``."""
    cell = harness.Cell("t", 1, {}, {}, [], [])
    ctx = harness.RunContext(cell, 0, 1.0, True, 0.0, None, {}, 1,
                             str(tmp_path))
    ctx.start_trace()
    try:
        body()
    finally:
        ctx.stop_trace()
    return program_trace.Reduced(
        program_trace.load(harness._trace_file(str(tmp_path))), chips=1)


def inside(child, parent) -> bool:
    return parent.start <= child.start and child.end <= parent.end


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import ServeEngine

    api = build_model(reduced(get_config("smollm-360m")))
    params = api.init(jax.random.PRNGKey(0))
    return lambda: ServeEngine(api, params, max_slots=2, max_seq=64,
                               prefill_chunk_tokens=8)


def test_engine_phase_spans(tmp_path, tiny_engine):
    import jax

    engine = tiny_engine()
    for n in (5, 11, 3):
        engine.submit(np.arange(1, n + 1), max_new_tokens=3)
    events = []
    engine.step_observers.append(events.append)
    engine.run_until_done()                 # compile every shape first
    for n in (5, 11, 3):
        engine.submit(np.arange(1, n + 1), max_new_tokens=3)
    events.clear()

    def steps():
        while engine.queue or engine._active():
            with jax.profiler.TraceAnnotation("bench.step"):
                engine.step()

    r = recorded(tmp_path, steps)
    bench_steps = r.spans_named("bench.step")
    assert len(bench_steps) == len(events) > 2
    chunk_spans = r.spans_named("serve.prefill_chunk")
    assert len(chunk_spans) == sum(len(e["chunks"]) for e in events) > 0
    for step, ev in zip(bench_steps, events):
        held = {name: [s for s in r.spans_named(name) if inside(s, step)]
                for name in ENGINE_PHASES}
        decoded = bool(ev["positions"])
        for name in ("serve.admit", "serve.chunks", "serve.ensure_kv",
                     "serve.observers"):
            assert len(held[name]) == 1, (name, ev)
        for name in ("serve.decode", "serve.sample", "serve.sync",
                     "serve.finish"):
            assert len(held[name]) == int(decoded), (name, ev)
        if decoded:
            assert inside(held["serve.sync"][0], held["serve.sample"][0])
        (chunks,) = held["serve.chunks"]
        assert sum(inside(c, chunks) for c in chunk_spans) \
            == len(ev["chunks"])


def test_decode_program_named(tiny_engine):
    import jax.numpy as jnp

    engine = tiny_engine()
    tok = jnp.zeros((engine.max_slots,), jnp.int32)
    text = engine._decode.lower(engine.params, tok, engine.cache,
                                tok).as_text()
    assert "serve_decode" in text
    chunk = engine._prefill_chunk.lower(
        engine.params, jnp.zeros((1, 8), jnp.int32),
        engine.api.init_cache(1, engine.max_seq), 0).as_text()
    assert "serve_prefill_chunk" in chunk


def two_stage_program():
    from repro.core.workloads import GEMMWorkload
    from repro.legion import Program, ProgramStage

    def wl(name):
        return GEMMWorkload(stage=name, m=8, k=256, n=64, weight_bits=2,
                            count=1)

    return Program([ProgramStage(name="a", workload=wl("a")),
                    ProgramStage(name="b", workload=wl("b"))])


@pytest.mark.parametrize("check", [True, False])
def test_legion_spans(tmp_path, check):
    from repro.core import dlegion
    from repro.legion import Machine, PipelinedExecutor

    drv = harness.load_module(harness.BENCH / "drivers" / "legion_passes.py")
    program = two_stage_program()
    machine = Machine(dlegion(), backend=PipelinedExecutor())
    counter = machine.add_instrument(drv.TileCounter())
    machine.run(program, check_outputs=check)     # compile first
    counter.on = True
    r = recorded(tmp_path, lambda: machine.run(program, check_outputs=check))
    tiles = r.spans_named("legion.tile")
    assert len(tiles) == len(counter.calls) > 0
    assert len(r.spans_named("legion.sync")) == len(tiles)
    stages = r.spans_named("legion.stage")
    assert len(stages) == 2
    for name in ("legion.plan", "legion.prepare", "legion.execute",
                 "legion.validate"):
        spans = r.spans_named(name)
        assert len(spans) == 2, name
        assert all(inside(s, st) for s, st in zip(spans, stages))
    assert len(r.spans_named("legion.check")) == (2 if check else 0)
    assert len(r.spans_named("legion.pipeline")) == 1
    executes = r.spans_named("legion.execute")
    assert all(any(inside(t, e) for e in executes) for t in tiles)


@pytest.mark.parametrize("name, present", [
    ("legion-gqa-attn", ("legion_check_share", "legion_execute_share",
                         "legion_tile_host_us")),
    ("serve-gqa-decode", ("engine_idle_ms",))])
def test_traced_run_reads_the_program_spans(monkeypatch, name, present):
    """A whole traced run of each cell, cut to CPU size: the harness reduces
    its trace, and the readers find the program's spans in it (the CPU has
    no device plane, so nothing reads ``decode_program_ms`` here)."""
    off_chip(monkeypatch)
    line = harness.run_cell(tiny(name), 11, 1.5, True, time.perf_counter())
    assert line["correct"] is True
    m = line["metrics"]
    for metric in present:
        assert m[metric]["value"] > 0, metric
    assert "decode_program_ms" not in m
    if "legion_check_share" in m:
        assert m["legion_check_share"]["value"] \
            + m["legion_execute_share"]["value"] <= 100.0
