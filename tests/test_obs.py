"""Telemetry subsystem: timeline tracing, metrics registry, load harness.

The acceptance gate for the observability PR:

* `TimelineTracer` conforms to the pinned Instrument event order (any
  out-of-order event raises `TimelineError`), and its trace-slice cycle
  sums equal `CycleCounter` totals **exactly** — on serial schedules AND
  on the overlapped placement of a pipelined two-layer serve-batch
  Program, whose makespan must equal `PipelineReport.overlapped_cycles`;
* the Chrome trace export is structurally valid (complete/instant/
  metadata events, both placements, per-stage slices summing to the
  serial total);
* `MetricsRegistry` snapshots are deterministic and the `Machine` /
  `ServeEngine` / `LegionServeBackend` wiring records the documented
  metric names;
* the fleet load harness replays Poisson/bursty arrival traces through a
  live engine with correct TTFT/per-token bookkeeping and bounded-queue
  admission control;
* `benchmarks/compare.py` flags direction-aware regressions between
  trajectory artifacts and exits nonzero.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import dlegion
from repro.core.workloads import (
    ATTN_SCORE,
    HEAD_PER_UNIT,
    N_PARTITION,
    QKV_PROJ,
    GEMMWorkload,
)
from repro.legion import Machine, PipelinedExecutor
from repro.models import build_model
from repro.obs import (
    SLO,
    MetricsRegistry,
    TimelineError,
    TimelineTracer,
    bursty_trace,
    lognormal_trace,
    poisson_trace,
    run_load,
)
from repro.obs.loadgen import RequestRecord
from repro.serve import LegionServeBackend, PagedKVCache, ServeEngine
from repro.serve.engine import prepare_params

CFG = dlegion()                 # 8 Legions x 8 cores x 16x16
CFG1 = dlegion(legions=1)


def _w2():
    return GEMMWorkload(stage=QKV_PROJ, m=32, k=256, n=128, weight_bits=2,
                        count=8, shared_input=True, mapping=HEAD_PER_UNIT)


def _w8():
    return GEMMWorkload(stage=ATTN_SCORE, m=32, k=128, n=128, weight_bits=8,
                        count=4, kv_group=2, mapping=N_PARTITION)


@pytest.fixture(scope="module")
def served():
    cfg = reduced(get_config("bitnet-1.58b"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    return cfg, api, params


# --------------------------------------------------------------------------- #
# TimelineTracer: slice sums == counter totals, exactly (serial)
# --------------------------------------------------------------------------- #

def test_serial_schedule_matches_counter_exactly():
    tracer = TimelineTracer(CFG)
    rep = Machine(CFG, instruments=[tracer]).run(_w2())
    tl = tracer.programs[-1]
    assert tl.complete
    # the tracer's internal counter saw the identical assignment stream
    assert tracer.serial_cycles() == rep.cycles.total_cycles
    assert tl.stage_cycles() == rep.cycles.stage_cycles()
    ser = tl.serial_schedule()
    assert ser.makespan == rep.cycles.total_cycles
    # per-stage span lengths equal the counter's per-stage cycles
    for stage, (lo, hi) in ser.stage_spans.items():
        assert hi - lo == rep.cycles.stage_cycles()[stage]
    # every round occupies its critical (max-over-Legions) path: slices of
    # one (stage, round) share a start, ends never exceed the round cursor
    by_round = {}
    for sl in ser.slices:
        by_round.setdefault((sl.stage, sl.round_), []).append(sl)
    for slices in by_round.values():
        assert len({sl.start for sl in slices}) == 1
    crit_sum = sum(max(sl.duration for sl in slices)
                   for slices in by_round.values())
    assert crit_sum == ser.makespan


def test_tracer_accumulates_across_programs():
    tracer = TimelineTracer(CFG)
    machine = Machine(CFG, instruments=[tracer])
    a = machine.run(_w8())
    b = machine.run(_w2())
    assert len(tracer.programs) == 2
    assert tracer.serial_cycles(0) == a.cycles.total_cycles
    assert tracer.serial_cycles(1) == b.cycles.total_cycles
    assert tracer.total_cycles() == \
        a.cycles.total_cycles + b.cycles.total_cycles
    assert tracer.total_cycles(0) == a.cycles.total_cycles


def test_cells_record_passes_skips_and_bytes():
    """The tiny-plan geometry from the Instrument conformance spec: 1
    Legion, 2 K-windows, one N-tile — dense vs ZTB cell contents."""
    from repro.core.scheduler import plan_stage
    from repro.legion import synthesize_operands

    w = GEMMWorkload(stage=QKV_PROJ, m=4, k=256, n=16, weight_bits=8,
                     count=1, shared_input=True, mapping=HEAD_PER_UNIT)
    plan = plan_stage(CFG1, w)
    x = np.ones((4, 256), dtype=np.int8)
    weights = np.ones((1, 256, 16), dtype=np.int8)
    wbytes, abytes, psum = 128 * 16 * 1.0, 4 * 128 * 1.0, 16 * 4 * 4.0

    tracer = TimelineTracer(CFG1)
    Machine(CFG1, instruments=[tracer]).run(plan, x, weights)
    cell = tracer.programs[-1].cells[(QKV_PROJ, 0, 0)]
    assert (cell.passes, cell.skips) == (2, 0)
    assert cell.weight_bytes == 2 * wbytes
    assert cell.act_bytes == 2 * abytes
    assert cell.psum_bytes == psum + 2.0 * psum   # write-only then RMW
    assert tracer.executed_passes() == 2 and tracer.skipped_passes() == 0

    ztb_weights = weights.copy()
    ztb_weights[:, :128, :] = 0                   # window 0 fully sparse
    tracer = TimelineTracer(CFG1)
    Machine(CFG1, instruments=[tracer]).run(plan, x, ztb_weights, ztb=True)
    tl = tracer.programs[-1]
    cell = tl.cells[(QKV_PROJ, 0, 0)]
    assert (cell.passes, cell.skips) == (1, 1)
    assert cell.weight_bytes == wbytes
    assert len(tl.skip_events) == 1
    assert tl.skip_events[0].k_tile == 0
    assert tracer.skipped_passes() == 1
    del synthesize_operands


def test_conformance_rejects_out_of_order_events():
    tracer = TimelineTracer(CFG1)
    # everything outside a program is an error
    with pytest.raises(TimelineError, match="outside a program"):
        tracer.on_weight_fetch(("w",), 1.0)
    with pytest.raises(TimelineError, match="outside a program"):
        tracer.on_pass(stage="s", round_=0, legion=0, instance=0, k_tile=0,
                       n_lo=0, n_hi=8)

    class P:
        names = ("s",)
    tracer.on_program_begin(P())
    tracer.on_stage_begin(stage="s", index=0, deps=())
    # act stream before its weight fetch
    with pytest.raises(TimelineError, match="weight"):
        tracer.on_act_stream(("a",), 1.0)
    # fetch -> psum without the act stream
    tracer.on_weight_fetch(("w",), 1.0)
    with pytest.raises(TimelineError, match="fetch \\+ stream"):
        tracer.on_psum(1.0)
    # a second fetch while the pass is half-built
    with pytest.raises(TimelineError, match="not closed"):
        tracer.on_weight_fetch(("w",), 1.0)
    # pass without psum
    tracer.on_act_stream(("a",), 1.0)
    with pytest.raises(TimelineError, match="expected fetch"):
        tracer.on_pass(stage="s", round_=0, legion=0, instance=0, k_tile=0,
                       n_lo=0, n_hi=8)
    # skip / assignment end / program end with a pending half-pass
    with pytest.raises(TimelineError, match="pending"):
        tracer.on_window_skip(stage="s", round_=0, legion=0, instance=0,
                              k_tile=1, n_lo=0, n_hi=8)
    with pytest.raises(TimelineError, match="pending"):
        tracer.on_assignment_end(stage="s", round_=0, legion=0, instance=0,
                                 m=4, passes=1, skipped=0, weight_bytes=1.0)
    with pytest.raises(TimelineError, match="pending"):
        tracer.on_program_end(("s",))
    # stage indices must arrive in topological order
    tracer2 = TimelineTracer(CFG1)
    tracer2.on_program_begin(P())
    with pytest.raises(TimelineError, match="topological"):
        tracer2.on_stage_begin(stage="s", index=3, deps=())


def test_conformance_passes_on_real_streams():
    """A full Machine run (dense AND ZTB) never trips the checker."""
    tracer = TimelineTracer(CFG)
    machine = Machine(CFG, instruments=[tracer])
    machine.run(_w2())
    machine.run(_w2(), ztb_sparsity=0.5)
    assert all(p.complete for p in tracer.programs)
    assert tracer.skipped_passes() > 0


# --------------------------------------------------------------------------- #
# Overlapped placement == compute_pipeline, exactly (the tentpole gate)
# --------------------------------------------------------------------------- #

def test_two_layer_serve_program_trace_parity(served):
    """Pipelined two-layer serve-batch Program: tracer serial/overlapped
    makespans equal the run's PipelineReport at 0% error, and the Chrome
    export's slices reproduce both totals."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(ACCEL := dlegion(), cfg, params)
    backend.attach(eng)
    prog = backend.step_program(decode_contexts=(8, 12), explicit_layers=2)

    tracer = TimelineTracer(ACCEL)
    machine = Machine(ACCEL, backend=PipelinedExecutor(),
                      instruments=[tracer])
    rep = machine.run(prog, validate=False)
    assert rep.pipeline is not None and rep.pipeline.ok
    tl = tracer.programs[-1]

    # exact parity, serial and overlapped
    assert tracer.serial_cycles() == rep.pipeline.serial_cycles
    assert tracer.serial_cycles() == rep.serial_cycles
    assert tracer.overlapped_cycles() == rep.pipeline.overlapped_cycles
    assert tracer.overlapped_cycles() == rep.total_cycles
    assert rep.pipeline.overlapped_cycles < rep.pipeline.serial_cycles

    ser, ov = tl.serial_schedule(), tl.overlapped_schedule()
    # same slices, shifted: identical (stage, round, legion, duration) sets
    key = lambda sl: (sl.stage, sl.round_, sl.legion, sl.duration)
    assert sorted(map(key, ser.slices)) == sorted(map(key, ov.slices))
    assert ov.makespan == ser.makespan - rep.pipeline.hidden_cycles
    assert max(sl.end for sl in ov.slices) == ov.makespan
    # per-stage serial spans equal each stage report's critical-path total
    for stage, stage_rep in rep.stage_reports.items():
        lo, hi = ser.stage_spans[stage]
        assert hi - lo == stage_rep.total_cycles

    # Chrome export: stage-lane slices on the serial pid sum to the serial
    # total; the overlapped pid's last event ends at the overlapped total
    doc = tracer.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert {e["ph"] for e in events} <= {"X", "M", "i"}
    serial_stage = [e for e in events
                    if e["ph"] == "X" and e["pid"] == 0
                    and e["cat"] == "stage"]
    assert sum(e["dur"] for e in serial_stage) == rep.pipeline.serial_cycles
    ov_rounds = [e for e in events
                 if e["ph"] == "X" and e["pid"] == 1 and e["cat"] == "round"]
    assert max(e["ts"] + e["dur"] for e in ov_rounds) == \
        rep.pipeline.overlapped_cycles
    # one lane per Legion plus the stage lane, both placements named
    names = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in names} == {"process_name", "thread_name"}


def test_mixed_step_program_trace_parity(served):
    """In-flight tentpole: a merged prefill-chunk + decode Program keeps
    exact serial/overlapped tracer parity with the run's PipelineReport —
    mixed-phase steps are priced by the same machinery as pure decode."""
    cfg, api, params = served
    backend = LegionServeBackend(ACCEL := dlegion(), cfg, params)
    prog = backend.step_program([(6, 6), (4, 10)], (8, 12))

    tracer = TimelineTracer(ACCEL)
    machine = Machine(ACCEL, backend=PipelinedExecutor(),
                      instruments=[tracer])
    rep = machine.run(prog, validate=False)
    assert rep.pipeline is not None and rep.pipeline.ok

    assert tracer.serial_cycles() == rep.pipeline.serial_cycles
    assert tracer.overlapped_cycles() == rep.pipeline.overlapped_cycles
    assert rep.pipeline.overlapped_cycles < rep.pipeline.serial_cycles
    tl = tracer.programs[-1]
    ser, ov = tl.serial_schedule(), tl.overlapped_schedule()
    key = lambda sl: (sl.stage, sl.round_, sl.legion, sl.duration)
    assert sorted(map(key, ser.slices)) == sorted(map(key, ov.slices))
    assert ov.makespan == ser.makespan - rep.pipeline.hidden_cycles
    # the scheduler's skeleton twin prices the same step identically
    # (scaled to all model layers, like every engine-view number)
    serial, overlapped = backend.step_pipeline([(6, 6), (4, 10)], (8, 12))
    assert (serial, overlapped) == \
        (rep.pipeline.serial_cycles * cfg.layers,
         rep.pipeline.overlapped_cycles * cfg.layers)


def test_chain_program_prefetch_and_blocked_parity():
    """Both chain-boundary cases stay in exact tracer/report parity: a
    concrete stationary operand prefetches its fill across the dependent
    boundary; a stationary operand produced by the outgoing stage blocks
    all hiding (overlapped == serial, the degenerate case)."""
    from repro.legion import Program, ProgramStage, Ref, requantize_int8

    w1 = GEMMWorkload(stage=QKV_PROJ, m=16, k=256, n=128, weight_bits=2,
                      count=1, shared_input=True, mapping=N_PARTITION)
    w2 = GEMMWorkload(stage="out_proj", m=16, k=128, n=64, weight_bits=2,
                      count=1, shared_input=True, mapping=N_PARTITION)
    rng = np.random.default_rng(0)
    prog = Program()
    prog.add(ProgramStage(
        name="a", workload=w1,
        x=rng.integers(-8, 9, size=(16, 256)).astype(np.int8),
        w=rng.integers(-1, 2, size=(1, 256, 128)).astype(np.int8)))
    prog.add(ProgramStage(
        name="b", workload=w2, x=Ref("a", transform=requantize_int8),
        w=rng.integers(-1, 2, size=(1, 128, 64)).astype(np.int8)))

    tracer = TimelineTracer(CFG)
    rep = Machine(CFG, backend=PipelinedExecutor(),
                  instruments=[tracer]).run(prog, validate=False)
    # b's weights exist before a's output does: its fill prefetches
    assert rep.pipeline.hidden_cycles > 0
    assert tracer.overlapped_cycles() == rep.pipeline.overlapped_cycles
    assert tracer.serial_cycles() == rep.pipeline.serial_cycles
    tl = tracer.programs[-1]
    assert tl.overlapped_schedule().makespan == \
        tl.serial_schedule().makespan - rep.pipeline.hidden_cycles

    # blocked variant: b's stationary operand IS a's output — nothing to
    # prefetch, both placements agree exactly
    w2b = GEMMWorkload(stage="attn_score", m=16, k=128, n=16, weight_bits=8,
                       count=1, shared_input=True, mapping=N_PARTITION)
    prog2 = Program()
    prog2.add(ProgramStage(
        name="a", workload=w1,
        x=rng.integers(-8, 9, size=(16, 256)).astype(np.int8),
        w=rng.integers(-1, 2, size=(1, 256, 128)).astype(np.int8)))
    prog2.add(ProgramStage(
        name="b", workload=w2b, x=Ref("a", transform=requantize_int8),
        w=Ref("a", transform=lambda o: requantize_int8(o)
              .transpose(0, 2, 1))))
    tracer2 = TimelineTracer(CFG)
    rep2 = Machine(CFG, backend=PipelinedExecutor(),
                   instruments=[tracer2]).run(prog2, validate=False)
    assert rep2.pipeline.hidden_cycles == 0
    assert tracer2.overlapped_cycles() == tracer2.serial_cycles()


def test_export_round_trips(tmp_path):
    tracer = TimelineTracer(CFG)
    Machine(CFG, instruments=[tracer]).run(_w8())
    path = tmp_path / "trace.json"
    doc = tracer.export(path)
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(doc))
    assert loaded["otherData"]["accelerator"] == CFG.name


# --------------------------------------------------------------------------- #
# MetricsRegistry
# --------------------------------------------------------------------------- #

def test_metrics_registry_basics():
    reg = MetricsRegistry()
    reg.counter("events").inc()
    reg.counter("events").inc(2)
    assert reg.counter("events").value() == 3
    with pytest.raises(ValueError, match="decrease"):
        reg.counter("events").inc(-1)
    reg.gauge("occupancy").set(0.5)
    assert reg.gauge("occupancy").value() == 0.5
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count() == 4
    assert h.percentile(50) == pytest.approx(2.5)
    # kind / label-set collisions are hard errors
    with pytest.raises(ValueError, match="registered as counter"):
        reg.gauge("events")
    reg.counter("by_stage", labels=("stage",)).inc(stage="qkv")
    with pytest.raises(ValueError, match="labels"):
        reg.counter("by_stage").inc()
    with pytest.raises(ValueError, match="labels"):
        reg.counter("by_stage", labels=("stage",)).inc(legion=3)
    assert "events" in reg and "nope" not in reg


def test_metrics_snapshot_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.counter("z_last").inc(5)
        reg.histogram("lat").observe(2.0)
        reg.histogram("lat").observe(1.0)
        reg.counter("a_first", labels=("s",)).inc(s="b")
        reg.counter("a_first", labels=("s",)).inc(s="a")
        return reg.snapshot()

    s1, s2 = build(), build()
    assert json.dumps(s1, sort_keys=False) == json.dumps(s2, sort_keys=False)
    assert list(s1) == sorted(s1)                       # metric names sorted
    assert list(s1["a_first"]["series"]) == ["s=a", "s=b"]
    lat = s1["lat"]["series"][""]
    assert lat["count"] == 2 and lat["p50"] == pytest.approx(1.5)
    assert lat["min"] == 1.0 and lat["max"] == 2.0


def test_machine_metrics_wiring():
    reg = MetricsRegistry()
    machine = Machine(CFG, metrics=reg)
    machine.run(_w2())
    machine.run(_w2(), ztb_sparsity=0.5)
    assert reg.counter("machine_stage_runs", labels=("stage",)) \
        .value(stage=QKV_PROJ) == 2
    assert reg.counter("machine_cycles").value() > 0
    assert reg.counter("machine_passes").value() > 0
    assert reg.counter("machine_skipped_passes").value() > 0
    assert reg.counter("machine_weight_bytes").value() > 0
    snap = reg.snapshot()
    assert snap["machine_stage_runs"]["series"][f"stage={QKV_PROJ}"] == 2


def test_serve_engine_step_log_and_metrics(served):
    """Satellite: occupancy history covers prefill AND decode steps."""
    cfg, api, params = served
    reg = MetricsRegistry()
    eng = ServeEngine(api, params, max_slots=2, max_seq=64, metrics=reg)
    for plen in (4, 8, 4):
        eng.submit(np.arange(1, plen + 1), max_new_tokens=2)
    eng.run_until_done()
    prefills = [e for e in eng.step_log if e["phase"] == "prefill"]
    decodes = [e for e in eng.step_log if e["phase"] == "decode"]
    assert len(prefills) == 3
    assert [e["tokens"] for e in decodes] == eng.decode_batch_sizes
    # prefill entries record the admitted request and post-admission slots
    assert {e["uid"] for e in prefills} == {0, 1, 2}
    assert all(1 <= e["slots"] <= 2 for e in eng.step_log)
    assert reg.counter("serve_prefill_steps").value() == 3
    assert reg.counter("serve_decode_steps").value() == len(decodes)
    assert reg.histogram("serve_batch_size").count() == len(decodes)
    assert reg.histogram("serve_prompt_tokens").observations() \
        == [4.0, 8.0, 4.0]
    assert 0 < reg.gauge("serve_slot_occupancy").value() <= 1.0


def test_serve_backend_metrics(served):
    cfg, api, params = served
    reg = MetricsRegistry()
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(dlegion(), cfg, params, metrics=reg)
    backend.attach(eng)
    eng.submit(np.arange(1, 5), max_new_tokens=2)
    eng.submit(np.arange(1, 9), max_new_tokens=3)
    eng.run_until_done()
    assert reg.counter("serve_backend_prefill_cycles").value() > 0
    serial = reg.counter("serve_backend_serial_cycles").value()
    overlapped = reg.counter("serve_backend_overlapped_cycles").value()
    assert 0 < overlapped <= serial
    for x in reg.histogram("serve_step_overlap_x").observations():
        assert x >= 1.0
    assert reg.gauge("serve_cycles_per_decode_token").value() > 0
    budget = backend.cache_budget(batch=2, max_seq=64,
                                  hbm_bytes_per_chip=8 << 30, chips=1)
    assert 0 < reg.gauge("kv_cache_utilization").value() < 1
    assert reg.gauge("kv_pipelining_speedup").value() >= 1.0
    assert budget is not None


# --------------------------------------------------------------------------- #
# Load harness
# --------------------------------------------------------------------------- #

def test_trace_generators_deterministic():
    a = poisson_trace(20, mean_interarrival_cycles=100.0, seed=3)
    b = poisson_trace(20, mean_interarrival_cycles=100.0, seed=3)
    assert a == b
    assert a != poisson_trace(20, mean_interarrival_cycles=100.0, seed=4)
    assert all(x.time <= y.time for x, y in zip(a, a[1:]))
    burst = bursty_trace(9, burst_size=3, burst_gap_cycles=50.0)
    assert [x.time for x in burst] == [0.0] * 3 + [50.0] * 3 + [100.0] * 3
    with pytest.raises(ValueError):
        poisson_trace(0, mean_interarrival_cycles=1.0)
    with pytest.raises(ValueError):
        bursty_trace(4, burst_size=0, burst_gap_cycles=1.0)


def test_run_load_poisson(served):
    cfg, api, params = served
    reg = MetricsRegistry()
    eng = ServeEngine(api, params, max_slots=4, max_seq=64, metrics=reg)
    backend = LegionServeBackend(dlegion(), cfg, params)
    backend.attach(eng)
    trace = poisson_trace(12, mean_interarrival_cycles=5000.0, seed=1)
    report = run_load(eng, backend, trace, metrics=reg)
    s = report.summary()
    assert s["requests"] == s["completed"] == 12
    assert s["rejected"] == 0
    assert 0 < s["p50_ttft_cycles"] <= s["p99_ttft_cycles"]
    assert 0 < s["p50_tok_cycles"] <= s["p99_tok_cycles"]
    assert 0 < s["mean_occupancy"] <= 4
    # every record's clock ordering is sane
    for rec in report.completed():
        assert rec.arrival < rec.first_token <= rec.finish
        assert rec.decode_tokens >= 1
    # occupancy covers prefill admissions, not just decode steps
    assert sum(1 for e in report.occupancy if e["phase"] == "prefill") == 12
    assert reg.histogram("load_ttft_cycles").count() == 12
    assert reg.counter("load_requests").value() == 12
    # physical units ride along when a clock frequency is given
    hz = s["makespan_cycles"]  # 1 Hz-equivalent: makespan == 1 s
    s2 = report.summary(freq_hz=hz)
    assert s2["tokens_per_sec"] == pytest.approx(s["decode_tokens"])
    assert s2["p99_ttft_us"] == pytest.approx(
        s["p99_ttft_cycles"] / hz * 1e6)


def test_run_load_bounded_queue_rejects(served):
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=1, max_seq=64)
    backend = LegionServeBackend(dlegion(), cfg, params)
    backend.attach(eng)
    trace = bursty_trace(10, burst_size=10, burst_gap_cycles=1.0, seed=2)
    report = run_load(eng, backend, trace, max_queue=2)
    s = report.summary()
    assert s["rejected"] > 0 and s["deferred"] > 0
    assert s["completed"] == 10 - s["rejected"]
    for rec in report.records:
        if rec.rejected:
            assert rec.uid is None and rec.finish is None
            assert rec.ttft is None and rec.cycles_per_token is None
    # rejected requests never reached the engine
    assert len(eng.finished) == s["completed"]


def test_run_load_inflight_engine(served):
    """The load harness prices in-flight engines off merged ``step``
    events (one overlapped clock advance per engine step, TTFT at the
    prompt-completing chunk) and drains every request."""
    cfg, api, params = served
    reg = MetricsRegistry()
    eng = ServeEngine(api, params, max_slots=4, max_seq=64,
                      prefill_chunk_tokens=8)
    backend = LegionServeBackend(dlegion(), cfg, params)
    backend.attach(eng)
    trace = poisson_trace(12, mean_interarrival_cycles=5000.0, seed=1)
    report = run_load(eng, backend, trace, metrics=reg)
    s = report.summary()
    assert s["requests"] == s["completed"] == 12
    assert s["truncated"] == s["refused"] == 0
    assert s["goodput"] == 12
    assert 0 < s["p50_ttft_cycles"] <= s["p99_ttft_cycles"]
    for rec in report.completed():
        assert rec.arrival < rec.first_token <= rec.finish
    # every clock advance is a merged step, no legacy events
    assert all(e["phase"] == "step" for e in report.occupancy)
    assert reg.histogram("load_ttft_cycles").count() == 12
    assert reg.histogram("load_step_cycles").count() == len(report.occupancy)


def test_run_load_reports_truncations(served):
    """Window-truncated completions surface in the summary (and are
    excluded from goodput) — distinguishable from natural finishes."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=16)
    backend = LegionServeBackend(dlegion(), cfg, params)
    backend.attach(eng)
    trace = poisson_trace(6, mean_interarrival_cycles=2000.0, seed=2,
                          prompt_lens=(12,), output_lens=(8,))
    report = run_load(eng, backend, trace)
    s = report.summary()
    assert s["completed"] == 6
    assert s["truncated"] == 6            # 12 + 8 never fits max_seq=16
    assert s["goodput"] == 0
    for rec in report.completed():
        assert rec.truncated and not rec.refused


def test_lognormal_trace_deterministic_and_quantized():
    a = lognormal_trace(30, mean_interarrival_cycles=100.0, seed=5)
    assert a == lognormal_trace(30, mean_interarrival_cycles=100.0, seed=5)
    assert a != lognormal_trace(30, mean_interarrival_cycles=100.0, seed=6)
    assert all(x.time <= y.time for x, y in zip(a, a[1:]))
    # prompt lengths are quantum-rounded and window-clamped; outputs >= 2
    for r in a:
        assert r.prompt_len % 4 == 0 and 4 <= r.prompt_len <= 16
        assert 2 <= r.max_new_tokens <= 6
    # heavier tail dispersion than poisson at the same mean rate: the
    # mean-preserving mu keeps total load comparable across generators
    gaps = [y.time - x.time for x, y in zip(a, a[1:])]
    assert max(gaps) > np.mean(gaps)
    with pytest.raises(ValueError):
        lognormal_trace(0, mean_interarrival_cycles=1.0)
    with pytest.raises(ValueError):
        lognormal_trace(4, mean_interarrival_cycles=0.0)
    with pytest.raises(ValueError):
        lognormal_trace(4, mean_interarrival_cycles=1.0, sigma=0.0)
    with pytest.raises(ValueError):
        lognormal_trace(4, mean_interarrival_cycles=1.0, quantum=0)
    with pytest.raises(ValueError):
        lognormal_trace(4, mean_interarrival_cycles=1.0,
                        max_prompt=2, quantum=4)


def test_slo_validation_and_met():
    with pytest.raises(ValueError):
        SLO(ttft_cycles=0.0)
    with pytest.raises(ValueError):
        SLO(per_token_cycles=-1.0)
    rec = RequestRecord(uid=1, arrival=0.0, prompt_len=4, max_new_tokens=4,
                        first_token=10.0, finish=30.0, decode_tokens=4)
    assert SLO().met(rec)
    assert SLO(ttft_cycles=10.0).met(rec)
    assert not SLO(ttft_cycles=9.0).met(rec)
    assert SLO(per_token_cycles=5.0).met(rec)
    assert not SLO(per_token_cycles=4.9).met(rec)
    assert not SLO(ttft_cycles=100.0).met(
        RequestRecord(uid=2, arrival=0.0, prompt_len=4, max_new_tokens=4))
    # no decode tokens -> no per-token latency to violate
    boundary = RequestRecord(uid=3, arrival=0.0, prompt_len=4,
                             max_new_tokens=4, first_token=5.0, finish=5.0)
    assert SLO(per_token_cycles=0.1).met(boundary)


def test_run_load_paged_preemption(served):
    """A page pool sized to exactly one max-length window forces
    evictions under a dense heavy-tailed trace — every preempted request
    still completes (re-prefill), counters agree across the serve and
    load layers, and the SLO knob grades the same records."""
    cfg, api, params = served
    reg = MetricsRegistry()
    paged = PagedKVCache(total_pages=8, page_tokens=8)
    eng = ServeEngine(api, params, max_slots=4, max_seq=64,
                      paged_kv=paged, metrics=reg)
    backend = LegionServeBackend(dlegion(), cfg, params, page_tokens=8)
    backend.attach(eng)
    trace = lognormal_trace(14, mean_interarrival_cycles=200.0, seed=3)
    report = run_load(eng, backend, trace, metrics=reg)
    s = report.summary()
    assert s["requests"] == s["completed"] == 14
    assert s["preempted"] > 0 and s["truncated"] == 0
    assert s["preempted"] == sum(r.preempted for r in report.records)
    # TTFT pins the FIRST prefill: re-prefill never resets it
    for rec in report.completed():
        assert rec.arrival < rec.first_token <= rec.finish
    # serve-layer and load-layer counters describe the same evictions
    assert reg.counter("serve_preempted_total").value() == s["preempted"]
    assert reg.counter("load_preempted").value() == s["preempted"]
    assert paged.allocator.stats().evictions == s["preempted"]
    assert paged.allocator.pinned_pages == 0    # all freed at drain
    # an impossible SLO zeroes goodput over the very same records
    tight = run_load_summary_with_slo(report, SLO(ttft_cycles=1.0))
    assert tight["goodput"] == 0 and tight["completed"] == 14


def run_load_summary_with_slo(report, slo):
    """Re-grade an existing report under a different SLO."""
    import dataclasses
    return dataclasses.replace(report, slo=slo).summary()


# --------------------------------------------------------------------------- #
# benchmarks: compare.py + diff-friendly artifacts
# --------------------------------------------------------------------------- #

def _write_artifact(dirpath, module, rows):
    from benchmarks.run import write_json
    write_json(str(dirpath), module, True, None, rows)


def test_compare_flags_direction_aware_regressions(tmp_path):
    from benchmarks.compare import compare_dirs, main

    old = tmp_path / "old"
    new = tmp_path / "new"
    row = {"name": "m/a", "us_per_call": 10.0,
           "derived": {"overlap_x": 1.5, "p99_ttft_kcycles": 10.0,
                       "total_cycles": 100, "xval_err": 0.01,
                       "requests": 200}}
    _write_artifact(old, "m", [row])
    worse = {"name": "m/a", "us_per_call": 99.0,   # ungated: never flagged
             "derived": {"overlap_x": 1.2, "p99_ttft_kcycles": 14.0,
                         "total_cycles": 100, "xval_err": 0.01,
                         "requests": 200}}
    _write_artifact(new, "m", [worse])
    deltas, notes = compare_dirs(str(old), str(new))
    regressed = {d.key for d in deltas if d.regressed}
    assert regressed == {"overlap_x", "p99_ttft_kcycles"}
    assert main([str(old), str(new)]) == 1
    # widened tolerance lets the same drift through
    assert main([str(old), str(new), "--rtol", "0.5"]) == 0
    # improvements are reported but never fail
    deltas, _ = compare_dirs(str(new), str(old))
    assert deltas and not any(d.regressed for d in deltas)
    assert main([str(new), str(old)]) == 0


def test_compare_handles_missing_rows_and_modules(tmp_path):
    from benchmarks.compare import compare_dirs, direction, main

    old = tmp_path / "old"
    new = tmp_path / "new"
    _write_artifact(old, "gone", [{"name": "gone/x", "us_per_call": 1.0,
                                   "derived": {"total_cycles": 5}}])
    _write_artifact(old, "keep", [{"name": "keep/x", "us_per_call": 1.0,
                                   "derived": {"total_cycles": 5}}])
    _write_artifact(new, "keep", [{"name": "keep/x", "us_per_call": 1.0,
                                   "derived": {"total_cycles": 5}},
                                  {"name": "keep/y", "us_per_call": 1.0,
                                   "derived": {"total_cycles": 7}}])
    _write_artifact(new, "fresh", [{"name": "fresh/x", "us_per_call": 1.0,
                                    "derived": {"speedup": 2.0}}])
    deltas, notes = compare_dirs(str(old), str(new))
    assert not deltas                       # notes, never failures
    assert any("missing from new run" in n for n in notes)
    assert any("new module" in n for n in notes)
    assert any("new row" in n for n in notes)
    assert main([str(old), str(new)]) == 0
    # empty dirs are a hard usage error (CI skips the step instead)
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError):
        compare_dirs(str(empty), str(new))
    # the direction heuristic the gates rely on
    assert direction("overlap_x") == 1
    assert direction("pipeline_speedup") == 1
    assert direction("tokens_per_sec") == 1
    assert direction("p99_ttft_kcycles") == -1
    assert direction("total_cycles") == -1
    assert direction("weight_mb") == -1
    assert direction("xval_err") == -1
    assert direction("requests") == 0


def test_bench_artifacts_are_diff_friendly(tmp_path):
    """write_json output is byte-stable: sorted keys, 6-sig-digit floats."""
    from benchmarks.common import emit
    from benchmarks.run import write_json

    row = emit("m/x", 123.456789, {"ratio": 1.234567891234,
                                   "count": 3, "flag": True})
    assert row["derived"]["ratio"] == 1.23457       # 6 significant digits
    assert row["derived"]["count"] == 3
    assert row["derived"]["flag"] is True
    p1 = write_json(str(tmp_path / "a"), "m", True, None, [row])
    p2 = write_json(str(tmp_path / "b"), "m", True, None,
                    [{"name": "m/x", "us_per_call": row["us_per_call"],
                      "derived": dict(reversed(list(
                          row["derived"].items())))}])
    with open(p1) as f1, open(p2) as f2:
        assert f1.read() == f2.read()               # key order irrelevant
