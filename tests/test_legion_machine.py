"""Machine session API: instruments, executor backends, option validation.

The acceptance gate for the `legion.Machine` redesign:

* `Machine.run` merges outputs, traffic, cycles, and per-stage validation
  into one RunReport (no hand-threaded tracer/counter objects);
* the Instrument event stream is exact and documented — a recording stub
  asserts fetch/pass/skip ordering for a tiny plan, with and without ZTB,
  so third-party instruments have a spec to code against;
* `ShardedExecutor` (Legion axis on a JAX mesh axis) is bit-exact with
  `InProcessExecutor` across the W1.58/W4/W8 ±ZTB mode matrix and fires an
  identical measurement stream;
* nonsensical options (accumulators<=0, unknown kernel_backend) are
  rejected with clear ValueErrors at the Machine boundary.

The deprecated `execute_plan`/`execute_workload` shims were removed in
PR 6; the export-hygiene test pins that they stay gone.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import dlegion
from repro.core.scheduler import plan_stage
from repro.core.workloads import (
    ATTN_SCORE,
    HEAD_PER_UNIT,
    N_PARTITION,
    QKV_PROJ,
    GEMMWorkload,
    attention_workloads,
    bitnet_1_58b_kv,
)
from repro.legion import (
    CycleCounter,
    InProcessExecutor,
    Instrument,
    Machine,
    RunReport,
    ShardedExecutor,
    TrafficTracer,
    synthesize_operands,
)
from repro.legion.runtime import exact_int_matmul
from repro.obs.metrics import MetricsRegistry

CFG = dlegion()                 # 8 Legions x 8 cores x 16x16
CFG1 = dlegion(legions=1)


def _w2():
    return GEMMWorkload(stage=QKV_PROJ, m=32, k=256, n=128, weight_bits=2,
                        count=8, shared_input=True, mapping=HEAD_PER_UNIT)


def _w8():
    return GEMMWorkload(stage=ATTN_SCORE, m=32, k=128, n=128, weight_bits=8,
                        count=4, kv_group=2, mapping=N_PARTITION)


def _reference(x, weights, count):
    out = []
    for i in range(count):
        xi = (x if x.ndim == 2 else x[i]).astype(np.int64)
        out.append(xi @ weights[i].astype(np.int64))
    return np.stack(out)


# --------------------------------------------------------------------------- #
# RunReport: one object merges outputs + bytes + cycles + validation
# --------------------------------------------------------------------------- #

def test_run_workload_merges_everything():
    w = _w2()
    rep = Machine(CFG).run(w)
    assert isinstance(rep, RunReport)
    x, weights = synthesize_operands(w)
    assert np.array_equal(rep.outputs.astype(np.int64),
                          _reference(x, weights, w.count))
    assert rep.mode.name == "W1.58"
    assert rep.backend == "in-process"
    assert rep.traffic.weight_bytes > 0 and rep.traffic.act_bytes > 0
    assert rep.total_cycles > 0
    # per-stage validation against simulate() rides along, at 0% error
    assert rep.traffic_validation is not None
    assert rep.cycle_validation is not None
    assert rep.ok
    assert all(e == 0.0 for e in rep.traffic_validation.errors.values())
    assert rep.cycle_validation.rel_err == 0.0


def test_run_explicit_plan_and_operands():
    w = _w8()
    plan = plan_stage(CFG, w)
    x, weights = synthesize_operands(w)
    rep = Machine(CFG).run(plan, x, weights)
    assert np.array_equal(rep.outputs.astype(np.int64),
                          _reference(x, weights, w.count))
    # no workload semantics -> no simulator validation, vacuously ok
    assert rep.traffic_validation is None and rep.ok


def test_plan_runs_check_outputs_by_default():
    """check_outputs guards every backend's numerics, plan runs included:
    an executor returning wrong outputs must be caught."""

    class Zeros(InProcessExecutor):
        name = "zeros"

        def execute(self, ctx, instruments):
            return np.zeros_like(super().execute(ctx, instruments))

    w = _w8()
    plan = plan_stage(CFG, w)
    x, weights = synthesize_operands(w)
    with pytest.raises(AssertionError, match="x @ w reference"):
        Machine(CFG, backend=Zeros()).run(plan, x, weights)
    rep = Machine(CFG, backend=Zeros()).run(plan, x, weights,
                                            check_outputs=False)
    assert not rep.outputs.any()


def _int64_products(x, w, count):
    return np.stack([
        (x if x.ndim == 2 else x[i]).astype(np.int64)
        @ (w if w.ndim == 2 else w[i]).astype(np.int64)
        for i in range(count)
    ])


def _int_operands(case, rng):
    """(x, w, count) for one exact_int_matmul case."""
    if case == "shared_int8":
        return (rng.integers(-8, 9, (16, 64)).astype(np.int8),
                rng.integers(-1, 2, (3, 64, 8)).astype(np.int8), 3)
    if case == "per_instance_int8_extremes":
        x = rng.choice([-128, 127], (4, 16, 128)).astype(np.int8)
        w = rng.choice([-128, 127], (4, 128, 8)).astype(np.int8)
        return x, w, 4
    if case == "per_instance_x_shared_w_int16":
        return (rng.integers(-2 ** 15, 2 ** 15, (2, 8, 32)).astype(np.int16),
                rng.integers(-2 ** 15, 2 ** 15, (32, 8)).astype(np.int16), 2)
    if case == "int32_small":
        return (rng.integers(-100, 101, (3, 8, 32)).astype(np.int32),
                rng.integers(-100, 101, (3, 32, 8)).astype(np.int32), 3)
    if case == "bound_just_under_2_24":
        # 16 * 2**10 * (2**10 - 1) = 2**24 - 2**14, with extreme elements
        x = rng.choice([-2 ** 10, 2 ** 10, 2 ** 10 - 3], (2, 4, 16))
        w = rng.choice([-(2 ** 10 - 1), 2 ** 10 - 1, 2 ** 10 - 5], (2, 16, 4))
        return x.astype(np.int16), w.astype(np.int32), 2
    if case == "bound_just_under_2_53":
        # 8 * 2**25 * (2**25 - 1) = 2**53 - 2**28, with extreme elements
        x = rng.choice([-2 ** 25, 2 ** 25, 2 ** 25 - 3], (2, 4, 8))
        w = rng.choice([-(2 ** 25 - 1), 2 ** 25 - 1, 2 ** 25 - 5], (2, 8, 4))
        return x.astype(np.int64), w.astype(np.int32), 2
    # each product (2**b + 1)**2 = 2**(2b) + 2**(b+1) + 1; three of them
    # sum to an odd integer past 2**(2b+1), which the narrower float
    # cannot hold
    b = {"bound_over_2_24": 12, "bound_over_2_53": 26}[case]
    x = np.full((2, 4, 3), 2 ** b + 1, np.int64)
    w = np.full((2, 3, 4), 2 ** b + 1, np.int64)
    w[1] *= -1
    return x, w, 2


@pytest.mark.parametrize("case,path,inexact_in", [
    ("shared_int8", "float32_exact", None),
    ("per_instance_int8_extremes", "float32_exact", None),
    ("per_instance_x_shared_w_int16", "float64_exact", None),
    ("int32_small", "float32_exact", None),
    ("bound_just_under_2_24", "float32_exact", None),
    ("bound_over_2_24", "float64_exact", np.float32),
    ("bound_just_under_2_53", "float64_exact", None),
    ("bound_over_2_53", "int64", np.float64),
])
def test_exact_int_matmul_equals_int64_product(case, path, inexact_in):
    """The check's matmul is the int64 product bit for bit: in the
    narrowest float that holds K * max|x| * max|w| (BLAS), in int64 past
    2**53."""
    x, w, count = _int_operands(case, np.random.default_rng(7))
    got, took = exact_int_matmul(x, w)
    want = _int64_products(x, w, count)
    assert took == path
    assert got.dtype == np.int64 and got.shape == want.shape
    assert np.array_equal(got, want)
    if inexact_in is not None:
        # the narrower float's product of these operands rounds: the wider
        # path is what keeps the result exact
        narrow = np.matmul(x.astype(inexact_in), w.astype(inexact_in))
        assert not np.array_equal(narrow.astype(np.int64), want)


PATHS = ("float32_exact", "float64_exact", "int64", "float")


def _checked_stage(path):
    """(plan, x, w) for a W8 stage whose output check takes ``path``."""
    w8 = _w8()
    plan = plan_stage(CFG, w8)
    x, weights = synthesize_operands(w8, seed=3)
    x = np.broadcast_to(x, (w8.count,) + x.shape[-2:]).copy()
    if path == "float":
        return plan, x.astype(np.float32), weights.astype(np.float32)
    if path != "float32_exact":
        # int32 extremes the products never meet: x's column 0 meets w's
        # zeroed row 0, w's row 1 meets x's zeroed column 1 — the bound
        # passes 2**24 (and, with both, 2**53), the outputs stay small
        x, weights = x.astype(np.int32), weights.astype(np.int32)
        weights[:, 0, :] = 0
        x[:, :, 1] = 0
        x[0, 0, 0] = np.iinfo(np.int32).max
        if path == "int64":
            weights[0, 1, 0] = np.iinfo(np.int32).max
    return plan, x, weights


@pytest.mark.parametrize("path", ["float32_exact", "float64_exact", "int64"])
def test_check_catches_one_wrong_element(path):
    """One wrong output element still fails the stage check, on every
    integer path, naming its instance and position count."""

    class OffByOne(InProcessExecutor):
        name = "off-by-one"

        def execute(self, ctx, instruments):
            out = super().execute(ctx, instruments)
            out[1, 3, 5] += 1
            return out

    plan, x, weights = _checked_stage(path)
    reg = MetricsRegistry()
    Machine(CFG, metrics=reg).run(plan, x, weights)
    assert reg.counter("machine_check_stages", labels=("path",)) \
        .value(path=path) == 1
    with pytest.raises(AssertionError,
                       match="instance 1: runtime output != x @ w reference "
                             "at 1 positions"):
        Machine(CFG, backend=OffByOne()).run(plan, x, weights)


@pytest.mark.parametrize(
    "path", ["float32_exact", "float64_exact", "int64", "float"])
def test_check_counts_its_path(path):
    """machine_check_stages counts each checked stage under its path, and
    nothing when the check is off."""
    plan, x, weights = _checked_stage(path)
    reg = MetricsRegistry()
    machine = Machine(CFG, metrics=reg)
    machine.run(plan, x, weights)
    machine.run(plan, x, weights)
    machine.run(plan, x, weights, check_outputs=False)
    counter = reg.counter("machine_check_stages", labels=("path",))
    assert {p: counter.value(path=p)
            for p in PATHS} == {p: (2 if p == path else 0) for p in PATHS}


def test_run_input_errors():
    w = _w8()
    x, weights = synthesize_operands(w)
    with pytest.raises(ValueError, match="both x and w"):
        Machine(CFG).run(w, x)
    with pytest.raises(ValueError, match="explicit x and w"):
        Machine(CFG).run(plan_stage(CFG, w))
    with pytest.raises(ValueError, match="ztb_sparsity"):
        Machine(CFG).run(plan_stage(CFG, w), x, weights, ztb_sparsity=0.5)
    # sparsity prunes synthesized operands — explicit x/w must not
    # silently run dense
    with pytest.raises(ValueError, match="ztb_sparsity"):
        Machine(CFG).run(w, x, weights, ztb_sparsity=0.5)
    with pytest.raises(TypeError, match="GEMMWorkload, StagePlan, or "
                                        "Program"):
        Machine(CFG).run("attn_score")


# --------------------------------------------------------------------------- #
# Option validation at the Machine boundary
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bad", [0, -3, 2.5, True])
def test_rejects_bad_accumulators(bad):
    with pytest.raises(ValueError, match="accumulators"):
        Machine(CFG, accumulators=bad)
    Machine(CFG, accumulators=np.int64(2))   # numpy integers are fine


def test_rejects_unknown_kernel_backend_and_granularity():
    # "auto" = the kernels' own dispatch (reference off-TPU): valid AND runs
    Machine(CFG, kernel_backend="auto").run(_w2())
    with pytest.raises(ValueError, match="kernel_backend"):
        Machine(CFG, kernel_backend="cuda")
    with pytest.raises(ValueError, match="granularity"):
        Machine(CFG, granularity="warp")
    with pytest.raises(ValueError, match="mem_bw"):
        Machine(CFG, mem_bw_bytes_per_cycle=0.0)


def test_validate_flag_semantics():
    """validate=None auto-validates with the run's own instruments;
    validate=True refuses to degrade silently; validate=False skips."""
    w = _w8()
    tr, cc = TrafficTracer(), CycleCounter(CFG)
    rep = Machine(CFG).run(w, instruments=[tr, cc], validate=True)
    assert rep.traffic_validation is not None and rep.ok
    assert rep.trace is tr and rep.cycles is cc
    # auto mode: caller-passed instruments may carry prior totals -> skip
    assert Machine(CFG).run(
        w, instruments=[TrafficTracer(), CycleCounter(CFG)],
    ).traffic_validation is None
    assert Machine(CFG).run(w, validate=False).traffic_validation is None
    with pytest.raises(ValueError, match="TrafficTracer"):
        Machine(CFG).run(w, instruments=[Recording()], validate=True)
    with pytest.raises(ValueError, match="analytic counterpart"):
        # 8-bit ZTB runs are outside simulate()'s ZTB model
        Machine(CFG).run(w, ztb_sparsity=0.5, validate=True)
    with pytest.raises(ValueError, match="analytic"):
        # explicit plans have no workload to simulate
        x, weights = synthesize_operands(w)
        Machine(CFG).run(plan_stage(CFG, w), x, weights, validate=True)


# --------------------------------------------------------------------------- #
# Instrument conformance: the exact event stream third parties code against
# --------------------------------------------------------------------------- #

class Recording(Instrument):
    def __init__(self):
        self.events = []

    def on_program_begin(self, program):
        self.events.append(("program_begin", program.names))

    def on_stage_begin(self, **ev):
        self.events.append(("stage_begin", ev["stage"], ev["index"],
                            ev["deps"]))

    def on_stage_end(self, **ev):
        self.events.append(("stage_end", ev["stage"], ev["outputs"].shape))

    def on_program_end(self, outputs):
        self.events.append(("program_end", tuple(outputs)))

    def on_plan_begin(self, plan, mode, ctx):
        self.events.append(("begin", plan.stage, mode.name))

    def on_weight_fetch(self, key, nbytes):
        self.events.append(("fetch_w", key, nbytes))

    def on_act_stream(self, key, nbytes):
        self.events.append(("stream_a", key, nbytes))

    def on_psum(self, nbytes):
        self.events.append(("psum", nbytes))

    def on_pass(self, **ev):
        self.events.append(("pass", ev["k_tile"], ev["n_lo"], ev["n_hi"]))

    def on_window_skip(self, **ev):
        self.events.append(("skip", ev["k_tile"], ev["n_lo"], ev["n_hi"]))

    def on_assignment_end(self, **ev):
        self.events.append(("assignment", ev["legion"], ev["round_"],
                            ev["passes"], ev["skipped"]))

    def on_plan_end(self, outputs):
        self.events.append(("end", outputs.shape))


def _tiny_plan():
    """1 Legion, 1 assignment, 2 K-windows of 128, a single 16-wide N-tile."""
    w = GEMMWorkload(stage=QKV_PROJ, m=4, k=256, n=16, weight_bits=8,
                     count=1, shared_input=True, mapping=HEAD_PER_UNIT)
    plan = plan_stage(CFG1, w)
    assert plan.assignments[0].k_tiles == 2
    x = np.ones((4, 256), dtype=np.int8)
    weights = np.ones((1, 256, 16), dtype=np.int8)
    return plan, x, weights


def test_instrument_event_stream_dense():
    plan, x, weights = _tiny_plan()
    rec = Recording()
    machine = Machine(CFG1, instruments=[rec])
    rep = machine.run(plan, x, weights)
    # units==1: no NoC, keys are per-instance; W8 n_tile = D = 16
    wbytes = 128 * 16 * 1.0
    abytes = 4 * 128 * 1.0
    psum = 16 * 4 * 4.0
    assert rec.events == [
        ("program_begin", ("qkv_proj",)),   # one-node program (the shim)
        ("stage_begin", "qkv_proj", 0, ()),
        ("begin", "qkv_proj", "W8"),
        ("fetch_w", ("w", "qkv_proj", ("inst", 0), 0, 0), wbytes),
        ("stream_a", ("a", "qkv_proj", ("inst", 0), 0, 0), abytes),
        ("psum", psum),                    # first window: write-only
        ("pass", 0, 0, 16),
        ("fetch_w", ("w", "qkv_proj", ("inst", 0), 1, 0), wbytes),
        ("stream_a", ("a", "qkv_proj", ("inst", 0), 0, 1), abytes),
        ("psum", 2.0 * psum),              # later windows: read-modify-write
        ("pass", 1, 0, 16),
        ("assignment", 0, 0, 2, 0),
        ("end", (1, 4, 16)),
        ("stage_end", "qkv_proj", (1, 4, 16)),
        ("program_end", ("qkv_proj",)),
    ]
    assert rep.traffic.weight_bytes == 2 * wbytes


def test_instrument_event_stream_with_ztb_skip():
    plan, x, weights = _tiny_plan()
    weights = weights.copy()
    weights[:, :128, :] = 0                # K-window 0 is fully sparse
    rec = Recording()
    machine = Machine(CFG1, instruments=[rec])
    rep = machine.run(plan, x, weights, ztb=True)
    wbytes = 128 * 16 * 1.0
    abytes = 4 * 128 * 1.0
    psum = 16 * 4 * 4.0
    assert rec.events == [
        ("program_begin", ("qkv_proj",)),
        ("stage_begin", "qkv_proj", 0, ()),
        ("begin", "qkv_proj", "W8+ZTB"),
        ("skip", 0, 0, 16),                # no fetch, no psum round
        ("fetch_w", ("w", "qkv_proj", ("inst", 0), 1, 0), wbytes),
        ("stream_a", ("a", "qkv_proj", ("inst", 0), 0, 1), abytes),
        ("psum", psum),                    # first *executed* window
        ("pass", 1, 0, 16),
        ("assignment", 0, 0, 1, 1),
        ("end", (1, 4, 16)),
        ("stage_end", "qkv_proj", (1, 4, 16)),
        ("program_end", ("qkv_proj",)),
    ]
    assert rep.ztb_stats.fully_sparse_fraction == pytest.approx(0.5)
    # skipping halved the stationary traffic
    assert rep.traffic.weight_bytes == wbytes


def test_session_instruments_observe_every_run():
    rec = Recording()
    machine = Machine(CFG, instruments=[rec])
    machine.run(_w8())
    n1 = len(rec.events)
    machine.run(_w2())
    assert n1 > 0 and len(rec.events) > n1   # accumulated across runs
    # per-run default tracer/counter stay fresh: two equal runs, equal bytes
    a = machine.run(_w8(), seed=1)
    b = machine.run(_w8(), seed=1)
    assert a.trace.totals == b.trace.totals


def test_report_binds_per_run_not_session_instruments():
    """A session-lifetime TrafficTracer accumulates across runs; the
    RunReport's trace must stay the run's own fresh one."""
    session_tracer = TrafficTracer()
    machine = Machine(CFG, instruments=[session_tracer])
    a = machine.run(_w8())
    b = machine.run(_w8())
    assert a.trace is not session_tracer and b.trace is not session_tracer
    assert a.trace.totals == b.trace.totals      # per-run, not cumulative
    # with explicit per-run instruments, session instruments never leak in
    probe = Recording()
    rep = machine.run(_w8(), instruments=[probe])
    assert rep.trace is None and rep.traffic is None


# --------------------------------------------------------------------------- #
# ShardedExecutor: Legions on a mesh axis, bit-exact with in-process
# --------------------------------------------------------------------------- #

MODE_MATRIX = [(bits, ztb) for bits in (2, 4, 8) for ztb in (False, True)]


@pytest.mark.parametrize("bits,ztb", MODE_MATRIX)
def test_sharded_bit_exact_mode_matrix(bits, ztb):
    w = dataclasses.replace(_w2(), weight_bits=bits)
    inproc = Machine(CFG).run(w, ztb_sparsity=0.5 if ztb else 0.0)
    sharded = Machine(CFG, backend=ShardedExecutor()).run(
        w, ztb_sparsity=0.5 if ztb else 0.0)
    assert np.array_equal(inproc.outputs, sharded.outputs)
    assert inproc.outputs.dtype == sharded.outputs.dtype
    # the measurement stream is backend-independent
    assert inproc.trace.totals == sharded.trace.totals
    assert inproc.cycles.total_cycles == sharded.cycles.total_cycles
    assert sharded.backend == "sharded"
    assert sharded.ok


def test_sharded_n_partition_and_caller_book_gating():
    """N-partitioned slices across Legions, and a caller-supplied book that
    gates windows which are NOT actually zero: the sharded path must
    reproduce the skip semantics (excluded contributions) bit-exactly."""
    w = _w8()
    plan = plan_stage(CFG, w)
    x, weights = synthesize_operands(w, seed=9)
    rep_a = Machine(CFG).run(plan, x, weights)
    rep_b = Machine(CFG, backend=ShardedExecutor()).run(plan, x, weights)
    assert np.array_equal(rep_a.outputs, rep_b.outputs)

    from repro.core.sparsity import ztb_from_weight
    masked = weights.copy().astype(np.int8)
    masked[0, : plan.assignments[0].k_window, :] = 0    # zero one window
    books = [ztb_from_weight(np.asarray(m), block_k=CFG.d,
                             block_n=CFG.d, window=CFG.cores)
             for m in masked]
    # books built from `masked`, but execution uses the UNmasked weights:
    # gated windows carry non-zero data that must be excluded either way
    in_g = Machine(CFG).run(plan, x, weights, ztb=books)
    sh_g = Machine(CFG, backend=ShardedExecutor()).run(plan, x, weights,
                                                       ztb=books)
    assert np.array_equal(in_g.outputs, sh_g.outputs)
    assert not np.array_equal(in_g.outputs, rep_a.outputs)


def test_sharded_uses_available_devices():
    import jax

    ex = ShardedExecutor()
    Machine(CFG, backend=ex).run(_w2())
    assert ex.devices_used == min(jax.device_count(), CFG.units)


def test_sharded_rejects_float_and_kernel_granularity():
    w = _w8()
    plan = plan_stage(CFG, w)
    x, weights = synthesize_operands(w)
    sharded = Machine(CFG, backend=ShardedExecutor())
    with pytest.raises(ValueError, match="bit-exact"):
        sharded.run(plan, x.astype(np.float32), weights.astype(np.float32),
                    check_outputs=False)
    with pytest.raises(ValueError, match="granularity"):
        Machine(CFG, backend=ShardedExecutor(),
                granularity="kernel").run(w)
    # per-core ZTB gating (emulate_cores + books) cannot be reproduced by
    # the one-matmul sharded path; without books emulation is equivalent
    with pytest.raises(ValueError, match="per-core"):
        Machine(CFG, backend=ShardedExecutor(),
                emulate_cores=True).run(_w2(), ztb_sparsity=0.5)
    Machine(CFG, backend=ShardedExecutor(), emulate_cores=True).run(_w2())
    # the sharded path never invokes the tile kernels — a non-reference
    # kernel_backend would be a silent no-op, so it is rejected
    with pytest.raises(ValueError, match="kernel_backend"):
        Machine(CFG, backend=ShardedExecutor(),
                kernel_backend="pallas").run(_w2())


def test_run_float_operands_checked_with_allclose():
    """Float operands take the float32 path; the output check must compare
    against a float reference, not an int64-truncated one."""
    w = _w8()
    x, weights = synthesize_operands(w, seed=2)
    rep = Machine(CFG).run(w, x.astype(np.float32) * 0.5,
                           weights.astype(np.float32))
    assert rep.outputs.dtype == np.float32
    ref = (x[0].astype(np.float64) * 0.5) @ weights[0].astype(np.float64)
    np.testing.assert_allclose(rep.outputs[0], ref, rtol=1e-5)


def test_sharded_cross_validates_attention_stages():
    """Machine-driven cross-validation with the sharded backend: BitNet
    attention traffic AND cycles still match simulate() per stage."""
    spec = dataclasses.replace(bitnet_1_58b_kv(seq_len=128), layers=1)
    machine = Machine(CFG, backend=ShardedExecutor())
    traffic_vals, cycle_vals = machine.cross_validate(
        attention_workloads(spec), rtol=0.05)
    assert {v.stage for v in traffic_vals} == {
        "qkv_proj", "attn_score", "attn_output", "out_proj",
    }
    for v in traffic_vals + cycle_vals:
        assert v.ok, str(v)


# --------------------------------------------------------------------------- #
# Machine-level knobs thread through (banks, emulate_cores, mem_bw)
# --------------------------------------------------------------------------- #

def test_machine_options_thread_through():
    w = _w8()
    base = Machine(CFG).run(w)
    one_bank = Machine(CFG, accumulators=1).run(w)
    emu = Machine(CFG, emulate_cores=True).run(w)
    assert np.array_equal(base.outputs, one_bank.outputs)
    assert np.array_equal(base.outputs, emu.outputs)
    starved = Machine(CFG, mem_bw_bytes_per_cycle=0.25).run(w)
    assert starved.total_cycles > base.total_cycles
    assert math.isinf(Machine(CFG).mem_bw)


# --------------------------------------------------------------------------- #
# Export hygiene
# --------------------------------------------------------------------------- #

def test_legion_exports_sorted_and_complete():
    import repro.legion as legion
    import repro.serve as serve

    assert legion.__all__ == sorted(legion.__all__)
    for name in ("Machine", "RunReport", "Instrument", "ExecutorBackend",
                 "InProcessExecutor", "ShardedExecutor"):
        assert name in legion.__all__ and hasattr(legion, name)
    # PR 10: the workload-zoo lowering surface — the unified dispatcher,
    # the spec family, and the zoo lowerings — is pinned public API
    for name in ("AttentionLoweringSpec", "HybridSpec", "LoweringSpec",
                 "MoESpec", "SSDSpec", "ServeStepSpec", "lower",
                 "lower_attention", "lower_hybrid", "lower_moe",
                 "lower_ssd", "zoo_spec"):
        assert name in legion.__all__ and hasattr(legion, name)
    # one serve spec lowers every engine step: its twins stay gone
    for name in ("ServeBatchSpec", "ServeMixedSpec", "lower_serve_batch",
                 "lower_serve_mixed", "lower_serve_step"):
        assert name not in legion.__all__ and not hasattr(legion, name)
    # the PR-3 deprecation shims were removed in PR 6 and must stay gone
    for name in ("execute_plan", "execute_workload", "ExecutionResult"):
        assert name not in legion.__all__ and not hasattr(legion, name)
    assert serve.__all__ == sorted(serve.__all__)
    assert "LegionServeBackend" in serve.__all__
    assert isinstance(InProcessExecutor(), object)
