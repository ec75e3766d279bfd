"""Program graph API: multi-stage dependency graphs, act-to-act attention
lowering, and the pipelined executor.

The acceptance gate for the `legion.Program` redesign:

* `Machine.run(Program)` executes a full BitNet attention block (QKV ->
  score -> softmax -> output -> O-proj) with the act-to-act stages lowered
  as real GEMMs (K/V stationary activations, GQA multicast), numerically
  exact against a pure-NumPy reference and cross-validated against
  ``simulate()`` at 0% traffic AND cycle error per stage;
* `PipelinedExecutor` overlapped cycles are <= the serial per-stage sum;
  a dependent boundary whose stationary operand already exists prefetches
  exactly its fill (``weight_prefetch_overlap_cycles``), while a boundary
  whose stationary operand comes from the outgoing stage hides nothing;
* decode-shaped act-to-act workloads (M=1, K/N = context t) cross-validate
  across the W1.58/W4/W8 mode matrix, including the GQA kv_group fanout;
* the graph validates (dup names, unknown refs, cycles, operand pairing)
  and the stage-boundary instrument events fire in pinned order;
* `Program.merge` interleaves independent per-slot programs as an
  antichain — a merged two-slot decode batch runs bit-exact vs per-slot
  serial execution with overlapped <= serial and 0% traffic xval — and
  `ServeStepSpec(explicit_layers=N)` spans explicit transformer layers
  through real cross-layer deps (diamond graphs overlap, chains stay
  exact).
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import dlegion, simulate_workload
from repro.core.analytical import (
    boundary_overlap_cycles,
    weight_prefetch_overlap_cycles,
)
from repro.core.scheduler import kv_multicast_fanout, plan_stage
from repro.core.workloads import (
    ATTN_OUTPUT,
    ATTN_SCORE,
    N_PARTITION,
    GEMMWorkload,
    bitnet_1_58b_kv,
    decode_attention_workloads,
)
from repro.legion import (
    CycleCounter,
    Instrument,
    Machine,
    PipelinedExecutor,
    Program,
    ProgramError,
    ProgramReport,
    ProgramStage,
    Ref,
    ShardedExecutor,
    TrafficTracer,
    ServeStepSpec,
    lower,
    lower_attention,
    reference_outputs,
    requantize_int8,
    softmax_int8,
)
from repro.legion.program import STATIONARY_ACT

CFG = dlegion()                 # 8 Legions x 8 cores x 16x16
SPEC = dataclasses.replace(bitnet_1_58b_kv(seq_len=64), layers=1)


def _wl(name, **kw):
    base = dict(stage=name, m=8, k=128, n=32, weight_bits=8, count=1)
    base.update(kw)
    return GEMMWorkload(**base)


# --------------------------------------------------------------------------- #
# Graph construction + validation
# --------------------------------------------------------------------------- #

def test_program_rejects_malformed_graphs():
    with pytest.raises(ProgramError, match="duplicate"):
        Program([ProgramStage(name="a", workload=_wl("a")),
                 ProgramStage(name="a", workload=_wl("a"))])
    with pytest.raises(ProgramError, match="exactly one"):
        Program([ProgramStage(name="a")])
    with pytest.raises(ProgramError, match="unknown stage"):
        Program([ProgramStage(name="a", workload=_wl("a"),
                              x=Ref("ghost"), w=np.ones((128, 32)))]) \
            .validate()
    with pytest.raises(ProgramError, match="cycle"):
        Program([
            ProgramStage(name="a", workload=_wl("a"), after=("b",)),
            ProgramStage(name="b", workload=_wl("b"), after=("a",)),
        ]).validate()
    with pytest.raises(ProgramError, match="depends on itself"):
        Program([ProgramStage(name="a", workload=_wl("a"),
                              after=("a",))]).validate()
    with pytest.raises(ProgramError, match="both x and w"):
        Program([ProgramStage(name="a", workload=_wl("a"),
                              x=np.ones((8, 128)))]).validate()
    with pytest.raises(ProgramError, match="empty"):
        Program().validate()
    with pytest.raises(ValueError, match="multi-producer"):
        Ref(("a", "b"))


def test_levels_and_chain_detection():
    prog = Program([
        ProgramStage(name="a", workload=_wl("a")),
        ProgramStage(name="b", workload=_wl("b")),
        ProgramStage(name="c", workload=_wl("c"), after=("a", "b")),
    ])
    assert [[s.name for s in lv] for lv in prog.levels()] == \
        [["a", "b"], ["c"]]
    assert not prog.is_chain
    chain = lower_attention(SPEC)
    assert chain.is_chain
    assert chain.names == ("qkv_proj", "attn_score", "attn_output",
                           "out_proj")
    split = lower_attention(SPEC, split_qkv=True)
    assert not split.is_chain
    assert [len(lv) for lv in split.levels()] == [3, 1, 1, 1]


# --------------------------------------------------------------------------- #
# Full attention block: numerics vs NumPy reference, xval vs simulate()
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("split_qkv", [False, True])
def test_attention_block_exact_vs_reference_and_simulate(split_qkv):
    prog = lower_attention(SPEC, split_qkv=split_qkv, seed=7)
    rep = Machine(CFG).run(prog)
    assert isinstance(rep, ProgramReport)
    assert rep.ok

    # end-to-end numerics: every stage bit-exact vs the pure-NumPy graph
    ref = reference_outputs(prog)
    assert set(ref) == set(rep.outputs)
    for name in ref:
        assert np.array_equal(rep.outputs[name], ref[name]), name
        assert rep.outputs[name].dtype == np.int32

    # act-to-act stages really lowered: K/V stationary, GQA multicast
    score = rep["attn_score"]
    assert score.plan.mapping == N_PARTITION
    assert score.workload.kv_group == SPEC.group_size == 4
    fanout = kv_multicast_fanout(score.plan)
    assert all(f == SPEC.group_size * CFG.units for f in fanout.values())

    # cross-validated against simulate() at exactly 0%
    assert len(rep.validations) == 2 * len(prog)
    for v in rep.stage_reports.values():
        assert all(e == 0.0 for e in v.traffic_validation.errors.values())
        assert v.cycle_validation.rel_err == 0.0


def test_run_program_rejects_call_level_operands():
    prog = lower_attention(SPEC)
    with pytest.raises(ValueError, match="its own operands"):
        Machine(CFG).run(prog, np.ones((4, 4)))
    with pytest.raises(ValueError, match="per-stage options"):
        Machine(CFG).run(prog, ztb_sparsity=0.5)
    with pytest.raises(ValueError, match="per-stage options"):
        Machine(CFG).run(prog, ztb=True)


def test_reference_outputs_requires_concrete_dense_operands():
    with pytest.raises(ProgramError, match="concrete"):
        reference_outputs(Program([ProgramStage(name="a",
                                                workload=_wl("a"))]))


# --------------------------------------------------------------------------- #
# PipelinedExecutor: overlapped <= serial; dependent boundaries prefetch
# their fill unless the stationary operand comes from the outgoing stage
# --------------------------------------------------------------------------- #

def _last_first(rep, prev_name, next_name):
    """Boundary rounds of two adjacent chain stages: (prev's last round
    critical, next's first round critical)."""
    prev_rc = rep[prev_name].cycles.round_criticals()[prev_name]
    next_rc = rep[next_name].cycles.round_criticals()[next_name]
    return prev_rc[-1], next_rc[0]


def test_pipelined_chain_prefetches_existing_stationary_operands():
    prog = lower_attention(SPEC)                      # pure chain
    rep = Machine(CFG, backend=PipelinedExecutor()).run(prog)
    assert rep.backend == "pipelined"
    pp = rep.pipeline
    assert pp is not None and pp.ok
    lv = pp.levels
    # qkv -> attn_score hides nothing: the stationary K IS qkv's output
    assert lv[1].stages == ("attn_score",)
    assert lv[1].hidden_cycles == 0
    # attn_score -> attn_output prefetches V (written back at qkv time):
    # exactly the incoming fill, bounded by the outgoing stream + drain
    pb, nb = _last_first(rep, "attn_score", "attn_output")
    assert lv[2].hidden_cycles == weight_prefetch_overlap_cycles(
        pb.stream, nb.fill, prev_drain=pb.drain) > 0
    # attn_output -> out_proj prefetches the concrete O-weights
    pb, nb = _last_first(rep, "attn_output", "out_proj")
    assert lv[3].hidden_cycles == weight_prefetch_overlap_cycles(
        pb.stream, nb.fill, prev_drain=pb.drain) > 0
    assert pp.overlapped_cycles < pp.serial_cycles
    assert pp.serial_cycles == rep.serial_cycles
    assert rep.total_cycles == pp.overlapped_cycles
    # serial side == the per-stage simulate() sums (0% cycle error)
    analytic = sum(r.cycle_validation.analytic
                   for r in rep.stage_reports.values())
    assert pp.serial_cycles == analytic
    # numerics are untouched by the timing overlay
    ref = reference_outputs(prog)
    assert all(np.array_equal(rep.outputs[k], ref[k]) for k in ref)


def test_pipelined_chain_with_produced_stationaries_stays_serial():
    """A chain whose every stationary operand comes from the previous
    stage has nothing to prefetch: overlapped == serial, exactly."""
    rng = np.random.default_rng(11)
    x = rng.integers(-8, 9, size=(16, 64)).astype(np.int8)
    wa = rng.integers(-8, 9, size=(64, 64)).astype(np.int8)
    mid_x = Ref("a", lambda o: requantize_int8(o[0]))
    mid_w = Ref("a", lambda o: requantize_int8(o[0]).T.copy())
    prog = Program([
        ProgramStage(name="a", workload=_wl("a", m=16, k=64, n=64),
                     x=x, w=wa),
        ProgramStage(name="b", workload=_wl("b", m=16, k=64, n=16),
                     x=mid_x, w=mid_w, w_source=STATIONARY_ACT),
    ])
    rep = Machine(CFG, backend=PipelinedExecutor()).run(prog)
    pp = rep.pipeline
    assert pp.ok
    assert pp.hidden_cycles == 0
    assert pp.overlapped_cycles == pp.serial_cycles == rep.total_cycles


def test_pipelined_split_graph_overlaps():
    prog = lower_attention(SPEC, split_qkv=True)
    rep = Machine(CFG, backend=PipelinedExecutor()).run(prog)
    pp = rep.pipeline
    assert pp.ok
    assert pp.overlapped_cycles < pp.serial_cycles    # q/k/v rounds overlap
    assert pp.speedup > 1.0
    assert rep.total_cycles == pp.overlapped_cycles < rep.serial_cycles
    # the independent first level overlaps fill + pipeline; every chain-
    # tail boundary still prefetches its fill (attn_score enters after a
    # q_proj round but takes its stationary K from k_proj; attn_output's
    # V and out_proj's weights exist before their streamed inputs)
    lv = pp.levels
    assert lv[0].stages == ("q_proj", "k_proj", "v_proj")
    assert lv[0].hidden_cycles > 0
    assert all(l.hidden_cycles > 0 for l in lv[1:])
    assert pp.hidden_cycles == sum(l.hidden_cycles for l in lv)
    ref = reference_outputs(prog)
    assert all(np.array_equal(rep.outputs[k], ref[k]) for k in ref)


def test_pipelined_round_criticals_are_consistent():
    """A stage's round criticals sum to its stage breakdown — the serial
    side of the pipeline schedule is the counted total, term for term."""
    rep = Machine(CFG).run(lower_attention(SPEC)["attn_score"].workload)
    rc = rep.cycles.round_criticals()
    assert sum(b.total for rounds in rc.values() for b in rounds) == \
        rep.cycles.total_cycles


def test_pipeline_report_needs_per_stage_counters():
    """Caller-passed instruments span the whole program — no per-stage
    counters to schedule with, so the pipeline report is skipped."""
    prog = lower_attention(SPEC, split_qkv=True)
    rep = Machine(CFG, backend=PipelinedExecutor()).run(
        prog, instruments=[TrafficTracer(), CycleCounter(CFG)])
    assert rep.pipeline is None
    # and the shared instruments must not bind to stage reports: their
    # totals span stages, so binding would overcount by the program prefix
    assert all(r.trace is None and r.cycles is None
               for r in rep.stage_reports.values())
    assert rep.serial_cycles == 0          # no per-stage measurement
    with pytest.raises(ValueError, match="multi-stage"):
        Machine(CFG).run(prog, validate=True,
                         instruments=[TrafficTracer(), CycleCounter(CFG)])


def test_pipelined_delegates_numerics_to_inner():
    w = _wl(ATTN_SCORE, count=4, kv_group=2, mapping=N_PARTITION)
    base = Machine(CFG).run(w)
    piped = Machine(CFG, backend=PipelinedExecutor()).run(w)
    sharded_inner = Machine(
        CFG, backend=PipelinedExecutor(ShardedExecutor())).run(w)
    assert np.array_equal(base.outputs, piped.outputs)
    assert np.array_equal(base.outputs, sharded_inner.outputs)
    assert base.trace.totals == piped.trace.totals == \
        sharded_inner.trace.totals


# --------------------------------------------------------------------------- #
# Decode-shaped act-to-act workloads (M=1, K/N = t) across the mode matrix
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("context", [1, 17, 64, 130])
def test_decode_attention_mode_matrix(bits, context):
    """M=1 score/output GEMMs with position-dependent K/N cross-validate
    at 0% for every stationary-operand precision (KV-cache quantization
    scenarios) and context length, including non-tile-aligned t."""
    score, output = decode_attention_workloads(
        heads=8, kv_heads=2, head_dim=128, context=context)
    for w in (dataclasses.replace(score, weight_bits=bits),
              dataclasses.replace(output, weight_bits=bits)):
        rep = Machine(CFG).run(w)
        assert rep.outputs.shape == (8, 1, w.n)
        assert all(e == 0.0
                   for e in rep.traffic_validation.errors.values()), str(w)
        assert rep.cycle_validation.rel_err == 0.0, str(w)


def test_decode_attention_gqa_multicast_fanout():
    """The kv_group multicast path: grouped KV tiles fetch once per group,
    shrinking stationary traffic by exactly the group size."""
    grouped, _ = decode_attention_workloads(
        heads=8, kv_heads=2, head_dim=128, context=96)
    solo = dataclasses.replace(grouped, kv_group=1)
    rep_g = Machine(CFG).run(grouped)
    rep_s = Machine(CFG).run(solo)
    assert rep_g.trace.multicast_hits > rep_s.trace.multicast_hits
    assert rep_s.trace.totals.weight_bytes == pytest.approx(
        rep_g.trace.totals.weight_bytes * grouped.kv_group)
    fanout = kv_multicast_fanout(rep_g.plan)
    assert set(fanout.values()) == {grouped.kv_group * CFG.units}
    assert rep_g.ok and rep_s.ok


def test_decode_attention_context_grows_cost_monotonically():
    machine = Machine(CFG)
    score_cycles = []
    out_cycles = []
    for t in (8, 64, 256):
        s, o = decode_attention_workloads(heads=8, kv_heads=2, head_dim=128,
                                          context=t)
        score_cycles.append(machine.run(s).total_cycles)
        out_cycles.append(machine.run(o).total_cycles)
    assert score_cycles == sorted(score_cycles)
    assert out_cycles == sorted(out_cycles)
    assert out_cycles[-1] > out_cycles[0]     # K = t streams more windows

    with pytest.raises(ValueError, match="context"):
        decode_attention_workloads(heads=8, kv_heads=2, head_dim=128,
                                   context=0)


# --------------------------------------------------------------------------- #
# Serve-step lowering
# --------------------------------------------------------------------------- #

class _Op:
    def __init__(self, workload, weights):
        self.workload = workload
        self.weights = weights


def _proj_ops(rng, d_model=256, hd=32, heads=4, kv=2, layers=1):
    from repro.core.workloads import HEAD_PER_UNIT, OUT_PROJ, QKV_PROJ
    qkv = GEMMWorkload(stage=QKV_PROJ, m=1, k=d_model, n=hd, weight_bits=2,
                       count=heads + 2 * kv, shared_input=True,
                       mapping=HEAD_PER_UNIT, layers=layers)
    opj = GEMMWorkload(stage=OUT_PROJ, m=1, k=heads * hd, n=d_model,
                       weight_bits=2, count=1, mapping=N_PARTITION,
                       layers=layers)
    tern = lambda *s: rng.integers(-1, 2, size=s).astype(np.int8)
    return [_Op(qkv, tern(heads + 2 * kv, d_model, hd)),
            _Op(opj, tern(1, heads * hd, d_model))]


def _mlp_ops(rng, d_model=256, d_ff=128, layers=1):
    up = GEMMWorkload(stage="mlp_up", m=1, k=d_model, n=d_ff, weight_bits=2,
                      count=2, shared_input=True, mapping=N_PARTITION,
                      layers=layers)
    down = GEMMWorkload(stage="mlp_down", m=1, k=d_ff, n=d_model,
                        weight_bits=2, count=1, mapping=N_PARTITION,
                        layers=layers)
    tern = lambda *s: rng.integers(-1, 2, size=s).astype(np.int8)
    return [_Op(up, tern(2, d_model, d_ff)),
            _Op(down, tern(1, d_ff, d_model))]


def _serve(ops, **kw):
    """Lower one engine step through the serve spec."""
    return lower(ServeStepSpec(projections=ops, **kw))


def test_lower_serve_step_decode_batched_graph():
    rng = np.random.default_rng(0)
    prog = _serve(_proj_ops(rng), m=2, contexts=(5, 9),
                  heads=4, kv_heads=2, head_dim=32)
    assert prog.names == ("qkv_proj", "attn_score[0]", "attn_output[0]",
                          "attn_score[1]", "attn_output[1]", "out_proj")
    # per-slot position-dependent K/N
    assert prog["attn_score[0]"].workload.n == 5
    assert prog["attn_score[1]"].workload.n == 9
    assert prog["attn_output[1]"].workload.k == 9
    assert prog["attn_score[0]"].workload.m == 1      # one row per slot
    # the two slots are dependency-independent: same level
    assert [sorted(s.name for s in lv) for lv in prog.levels()][1] == \
        ["attn_score[0]", "attn_score[1]"]

    rep = Machine(CFG).run(prog)
    assert rep.ok
    ref = reference_outputs(prog)
    assert all(np.array_equal(rep.outputs[k], ref[k]) for k in ref)
    # O-proj concatenates both slots' attended rows
    assert rep.outputs["out_proj"].shape == (1, 2, 256)

    # batched slots overlap under the pipelined executor
    piped = Machine(CFG, backend=PipelinedExecutor()).run(prog)
    assert piped.pipeline.overlapped_cycles < piped.pipeline.serial_cycles


def test_lower_serve_step_errors():
    rng = np.random.default_rng(1)
    ops = _proj_ops(rng)
    with pytest.raises(ValueError, match="heads"):
        ServeStepSpec(projections=ops, m=1, contexts=(4,))
    with pytest.raises(ValueError, match="slots"):
        ServeStepSpec(projections=ops, m=3, contexts=(4, 5), heads=4,
                      kv_heads=2, head_dim=32)
    with pytest.raises(ValueError, match="qkv_proj"):
        ServeStepSpec(projections=ops[1:], m=1, contexts=(4,), heads=4,
                      kv_heads=2, head_dim=32)


# --------------------------------------------------------------------------- #
# Stage-boundary instrument events (pinned order, multi-stage)
# --------------------------------------------------------------------------- #

class BoundaryRecorder(Instrument):
    def __init__(self):
        self.events = []

    def on_program_begin(self, program):
        self.events.append(("program_begin", program.names))

    def on_stage_begin(self, **ev):
        self.events.append(("stage_begin", ev["stage"], ev["index"],
                            ev["deps"]))

    def on_stage_end(self, **ev):
        self.events.append(("stage_end", ev["stage"]))

    def on_program_end(self, outputs):
        self.events.append(("program_end", tuple(outputs)))


def test_stage_boundary_event_stream_pinned():
    prog = lower_attention(SPEC)
    rec = BoundaryRecorder()
    Machine(CFG, instruments=[rec]).run(prog)
    names = ("qkv_proj", "attn_score", "attn_output", "out_proj")
    deps = ((), ("qkv_proj",), ("attn_score", "qkv_proj"),
            ("attn_output",))
    expect = [("program_begin", names)]
    for i, (n, d) in enumerate(zip(names, deps)):
        expect += [("stage_begin", n, i, d), ("stage_end", n)]
    expect.append(("program_end", names))
    assert rec.events == expect


def test_transforms_are_deterministic_and_int8():
    rng = np.random.default_rng(3)
    raw = rng.integers(-50_000, 50_000, size=(4, 8, 16)).astype(np.int32)
    a, b = requantize_int8(raw), requantize_int8(raw)
    assert a.dtype == np.int8 and np.array_equal(a, b)
    assert requantize_int8(np.zeros((2, 2))).dtype == np.int8
    p = softmax_int8(raw, scale=1e-4)
    assert p.dtype == np.int8 and p.min() >= 0 and p.max() <= 127


def test_program_report_merges_stage_reports():
    prog = lower_attention(SPEC)
    rep = Machine(CFG).run(prog)
    assert rep.pipeline is None                 # not a pipelined backend
    assert rep.total_cycles == rep.serial_cycles == sum(
        r.total_cycles for r in rep.stage_reports.values())
    assert rep["attn_score"] is rep.stage_reports["attn_score"]
    assert "4 stages" in str(rep)
    # per-node plans carry the node name (instrument/cycle cell keys)
    assert rep["attn_score"].plan.stage == "attn_score"


# --------------------------------------------------------------------------- #
# Program.merge: batch graphs of independent per-slot programs
# --------------------------------------------------------------------------- #

def _slot_attention(seed, t, heads=8, kv=2, hd=128, rows=1):
    """A standalone decode-slot attention pair (score -> softmax -> output)
    with concrete synthetic Q / KV-cache operands at context ``t``."""
    score_wl, out_wl = decode_attention_workloads(
        heads=heads, kv_heads=kv, head_dim=hd, context=t, m=rows)
    rng = np.random.default_rng(seed)
    q = rng.integers(-8, 9, size=(heads, rows, hd)).astype(np.int8)
    kvm = rng.integers(-8, 9, size=(2, kv, t, hd)).astype(np.int8)
    group = np.arange(heads) // (heads // kv)
    scale = 1.0 / (8.0 * 8.0 * math.sqrt(hd))
    return Program([
        ProgramStage(name=ATTN_SCORE, workload=score_wl, x=q,
                     w=np.transpose(kvm[0], (0, 2, 1))[group],
                     w_source=STATIONARY_ACT),
        ProgramStage(name=ATTN_OUTPUT, workload=out_wl,
                     x=Ref(ATTN_SCORE,
                           lambda o: softmax_int8(o, scale=scale)),
                     w=kvm[1][group], w_source=STATIONARY_ACT),
    ])


def test_program_merge_two_slot_decode_batch():
    """The merged-batch acceptance gate: two slots' attention programs
    merged into one graph run bit-exact vs per-slot serial execution,
    cross-validate at 0%, and overlap under the pipelined executor."""
    slots = [_slot_attention(11, 64), _slot_attention(22, 96)]
    merged = Program.merge(slots)
    merged.validate()
    assert merged.names == ("attn_score[0]", "attn_output[0]",
                            "attn_score[1]", "attn_output[1]")
    # slots are dependency-independent: their levels align as antichains
    assert [sorted(s.name for s in lv) for lv in merged.levels()] == [
        ["attn_score[0]", "attn_score[1]"],
        ["attn_output[0]", "attn_output[1]"],
    ]

    solo = [Machine(CFG).run(p) for p in slots]     # per-slot serial runs
    rep = Machine(CFG, backend=PipelinedExecutor()).run(merged)
    assert rep.ok
    # bit-exact vs per-slot serial execution (merging only re-schedules)
    for j, srep in enumerate(solo):
        for name in (ATTN_SCORE, ATTN_OUTPUT):
            assert np.array_equal(rep.outputs[f"{name}[{j}]"],
                                  srep.outputs[name]), f"{name}[{j}]"
    # 0% traffic AND cycle xval per merged stage
    for r in rep.stage_reports.values():
        assert all(e == 0.0 for e in r.traffic_validation.errors.values())
        assert r.cycle_validation.rel_err == 0.0
    pp = rep.pipeline
    assert pp.ok
    assert pp.overlapped_cycles < pp.serial_cycles
    # the serial side is exactly the two standalone runs, and every level
    # hides cycles — within the level AND across the level boundary (the
    # outgoing round belongs to the *other* slot's chain)
    assert pp.serial_cycles == sum(s.serial_cycles for s in solo)
    assert all(lv.hidden_cycles > 0 for lv in pp.levels)


def test_program_merge_tags_refs_and_external_producers():
    a, b = _slot_attention(1, 32), _slot_attention(2, 32)
    with pytest.raises(ValueError, match="tags"):
        Program.merge([a, b], tags=("only-one",))
    with pytest.raises(ProgramError, match="duplicate"):
        Program.merge([a, b], tags=("", ""))
    merged = Program.merge([a, b], tags=(":a", ":b"))
    assert set(merged.names) == {"attn_score:a", "attn_output:a",
                                 "attn_score:b", "attn_output:b"}
    # internal refs renamed along with their producers
    assert merged["attn_output:a"].x.producers == ("attn_score:a",)
    # a single program keeps its names by default
    assert Program.merge([a]).names == a.names
    # external refs pass through: per-slot programs may hang off shared
    # stages the caller adds around the merged graph
    ext = Program([ProgramStage(
        name="s", workload=_wl("s"),
        x=Ref("shared", lambda o: requantize_int8(o[0])),
        w=np.ones((128, 32), np.int8),
    )])
    m2 = Program.merge([ext], tags=("[0]",))
    assert m2["s[0]"].x.producers == ("shared",)
    with pytest.raises(ProgramError, match="unknown"):
        m2.validate()                    # dangling until the caller adds it
    m2.add(ProgramStage(name="shared", workload=_wl("shared", n=128)))
    m2.validate()


def test_pipelined_diamond_graph():
    """Diamond a -> (b, c) -> d: the independent middle pair overlaps
    fill + pipeline; the dependent edges prefetch exactly their fill
    (b/c/d's weights are concrete — they exist before a's output does),
    and outputs stay bit-exact vs NumPy."""
    rng = np.random.default_rng(5)
    x = rng.integers(-8, 9, size=(16, 128)).astype(np.int8)
    wa = rng.integers(-8, 9, size=(128, 64)).astype(np.int8)
    wb = rng.integers(-8, 9, size=(64, 64)).astype(np.int8)
    wc = rng.integers(-8, 9, size=(64, 64)).astype(np.int8)
    wd = rng.integers(-8, 9, size=(64, 64)).astype(np.int8)
    mid = Ref("a", lambda o: requantize_int8(o[0]))
    prog = Program([
        ProgramStage(name="a", workload=_wl("a", m=16, k=128, n=64),
                     x=x, w=wa),
        ProgramStage(name="b", workload=_wl("b", m=16, k=64, n=64),
                     x=mid, w=wb),
        ProgramStage(name="c", workload=_wl("c", m=16, k=64, n=64),
                     x=mid, w=wc),
        ProgramStage(name="d", workload=_wl("d", m=16, k=64, n=64),
                     x=Ref("b", lambda o: requantize_int8(o[0])),
                     w=wd, after=("c",)),
    ])
    assert [[s.name for s in lv] for lv in prog.levels()] == \
        [["a"], ["b", "c"], ["d"]]
    assert prog.ancestors()["d"] == frozenset({"a", "b", "c"})

    rep = Machine(CFG, backend=PipelinedExecutor()).run(prog)
    assert rep.ok
    ref = reference_outputs(prog)
    assert all(np.array_equal(rep.outputs[k], ref[k]) for k in ref)
    pp = rep.pipeline
    assert pp.ok
    assert pp.overlapped_cycles < pp.serial_cycles
    lv = pp.levels
    assert lv[0].hidden_cycles == 0                   # nothing precedes a
    # level 1: a -> b prefetches b's concrete weights (fill only), then
    # the independent b -> c boundary overlaps fill + pipeline
    ab, bb = _last_first(rep, "a", "b")
    _, cb = _last_first(rep, "b", "c")
    assert lv[1].hidden_cycles == (
        weight_prefetch_overlap_cycles(ab.stream, bb.fill,
                                       prev_drain=ab.drain)
        + boundary_overlap_cycles(bb.stream, cb.fill, cb.pipeline,
                                  prev_drain=bb.drain)
    )
    # level 2: c -> d is data-dependent but d's weights are concrete
    cb2, db = _last_first(rep, "c", "d")
    assert lv[2].hidden_cycles == weight_prefetch_overlap_cycles(
        cb2.stream, db.fill, prev_drain=cb2.drain) > 0


# --------------------------------------------------------------------------- #
# Multi-layer programs: explicit cross-layer dependencies
# --------------------------------------------------------------------------- #

def test_lower_serve_step_multi_layer_explicit_deps():
    """The multi-layer acceptance gate: a >=2-explicit-layer program whose
    layer-1 QKV streams layer-0's MLP output validates at 0% traffic AND
    cycle error vs simulate() and runs bit-exact vs NumPy."""
    rng = np.random.default_rng(7)
    ops = _proj_ops(rng, layers=2) + _mlp_ops(rng, layers=2)
    prog = _serve(ops, m=1, contexts=(8,), heads=4, kv_heads=2,
                  head_dim=32, layers=2, explicit_layers=2)
    assert prog.names == (
        "qkv_proj", "attn_score", "attn_output", "out_proj",
        "mlp_up", "mlp_down",
        "qkv_proj@1", "attn_score@1", "attn_output@1", "out_proj@1",
        "mlp_up@1", "mlp_down@1",
    )
    # the cross-layer link is an explicit data dependency, not a scalar
    assert prog["qkv_proj@1"].deps == ("mlp_down",)
    assert isinstance(prog["qkv_proj@1"].x, Ref)
    # each explicit layer carries its share of the layers multiplier
    assert all(s.workload.layers == 1 for s in prog)

    rep = Machine(CFG).run(prog)
    assert rep.ok
    ref = reference_outputs(prog)
    assert all(np.array_equal(rep.outputs[k], ref[k]) for k in ref)
    for r in rep.stage_reports.values():
        assert all(e == 0.0 for e in r.traffic_validation.errors.values())
        assert r.cycle_validation.rel_err == 0.0
    # one slot -> the layered graph is a pure chain; its stationary
    # operands (weights, per-slot KV caches) all exist before their
    # streamed inputs, so every boundary prefetches its fill — overlapped
    # strictly below serial, never beyond the prefetch bound
    pp = Machine(CFG, backend=PipelinedExecutor()).run(prog).pipeline
    assert pp.ok
    assert pp.overlapped_cycles < pp.serial_cycles


def test_lower_serve_step_multi_layer_validation():
    rng = np.random.default_rng(8)
    ops = _proj_ops(rng) + _mlp_ops(rng)             # layers=1 workloads
    with pytest.raises(ValueError, match="explicit_layers"):
        ServeStepSpec(projections=ops, m=1, explicit_layers=0)
    with pytest.raises(ValueError, match="cannot split"):
        ServeStepSpec(projections=ops, m=1, layers=1, explicit_layers=2)
    with pytest.raises(ValueError, match="mlp_down"):
        ServeStepSpec(projections=ops[:1], m=1, layers=2, explicit_layers=2)
    # layer count must divide every projection's layers multiplier too
    mixed = _proj_ops(rng, layers=3) + _mlp_ops(rng, layers=3)
    with pytest.raises(ValueError, match="cannot split"):
        ServeStepSpec(projections=mixed, m=1, layers=2, explicit_layers=2)


def test_lower_serve_batch_two_slots_two_layers():
    """Batch x layers: the merged decode-batch graph spans two explicit
    layers and overlaps across slots under the pipelined executor."""
    rng = np.random.default_rng(9)
    ops = _proj_ops(rng, layers=2) + _mlp_ops(rng, layers=2)
    prog = _serve(ops, m=2, contexts=(5, 9), heads=4, kv_heads=2,
                  head_dim=32, layers=2, explicit_layers=2)
    # slot tags then layer tags; per-slot position-dependent K/N per layer
    assert prog["attn_score[1]@1"].workload.n == 9
    assert prog["attn_output[0]@1"].workload.k == 5
    rep = Machine(CFG, backend=PipelinedExecutor()).run(prog)
    assert rep.ok
    ref = reference_outputs(prog)
    assert all(np.array_equal(rep.outputs[k], ref[k]) for k in ref)
    pp = rep.pipeline
    assert pp.ok
    assert pp.overlapped_cycles < pp.serial_cycles
    with pytest.raises(ValueError, match="slot context"):
        ServeStepSpec(projections=ops, contexts=(), heads=4, kv_heads=2,
                      head_dim=32)
