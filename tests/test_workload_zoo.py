"""Workload zoo: MoE + SSD lowering behind the unified ``legion.lower``.

What's covered (ISSUE PR 10):

- `lower_moe` turns router top-k into program-level ZTB sparsity: skipped
  experts move zero bytes, a k-of-E step's weight traffic equals the
  dense-E step minus the skipped experts' stationary bytes EXACTLY, and
  outputs stay bit-exact vs the NumPy reference (seeded property test).
- `lower_ssd` maps the chunked Mamba-2 SSD scan onto ProgramStages with
  the recurrent state as a cross-chunk stationary Ref; bit-exact, 0% xval.
- `lower(spec)` dispatches every lowering; the legacy ``lower_*`` entry
  points remain passing aliases.
- Spec dataclasses validate at construction (bad combos raise).
- The full 12-config ``repro.configs`` registry runs through
  ``Machine.run(Program)`` — the CI matrix.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import arch_names, get_config, reduced
from repro.core.config import dlegion
from repro.core.workloads import ROUTER
from repro.legion import (
    AttentionLoweringSpec,
    HybridSpec,
    Machine,
    MoESpec,
    SSDSpec,
    ServeStepSpec,
    lower,
    lower_attention,
    lower_moe,
    lower_ssd,
    moe_stage_names,
    reference_outputs,
    ssd_stage_names,
    zoo_spec,
)

CFG = dlegion()


def _worst_err(rep):
    worst = 0.0
    for name in rep.outputs:
        r = rep[name]
        if r.traffic_validation is not None:
            worst = max(worst, *r.traffic_validation.errors.values())
        if r.cycle_validation is not None:
            worst = max(worst, r.cycle_validation.rel_err)
    return worst


def _assert_bit_exact(rep, prog):
    ref = reference_outputs(prog)
    assert set(rep.outputs) == set(ref)
    for name, out in rep.outputs.items():
        assert np.array_equal(out, ref[name]), name


# --------------------------------------------------------------------------- #
# MoE: expert-skip program sparsity
# --------------------------------------------------------------------------- #

def test_lower_moe_bit_exact_and_zero_xval():
    spec = MoESpec(d_model=64, d_ff=48, n_experts=8, top_k=2, tokens=16)
    prog = lower_moe(spec)
    # router + (up, down) per expert
    assert len(prog) == 1 + 2 * spec.n_experts
    rep = Machine(CFG).run(prog)
    assert rep.ok
    _assert_bit_exact(rep, prog)
    assert _worst_err(rep) == 0.0


def test_lower_moe_skipped_experts_move_zero_bytes():
    spec = MoESpec(d_model=64, d_ff=48, n_experts=8, top_k=2, tokens=16)
    rep = Machine(CFG).run(lower_moe(spec))
    chosen, skipped = spec.routing()
    assert len(chosen) == spec.top_k
    assert len(skipped) == spec.n_experts - spec.top_k
    for e in skipped:
        for name in moe_stage_names(e):
            t = rep[name].traffic
            assert (t.weight_bytes, t.act_bytes, t.psum_bytes) == (0, 0, 0)
            # output is still produced (zeros) and matches the reference
            assert not rep.outputs[name].any()
    for e in chosen:
        for name in moe_stage_names(e):
            assert rep[name].traffic.weight_bytes > 0


def test_moe_chosen_override_and_routing_validation():
    spec = MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=2, tokens=8,
                   chosen=(3, 1))
    assert spec.routing() == ((1, 3), (0, 2))
    prog = lower_moe(spec)
    assert Machine(CFG).run(prog).ok
    with pytest.raises(ValueError, match="duplicate"):
        MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=2, tokens=8,
                chosen=(1, 1))
    with pytest.raises(ValueError, match="chosen"):
        MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=2, tokens=8,
                chosen=(0, 1, 2))
    with pytest.raises(ValueError, match="outside"):
        MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=2, tokens=8,
                chosen=(0, 7))


@pytest.mark.parametrize("seed", range(5))
def test_moe_traffic_equals_dense_minus_skipped_property(seed):
    """Seeded property: random (E, k, shapes) -> the k-of-E program's
    weight traffic equals dense-E minus the skipped experts' stationary
    bytes, EXACTLY (== on floats: dedup keys are per-stage, so per-stage
    totals sum with no rounding); outputs are bit-exact vs the dense
    program with the unchosen experts' weights zeroed."""
    rng = np.random.default_rng(1000 + seed)
    e = int(rng.integers(3, 9))
    k = int(rng.integers(1, e))
    spec = MoESpec(
        d_model=int(rng.integers(2, 6)) * 16,
        d_ff=int(rng.integers(1, 5)) * 16,
        n_experts=e, top_k=k,
        tokens=int(rng.integers(1, 4)) * 8,
        seed=seed,
    )
    dense = dataclasses.replace(spec, top_k=e, chosen=None)
    m = Machine(CFG)
    rep_k = m.run(lower_moe(spec))
    rep_d = m.run(lower_moe(dense))
    assert rep_k.ok and rep_d.ok

    chosen, skipped = spec.routing()
    total = lambda rep: sum(rep[n].traffic.weight_bytes
                            for n in rep.outputs)
    skipped_bytes = sum(rep_d[n].traffic.weight_bytes
                        for ex in skipped for n in moe_stage_names(ex))
    assert total(rep_k) == total(rep_d) - skipped_bytes
    if skipped:
        assert skipped_bytes > 0

    # bit-exact vs dense-with-zeroed-unchosen: zero the skipped experts'
    # weights in the dense program (ztb left off) -> same numerics
    from repro.legion import Program

    dense_prog = lower_moe(dense)
    zeroed = Program()
    skip_stages = {n for ex in skipped for n in moe_stage_names(ex)}
    for st in dense_prog:
        if st.name in skip_stages:
            st = dataclasses.replace(st, w=np.zeros_like(st.w))
        zeroed.add(st)
    rep_z = m.run(zeroed)
    for name in rep_k.outputs:
        assert np.array_equal(rep_k.outputs[name], rep_z.outputs[name]), name


def test_moe_router_gates_expert_stages():
    prog = lower_moe(MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=1,
                             tokens=8))
    for e in range(4):
        up, down = moe_stage_names(e)
        assert ROUTER in prog[up].deps
        assert up in prog[down].deps


# --------------------------------------------------------------------------- #
# SSD: chunked scan with the recurrent state as a stationary Ref
# --------------------------------------------------------------------------- #

def test_lower_ssd_bit_exact_and_zero_xval():
    spec = SSDSpec(heads=4, chunk=32, state=16, head_dim=16, chunks=3)
    prog = lower_ssd(spec)
    # per chunk: score/intra/state, plus inter for chunks >= 1
    assert len(prog) == 3 * spec.chunks + (spec.chunks - 1)
    rep = Machine(CFG).run(prog)
    assert rep.ok
    _assert_bit_exact(rep, prog)
    assert _worst_err(rep) == 0.0


def test_ssd_state_is_cross_chunk_stationary_ref():
    from repro.legion import Ref
    from repro.legion.program import STATIONARY_ACT

    prog = lower_ssd(SSDSpec(heads=2, chunk=16, state=8, head_dim=8,
                             chunks=3))
    for c in range(1, 3):
        inter = prog[ssd_stage_names(c)[3]]
        assert isinstance(inter.w, Ref)
        assert inter.w_source == STATIONARY_ACT
        # the recurrence reaches back to EVERY earlier chunk's state stage
        assert inter.w.producers == tuple(ssd_stage_names(j)[2]
                                          for j in range(c))
    # chunk 0 has no inter stage (no prior state)
    assert ssd_stage_names(0)[3] not in prog


def test_ssd_single_chunk_has_no_recurrence():
    prog = lower_ssd(SSDSpec(heads=2, chunk=16, state=8, head_dim=8))
    assert len(prog) == 3
    assert Machine(CFG).run(prog).ok


# --------------------------------------------------------------------------- #
# The unified dispatcher + spec validation
# --------------------------------------------------------------------------- #

def test_lower_dispatches_attention_and_matches_alias():
    spec = AttentionLoweringSpec(heads=4, kv_heads=2, head_dim=32,
                                 hidden=128, seq_len=64, seed=7)
    via_dispatch = lower(spec)
    via_alias = lower_attention(spec.attention_spec(), seed=7)
    assert via_dispatch.names == via_alias.names
    ref_a, ref_b = reference_outputs(via_dispatch), \
        reference_outputs(via_alias)
    for name in ref_a:
        assert np.array_equal(ref_a[name], ref_b[name])


def test_lower_dispatches_serve_step_and_matches_alias():
    from repro.core.workloads import (HEAD_PER_UNIT, N_PARTITION, OUT_PROJ,
                                      QKV_PROJ, GEMMWorkload)
    from repro.serve.legion_backend import ProjectionOp

    rng = np.random.default_rng(0)
    d, hd, h, kv = 128, 32, 4, 2
    tern = lambda *s: rng.integers(-1, 2, size=s).astype(np.int8)
    ops = [
        ProjectionOp(GEMMWorkload(stage=QKV_PROJ, m=1, k=d, n=hd,
                                  weight_bits=2, count=h + 2 * kv,
                                  shared_input=True,
                                  mapping=HEAD_PER_UNIT),
                     tern(h + 2 * kv, d, hd)),
        ProjectionOp(GEMMWorkload(stage=OUT_PROJ, m=1, k=h * hd, n=d,
                                  weight_bits=2, count=1,
                                  mapping=N_PARTITION),
                     tern(1, h * hd, d)),
    ]
    spec = ServeStepSpec(projections=ops, m=2, contexts=(5, 9), heads=h,
                         kv_heads=kv, head_dim=hd)
    via_dispatch = lower(spec)
    assert via_dispatch.names == (
        "qkv_proj", "attn_score[0]", "attn_output[0]",
        "attn_score[1]", "attn_output[1]", "out_proj")
    assert Machine(CFG).run(via_dispatch).ok
    # prefill chunks merge beside the decode part under {p<i>} / {d} tags
    mixed = lower(ServeStepSpec(projections=ops, chunks=((4, 9),), m=2,
                                contexts=(5, 9), heads=h, kv_heads=kv,
                                head_dim=hd))
    assert mixed.names == tuple(
        f"{n}{{p0}}" for n in ("qkv_proj", "attn_score", "attn_output",
                               "out_proj")
    ) + tuple(f"{n}{{d}}" for n in via_dispatch.names)
    assert Machine(CFG).run(mixed).ok
    # without attention heads every part lowers to its projections alone
    bare = lower(ServeStepSpec(projections=ops, chunks=((4, 9),), m=2))
    assert bare.names == ("qkv_proj{p0}", "out_proj{p0}",
                          "qkv_proj{d}", "out_proj{d}")

    # kwargs normalized onto the spec: bad combos raise at construction
    with pytest.raises(ValueError, match="cannot split"):
        ServeStepSpec(projections=ops, m=3, contexts=(4, 5), heads=h,
                      kv_heads=kv, head_dim=hd)
    with pytest.raises(ValueError, match="projection"):
        ServeStepSpec(projections=(), m=1)


def test_lower_hybrid_sequences_ssm_after_attention():
    spec = HybridSpec(
        attention=AttentionLoweringSpec(heads=4, kv_heads=2, head_dim=32,
                                        hidden=128, seq_len=32),
        ssd=SSDSpec(heads=2, chunk=16, state=8, head_dim=8, chunks=2),
    )
    prog = lower(spec)
    rep = Machine(CFG).run(prog)
    assert rep.ok
    _assert_bit_exact(rep, prog)
    gates = {a for st in prog if st.name.endswith("{ssm}")
             for a in st.after}
    assert gates == {"out_proj{attn}"}


def test_spec_construction_errors():
    with pytest.raises(ValueError, match="weight_bits"):
        MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=2, tokens=8,
                weight_bits=3)
    with pytest.raises(ValueError, match="top_k"):
        MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=5, tokens=8)
    with pytest.raises(ValueError, match="d_ff"):
        MoESpec(d_model=32, d_ff=0, n_experts=4, top_k=2, tokens=8)
    # paging is a serve-spec concept; everywhere else it raises
    with pytest.raises(ValueError, match="page"):
        MoESpec(d_model=32, d_ff=16, n_experts=4, top_k=2, tokens=8,
                page_tokens=16)
    with pytest.raises(ValueError, match="page"):
        SSDSpec(heads=2, chunk=16, state=8, head_dim=8,
                page_tables=[[0]])
    with pytest.raises(ValueError, match="int8"):
        SSDSpec(heads=2, chunk=16, state=8, head_dim=8, weight_bits=2)
    with pytest.raises(ValueError, match="divisible"):
        AttentionLoweringSpec(heads=4, kv_heads=3, head_dim=32, hidden=128,
                              seq_len=32)
    with pytest.raises(ValueError, match="layers"):
        SSDSpec(heads=2, chunk=16, state=8, head_dim=8, layers=0)
    with pytest.raises(ValueError, match="sub-spec"):
        HybridSpec(ssd=SSDSpec(heads=2, chunk=16, state=8, head_dim=8))
    with pytest.raises(TypeError, match="LoweringSpec"):
        lower("not a spec")


def test_spec_tag_suffixes_stage_names():
    prog = lower(MoESpec(d_model=32, d_ff=16, n_experts=2, top_k=1,
                         tokens=8, tag="{ffn}"))
    assert all(name.endswith("{ffn}") for name in prog.names)
    assert Machine(CFG).run(prog).ok


# --------------------------------------------------------------------------- #
# The CI matrix: every registry config through Machine.run(Program)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("arch", arch_names())
def test_registry_matrix_runs_through_machine(arch):
    cfg = reduced(get_config(arch))
    spec = zoo_spec(cfg)
    prog = lower(spec)
    rep = Machine(CFG).run(prog)
    assert rep.ok
    _assert_bit_exact(rep, prog)
    assert _worst_err(rep) == 0.0
