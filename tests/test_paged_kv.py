"""Paged KV-cache subsystem: allocator invariants, bit-exact serving,
page-granular Legion traffic.

The acceptance gates of the paged-KV PR:

* :class:`~repro.serve.paged_kv.PageAllocator` holds its invariants under
  arbitrary alloc/extend/free/evict sequences — no double free,
  ``free + pinned == total`` after every operation, per-request last-page
  waste strictly under one page, deterministic page tables (seeded sweep
  always runs; hypothesis additionally shrinks when installed);
* a paged :class:`~repro.serve.engine.ServeEngine` produces **bit-exact**
  outputs vs the contiguous engine on the same request trace — including
  across forced evictions (preemption + re-prefill), in both legacy and
  in-flight batching modes;
* page-granular lowering changes traffic accounting, never compute:
  serial cycles equal the contiguous run exactly, the weight-byte delta
  equals the accounted page-boundary waste exactly, and the measured page
  channel cross-validates against ``simulate()`` at 0%;
* the planning/observability surfaces agree with the allocator:
  ``kv_cache.plan(page_tokens=)`` pool geometry, timeline page cells,
  lowerer page-table validation, and the backend's paged pricing.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import dlegion
from repro.core.workloads import ATTN_OUTPUT, ATTN_SCORE, GEMMWorkload, \
    decode_attention_workloads
from repro.legion import Machine, ServeStepSpec, lower
from repro.models import build_model
from repro.obs import TimelineTracer
from repro.serve import (
    LegionServeBackend,
    PageAllocator,
    PagedKVCache,
    PageError,
    ServeEngine,
)
from repro.serve.engine import prepare_params
from repro.serve.kv_cache import plan
from repro.serve.legion_backend import extract_projection_ops

ACCEL = dlegion()


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced(get_config("smollm-360m"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    return cfg, api, params


@pytest.fixture(scope="module")
def bitnet():
    cfg = reduced(get_config("bitnet-1.58b"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    return cfg, api, params


# --------------------------------------------------------------------------- #
# PageAllocator: lifecycle, determinism, errors
# --------------------------------------------------------------------------- #

def test_allocator_lifecycle_and_determinism():
    a = PageAllocator(total_pages=8, page_tokens=4)
    assert a.alloc(1, 5) == (0, 1)        # ceil(5/4) = 2 pages, lowest first
    assert a.alloc(2, 4) == (2,)
    assert a.free_pages == 5 and a.pinned_pages == 3
    assert a.tokens(1) == 5 and a.waste_tokens(1) == 3
    assert a.waste_tokens(2) == 0
    # growth within the last page allocates nothing
    assert a.extend(1, 8) and a.page_table(1) == (0, 1)
    assert a.extend(1, 9) and a.page_table(1) == (0, 1, 3)
    # free returns pages to the pool; the NEXT alloc reuses the lowest ids
    assert a.free(1) == 3
    assert a.alloc(3, 4) == (0,)
    st = a.stats()
    assert st.free_pages + st.pinned_pages == st.total_pages == 8
    assert st.active_requests == 2 and st.evictions == 0
    assert st.pinned_tokens == 2 * 4
    assert st.waste_frac == 0.0
    # identical call sequences -> identical tables
    b1, b2 = PageAllocator(6, 4), PageAllocator(6, 4)
    for alloc in (b1, b2):
        alloc.alloc(1, 6), alloc.alloc(2, 4), alloc.free(1), alloc.alloc(3, 9)
    assert b1.page_table(3) == b2.page_table(3)
    assert b1.eviction_order() == b2.eviction_order() == [3, 2]


def test_allocator_atomicity_and_errors():
    a = PageAllocator(total_pages=3, page_tokens=4)
    assert a.alloc(1, 8) == (0, 1)
    # shortfall: nothing allocated, nothing mutated
    assert a.alloc(2, 9) is None
    assert a.free_pages == 1 and not a.holds(2)
    # failed extend keeps the old reservation whole
    assert a.alloc(2, 2) == (2,)
    assert not a.extend(2, 12)
    assert a.page_table(2) == (2,) and a.tokens(2) == 2
    with pytest.raises(PageError):
        a.alloc(1, 4)                     # already holds pages
    with pytest.raises(PageError):
        a.extend(2, 1)                    # shrink
    with pytest.raises(PageError):
        a.extend(9, 4)                    # unknown uid
    a.free(1)
    with pytest.raises(PageError):
        a.free(1)                         # double free
    with pytest.raises(PageError):
        a.page_table(1)
    with pytest.raises(ValueError):
        a.alloc(7, 0)
    with pytest.raises(ValueError):
        PageAllocator(0, 4)
    with pytest.raises(ValueError):
        PageAllocator(4, 0)
    # eviction accounting
    assert a.evict(2) == 1 and a.evictions == 1
    assert a.stats().evictions == 1


def _check_invariants(a: PageAllocator, lengths: dict) -> None:
    st = a.stats()
    assert st.free_pages + st.pinned_pages == st.total_pages
    assert st.free_pages >= 0 and st.pinned_pages >= 0
    # page tables partition: no page held twice, none both free and held
    held = [p for u in lengths for p in a.page_table(u)]
    assert len(held) == len(set(held))
    assert st.pinned_pages == len(held)
    for u, toks in lengths.items():
        assert a.tokens(u) == toks
        assert 0 <= a.waste_tokens(u) < a.page_tokens
        assert len(a.page_table(u)) == a.pages_needed(toks)
    assert st.waste_tokens == sum(a.waste_tokens(u) for u in lengths)


def _random_ops(a: PageAllocator, rng, steps: int) -> None:
    """Drive ``steps`` random lifecycle ops, checking every invariant."""
    lengths: dict = {}
    next_uid = 0
    for _ in range(steps):
        op = rng.choice(["alloc", "extend", "free", "evict"])
        if op == "alloc" or not lengths:
            toks = int(rng.integers(1, 4 * a.page_tokens))
            got = a.alloc(next_uid, toks)
            if got is not None:
                lengths[next_uid] = toks
            next_uid += 1
        elif op == "extend":
            uid = int(rng.choice(list(lengths)))
            toks = lengths[uid] + int(rng.integers(0, 2 * a.page_tokens))
            if a.extend(uid, toks):
                lengths[uid] = toks
        else:
            uid = int(rng.choice(list(lengths)))
            (a.evict if op == "evict" else a.free)(uid)
            del lengths[uid]
            with pytest.raises(PageError):
                a.free(uid)               # double free always raises
        _check_invariants(a, lengths)


@pytest.mark.parametrize("seed", range(6))
def test_allocator_random_sequences_hold_invariants(seed):
    """Always-running seeded property sweep (no hypothesis needed)."""
    rng = np.random.default_rng(seed)
    total = int(rng.integers(2, 24))
    page = int(rng.integers(1, 9))
    _random_ops(PageAllocator(total, page), rng, steps=60)


# --------------------------------------------------------------------------- #
# Hypothesis property tests (guarded import — the deterministic sweep above
# must keep running when hypothesis is absent, so no module-level skip)
# --------------------------------------------------------------------------- #

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                     # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(
        total=st.integers(1, 32),
        page=st.integers(1, 16),
        steps=st.integers(1, 80),
        seed=st.integers(0, 2**16),
    )
    def test_allocator_property(total, page, steps, seed):
        _random_ops(PageAllocator(total, page), np.random.default_rng(seed),
                    steps)


# --------------------------------------------------------------------------- #
# PagedKVCache view + kv_cache.plan page geometry
# --------------------------------------------------------------------------- #

def test_paged_cache_view_and_write_gating():
    kv = PagedKVCache(total_pages=4, page_tokens=8)
    assert kv.admit(5, 10)
    assert kv.page_tables([5]) == [(0, 1)]
    # the cache view refuses writes that outrun the reservation
    with pytest.raises(PageError):
        kv.write_slot(None, None, 0, uid=9, tokens=4)   # no reservation
    with pytest.raises(PageError):
        kv.write_slot(None, None, 0, uid=5, tokens=17)  # > 2 pages
    assert kv.extend(5, 17)
    assert kv.eviction_order() == [5]
    assert kv.release(5) == 3
    assert not kv.holds(5)


def test_plan_page_geometry_matches_allocator(bitnet):
    cfg, _api, _params = bitnet
    contiguous = plan(cfg, batch=4, max_seq=60, hbm_bytes_per_chip=8 << 30,
                      chips=1)
    budget = plan(cfg, batch=4, max_seq=60, hbm_bytes_per_chip=8 << 30,
                  chips=1, page_tokens=16)
    assert budget.page_tokens == 16
    assert budget.pages_per_request == 4                 # ceil(60/16)
    assert budget.pages_total == 16
    assert budget.bytes_per_page == budget.bytes_per_token * 16
    # page quantization IS the extra footprint: total = contiguous + waste
    assert budget.page_waste_bytes == 4 * budget.bytes_per_token * 4
    assert budget.total_bytes == \
        contiguous.total_bytes + budget.page_waste_bytes
    # the budget builds the allocator the engine would actually run with
    kv = PagedKVCache.from_budget(budget)
    assert kv.allocator.total_pages == 16
    assert kv.page_tokens == 16
    with pytest.raises(ValueError):
        PagedKVCache.from_budget(contiguous)             # no page geometry
    with pytest.raises(ValueError):
        plan(cfg, batch=4, max_seq=60, hbm_bytes_per_chip=8 << 30,
             chips=1, page_tokens=0)


# --------------------------------------------------------------------------- #
# Workload annotation + page-granular traffic: 0% cross-validation
# --------------------------------------------------------------------------- #

def test_decode_attention_workloads_page_annotation():
    score, output = decode_attention_workloads(
        heads=16, kv_heads=4, head_dim=64, context=21, page_tokens=8)
    assert (score.stage, score.page_axis) == (ATTN_SCORE, "n")
    assert (output.stage, output.page_axis) == (ATTN_OUTPUT, "k")
    for w in (score, output):
        assert w.page_token_count == 21
        assert w.page_count == 3
        assert w.page_waste_tokens == 3
    plain, _ = decode_attention_workloads(heads=16, kv_heads=4, head_dim=64,
                                          context=21)
    assert plain.page_tokens == 0 and plain.page_count == 0
    with pytest.raises(ValueError):
        GEMMWorkload(stage=ATTN_SCORE, m=1, k=64, n=21, weight_bits=8,
                     page_tokens=8)                      # axis missing
    with pytest.raises(ValueError):
        GEMMWorkload(stage=ATTN_SCORE, m=1, k=64, n=21, weight_bits=8,
                     page_tokens=8, page_axis="m")


@pytest.mark.parametrize("page_tokens", [8, 16])
def test_page_traffic_cross_validates_at_zero(page_tokens):
    """The tentpole traffic gate: page-granular lowering leaves every
    cycle untouched, adds exactly the page-boundary waste to weight
    traffic, and the measured page channel equals ``simulate()`` at 0%."""
    for context in (5, 23, 64):
        ws_c = decode_attention_workloads(heads=16, kv_heads=4, head_dim=128,
                                          context=context)
        ws_p = decode_attention_workloads(heads=16, kv_heads=4, head_dim=128,
                                          context=context,
                                          page_tokens=page_tokens)
        machine = Machine(ACCEL)
        tv_c, cv_c = machine.cross_validate(ws_c, check_outputs=True)
        tv_p, cv_p = machine.cross_validate(ws_p, check_outputs=True)
        for v in tv_c + tv_p:
            assert all(e == 0.0 for e in v.errors.values()), str(v)
        # paging may never change a cycle
        for vc, vp in zip(cv_c, cv_p):
            assert vc.measured == vp.measured, (context, vc.stage)
        # the weight-byte delta IS the accounted last-page padding
        for vc, vp in zip(tv_c, tv_p):
            delta = vp.measured.weight_bytes - vc.measured.weight_bytes
            assert delta == pytest.approx(vp.measured.page_waste_bytes)
            assert vp.measured.page_fetches > 0
            assert vc.measured.page_fetches == 0


def test_timeline_page_cells_and_chrome_export():
    """Page fetches land on timeline cells without breaking the strict
    event-order checker, and the Chrome export carries them."""
    ws = decode_attention_workloads(heads=16, kv_heads=4, head_dim=128,
                                    context=23, page_tokens=8)
    tracer = TimelineTracer(ACCEL)
    machine = Machine(ACCEL, instruments=[tracer])
    for w in ws:
        machine.run(w)
    assert all(tl.complete for tl in tracer.programs)
    cells = [c for tl in tracer.programs
             for c in tl.cells.values() if c.page_fetches]
    assert cells, "paged run produced no page cells"
    # cells log RAW per-assignment page events (no multicast dedup — that
    # is TrafficTracer's job), so the invariants are per-cell sanity plus
    # export fidelity, not equality with the deduped simulate() totals
    for c in cells:
        assert c.page_bytes > 0
        assert 0 <= c.page_waste_bytes < c.page_bytes
    paged_args = [e["args"] for e in tracer.to_chrome()["traceEvents"]
                  if e.get("args", {}).get("page_fetches")]
    assert paged_args
    # both placements (serial + overlapped pids) carry every page cell
    assert sum(a["page_fetches"] for a in paged_args) == \
        2 * sum(c.page_fetches for c in cells)
    assert sum(a["page_waste_bytes"] for a in paged_args) == \
        pytest.approx(2 * sum(c.page_waste_bytes for c in cells))
    # contiguous runs stay page-free end to end
    tracer2 = TimelineTracer(ACCEL)
    machine2 = Machine(ACCEL, instruments=[tracer2])
    for w in decode_attention_workloads(heads=16, kv_heads=4, head_dim=128,
                                        context=23):
        machine2.run(w)
    assert not any(c.page_fetches for tl in tracer2.programs
                   for c in tl.cells.values())


def test_lower_serve_step_validates_page_tables(bitnet):
    cfg, _api, params = bitnet
    ops = extract_projection_ops(cfg, params)
    hd = cfg.head_dim_
    kw = dict(projections=ops, m=2, contexts=(9, 17), heads=cfg.n_heads,
              kv_heads=cfg.kv_heads, head_dim=hd, page_tokens=8)
    prog = lower(ServeStepSpec(page_tables=((0, 1), (2, 3, 4)), **kw))
    assert any(s.workload.page_tokens == 8 for s in prog.stages)
    with pytest.raises(ValueError, match="without page_tokens"):
        ServeStepSpec(projections=ops, m=2, contexts=(9, 17),
                      heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=hd,
                      page_tables=((0, 1), (2, 3, 4)))
    with pytest.raises(ValueError, match="page tables for"):
        ServeStepSpec(page_tables=((0, 1),), **kw)
    with pytest.raises(ValueError, match="needs"):
        ServeStepSpec(page_tables=((0, 1), (2, 3)), **kw)
    with pytest.raises(ValueError, match="chunk page tables"):
        ServeStepSpec(projections=ops, chunks=[(4, 9)], m=1,
                      contexts=(13,), heads=cfg.n_heads,
                      kv_heads=cfg.kv_heads, head_dim=hd, page_tokens=8,
                      chunk_page_tables=((0, 1), (2,)),
                      page_tables=((3, 4),))


# --------------------------------------------------------------------------- #
# Paged engine: bit-exact vs contiguous, including forced preemption
# --------------------------------------------------------------------------- #

def _run_engine(api, params, vocab, prompts, *, paged=None, chunk=None):
    eng = ServeEngine(api, params, max_slots=3, max_seq=32, paged_kv=paged,
                      prefill_chunk_tokens=chunk)
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    done = eng.run_until_done()
    return eng, {r.uid: list(r.output) for r in done}


def test_paged_engine_bitexact_including_preemption(smollm):
    """The tentpole numeric gate: the paged engine's outputs equal the
    contiguous engine's exactly — with an ample pool (no evictions) AND
    with a pool tight enough to force preemption + re-prefill, in both
    legacy and in-flight batching modes."""
    cfg, api, params = smollm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, rng.integers(4, 12))
               for _ in range(6)]

    _e0, ref = _run_engine(api, params, cfg.vocab, prompts)
    e1, ample = _run_engine(api, params, cfg.vocab, prompts,
                            paged=PagedKVCache(total_pages=64, page_tokens=4))
    assert ample == ref
    assert e1.preemptions == 0

    e2, tight = _run_engine(api, params, cfg.vocab, prompts,
                            paged=PagedKVCache(total_pages=10, page_tokens=4))
    assert tight == ref
    assert e2.preemptions > 0, "the tight pool must evict"
    assert sum(r.preempted for r in e2.finished) == e2.preemptions
    phases = [e["phase"] for e in e2.step_log]
    assert "preempt" in phases
    # evicted requests re-enter at the queue head and re-prefill
    assert e2.paged_kv.stats().evictions == e2.preemptions
    assert e2.paged_kv.stats().pinned_pages == 0          # all retired

    _e3, ref_if = _run_engine(api, params, cfg.vocab, prompts, chunk=6)
    e4, tight_if = _run_engine(api, params, cfg.vocab, prompts, chunk=6,
                               paged=PagedKVCache(total_pages=10,
                                                  page_tokens=4))
    assert ref_if == ref
    assert tight_if == ref
    assert e4.preemptions > 0


def test_paged_engine_rejects_undersized_pool(smollm):
    cfg, api, params = smollm
    with pytest.raises(ValueError, match="page"):
        # 7 pages x 4 tokens can never hold one max_seq=32 request
        ServeEngine(api, params, max_slots=2, max_seq=32,
                    paged_kv=PagedKVCache(total_pages=7, page_tokens=4))


# --------------------------------------------------------------------------- #
# Backend pricing: serial cycles unchanged, traffic delta == waste, 0% xval
# --------------------------------------------------------------------------- #

def test_backend_paged_pricing_and_cross_validation(bitnet):
    cfg, api, params = bitnet
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab, rng.integers(4, 12))
               for _ in range(4)]

    def run(page_tokens=0, pool=None):
        backend = LegionServeBackend(ACCEL, cfg, params,
                                     page_tokens=page_tokens)
        paged = PagedKVCache(**pool) if pool else None
        eng = ServeEngine(api, params, max_slots=2, max_seq=32,
                          paged_kv=paged)
        backend.attach(eng)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run_until_done()
        return eng, backend

    e0, b0 = run()
    e1, b1 = run(page_tokens=8, pool=dict(total_pages=64, page_tokens=8))
    assert {r.uid: r.output for r in e0.finished} \
        == {r.uid: r.output for r in e1.finished}
    s0, s1 = b0.summary(), b1.summary()
    # page annotation changes WHAT traffic is accounted, never a cycle
    assert s0["cycles"] == s1["cycles"]
    assert s0["serial_cycles_per_step"] == s1["serial_cycles_per_step"]
    assert s0["overlapped_cycles_per_step"] == s1["overlapped_cycles_per_step"]
    # the whole-trace weight delta is exactly the page-boundary waste
    assert s1["weight_bytes"] - s0["weight_bytes"] \
        == pytest.approx(s1["page_waste_bytes"])
    assert s1["page_fetches"] > 0 and s0["page_fetches"] == 0
    assert 0 < s1["page_waste_frac"] < 1
    assert s1["page_fetch_bytes"] > s1["page_waste_bytes"]
    # measured page channel == simulate(), decode and mixed graphs alike
    tv, cv = b1.cross_validate(decode_contexts=(13,))
    for v in tv:
        assert all(e == 0.0 for e in v.errors.values()), str(v)
    for v in cv:
        assert v.ok, str(v)
    tvm, _cvm = b1.cross_validate([(4, 9)], (7, 13))
    for v in tvm:
        assert all(e == 0.0 for e in v.errors.values()), str(v)
    # the measured budget carries the backend's own page geometry
    budget = b1.cache_budget(batch=2, max_seq=32,
                             hbm_bytes_per_chip=8 << 30, chips=1)
    assert budget.page_tokens == 8
    assert budget.pages_total == 2 * 4
    assert PagedKVCache.from_budget(budget).page_tokens == 8
