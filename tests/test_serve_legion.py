"""Serve-path Legion backend: engine steps executed through the runtime.

The acceptance gate for the serve bridge: a ServeEngine's prefill/decode
steps must lower to one Program each — projection GEMMs AND the act-to-act
attention stages over each slot's KV context — execute through the Legion
runtime bit-exactly, accumulate per-request traffic/cycle tallies covering
the full step, and cross-validate against ``simulate()`` on the same
workloads.  Measured per-token decode cycles feed ``serve.kv_cache.plan``
for a latency-aware cache budget.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import dlegion
from repro.legion import ServeStepSpec
from repro.models import build_model
from repro.serve import LegionServeBackend, ServeEngine
from repro.serve.engine import prepare_params
from repro.serve.kv_cache import plan as kv_plan
from repro.serve.legion_backend import (
    MLP_DOWN,
    MLP_UP,
    extract_projection_ops,
)

ACCEL = dlegion()    # 8 Legions x 8 cores x 16x16


@pytest.fixture(scope="module")
def served():
    cfg = reduced(get_config("bitnet-1.58b"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    return cfg, api, params


def test_extract_projection_ops_shapes(served):
    cfg, _api, params = served
    ops = extract_projection_ops(cfg, params)
    by_stage = {op.workload.stage: op for op in ops}
    assert set(by_stage) == {"qkv_proj", "out_proj", MLP_UP, MLP_DOWN}
    hd = cfg.head_dim_
    qkv = by_stage["qkv_proj"]
    assert qkv.workload.count == cfg.n_heads + 2 * cfg.kv_heads
    assert qkv.weights.shape == (qkv.workload.count, cfg.d_model, hd)
    assert qkv.weights.dtype == np.int8
    assert set(np.unique(qkv.weights)) <= {-1, 0, 1}     # ternary
    assert by_stage["out_proj"].weights.shape == \
        (1, cfg.n_heads * hd, cfg.d_model)
    assert by_stage[MLP_UP].weights.shape == (2, cfg.d_model, cfg.d_ff)
    assert by_stage[MLP_DOWN].weights.shape == (1, cfg.d_ff, cfg.d_model)
    for op in ops:
        assert op.workload.layers == cfg.layers
        assert op.workload.weight_bits == 2


def test_extract_projection_ops_takes_served_codes(served):
    """The backend's weights are the served codes themselves; latent
    weights give the same codes, quantized as the engine would."""
    cfg, api, params = served
    layer = cfg.layers - 1
    codes = extract_projection_ops(cfg, params, layer=layer)
    latent = extract_projection_ops(
        cfg, api.init(jax.random.PRNGKey(0)), layer=layer)
    by_stage = {op.workload.stage: op.weights for op in codes}
    wo = np.asarray(params["blocks"]["attn"]["wo"].q[layer])
    np.testing.assert_array_equal(by_stage["out_proj"][0], wo)
    for a, b in zip(codes, latent):
        np.testing.assert_array_equal(a.weights, b.weights)


def test_decode_step_cross_validates_traffic_and_cycles(served):
    """A decode step at context 16: projections + act-to-act attention
    (KV-cache matrices as stationary operands), all six stage families
    within tolerance of simulate() on the same workloads."""
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    traffic_vals, cycle_vals = backend.cross_validate(
        decode_contexts=(16,), rtol=0.05)
    assert len(traffic_vals) == len(cycle_vals) == 6
    assert {v.stage for v in traffic_vals} == {
        "qkv_proj", "attn_score", "attn_output", "out_proj",
        MLP_UP, MLP_DOWN,
    }
    for v in traffic_vals:
        assert v.ok, str(v)
    for v in cycle_vals:
        assert v.ok, str(v)
        assert v.measured > 0


def test_projection_only_backend_keeps_four_stages(served):
    """attention=False reproduces the PR-2 projection-only tallies."""
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params, attention=False)
    traffic_vals, cycle_vals = backend.cross_validate(chunks=((1, 1),),
                                                      rtol=0.05)
    assert len(traffic_vals) == len(cycle_vals) == 4
    for v in traffic_vals + cycle_vals:
        assert v.ok, str(v)
    assert backend.step_tally(decode_contexts=(1,)).gemms == 4


def test_prefill_step_cross_validates(served):
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    # a whole-prompt prefill: one chunk attending over its own 24 rows
    traffic_vals, cycle_vals = backend.cross_validate(chunks=((24, 24),),
                                                      rtol=0.05)
    assert len(traffic_vals) == 6
    for v in traffic_vals + cycle_vals:
        assert v.ok, str(v)


def test_composed_tally_equals_full_step_program(served):
    """step_tally composes cached sub-programs (projections by m,
    attention by (rows, context)); the result must match executing the
    step's single Program byte for byte and cycle for cycle — and only
    the attention pair re-executes as the context advances."""
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    composed = backend.step_tally(decode_contexts=(5, 9))
    full = backend._tally_program(
        backend.step_program(decode_contexts=(5, 9)), 2)
    assert composed.gemms == full.gemms
    assert composed.cycles == full.cycles
    assert (composed.weight_bytes, composed.act_bytes, composed.psum_bytes) \
        == (full.weight_bytes, full.act_bytes, full.psum_bytes)
    for stage in full.stages:
        assert composed.stages[stage].cycles == full.stages[stage].cycles
    # advancing the context reuses the cached projection part
    backend.step_tally(decode_contexts=(6, 10))
    assert set(backend._proj_cache) == {2}
    assert (1, 5) in backend._attn_cache and (1, 6) in backend._attn_cache
    # a decode part's rows split evenly over its slots, checked once by
    # the step spec every pricing method lowers through
    with pytest.raises(ValueError, match="slots"):
        ServeStepSpec(projections=backend.ops, m=3, contexts=(4, 5),
                      heads=cfg.n_heads, kv_heads=cfg.kv_heads,
                      head_dim=cfg.head_dim_)


def test_attention_cost_grows_with_context(served):
    """Position-dependent K/N: the same decode token costs more cycles and
    bytes at a longer context — the admission-control signal."""
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    short = backend.step_tally(decode_contexts=(4,))
    mid = backend.step_tally(decode_contexts=(48,))
    # below one K-window / N-tile (128) the array shape hides the growth in
    # padding: cycles stay flat while stationary bytes already grow
    assert mid.weight_bytes > short.weight_bytes
    # crossing the tile boundary adds psum rounds and passes: cycles grow
    long = backend.step_tally(decode_contexts=(200,))
    assert long.cycles > short.cycles
    assert long.act_bytes > short.act_bytes
    assert short.gemms == long.gemms == 6
    # the projection stages are context-independent; attention is the delta
    for st in ("qkv_proj", MLP_UP, MLP_DOWN):
        assert short.stages[st].cycles == long.stages[st].cycles
    assert long.stages["attn_score"].cycles > \
        short.stages["attn_score"].cycles


def test_engine_steps_accumulate_per_request_tallies(served):
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(ACCEL, cfg, params).attach(eng)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, cfg.vocab, size=8),
                       max_new_tokens=4) for _ in range(3)]
    done = eng.run_until_done()
    assert len(done) == 3

    assert set(backend.per_request) == {r.uid for r in reqs}
    for r in done:
        tally = backend.per_request[r.uid]
        assert tally.prefill_tokens == len(r.prompt)
        # first output token comes from prefill, the rest from decode steps
        assert tally.decode_tokens == len(r.output) - 1
        assert tally.cycles > 0
        assert tally.mem_bytes > 0
        # exact standalone ledger: one prefill step attending its 8-token
        # prompt, then one m=1 decode step per token at its growing
        # position-dependent context (9, 10, ... — prompt + decoded so far)
        expected = backend.step_tally(chunks=((8, 8),)).cycles + sum(
            backend.step_tally(decode_contexts=(t,)).cycles
            for t in range(9, 9 + tally.decode_tokens)
        )
        assert tally.cycles == expected

    s = backend.summary()
    assert s["requests"] == 3
    assert s["decode_tokens"] == sum(r.decode_tokens for r in
                                     backend.per_request.values())
    # mean standalone per-token decode cost (context-dependent steps)
    assert s["cycles_per_decode_token"] == pytest.approx(
        sum(backend.step_tally(decode_contexts=(t,)).cycles
            for t in (9, 10, 11)) / 3.0)
    # batched decode steps executed as m=2 programs with per-slot contexts
    assert any(m == 2 and len(ctx) == 2 for m, ctx in backend._part_cache)
    # engine totals are batch-accurate: 3 prefills + the batched decode
    # steps, each counted once at its true batch size
    assert s["cycles"] == backend.totals.cycles > 0
    # the standalone per-request sum exceeds the batched total: that gap
    # is the batching win (shared stationary-weight fetches), by design
    assert sum(r.cycles for r in backend.per_request.values()) >= s["cycles"]
    assert sum(r.weight_bytes for r in backend.per_request.values()) > \
        s["weight_bytes"]


def test_summary_cycles_feed_latency_aware_cache_budget(served):
    """ROADMAP admission-control item: measured serve-path cycles flow into
    serve.kv_cache.plan, yielding a tokens/sec-aware CacheBudget."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(ACCEL, cfg, params).attach(eng)
    rng = np.random.default_rng(2)
    eng.submit(rng.integers(1, cfg.vocab, size=6), max_new_tokens=3)
    eng.run_until_done()
    s = backend.summary()
    assert s["cycles_per_decode_token"] > 0

    budget = kv_plan(cfg, batch=2, max_seq=64, hbm_bytes_per_chip=16e9,
                     chips=1, cycles_per_token=s["cycles_per_decode_token"],
                     freq_hz=ACCEL.freq_hz)
    assert budget.fits_hbm
    assert budget.tokens_per_sec == pytest.approx(
        ACCEL.freq_hz / s["cycles_per_decode_token"])
    assert budget.batch_tokens_per_sec == pytest.approx(
        2 * budget.tokens_per_sec)
    assert budget.seconds_to_fill(64) == pytest.approx(
        64 / budget.tokens_per_sec)
    # capacity-only planning stays available (and rate-less)
    plain = kv_plan(cfg, batch=2, max_seq=64, hbm_bytes_per_chip=16e9,
                    chips=1)
    assert plain.tokens_per_sec is None and plain.seconds_to_fill(64) is None
    with pytest.raises(ValueError, match="together"):
        kv_plan(cfg, batch=2, max_seq=64, hbm_bytes_per_chip=16e9, chips=1,
                cycles_per_token=100.0)


def test_engine_view_overlapped_latency(served):
    """The engine view: every batched decode step also runs as a merged
    batch graph through the pipelined schedule — summary() reports an
    overlapped per-step latency that never exceeds the serial one, with
    the serial side exactly equal to the batched step tally."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(ACCEL, cfg, params).attach(eng)
    rng = np.random.default_rng(3)
    for _ in range(3):
        eng.submit(rng.integers(1, cfg.vocab, size=8), max_new_tokens=4)
    eng.run_until_done()

    s = backend.summary()
    assert 0 < s["overlapped_cycles_per_step"] <= s["serial_cycles_per_step"]
    assert 0 < s["overlapped_cycles_per_decode_token"] <= \
        s["serial_cycles_per_decode_token"]
    # batched steps really ran (engine tracks occupancy) and their slots'
    # attention rounds interleaved: real overlap, speedup > 1
    assert any(b == 2 for b in eng.decode_batch_sizes)
    assert len(eng.decode_batch_sizes) == s["decode_steps"]
    assert s["pipeline_speedup"] > 1.0
    assert s["overlapped_us_per_decode_token"] == pytest.approx(
        s["overlapped_cycles_per_decode_token"] / ACCEL.freq_hz * 1e6)

    # the merged schedule's serial side == the batched tally, cycle for
    # cycle (same per-stage round criticals, just not interleaved)
    serial, overlapped = backend.step_pipeline(decode_contexts=(9, 9))
    assert serial == backend.step_tally(decode_contexts=(9, 9)).cycles
    assert overlapped < serial
    # single-slot steps are chains, but every stationary operand (weights,
    # the slot's KV cache) exists before its streamed input, so the
    # dependent boundaries still prefetch their fill: overlapped < serial
    s1, o1 = backend.step_pipeline(decode_contexts=(16,))
    assert s1 == backend.step_tally(decode_contexts=(16,)).cycles
    assert 0 < o1 < s1


def test_cache_budget_feeds_overlapped_rate(served):
    """The engine-view overlapped per-token cycles set the CacheBudget's
    tokens/sec; the serial reference rides along as pipelining_speedup."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(ACCEL, cfg, params).attach(eng)
    rng = np.random.default_rng(4)
    for _ in range(2):
        eng.submit(rng.integers(1, cfg.vocab, size=6), max_new_tokens=3)
    eng.run_until_done()

    s = backend.summary()
    budget = backend.cache_budget(batch=2, max_seq=64,
                                  hbm_bytes_per_chip=16e9, chips=1)
    assert budget.fits_hbm
    assert budget.tokens_per_sec == pytest.approx(
        ACCEL.freq_hz / s["overlapped_cycles_per_decode_token"])
    assert budget.serial_tokens_per_sec == pytest.approx(
        ACCEL.freq_hz / s["serial_cycles_per_decode_token"])
    assert budget.pipelining_speedup is not None
    assert budget.pipelining_speedup >= 1.0
    assert budget.batch_tokens_per_sec == pytest.approx(
        2 * budget.tokens_per_sec)

    # an unattached backend has no measured steps to budget from
    fresh = LegionServeBackend(ACCEL, cfg, params)
    with pytest.raises(ValueError, match="decode"):
        fresh.cache_budget(batch=1, max_seq=64, hbm_bytes_per_chip=16e9,
                           chips=1)
    # plan-level validation of the serial reference
    with pytest.raises(ValueError, match="serial_cycles_per_token"):
        kv_plan(cfg, batch=1, max_seq=64, hbm_bytes_per_chip=16e9, chips=1,
                serial_cycles_per_token=10.0)
    with pytest.raises(ValueError, match="never exceed"):
        kv_plan(cfg, batch=1, max_seq=64, hbm_bytes_per_chip=16e9, chips=1,
                cycles_per_token=100.0, freq_hz=1e9,
                serial_cycles_per_token=50.0)
    # a rate-less budget has no speedup to report
    plain = kv_plan(cfg, batch=1, max_seq=64, hbm_bytes_per_chip=16e9,
                    chips=1)
    assert plain.pipelining_speedup is None


def test_uids_unique_across_interleaved_submits(served):
    """Submitting while earlier requests sit in slots (neither queued nor
    finished) must not recycle uids — per_request keys on them."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(ACCEL, cfg, params).attach(eng)
    rng = np.random.default_rng(1)
    a = eng.submit(rng.integers(1, cfg.vocab, size=8), max_new_tokens=8)
    eng.step()                       # admits a; queue and finished both empty
    b = eng.submit(rng.integers(1, cfg.vocab, size=8), max_new_tokens=8)
    done = eng.run_until_done()
    assert a.uid != b.uid
    assert len(done) == 2
    assert set(backend.per_request) == {a.uid, b.uid}


def test_sharded_executor_serve_step_matches_in_process(served):
    """The serve backend's Machine session accepts any ExecutorBackend:
    a ShardedExecutor step (attention stages included) must tally
    identically to the in-process one (same instrument stream) and still
    cross-validate."""
    from repro.legion import ShardedExecutor

    cfg, _api, params = served
    inproc = LegionServeBackend(ACCEL, cfg, params)
    sharded = LegionServeBackend(ACCEL, cfg, params,
                                 executor=ShardedExecutor())
    assert sharded.machine.backend.name == "sharded"
    a = inproc.step_tally(decode_contexts=(8,))
    b = sharded.step_tally(decode_contexts=(8,))
    assert (a.cycles, a.weight_bytes, a.act_bytes, a.psum_bytes) == \
        (b.cycles, b.weight_bytes, b.act_bytes, b.psum_bytes)
    traffic_vals, cycle_vals = sharded.cross_validate(decode_contexts=(8,),
                                                      rtol=0.05)
    for v in traffic_vals + cycle_vals:
        assert v.ok, str(v)


def test_pipelined_executor_serve_step(served):
    """PipelinedExecutor runs the step program with identical tallies (the
    overlap is a timing overlay, not a numerics change)."""
    from repro.legion import PipelinedExecutor

    cfg, _api, params = served
    inproc = LegionServeBackend(ACCEL, cfg, params)
    piped = LegionServeBackend(ACCEL, cfg, params,
                               executor=PipelinedExecutor())
    a = inproc.step_tally(decode_contexts=(5, 9))
    b = piped.step_tally(decode_contexts=(5, 9))
    assert (a.cycles, a.weight_bytes, a.act_bytes) == \
        (b.cycles, b.weight_bytes, b.act_bytes)
    rep = piped.machine.run(piped.step_program(decode_contexts=(5, 9)),
                            validate=False)
    assert rep.pipeline is not None
    assert rep.pipeline.overlapped_cycles <= rep.pipeline.serial_cycles


def test_mixed_step_cross_validates(served):
    """Tentpole: a merged prefill-chunk + decode step graph — measured
    per-stage traffic and cycles vs simulate() on the concatenated
    workload list, all six families within tolerance."""
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    traffic_vals, cycle_vals = backend.cross_validate(
        [(8, 8), (4, 12)], (5, 9, 13), rtol=0.05)
    assert len(traffic_vals) == len(cycle_vals) == 6
    for v in traffic_vals + cycle_vals:
        assert v.ok, str(v)
    for v in cycle_vals:
        assert v.measured > 0


def test_mixed_pipeline_serial_matches_parts(served):
    """step_pipeline on a mixed step: the serial side equals the summed
    part tallies exactly; the overlapped side is a real (<=) pipelined
    latency; degenerate shapes price as the pure-decode step."""
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    chunks, dctx = ((8, 8), (4, 12)), (5, 9, 13)
    serial, overlapped = backend.step_pipeline(chunks, dctx)
    assert serial == backend.step_tally(chunks, dctx).cycles
    assert 0 < overlapped <= serial
    # a mixed step beats running the phases back to back: the merged
    # graph overlaps chunk rounds with decode rounds
    _, chunk_only = backend.step_pipeline(chunks)
    _, decode_only = backend.step_pipeline(decode_contexts=dctx)
    assert overlapped < chunk_only + decode_only
    # no chunks -> exactly the decode-only engine view
    assert backend.step_pipeline((), dctx) == \
        backend.step_pipeline(decode_contexts=dctx)
    assert backend.step_pipeline(decode_contexts=dctx)[0] == \
        backend.step_tally(decode_contexts=dctx).cycles
    assert backend.step_pipeline(()) == (0, 0)
    # cached: the same shapes never rebuild the merged skeleton
    key = (chunks, len(dctx), dctx)
    assert backend._pipeline_cache[key] == (serial, overlapped)
    # projection-only backends schedule mixed steps too
    proj = LegionServeBackend(ACCEL, cfg, params, attention=False)
    s_p, o_p = proj.step_pipeline(chunks, dctx)
    assert s_p == proj.step_tally(chunks, dctx).cycles
    assert 0 < o_p <= s_p


def test_inflight_engine_backend_accounting(served):
    """An in-flight engine drives the backend through merged ``step``
    events: prefill chunks and decode land in the same tallies the
    legacy path produces, and the engine view covers the merged steps."""
    cfg, api, params = served
    eng = ServeEngine(api, params, max_slots=2, max_seq=64,
                      prefill_chunk_tokens=6)
    backend = LegionServeBackend(ACCEL, cfg, params).attach(eng)
    events = []
    eng.step_observers.append(events.append)
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(1, cfg.vocab, size=8),
                       max_new_tokens=4) for _ in range(3)]
    done = eng.run_until_done()
    assert len(done) == 3

    assert set(backend.per_request) == {r.uid for r in reqs}
    for r in done:
        tally = backend.per_request[r.uid]
        assert tally.prefill_tokens == len(r.prompt)
        assert tally.decode_tokens == len(r.output) - 1
    # ONE merged event per engine step, and the engine view counts each
    # mixed step once — prefill chunks included
    assert all(e["kind"] == "step" for e in events)
    assert backend.engine_steps == len(events)
    assert any(e["chunks"] and e["uids"] for e in events)   # truly mixed
    s = backend.summary()
    assert s["engine_steps"] == backend.engine_steps > 0
    assert 0 < s["overlapped_cycles_per_step"] <= \
        s["serial_cycles_per_step"]
    # the per-token decode rate stays decode-only (cache_budget's input)
    assert 0 < s["overlapped_cycles_per_decode_token"] <= \
        s["serial_cycles_per_decode_token"]
    budget = backend.cache_budget(batch=2, max_seq=64,
                                  hbm_bytes_per_chip=16e9, chips=1)
    assert budget.tokens_per_sec == pytest.approx(
        ACCEL.freq_hz / s["overlapped_cycles_per_decode_token"])


def test_live_admission_gates_intake(served):
    """LiveAdmission refuses requests that can never fit the KV budget,
    defers under pressure, and always admits on an idle engine."""
    from repro.serve import LiveAdmission
    from repro.serve.kv_cache import kv_bytes_per_token

    cfg, api, params = served
    bpt = kv_bytes_per_token(cfg)
    # capacity for 15 KV rows: a 6+4 request (10 rows) fits alone; two
    # concurrently (20 rows) exceed it, so the second defers
    policy_capacity = 15 * bpt
    backend = LegionServeBackend(ACCEL, cfg, params)
    policy = LiveAdmission(backend, hbm_bytes_per_chip=policy_capacity)
    eng = ServeEngine(api, params, max_slots=4, max_seq=64,
                      admission=policy)
    backend.attach(eng)

    big = eng.submit(np.arange(1, 30), max_new_tokens=8)   # 37 rows: never
    a = eng.submit(np.arange(1, 7), max_new_tokens=4)      # 10 rows
    b = eng.submit(np.arange(1, 7), max_new_tokens=4)      # 10 rows: defers
    done = eng.run_until_done()

    assert big.refused and big.done and big.output == []
    assert big in eng.refused and big not in done
    assert a.done and b.done and not a.refused and not b.refused
    assert len(done) == 2
    assert policy.stats.refused == 1
    assert policy.stats.deferred_kv >= 1          # b waited for a to drain
    assert policy.stats.admitted >= 2
    phases = [e["phase"] for e in eng.step_log]
    assert "refuse" in phases and "defer" in phases
    # idle-engine progress guarantee: b was admitted once a finished
    assert len(b.output) == b.max_new_tokens


def test_step_tally_scales_with_model_layers(served):
    cfg, _api, params = served
    backend = LegionServeBackend(ACCEL, cfg, params)
    tally = backend.step_tally(decode_contexts=(4,))
    per_layer = sum(
        st.cycles for st in tally.stages.values()
    ) / cfg.layers
    assert tally.cycles == pytest.approx(per_layer * cfg.layers)
    assert tally.gemms == 6
    assert tally.executed_passes > 0 and tally.skipped_passes == 0


# --------------------------------------------------------------------------- #
# Pinned backend behaviour: summary() and per-request tallies, bit for bit
# --------------------------------------------------------------------------- #

PINNED_PROMPTS = ((5, 11, 17, 23, 29), (3, 8, 13, 21, 34, 55, 89), (7, 9, 2))

PINNED_CASES = {
    "legacy": dict(chunk=None, page_tokens=0, attention=True),
    "inflight": dict(chunk=4, page_tokens=0, attention=True),
    "legacy-paged": dict(chunk=None, page_tokens=4, attention=True),
    "inflight-paged": dict(chunk=4, page_tokens=4, attention=True),
    "inflight-no-attention": dict(chunk=4, page_tokens=0, attention=False),
}

# Recorded before the backend's pricing methods were merged into one per
# job: summary() and each request's (prefill_tokens, decode_tokens,
# cycles, weight_bytes, act_bytes, psum_bytes), bit for bit.
PINNED = {
    'legacy': (
        {
            'requests': 3, 'prefill_steps': 3, 'decode_steps': 4,
            'engine_steps': 4, 'prefill_tokens': 15, 'decode_tokens': 6,
            'weight_bytes': 805888.0, 'act_bytes': 75264.0,
            'psum_bytes': 240448.0, 'page_fetches': 0.0,
            'page_fetch_bytes': 0.0, 'page_waste_bytes': 0.0,
            'page_waste_frac': 0.0, 'cycles': 11632, 'stall_cycles': 0,
            'stall_frac': 0.0, 'cycles_per_decode_token': 1424.0,
            'us_per_decode_token': 1.4240000000000002,
            'serial_cycles_per_step': 1840.0,
            'overlapped_cycles_per_step': 1364.0,
            'serial_cycles_per_decode_token': 1226.6666666666667,
            'overlapped_cycles_per_decode_token': 909.3333333333334,
            'pipeline_speedup': 1.348973607038123,
            'overlapped_us_per_decode_token': 0.9093333333333333,
        },
        {
            0: (5, 2, 4272, 328704.0, 25088.0, 80064.0),
            1: (7, 2, 4272, 331776.0, 32256.0, 103488.0),
            2: (3, 2, 4272, 325632.0, 17920.0, 56896.0),
        },
    ),
    'inflight': (
        {
            'requests': 3, 'prefill_steps': 5, 'decode_steps': 4,
            'engine_steps': 5, 'prefill_tokens': 15, 'decode_tokens': 6,
            'weight_bytes': 1022464.0, 'act_bytes': 75264.0,
            'psum_bytes': 239936.0, 'page_fetches': 0.0,
            'page_fetch_bytes': 0.0, 'page_waste_bytes': 0.0,
            'page_waste_frac': 0.0, 'cycles': 14480, 'stall_cycles': 0,
            'stall_frac': 0.0, 'cycles_per_decode_token': 1424.0,
            'us_per_decode_token': 1.4240000000000002,
            'serial_cycles_per_step': 2896.0,
            'overlapped_cycles_per_step': 1940.8,
            'serial_cycles_per_decode_token': 1226.6666666666667,
            'overlapped_cycles_per_decode_token': 909.3333333333334,
            'pipeline_speedup': 1.4921681780708986,
            'overlapped_us_per_decode_token': 0.9093333333333333,
        },
        {
            0: (5, 2, 5696, 437248.0, 25088.0, 79936.0),
            1: (7, 2, 5696, 439808.0, 32256.0, 103104.0),
            2: (3, 2, 4272, 325632.0, 17920.0, 56896.0),
        },
    ),
    'legacy-paged': (
        {
            'requests': 3, 'prefill_steps': 3, 'decode_steps': 4,
            'engine_steps': 4, 'prefill_tokens': 15, 'decode_tokens': 6,
            'weight_bytes': 809472.0, 'act_bytes': 75264.0,
            'psum_bytes': 240448.0, 'page_fetches': 136.0,
            'page_fetch_bytes': 17408.0, 'page_waste_bytes': 3584.0,
            'page_waste_frac': 0.20588235294117646, 'cycles': 11632,
            'stall_cycles': 0, 'stall_frac': 0.0,
            'cycles_per_decode_token': 1424.0,
            'us_per_decode_token': 1.4240000000000002,
            'serial_cycles_per_step': 1840.0,
            'overlapped_cycles_per_step': 1364.0,
            'serial_cycles_per_decode_token': 1226.6666666666667,
            'overlapped_cycles_per_decode_token': 909.3333333333334,
            'pipeline_speedup': 1.348973607038123,
            'overlapped_us_per_decode_token': 0.9093333333333333,
        },
        {
            0: (5, 2, 4272, 330240.0, 25088.0, 80064.0),
            1: (7, 2, 4272, 332800.0, 32256.0, 103488.0),
            2: (3, 2, 4272, 326656.0, 17920.0, 56896.0),
        },
    ),
    'inflight-paged': (
        {
            'requests': 3, 'prefill_steps': 5, 'decode_steps': 4,
            'engine_steps': 5, 'prefill_tokens': 15, 'decode_tokens': 6,
            'weight_bytes': 1026304.0, 'act_bytes': 75264.0,
            'psum_bytes': 239936.0, 'page_fetches': 152.0,
            'page_fetch_bytes': 19456.0, 'page_waste_bytes': 3840.0,
            'page_waste_frac': 0.19736842105263158, 'cycles': 14480,
            'stall_cycles': 0, 'stall_frac': 0.0,
            'cycles_per_decode_token': 1424.0,
            'us_per_decode_token': 1.4240000000000002,
            'serial_cycles_per_step': 2896.0,
            'overlapped_cycles_per_step': 1940.8,
            'serial_cycles_per_decode_token': 1226.6666666666667,
            'overlapped_cycles_per_decode_token': 909.3333333333334,
            'pipeline_speedup': 1.4921681780708986,
            'overlapped_us_per_decode_token': 0.9093333333333333,
        },
        {
            0: (5, 2, 5696, 438784.0, 25088.0, 79936.0),
            1: (7, 2, 5696, 441088.0, 32256.0, 103104.0),
            2: (3, 2, 4272, 326656.0, 17920.0, 56896.0),
        },
    ),
    'inflight-no-attention': (
        {
            'requests': 3, 'prefill_steps': 5, 'decode_steps': 4,
            'engine_steps': 5, 'prefill_tokens': 15, 'decode_tokens': 6,
            'weight_bytes': 811008.0, 'act_bytes': 32256.0,
            'psum_bytes': 215040.0, 'page_fetches': 0.0,
            'page_fetch_bytes': 0.0, 'page_waste_bytes': 0.0,
            'page_waste_frac': 0.0, 'cycles': 5328, 'stall_cycles': 0,
            'stall_frac': 0.0, 'cycles_per_decode_token': 592.0,
            'us_per_decode_token': 0.592, 'serial_cycles_per_step': 1065.6,
            'overlapped_cycles_per_step': 708.8,
            'serial_cycles_per_decode_token': 394.6666666666667,
            'overlapped_cycles_per_decode_token': 304.0,
            'pipeline_speedup': 1.5033860045146727,
            'overlapped_us_per_decode_token': 0.30400000000000005,
        },
        {
            0: (5, 2, 2368, 360448.0, 10752.0, 71680.0),
            1: (7, 2, 2368, 360448.0, 13824.0, 92160.0),
            2: (3, 2, 1776, 270336.0, 7680.0, 51200.0),
        },
    ),
}


@pytest.fixture(scope="module")
def served_kv():
    cfg = reduced(get_config("bitnet-1.58b-kv"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    return cfg, api, params


def _pinned_run(served_kv, *, chunk, page_tokens, attention):
    """Serve the fixed queue through an engine with the backend attached;
    return the backend's summary and each request's cycles and bytes."""
    from repro.serve import PagedKVCache

    cfg, api, params = served_kv
    paged = (PagedKVCache(total_pages=32, page_tokens=page_tokens)
             if page_tokens else None)
    eng = ServeEngine(api, params, max_slots=2, max_seq=32,
                      prefill_chunk_tokens=chunk, paged_kv=paged)
    backend = LegionServeBackend(ACCEL, cfg, params, attention=attention,
                                 page_tokens=page_tokens).attach(eng)
    for prompt in PINNED_PROMPTS:
        eng.submit(np.asarray(prompt), max_new_tokens=3)
    eng.run_until_done()
    requests = {
        uid: (r.prefill_tokens, r.decode_tokens, r.cycles, r.weight_bytes,
              r.act_bytes, r.psum_bytes)
        for uid, r in sorted(backend.per_request.items())
    }
    return backend.summary(), requests


@pytest.mark.parametrize("case", sorted(PINNED_CASES))
def test_backend_summary_pinned(served_kv, case):
    """Legacy and in-flight engines, paged and unpaged, attention on and
    off: the backend's summary and per-request tallies match the values
    recorded for this queue exactly."""
    summary, requests = _pinned_run(served_kv, **PINNED_CASES[case])
    want_summary, want_requests = PINNED[case]
    assert summary == want_summary
    assert requests == want_requests
