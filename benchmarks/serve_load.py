"""Fleet-scale serve load benchmark — Poisson + bursty arrival traces.

Drives hundreds of requests through the continuous-batching engine with
the Legion serve backend attached, clocked by the cycle model
(``repro.obs.loadgen``): prefill admission costs one standalone prefill
tally, each batched decode step costs its *overlapped* merged-batch
pipeline cycles.  The rows report the latency distribution a deployment
would see:

* ``p50_ttft_kcycles`` / ``p99_ttft_kcycles`` — time-to-first-token
  (arrival -> prefill complete) percentiles, in kilocycles;
* ``p50_tok_kcycles`` / ``p99_tok_kcycles`` — per-request mean decode
  cycles per output token;
* ``mean_occupancy`` — average active slots over all engine steps
  (prefill and decode both count, via ``ServeEngine.step_log``);
* ``rejected`` / ``deferred`` — admission-control outcomes under a
  bounded queue;
* ``overlap_x`` — the backend's whole-run pipelining speedup (rides the
  run.py >= 1.0 trajectory gate).

The ``lognormal_120_paged`` / ``lognormal_120_paged_tight`` pair replays
one heavy-tailed (lognormal) trace through the PAGED KV engine
(``ServeEngine(paged_kv=...)`` + ``LegionServeBackend(page_tokens=...)``):
the first with a pool covering every slot's window (isolating page-fetch
traffic and last-page padding, with ``page_xval_err`` cross-validating
the page channel against ``simulate()``), the second with the pool
tightened to ONE max-length window — every request must still complete,
``preempted`` must be nonzero (eviction + re-prefill really ran), and
``goodput`` grades completions against a TTFT/per-token SLO.

The ``poisson_200_inflight`` row replays the SAME Poisson trace with
in-flight batching on (``prefill_chunk_tokens=`` chunked prefill merged
with the decode batch into one Program per step) and ``LiveAdmission``
gating intake; its p50 TTFT must strictly beat the in-flight-off row,
and ``refused`` / ``truncated`` ride along so admission or window
regressions surface in the trend.  ``compare.py``'s direction-aware
gates track ``p50_*``/``p99_*`` (lower is better) and ``overlap_x``
(higher is better) across both variants.

A red run means admission, the load clock, or the percentile math
regressed — the numbers land in ``BENCH_serve_load.json`` and are
trended by ``benchmarks/compare.py`` in CI.
"""
from __future__ import annotations

import time

from benchmarks.common import emit
from repro.core import dlegion

POISSON_REQUESTS = 200
LOGNORMAL_REQUESTS = 120
BURST_REQUESTS = 60
MAX_SLOTS = 4
MAX_SEQ = 64


def _fresh(metrics=None, *, prefill_chunk_tokens=None, live_admission=False,
           page_tokens=None, total_pages=None):
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import (
        LegionServeBackend, LiveAdmission, PagedKVCache, ServeEngine,
    )
    from repro.serve.engine import prepare_params

    cfg = reduced(get_config("bitnet-1.58b"))
    api = build_model(cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    backend = LegionServeBackend(dlegion(), cfg, params,
                                 page_tokens=page_tokens or 0)
    # a generous budget: the policy runs (and is exercised every step)
    # without throttling this trace — deferrals/refusals would show up in
    # the emitted row if the KV math ever regressed
    admission = LiveAdmission(backend, hbm_bytes_per_chip=8 << 30) \
        if live_admission else None
    paged = (PagedKVCache(total_pages=total_pages,
                          page_tokens=page_tokens)
             if page_tokens is not None else None)
    eng = ServeEngine(api, params, max_slots=MAX_SLOTS, max_seq=MAX_SEQ,
                      metrics=metrics,
                      prefill_chunk_tokens=prefill_chunk_tokens,
                      admission=admission, paged_kv=paged)
    backend.attach(eng)
    return eng, backend


def run():
    from repro.obs import (
        SLO, MetricsRegistry, bursty_trace, lognormal_trace, poisson_trace,
        run_load,
    )

    rows = []

    # ---------------- Poisson open-loop trace, near saturation ----------- #
    reg = MetricsRegistry()
    eng, backend = _fresh(metrics=reg)
    # calibrate the arrival rate to the service rate: one full decode step
    # (4 slots) costs this many overlapped cycles, so a mean interarrival
    # of ~1.25 steps keeps utilization high without unbounded queueing
    _, step_cycles = backend.step_pipeline(
        decode_contexts=tuple([8] * MAX_SLOTS))
    trace = poisson_trace(
        POISSON_REQUESTS, mean_interarrival_cycles=1.25 * step_cycles,
        seed=0)
    t0 = time.perf_counter()
    report = run_load(eng, backend, trace, metrics=reg)
    us = (time.perf_counter() - t0) * 1e6 / POISSON_REQUESTS
    s = report.summary()
    assert s["completed"] == POISSON_REQUESTS, s
    assert s["rejected"] == 0                       # unbounded queue
    assert 0 < s["p50_ttft_cycles"] <= s["p99_ttft_cycles"]
    assert 0 < s["p50_tok_cycles"] <= s["p99_tok_cycles"]
    assert 0 < s["mean_occupancy"] <= MAX_SLOTS
    # the occupancy series really covers admissions, not just decode
    assert sum(1 for e in eng.step_log if e["phase"] == "prefill") \
        == POISSON_REQUESTS
    snap = reg.snapshot()
    assert snap["load_ttft_cycles"]["series"][""]["count"] \
        == POISSON_REQUESTS
    rows.append(emit("serve_load/poisson_200", us, {
        "requests": s["requests"],
        "completed": s["completed"],
        "rejected": s["rejected"],
        "deferred": s["deferred"],
        "decode_tokens": s["decode_tokens"],
        "p50_ttft_kcycles": s["p50_ttft_cycles"] / 1e3,
        "p99_ttft_kcycles": s["p99_ttft_cycles"] / 1e3,
        "p50_tok_kcycles": s["p50_tok_cycles"] / 1e3,
        "p99_tok_kcycles": s["p99_tok_cycles"] / 1e3,
        "mean_occupancy": s["mean_occupancy"],
        "peak_occupancy": s["peak_occupancy"],
        "overlap_x": backend.summary()["pipeline_speedup"],
    }))

    # -------- the SAME Poisson trace, in-flight batching switched on ----- #
    # Chunked prefill merges with the batched decode into one Program per
    # engine step, and LiveAdmission gates intake on the measured budget.
    # The acceptance gate: p50 TTFT strictly improves vs the row above —
    # prefill no longer serializes in front of the decode batch.
    # budget 24 = two max-length prompts per step: every prompt lands its
    # first token in one merged step while decode batches ride along
    eng, backend = _fresh(prefill_chunk_tokens=24, live_admission=True)
    t0 = time.perf_counter()
    inflight = run_load(eng, backend, trace)
    us = (time.perf_counter() - t0) * 1e6 / POISSON_REQUESTS
    si = inflight.summary()
    assert si["completed"] == POISSON_REQUESTS, si
    assert si["refused"] == 0 and si["truncated"] == 0, si
    assert si["goodput"] == POISSON_REQUESTS, si
    assert 0 < si["p50_ttft_cycles"] < s["p50_ttft_cycles"], \
        (si["p50_ttft_cycles"], s["p50_ttft_cycles"])
    rows.append(emit("serve_load/poisson_200_inflight", us, {
        "requests": si["requests"],
        "completed": si["completed"],
        "rejected": si["rejected"],
        "deferred": si["deferred"],
        "refused": si["refused"],
        "truncated": si["truncated"],
        "decode_tokens": si["decode_tokens"],
        "p50_ttft_kcycles": si["p50_ttft_cycles"] / 1e3,
        "p99_ttft_kcycles": si["p99_ttft_cycles"] / 1e3,
        "p50_tok_kcycles": si["p50_tok_cycles"] / 1e3,
        "p99_tok_kcycles": si["p99_tok_cycles"] / 1e3,
        "mean_occupancy": si["mean_occupancy"],
        "peak_occupancy": si["peak_occupancy"],
        "overlap_x": backend.summary()["pipeline_speedup"],
    }))

    # ------- heavy-tailed trace through the PAGED engine, roomy pool ----- #
    # Lognormal arrivals/lengths (the production shape) through a paged-KV
    # engine whose pool covers every slot's full window: no preemption is
    # possible, so this row isolates the page-granularity costs — whole-
    # page fetch traffic, last-page padding share — and cross-validates
    # the page channel against simulate() (page_xval_err rides the run.py
    # *_err gate).  Goodput is graded against a TTFT + per-token SLO.
    PAGE_TOKENS = 8
    pages_per_slot = -(-MAX_SEQ // PAGE_TOKENS)
    slo = SLO(ttft_cycles=40.0 * step_cycles,
              per_token_cycles=4.0 * step_cycles)
    eng, backend = _fresh(page_tokens=PAGE_TOKENS,
                          total_pages=MAX_SLOTS * pages_per_slot)
    tail = lognormal_trace(LOGNORMAL_REQUESTS,
                           mean_interarrival_cycles=1.25 * step_cycles,
                           max_prompt=16, quantum=4, seed=2)
    t0 = time.perf_counter()
    report = run_load(eng, backend, tail, slo=slo)
    us = (time.perf_counter() - t0) * 1e6 / LOGNORMAL_REQUESTS
    s = report.summary()
    assert s["completed"] == LOGNORMAL_REQUESTS, s
    assert s["preempted"] == 0, s         # pool covers every slot's window
    assert 0 < s["goodput"] <= s["completed"], s
    bsum = backend.summary()
    assert bsum["page_fetch_bytes"] > 0   # pages really were priced
    assert 0 <= bsum["page_waste_frac"] < 1
    tvals, cvals = backend.cross_validate(decode_contexts=(MAX_SEQ,))
    xval = max([e for v in tvals for e in v.errors.values()]
               + [v.rel_err for v in cvals])
    rows.append(emit("serve_load/lognormal_120_paged", us, {
        "requests": s["requests"],
        "completed": s["completed"],
        "goodput": s["goodput"],
        "preempted": s["preempted"],
        "deferred": s["deferred"],
        "p50_ttft_kcycles": s["p50_ttft_cycles"] / 1e3,
        "p99_ttft_kcycles": s["p99_ttft_cycles"] / 1e3,
        "p99_tok_kcycles": s["p99_tok_cycles"] / 1e3,
        "page_fetch_bytes": bsum["page_fetch_bytes"],
        "page_waste_frac": bsum["page_waste_frac"],
        "page_xval_err": xval,
        "overlap_x": bsum["pipeline_speedup"],
    }))

    # ------- the SAME tail, page pool tightened to one window ------------ #
    # The HBM pool now holds exactly ONE max-length request's pages: slots
    # only run concurrently while their page demand fits, and pressure is
    # resolved by evicting the latest-admitted slot (pages freed, request
    # re-queued for re-prefill).  The acceptance gate: every request still
    # completes, preemptions actually happened, and goodput (same SLO)
    # reports what the shrunken pool really delivered.
    eng, backend = _fresh(page_tokens=PAGE_TOKENS,
                          total_pages=pages_per_slot)
    t0 = time.perf_counter()
    tight = run_load(eng, backend, tail, slo=slo)
    us = (time.perf_counter() - t0) * 1e6 / LOGNORMAL_REQUESTS
    st = tight.summary()
    assert st["completed"] == LOGNORMAL_REQUESTS, st
    assert st["preempted"] > 0, st        # the tight pool must evict
    assert 0 <= st["goodput"] <= st["completed"], st
    bsum = backend.summary()
    tvals, cvals = backend.cross_validate(decode_contexts=(MAX_SEQ,))
    xval = max([e for v in tvals for e in v.errors.values()]
               + [v.rel_err for v in cvals])
    rows.append(emit("serve_load/lognormal_120_paged_tight", us, {
        "requests": st["requests"],
        "completed": st["completed"],
        "goodput": st["goodput"],
        "preempted": st["preempted"],
        "deferred": st["deferred"],
        "p50_ttft_kcycles": st["p50_ttft_cycles"] / 1e3,
        "p99_ttft_kcycles": st["p99_ttft_cycles"] / 1e3,
        "p99_tok_kcycles": st["p99_tok_cycles"] / 1e3,
        "page_fetch_bytes": bsum["page_fetch_bytes"],
        "page_waste_frac": bsum["page_waste_frac"],
        "page_xval_err": xval,
        "overlap_x": bsum["pipeline_speedup"],
    }))

    # ---------------- bursty trace against a bounded queue --------------- #
    eng, backend = _fresh()
    trace = bursty_trace(BURST_REQUESTS, burst_size=12,
                         burst_gap_cycles=20.0 * step_cycles, seed=1)
    t0 = time.perf_counter()
    report = run_load(eng, backend, trace, max_queue=2 * MAX_SLOTS)
    us = (time.perf_counter() - t0) * 1e6 / BURST_REQUESTS
    s = report.summary()
    # 12-deep bursts against 4 slots + an 8-deep queue: admission control
    # must visibly defer, and everything admitted must finish
    assert s["deferred"] > 0, s
    assert s["completed"] + s["rejected"] == BURST_REQUESTS, s
    rows.append(emit("serve_load/burst_12x5_bounded_queue", us, {
        "requests": s["requests"],
        "completed": s["completed"],
        "rejected": s["rejected"],
        "deferred": s["deferred"],
        "p50_ttft_kcycles": s["p50_ttft_cycles"] / 1e3,
        "p99_ttft_kcycles": s["p99_ttft_cycles"] / 1e3,
        "p99_tok_kcycles": s["p99_tok_cycles"] / 1e3,
        "mean_occupancy": s["mean_occupancy"],
        "peak_occupancy": s["peak_occupancy"],
        "overlap_x": backend.summary()["pipeline_speedup"],
    }))
    return rows


if __name__ == "__main__":
    run()
