"""Serve-pipelining benchmark — batch-level overlap + multi-layer programs.

Drives a reduced BitNet model through the continuous-batching engine with
the Legion serve backend attached, then checks the two PR-5 claims on the
measured numbers:

* **engine view** — every batched decode step also runs as one merged
  batch graph (shared projections, per-slot attention antichain) through
  the pipelined schedule: ``overlapped_cycles_per_step`` must be <= the
  serial per-stage sum, and the overlapped per-token cycles feed
  ``serve.kv_cache.plan``'s tokens/sec budget (``pipelining_speedup``
  >= 1);
* **multi-layer programs** — a two-explicit-layer serve step (layer 1's
  QKV streaming layer 0's MLP output through a real cross-layer Ref)
  validates against ``simulate()`` at 0% traffic/cycle error, bit-exact
  vs the pure-NumPy reference, and a merged two-slot two-layer batch
  overlaps (serial > overlapped).

A red run means the merged-graph schedule, the cross-layer lowering, or
the ``overlapped <= serial`` invariant regressed.  Derived ``overlap_x``
ratios and ``*_err`` fractions are the bench-trajectory CI gates.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.core import dlegion


def run():
    import jax

    from repro.configs import get_config, reduced
    from repro.legion import Machine, PipelinedExecutor, reference_outputs
    from repro.models import build_model
    from repro.serve import LegionServeBackend, ServeEngine
    from repro.serve.engine import prepare_params

    rows = []
    model_cfg = reduced(get_config("bitnet-1.58b"))
    api = build_model(model_cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    accel = dlegion()

    # ---- engine view: batched decode steps, pipelined ------------------- #
    eng = ServeEngine(api, params, max_slots=2, max_seq=64)
    backend = LegionServeBackend(accel, model_cfg, params).attach(eng)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for _ in range(3):
        eng.submit(rng.integers(1, model_cfg.vocab, size=8),
                   max_new_tokens=4)
    done = eng.run_until_done()
    us = (time.perf_counter() - t0) * 1e6
    assert len(done) == 3
    s = backend.summary()
    assert s["overlapped_cycles_per_step"] <= s["serial_cycles_per_step"], s
    assert s["overlapped_cycles_per_decode_token"] > 0
    budget = backend.cache_budget(batch=eng.max_slots, max_seq=eng.max_seq,
                                  hbm_bytes_per_chip=16e9, chips=1)
    assert budget.tokens_per_sec and budget.pipelining_speedup >= 1.0
    mean_batch = float(np.mean(eng.decode_batch_sizes))
    rows.append(emit(
        "serve_pipeline/engine_view", us, {
            "requests": int(s["requests"]),
            "decode_steps": int(s["decode_steps"]),
            "mean_batch": mean_batch,
            "serial_cycles_per_step": s["serial_cycles_per_step"],
            "overlapped_cycles_per_step": s["overlapped_cycles_per_step"],
            "overlap_x": s["pipeline_speedup"],
            "overlapped_cycles_per_token":
                s["overlapped_cycles_per_decode_token"],
            "tokens_per_sec": budget.tokens_per_sec,
        },
    ))

    # ---- merged two-slot decode batch: xval + overlap ------------------- #
    contexts = (9, 17)
    tvals, cvals = backend.cross_validate(decode_contexts=contexts,
                                          rtol=0.05)
    worst = max([e for v in tvals for e in v.errors.values()]
                + [v.rel_err for v in cvals])
    assert worst <= 0.05, f"merged batch xval err {worst:.4f}"
    serial, overlapped = backend.step_pipeline(decode_contexts=contexts)
    assert overlapped <= serial, (serial, overlapped)
    assert overlapped < serial, "independent slots should overlap"
    rows.append(emit(
        "serve_pipeline/merged_batch", 0.0, {
            "slots": len(contexts),
            "serial_kcycles": serial / 1e3,
            "overlapped_kcycles": overlapped / 1e3,
            "overlap_x": serial / overlapped,
            "worst_xval_err": worst,
        },
    ))

    # ---- multi-layer program: explicit cross-layer deps ----------------- #
    machine = Machine(accel, backend=PipelinedExecutor())
    two_layer = backend.step_program(decode_contexts=contexts,
                                     explicit_layers=2)
    t0 = time.perf_counter()
    rep = machine.run(two_layer)
    us2 = (time.perf_counter() - t0) * 1e6
    assert rep.ok, str(rep)
    ref = reference_outputs(two_layer)
    for name in ref:
        assert np.array_equal(rep.outputs[name], ref[name]), \
            f"{name}: runtime != NumPy reference"
    worst_ml = max(
        [e for r in rep.stage_reports.values()
         for e in r.traffic_validation.errors.values()]
        + [r.cycle_validation.rel_err for r in rep.stage_reports.values()]
    )
    assert worst_ml == 0.0, f"multi-layer xval err {worst_ml:.4f}"
    pp = rep.pipeline
    assert pp.overlapped_cycles <= pp.serial_cycles, str(pp)
    assert pp.overlapped_cycles < pp.serial_cycles, \
        f"two slots x two layers should overlap: {pp}"
    rows.append(emit(
        "serve_pipeline/two_layer_batch", us2, {
            "stages": len(two_layer),
            "explicit_layers": 2,
            "serial_kcycles": pp.serial_cycles / 1e3,
            "overlapped_kcycles": pp.overlapped_cycles / 1e3,
            "overlap_x": pp.speedup,
            "worst_xval_err": worst_ml,
        },
    ))
    return rows


if __name__ == "__main__":
    run()
