"""Runtime-vs-analytic benchmark — the executable check of SS IV-B + eq. (2).

Runs the BitNet attention workloads end-to-end through the legion runtime
(one layer, synthetic int8 operands) on a 1-Legion and an 8-Legion config,
and emits runtime-measured vs ``simulate()``-derived traffic AND cycles per
stage.  Asserts every stage agrees within 5% — a red run means the
simulator's formulas (and therefore every paper figure derived from them,
the 8.2x latency and 135.68 TOPS headlines included) diverged from what
executing the schedule actually moves / spends.

The serve-path variant drives one BitNet decode step's projection GEMMs
(wq/wk/wv/wo, w1/w2/w3) through ``repro.serve.legion_backend`` and reports
per-token cycles and bytes, cross-validated the same way.
"""
from __future__ import annotations

import dataclasses
import time

from benchmarks.common import emit, timed
from repro.core import dlegion, simulate
from repro.core.workloads import attention_workloads, bitnet_1_58b_kv


def run():
    rows = []
    spec = dataclasses.replace(bitnet_1_58b_kv(seq_len=128), layers=1)
    workloads = attention_workloads(spec)
    from repro.legion import Machine, total_cycle_error

    measured = {}
    for legions in (1, 8):
        cfg = dlegion(legions=legions)
        machine = Machine(cfg)
        # One Machine session measures traffic AND cycles in a single pass
        # (the old module-level cross_validate/cross_validate_cycles pair
        # executed every workload twice).
        (validations, cycle_vals), us = timed(
            machine.cross_validate, workloads, rtol=0.05, repeats=1,
        )
        for v in validations:
            assert v.ok, f"{cfg.name}: {v}"
        total_w = sum(v.measured.weight_bytes for v in validations)
        total_a = sum(v.measured.act_bytes for v in validations)
        total_p = sum(v.measured.psum_bytes for v in validations)
        measured[legions] = (total_w, total_a)
        worst = max(e for v in validations for e in v.errors.values())
        rows.append(emit(
            f"legion_runtime/traffic_xval_{cfg.name}", us, {
                "stages_ok": len(validations),
                "worst_rel_err": worst,
                "weight_mb": total_w / 1e6,
                "act_mb": total_a / 1e6,
                "psum_mb": total_p / 1e6,
            },
        ))

        # ---- cycle cross-validation (the latency behind Figs. 7/9) ------ #
        for v in cycle_vals:
            assert v.ok, f"{cfg.name}: {v}"
        worst_cyc = max(v.rel_err for v in cycle_vals)
        assert worst_cyc <= 0.05, f"{cfg.name}: cycle err {worst_cyc:.3f}"
        total_meas = sum(v.measured for v in cycle_vals)
        # us=0: cycles were measured in the traffic row's single pass
        rows.append(emit(
            f"legion_runtime/cycle_xval_{cfg.name}", 0.0, {
                "stages_ok": len(cycle_vals),
                "worst_rel_err": worst_cyc,
                "total_rel_err": total_cycle_error(cycle_vals),
                "total_kcycles": total_meas / 1e3,
                "ms_at_1ghz": total_meas / cfg.freq_hz * 1e3,
            },
        ))

    # NoC multicast reuse (SS IV-B): 8 Legions move *fewer* stationary bytes
    # than one Legion on the GQA model (KV tiles fetched once per group) and
    # the input broadcast gives the paper's L-x activation-stream reuse.
    w1, a1 = measured[1]
    w8, a8 = measured[8]
    assert w8 < w1, f"KV multicast should shrink weight traffic ({w8} vs {w1})"
    assert a1 / a8 > 7.0, f"input broadcast reuse {a1 / a8:.2f}x, expected ~8x"
    rows.append(emit(
        "legion_runtime/noc_multicast_reuse", 0.0,
        {"weight_traffic_x": w1 / w8, "act_traffic_x": a1 / a8},
    ))
    rows += _serve_path()
    return rows


def _serve_path():
    """One BitNet decode step through the serve-path Legion backend — the
    full step Program (projections AND act-to-act attention over the KV
    context), per-token cycles/bytes cross-validated."""
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve.engine import prepare_params
    from repro.serve.legion_backend import LegionServeBackend

    model_cfg = reduced(get_config("bitnet-1.58b"))
    api = build_model(model_cfg)
    params = prepare_params(api.init(jax.random.PRNGKey(0)))
    accel = dlegion()
    backend = LegionServeBackend(accel, model_cfg, params)

    # step executions cache by (rows, contexts) — time the cold execution
    context = 16
    t0 = time.perf_counter()
    tally = backend.step_tally(decode_contexts=(context,))
    us = (time.perf_counter() - t0) * 1e6
    traffic_vals, cycle_vals = backend.cross_validate(
        decode_contexts=(context,), rtol=0.05)
    assert {v.stage for v in traffic_vals} >= {"attn_score", "attn_output"}
    for v in traffic_vals + cycle_vals:
        assert v.ok, f"serve decode: {v}"
    worst_cyc = max(v.rel_err for v in cycle_vals)
    assert worst_cyc <= 0.05, f"serve decode cycle err {worst_cyc:.3f}"
    attn = (tally.stages["attn_score"].cycles
            + tally.stages["attn_output"].cycles)
    return [emit(
        "legion_runtime/serve_decode_bitnet", us, {
            "gemms": tally.gemms,
            "kv_context": context,
            "cycles_per_token": tally.cycles,
            "attn_cycle_frac": attn / tally.cycles,
            "us_per_token_at_1ghz": tally.seconds(accel.freq_hz) * 1e6,
            "weight_kb_per_token": tally.weight_bytes / 1e3,
            "act_kb_per_token": tally.act_bytes / 1e3,
            "worst_cycle_err": worst_cyc,
        },
    )]
