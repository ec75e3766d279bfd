"""Closed-loop passes of the Legion executor over attention Programs, the
Legion axis sharded over the cell's chips.

As ``legion_passes``, whose helpers it uses: set-up lowers ``programs``
attention blocks (the configuration's ``legion`` group), each streaming its
own rows from the seed, and runs one pass to compile; the window runs
``Machine.run`` back to back, cycling through them, the last pass started
in the window finished and counted; every pass's stage outputs are
compared with the plain reference after the window.  The machine here is
``Machine(accel, ShardedExecutor(devices=<the cell's chips>),
kernel_backend=<the mix's>)``: each stage's Legions are split over a mesh
of the chips and computed in one sharded integer matmul (the sharded
executor never calls the tile kernels, so the mix sets the reference
backend).
"""
from __future__ import annotations

import sys
import time
import traceback

import jax

from bench.drivers import legion_passes as single
from bench.references import legion_attention as ref


def run(ctx):
    from bench.harness import Outcome
    from bench.work import legion as work
    from repro.core import config as accel_configs
    from repro.core.workloads import AttentionSpec
    from repro.legion import Machine, ShardedExecutor, lower_attention

    g = ctx.config["legion"]
    block = single.block_of(ctx.config)
    spec = AttentionSpec(name=ctx.config["registry"], **block)
    rows = ref.streamed_rows(block, ctx.seed, ctx.mix["programs"])
    programs = [lower_attention(spec, x=x, seed=ref.weight_seed(ctx.seed))
                for x in rows]
    accel = getattr(accel_configs, g["accelerator"])()
    executor = ShardedExecutor(devices=jax.devices()[:ctx.chips])
    machine = Machine(accel, backend=executor,
                      kernel_backend=ctx.mix["kernel_backend"])
    machine.run(programs[0])

    lead, span = 0.0, float("inf")
    if ctx.trace:
        lead, span = ctx.mix["trace"]["lead_s"], ctx.mix["trace"]["span_s"]
    seconds = min(ctx.seconds, lead + span)
    passes, got, xval = [], [], []
    tracing = False
    t0 = ctx.open_window()
    while True:
        now = time.perf_counter() - t0
        if passes and now >= seconds:
            break
        if ctx.trace and not tracing and now >= lead:
            ctx.start_trace()
            tracing = True
        i = len(passes) % len(programs)
        a = time.perf_counter() - t0
        try:
            with jax.profiler.TraceAnnotation(single.PASS_SPAN):
                report = machine.run(programs[i])
        except Exception:    # the pass failed: it answered nothing
            traceback.print_exc(file=sys.stderr)
            report = None
        passes.append({"t0": a, "t1": time.perf_counter() - t0,
                       "program": i, "traced": tracing,
                       "devices": executor.devices_used})
        if report is None:
            got.append({})
            xval.append(float("inf"))
            break
        got.append({name: report.stage_reports[name].outputs
                    for name in report.stage_reports})
        xval.append(single._worst_xval(report))
    elapsed = time.perf_counter() - t0
    if tracing:
        ctx.stop_trace()
    ctx.close_window()
    ctx.read_memory()

    wqkv, wo = ref.weights(block, ctx.seed)
    want = {}
    bad = []
    for p, outs in zip(passes, got):
        i = p["program"]
        if i not in want:
            want[i] = ref.outputs(block, rows[i], wqkv, wo)
        bad.append(ref.mismatches(outs, want[i]))
    traced = [p for p in passes if p["traced"]]
    observed = {"passes": traced, "tile_calls": [],
                "program_ops": work.program_ops(
                    st.workload for st in programs[0])}
    checks = {"stage_mismatch": {"value": sum(bad),
                                 "limit": g["mismatch_limit"]}}
    notes = {"passes": len(passes), "programs": len(programs),
             "xval_err": max(xval) if xval else None,
             "devices_used": sorted({p["devices"] for p in passes})}
    return Outcome(end_to_end={"legion_pass_s": elapsed / len(passes)},
                   checks=checks, attempted=len(passes),
                   failed=sum(b > 0 for b in bad), observed=observed,
                   notes=notes)
