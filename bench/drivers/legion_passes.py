"""Closed-loop passes of the Legion executor over attention Programs.

Set-up lowers ``programs`` attention blocks (the configuration's
``legion`` group: layers, sequence length, accelerator, kernel backend),
each streaming its own rows from the seed, and runs one pass to compile.
The window then runs ``Machine.run`` back to back, cycling through them;
the last pass started in the window is finished and counted.  Every pass's
stage outputs are compared with the plain reference after the window.
"""
from __future__ import annotations

import sys
import time
import traceback

import jax

from bench.references import legion_attention as ref

PASS_SPAN = "bench.pass"


class TileCounter:
    """Counts the bitlinear tile calls of a run: (m, k, n, bits) each."""

    def __init__(self) -> None:
        self.on = False
        self.calls: list = []
        self._tile = None

    def on_plan_begin(self, plan, mode, ctx) -> None:
        from repro.legion.modes import BITLINEAR

        self._tile = ((ctx.m, ctx.k_window, mode.weight_bits)
                      if mode.backend == BITLINEAR else None)

    def on_pass(self, *, n_lo: int, n_hi: int, **_kw) -> None:
        if self.on and self._tile is not None:
            m, k, bits = self._tile
            self.calls.append((m, k, n_hi - n_lo, bits))


def block_of(config: dict) -> dict:
    g = config["legion"]
    return {"hidden": config["hidden_size"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"], "layers": g["layers"],
            "seq_len": g["seq_len"], "weight_bits": g["weight_bits"]}


def _worst_xval(report) -> float:
    errs = []
    for r in report.stage_reports.values():
        if r.traffic_validation is None or r.cycle_validation is None:
            return float("inf")
        errs.extend(r.traffic_validation.errors.values())
        errs.append(r.cycle_validation.rel_err)
    return max(errs)


def run(ctx):
    from bench.harness import Outcome
    from bench.work import legion as work
    from repro.core import config as accel_configs
    from repro.core.workloads import AttentionSpec
    from repro.legion import Machine, PipelinedExecutor, lower_attention

    g = ctx.config["legion"]
    block = block_of(ctx.config)
    spec = AttentionSpec(name=ctx.config["registry"], **block)
    rows = ref.streamed_rows(block, ctx.seed, ctx.mix["programs"])
    programs = [lower_attention(spec, x=x, seed=ref.weight_seed(ctx.seed))
                for x in rows]
    accel = getattr(accel_configs, g["accelerator"])()
    machine = Machine(accel, backend=PipelinedExecutor(),
                      kernel_backend=g["kernel_backend"])
    counter = machine.add_instrument(TileCounter())
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
            tracing = counter.on = True
        i = len(passes) % len(programs)
        a = time.perf_counter() - t0
        try:
            with jax.profiler.TraceAnnotation(PASS_SPAN):
                report = machine.run(programs[i])
        except Exception:    # the pass failed: it answered nothing
            traceback.print_exc(file=sys.stderr)
            report = None
        passes.append({"t0": a, "t1": time.perf_counter() - t0,
                       "program": i, "traced": tracing})
        if report is None:
            got.append({})
            xval.append(float("inf"))
            break
        got.append({name: report.stage_reports[name].outputs
                    for name in report.stage_reports})
        xval.append(_worst_xval(report))
    elapsed = time.perf_counter() - t0
    if tracing:
        counter.on = False
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
    g_ops = work.program_ops(st.workload for st in programs[0])
    traced = [p for p in passes if p["traced"]]
    observed = {"passes": traced, "tile_calls": counter.calls,
                "program_ops": g_ops}
    checks = {"stage_mismatch": {"value": sum(bad),
                                 "limit": g["mismatch_limit"]}}
    # the program's own traffic and cycle model against simulate(): a
    # behaviour check with no reference of its own, reported beside
    notes = {"passes": len(passes), "programs": len(programs),
             "xval_err": max(xval) if xval else None,
             "tile_calls_traced": len(counter.calls)}
    return Outcome(end_to_end={"legion_pass_s": elapsed / len(passes)},
                   checks=checks, attempted=len(passes),
                   failed=sum(b > 0 for b in bad), observed=observed,
                   notes=notes)
