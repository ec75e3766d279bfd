"""Smoke run of the main path on one TPU chip, at published width.

All phases run in this one process, on the device JAX reports:

* device: refuse anything that is not a TPU (no CPU fallback);
* serve: ``bitnet-1.58b`` as published (32 layers, d_model 2560, vocab
  32000, bf16), random weights from ``--seed``, through ``ServeEngine``
  (``max_slots=4``, ``max_seq=2048``): legacy steps, in-flight steps
  (``prefill_chunk_tokens=256``), the Legion serve backend on a short run,
  and the cached prefill-then-decode logits of one request against the
  float32 full forward pass (``api.train_logits``, no cache);
* legion: the BitNet-1.58B attention block lowered to a Legion Program and
  run through ``Machine(kernel_backend="pallas")``, bit-exact against the
  NumPy reference, with the compiled bitlinear kernel confirmed.

``--four-chips`` runs only the sharded Legion phase instead: the same
attention Program through ``PipelinedExecutor(ShardedExecutor())`` on four
chips, bit-for-bit against ``InProcessExecutor``.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --four-chips

Each phase prints one ``phase <name> {json}`` line with its wall and compile
seconds and the device-memory high-water mark.  The last line is
``{"ok": true, "device": {...}}``; any failure raises before it, so the exit
code is non-zero and that line is absent.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import dlegion  # noqa: E402
from repro.core.workloads import bitnet_1_58b_kv  # noqa: E402
from repro.kernels.bitlinear.ops import tile_gemm  # noqa: E402
from repro.legion import (  # noqa: E402
    InProcessExecutor,
    Machine,
    PipelinedExecutor,
    ShardedExecutor,
    lower_attention,
    reference_outputs,
)
from repro.legion.modes import BITLINEAR  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import LegionServeBackend, ServeEngine  # noqa: E402
from repro.serve.engine import prepare_params  # noqa: E402

# Requests of the serve phase: a few distinct prompt lengths (each one
# compiles its own whole-prompt prefill) and one more request than slots,
# so one of them waits for a slot.
PROMPT_LENS = (16, 240, 256, 512, 16)
MAX_NEW = (8, 12, 16, 10, 8)
CHUNK_TOKENS = 256
# Cached decode against the float32 full forward pass, per position:
# ||engine - ref|| / ||ref|| over the vocabulary.  The engine computes in
# bf16 (unit roundoff 2**-9) and re-quantizes activations to int8 per
# token, so a rounding difference can move an activation by a whole int8
# step; that gave ~2% on the CPU at full depth and reduced width.  0.05
# leaves room for the wider model while a format with fewer mantissa bits
# (fp8 e4m3: 16x bf16's roundoff) would exceed it.
LOGIT_REL_TOL = 0.05
CHECK_NEW = 8


class CompileClock:
    """Sums JAX's backend-compile seconds and persistent-cache hits."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.functions: list = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw) -> None:
        if event == self.COMPILE:
            self.seconds += secs
            self.functions.append(kw.get("fun_name", ""))

    def _event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        """Yield a dict the phase fills; print it with wall and compile
        seconds when the phase completes."""
        info: dict = {}
        t0, c0, h0, m0 = time.perf_counter(), self.seconds, self.hits, \
            self.misses
        n0 = len(self.functions)
        yield info
        # the process's device-memory high-water mark so far (None where
        # the backend keeps no statistics)
        stats = jax.devices()[0].memory_stats() or {}
        info.update(
            wall_s=time.perf_counter() - t0,
            compile_s=self.seconds - c0,
            compiles=len(self.functions) - n0,
            cache_hits=self.hits - h0,
            cache_misses=self.misses - m0,
            peak_bytes=stats.get("peak_bytes_in_use"),
        )
        print(f"phase {name} {json.dumps(info, default=_jsonable)}",
              flush=True)


def _jsonable(v):
    if isinstance(v, (np.integer, np.floating)):
        return v.item()
    raise TypeError(type(v))


class LogitRecorder(ServeEngine):
    """A ServeEngine that keeps every logits row it samples from."""

    def __init__(self, *args, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        self.sampled: list = []

    def _sample(self, logits):
        self.sampled.append(np.asarray(logits, np.float32))
        return super()._sample(logits)


def _serve_requests(engine, prompts) -> list:
    reqs = [engine.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, MAX_NEW)]
    engine.run_until_done()
    for r in reqs:
        if not (r.done and not r.truncated and not r.refused
                and len(r.output) == r.max_new_tokens):
            raise AssertionError(f"request {r.uid} did not finish: {r}")
    for row in engine.sampled:
        if not np.isfinite(row).all():
            raise AssertionError("non-finite logits in a served step")
    return reqs


def serve_phase(clock: CompileClock, cfg, seed: int, *, max_seq: int = 2048,
                prompt_lens=PROMPT_LENS, chunk_tokens: int = CHUNK_TOKENS
                ) -> None:
    api = build_model(cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in prompt_lens]

    with clock.phase("serve/init") as info:
        params = prepare_params(jax.jit(api.init)(jax.random.PRNGKey(seed)))
        jax.block_until_ready(params)
        info.update(arch=cfg.name, layers=cfg.layers, d_model=cfg.d_model,
                    vocab=cfg.vocab, dtype=cfg.dtype,
                    param_bytes=sum(a.nbytes for a in jax.tree.leaves(params)))

    with clock.phase("serve/legacy") as info:
        eng = LogitRecorder(api, params, max_slots=4, max_seq=max_seq)
        reqs = _serve_requests(eng, prompts)
        legacy_out = [r.output for r in reqs]
        info.update(requests=len(reqs),
                    prompt_tokens=sum(len(p) for p in prompts),
                    new_tokens=sum(len(o) for o in legacy_out),
                    decode_steps=len(eng.decode_batch_sizes),
                    max_slots=4, max_seq=max_seq)

    # One request alone on the same engine: it takes slot 0, so row 0 of
    # every decode step is its logits.
    with clock.phase("serve/check_request") as info:
        eng.sampled = []
        check_prompt = prompts[1]
        check = eng.submit(check_prompt, max_new_tokens=CHECK_NEW)
        eng.run_until_done()
        engine_logits = np.stack([eng.sampled[0][0]]
                                 + [row[0] for row in eng.sampled[1:]])
        check_tokens = np.concatenate(
            [check_prompt, np.asarray(check.output[:-1], np.int32)])
        info.update(prompt_tokens=len(check_prompt),
                    new_tokens=len(check.output))

    with clock.phase("serve/legion_backend") as info:
        backend = LegionServeBackend(dlegion(), cfg, params).attach(eng)
        t0 = time.perf_counter()
        for p in (prompts[0], prompts[-1]):
            eng.submit(p, max_new_tokens=4)
        eng.run_until_done()
        run_s = time.perf_counter() - t0
        steps = backend.prefill_steps + backend.decode_steps
        tv, cv = backend.cross_validate(m=1, contexts=(16,))
        worst = max([e for v in tv for e in v.errors.values()]
                    + [v.rel_err for v in cv])
        if not all(v.ok for v in tv + cv):
            raise AssertionError(f"Legion serve cross-validation: {tv} {cv}")
        info.update(requests=2, new_tokens=4,
                    prefill_steps=backend.prefill_steps,
                    decode_steps=backend.decode_steps,
                    s_per_step=run_s / steps, xval_worst_err=worst,
                    executor=backend.machine.backend.name)
    del eng, backend

    with clock.phase("serve/inflight") as info:
        eng = LogitRecorder(api, params, max_slots=4, max_seq=max_seq,
                         prefill_chunk_tokens=chunk_tokens)
        reqs = _serve_requests(eng, prompts)
        same = sum(r.output == o for r, o in zip(reqs, legacy_out))
        info.update(requests=len(reqs),
                    new_tokens=sum(len(r.output) for r in reqs),
                    prefill_chunk_tokens=chunk_tokens,
                    outputs_equal_to_legacy=same)
    del eng

    # float32 reference: every bf16 leaf is widened (exactly) and dropped
    # one at a time, so the two copies never sit on the device together.
    with clock.phase("serve/reference") as info:
        leaves, treedef = jax.tree.flatten(params)
        del params
        wide = []
        while leaves:
            wide.append(leaves.pop(0).astype(jnp.float32))
        params32 = jax.tree.unflatten(treedef, wide)
        del wide
        # remat is a training memory policy; under it the compiled forward
        # keeps 4.4 GB of temporaries beside the 9.8 GB of f32 weights
        api32 = build_model(cfg.replace(dtype="float32", remat="none"))
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(api32.train_logits)(
                params32, {"tokens": jnp.asarray(check_tokens[None])})
        start = len(check_prompt) - 1
        ref = np.asarray(ref[0, start:start + CHECK_NEW], np.float32)
        rel = (np.linalg.norm(engine_logits - ref, axis=-1)
               / np.linalg.norm(ref, axis=-1))
        info.update(positions=len(rel), rel_err_max=float(rel.max()),
                    rel_err_mean=float(rel.mean()), tol=LOGIT_REL_TOL,
                    top1_agree=float((engine_logits.argmax(-1)
                                      == ref.argmax(-1)).mean()))
        if not rel.max() <= LOGIT_REL_TOL:
            raise AssertionError(
                f"cached decode vs full forward: relative error "
                f"{rel.max():.4f} > {LOGIT_REL_TOL}")


def _attention_program(seed: int):
    spec = dataclasses.replace(bitnet_1_58b_kv(seq_len=128), layers=1)
    return lower_attention(spec, seed=seed)


def _check_bit_exact(report, program) -> float:
    """Every stage equals the NumPy reference; returns the worst traffic
    and cycle cross-validation error (required to be 0)."""
    for name, want in reference_outputs(program).items():
        if not np.array_equal(report.outputs[name], want):
            raise AssertionError(f"{name}: runtime != NumPy reference")
    worst = max(
        [e for r in report.stage_reports.values()
         for e in r.traffic_validation.errors.values()]
        + [r.cycle_validation.rel_err for r in report.stage_reports.values()]
    )
    if worst != 0.0:
        raise AssertionError(f"cross-validation error {worst} (expected 0)")
    return worst


def legion_phase(clock: CompileClock, seed: int) -> None:
    accel = dlegion()
    program = _attention_program(seed)
    with clock.phase("legion/attention_pallas") as info:
        machine = Machine(accel, backend=PipelinedExecutor(),
                          kernel_backend="pallas")
        n0 = len(clock.functions)
        t0 = time.perf_counter()
        report = machine.run(program)
        run_s = time.perf_counter() - t0
        compiled = clock.functions[n0:]
        worst = _check_bit_exact(report, program)

        # The projection stages ran the bitlinear Pallas kernel, compiled:
        # the run compiled it, and the exact tile call it makes lowers to
        # a Mosaic custom call (interpret mode would lower to plain HLO).
        qkv = report.stage_reports["qkv_proj"]
        if qkv.mode.backend != BITLINEAR:
            raise AssertionError(f"qkv_proj ran mode {qkv.mode.name}")
        if "jit(bitlinear_matmul)" not in compiled:
            raise AssertionError(
                f"the run compiled no bitlinear kernel: {sorted(set(compiled))}")
        k_win = qkv.plan.assignments[0].k_window or accel.cores * accel.d
        bits = qkv.mode.weight_bits
        x = jax.ShapeDtypeStruct((qkv.workload.m, k_win), jnp.int8)
        w = jax.ShapeDtypeStruct((k_win * bits // 8, qkv.mode.n_tile(accel.d)),
                                 jnp.uint8)
        hlo = jax.jit(functools.partial(
            tile_gemm, bits=bits, backend="pallas")).lower(x, w).as_text()
        if "tpu_custom_call" not in hlo:
            raise AssertionError("bitlinear tile call is not a Mosaic kernel")
        info.update(stages=len(program), run_s=run_s, xval_worst_err=worst,
                    tile=[qkv.workload.m, k_win, qkv.mode.n_tile(accel.d)],
                    kernel_compiles=compiled.count("jit(bitlinear_matmul)"),
                    tpu_custom_call=True,
                    overlap_x=report.pipeline.speedup)


def four_chip_phase(clock: CompileClock, seed: int) -> None:
    accel = dlegion()
    program = _attention_program(seed)
    with clock.phase("legion/sharded_4chips") as info:
        t0 = time.perf_counter()
        base = Machine(accel, backend=InProcessExecutor(),
                       kernel_backend="pallas").run(program)
        base_s = time.perf_counter() - t0
        sharded = ShardedExecutor()
        t0 = time.perf_counter()
        report = Machine(accel, backend=PipelinedExecutor(sharded)) \
            .run(program)
        sharded_s = time.perf_counter() - t0
        for name, want in base.outputs.items():
            if not np.array_equal(report.outputs[name], want):
                raise AssertionError(f"{name}: sharded != in-process")
        worst = _check_bit_exact(report, program)
        if sharded.devices_used != 4:
            raise AssertionError(
                f"shards landed on {sharded.devices_used} devices, not 4")
        info.update(stages=len(program), devices_used=sharded.devices_used,
                    inprocess_s=base_s, sharded_s=sharded_s,
                    xval_worst_err=worst, bit_exact=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and operands")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded Legion phase, on 4 chips")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    want = 4 if args.four_chips else 1
    with clock.phase("device") as info:
        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        if device["platform"] != "tpu":
            raise SystemExit(f"chip_smoke needs a TPU; JAX found {device}")
        if device["count"] < want:
            raise SystemExit(
                f"chip_smoke needs {want} chip(s); found {device}")
        info.update(device, cache_dir=cache_dir)

    if args.four_chips:
        four_chip_phase(clock, args.seed)
    else:
        serve_phase(clock, get_config("bitnet-1.58b"), args.seed)
        legion_phase(clock, args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
