"""Quickstart: the D-Legion stack in five minutes.

    PYTHONPATH=src python examples/quickstart.py

1. Reproduce the paper's headline comparison with the cycle simulator.
2. Run the packed-ternary bitlinear Pallas kernel (interpret mode).
3. Build a ZTB from a block-sparse weight and run the sparse kernel.
4. One QAT train step + one serving step of a tiny BitNet model.
5. Execute one attention stage through a `legion.Machine` session and
   cross-check its measured traffic against the simulator.
6. Drive one serving decode step's projection GEMMs through the serve-path
   Legion backend — per-token bytes AND cycles, cross-validated.
7. The Machine session API: one-liner runs, custom instruments, and the
   sharded executor backend (Legions on a JAX mesh axis, bit-exact).
8. The Program graph API: a full attention block (QKV -> score -> softmax
   -> output -> O-proj) as one dependency graph, bit-exact against a pure
   NumPy reference, with the PipelinedExecutor overlapping rounds of
   independent stages.
9. Serve pipelining: a two-explicit-layer program (layer 1's QKV streams
   layer 0's MLP output), a merged two-slot decode batch overlapping
   across slots, and the engine-view overlapped tokens/sec feeding the
   KV-cache budget.
10. Observability: a `TimelineTracer` instrument reconstructs the cycle
    timeline of a pipelined program (exact parity with the counter) and
    exports a Chrome/Perfetto trace; a `MetricsRegistry` snapshots the
    machine + serve metric families.
11. In-flight batching: chunked prefill merged with the decode batch into
    one Program per step, intake gated by `LiveAdmission` — bit-exact vs
    the legacy engine.
12. Paged KV cache: a page pool squeezed to force preemption mid-decode —
    evicted requests re-prefill and finish bit-exactly, while the Legion
    backend prices real page fetches and last-page waste.
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    adip_64, attention_workloads, bitnet_1_58b, compare, dip_64, dlegion,
    simulate, ws_64,
)
from repro.core.sparsity import prune_block_structured, ztb_from_weight
from repro.kernels.bitlinear.kernel import bitlinear_matmul
from repro.kernels.block_sparse.ops import ztb_matmul
from repro.quant.packing import pack_2bit_kmajor

print("=" * 70)
print("1. Cycle simulator — D-Legion vs WS / DiP / ADiP (paper Figs. 7-10)")
wl = attention_workloads(bitnet_1_58b())
reports = [simulate(c, wl) for c in (ws_64(), dip_64(), adip_64(),
                                     dlegion())]
for r in reports:
    print(f"   {r.arch:14s} latency={r.total_seconds*1e3:8.2f} ms"
          f"  throughput={r.total_tops:6.2f} TOPS"
          f"  memory={r.total_mem_gb:6.2f} GB  psum={r.total_psum_gb:6.1f} GB")
ratios = compare(reports, "ADiP-64x64")["D-Legion-8L"]
print(f"   D-Legion vs ADiP: {ratios['latency_x']:.2f}x latency, "
      f"{ratios['mem_x']:.2f}x memory, {ratios['psum_x']:.2f}x psum")

print("=" * 70)
print("2. bitlinear kernel — ternary weights packed 4-per-byte")
rng = np.random.default_rng(0)
w = rng.integers(-1, 2, size=(512, 256)).astype(np.int8)
x = rng.integers(-128, 128, size=(128, 512)).astype(np.int8)
wp = pack_2bit_kmajor(jnp.asarray(w))
out = bitlinear_matmul(jnp.asarray(x), wp, interpret=True)
assert (np.asarray(out) == x.astype(np.int32) @ w.astype(np.int32)).all()
print(f"   x[{x.shape}] @ packed w[{wp.shape} uint8] == int32 GEMM: OK "
      f"(weight bytes: {w.size * 2}B bf16 -> {wp.size}B packed, "
      f"{w.size * 2 / wp.size:.0f}x less)")

print("=" * 70)
print("3. ZTB block-sparse kernel — fully-sparse windows never touched")
wf = rng.standard_normal((512, 384)).astype(np.float32)
wf = prune_block_structured(wf, block_k=128, block_n=128, sparsity=0.5)
book = ztb_from_weight(wf, block_k=128, block_n=128, window=4)
nz = book.tile_nonzero.reshape(-1, 384 // 128)[: 512 // 128]
xf = rng.standard_normal((128, 512)).astype(np.float32)
out = ztb_matmul(jnp.asarray(xf), jnp.asarray(wf), np.asarray(nz),
                 backend="pallas", interpret=True)
np.testing.assert_allclose(np.asarray(out), xf @ wf, rtol=1e-4, atol=1e-3)
stats = book.stats()
print(f"   sparsity={stats.zero_tile_fraction:.2f}, "
      f"fully-sparse windows={stats.fully_sparse_fraction:.2f}, allclose OK")

print("=" * 70)
print("4. Tiny BitNet: one QAT step + one serving decode")
from repro.configs import get_config, reduced
from repro.data import synthetic_batch
from repro.models import build_model
from repro.serve.engine import prepare_params
from repro.train import AdamW, build_train_step, init_train_state

cfg = reduced(get_config("bitnet-1.58b"))
api = build_model(cfg)
opt = AdamW(lr=1e-3)
state = init_train_state(api, opt, jax.random.PRNGKey(0))
step = jax.jit(build_train_step(api, opt))
batch = {k: jnp.asarray(v) for k, v in
         synthetic_batch(cfg, batch=2, seq=64, step=0).items()}
state, metrics = step(state, batch)
print(f"   QAT train step: loss={float(metrics['loss']):.3f}")
params = prepare_params(state.params)
cache = api.init_cache(1, 80)
logits, cache = api.prefill(params, {"tokens": batch["tokens"][:1]}, cache)
tok = int(jnp.argmax(logits[0, -1]))
logits, cache = api.decode(params, jnp.array([tok]), cache, jnp.int32(64))
print(f"   served (ternary weights): first sampled token={tok}")

print("=" * 70)
print("5. Legion Machine — one attention stage executed through the plan")
import dataclasses

from repro.core.workloads import attention_workloads as _wl, bitnet_1_58b_kv
from repro.legion import Machine

spec = dataclasses.replace(bitnet_1_58b_kv(seq_len=128), layers=1)
score = _wl(spec)[1]          # Q @ K^T per head, int8, GQA KV multicast
cfg_leg = dlegion()
machine = Machine(cfg_leg)
res = machine.run(score)      # plan + synthesize + execute + validate
assert res.ok                 # traffic AND cycles within 5% of simulate()
tot, sim = res.trace.totals, simulate(cfg_leg, [score]).stages[score.stage]
print(f"   {score.stage}: {score.count} heads on {cfg_leg.units} Legions, "
      f"mode={res.mode.name}, outputs={res.outputs.shape} == x @ w: OK")
print(f"   measured  weight={tot.weight_bytes / 1e6:6.3f} MB  "
      f"act={tot.act_bytes / 1e6:6.3f} MB  psum={tot.psum_bytes / 1e6:6.3f} MB")
print(f"   analytic  weight={sim.weight_bytes / 1e6:6.3f} MB  "
      f"act={sim.act_bytes / 1e6:6.3f} MB  psum={sim.psum_bytes / 1e6:6.3f} MB")
print(f"   NoC multicast deduped {res.trace.multicast_hits} tile transfers")

print("=" * 70)
print("6. Serve-path Legion backend — one decode step through the Machine")
from repro.serve.legion_backend import LegionServeBackend

backend = LegionServeBackend(cfg_leg, cfg, params)   # SS4's served weights
# one decode token at context 16
tally = backend.step_tally(decode_contexts=(16,))
tvals, cvals = backend.cross_validate(decode_contexts=(16,))
assert all(v.ok for v in tvals + cvals)
print(f"   {tally.gemms} GEMMs lowered to one Program and executed: "
      f"wq/wk/wv/wo + w1/w2/w3 projections AND the act-to-act attention "
      f"stages\n   (KV cache as stationary operands, K/N = context 16)")
print(f"   per decode token: {tally.cycles} cycles "
      f"({tally.seconds(cfg_leg.freq_hz) * 1e6:.2f} us @ 1 GHz), "
      f"weight={tally.weight_bytes / 1e3:.1f} KB, "
      f"act={tally.act_bytes / 1e3:.1f} KB")
worst = max(v.rel_err for v in cvals)
print(f"   measured vs simulate() on the same workloads: "
      f"worst cycle error {worst * 100:.2f}% — serve path cross-validated")

print("=" * 70)
print("7. Machine session API — instruments + executor backends")
from repro.legion import Instrument, ShardedExecutor


class PassCounter(Instrument):
    """Custom instrument: count executed vs ZTB-skipped passes."""

    def __init__(self):
        self.executed = 0
        self.skipped = 0

    def on_pass(self, **event):
        self.executed += 1

    def on_window_skip(self, **event):
        self.skipped += 1


probe = PassCounter()
machine = Machine(cfg_leg, instruments=[probe])   # session-lifetime hook
rep = machine.run(score)                          # fresh tracer+counter/run
print(f"   instrument saw {probe.executed} executed passes; report merges "
      f"weight={rep.traffic.weight_bytes / 1e6:.3f} MB, "
      f"{rep.total_cycles} cycles, validation ok={rep.ok}")

sharded = Machine(cfg_leg, backend=ShardedExecutor())
rep_sh = sharded.run(score)   # Legion axis on a JAX mesh axis (shard_map)
assert np.array_equal(rep.outputs, rep_sh.outputs)   # bit-exact parity
assert rep_sh.trace.totals == rep.trace.totals
print(f"   ShardedExecutor on {sharded.backend.devices_used} device(s): "
      f"outputs bit-exact, traffic/cycles identical "
      f"(run with XLA_FLAGS=--xla_force_host_platform_device_count=8 to "
      f"spread 8 Legions)")

print("=" * 70)
print("8. Program graph API — whole attention block, pipelined")
from repro.legion import PipelinedExecutor, lower_attention, reference_outputs

block = lower_attention(spec)                 # QKV -> score -> out -> O-proj
piped = Machine(cfg_leg, backend=PipelinedExecutor())
prep = piped.run(block)                       # ProgramReport
assert prep.ok                                # every stage at 0% vs simulate()
ref = reference_outputs(block)                # pure-NumPy graph execution
assert all(np.array_equal(prep.outputs[k], ref[k]) for k in ref)
print(f"   {len(block)} stages ({' -> '.join(block.names)})")
print(f"   act-to-act stages executed as real GEMMs (K/V stationary, GQA "
      f"multicast); all outputs == NumPy reference")
pp = prep.pipeline
print(f"   chain graph: overlapped == serial == {pp.serial_cycles} cycles "
      f"(dependency chains cannot overlap)")
split = lower_attention(spec, split_qkv=True)  # q/k/v independent stages
pp2 = piped.run(split).pipeline
print(f"   split q/k/v graph: serial={pp2.serial_cycles} -> "
      f"overlapped={pp2.overlapped_cycles} cycles "
      f"({pp2.speedup:.3f}x, {pp2.hidden_cycles} fill/pipeline cycles "
      f"hidden under independent streams)")

print("=" * 70)
print("9. Serve pipelining — multi-layer programs + merged decode batches")
# Two EXPLICIT transformer layers: layer 1's QKV streams layer 0's MLP
# output through a real cross-layer dependency (no `layers` scalar).
two_layer = backend.step_program(decode_contexts=(16,), explicit_layers=2)
rep9 = piped.run(two_layer)
assert rep9.ok
print(f"   two-layer step program: {len(two_layer)} stages, "
      f"qkv_proj@1 depends on {two_layer['qkv_proj@1'].deps} — "
      f"explicit cross-layer dep, 0% xval per stage")

# One decode step's merged batch graph: two slots at different contexts,
# per-slot attention interleaved as an antichain under shared projections.
merged = backend.step_program(decode_contexts=(12, 20))
pp9 = piped.run(merged).pipeline
print(f"   merged 2-slot decode batch: serial={pp9.serial_cycles} -> "
      f"overlapped={pp9.overlapped_cycles} cycles "
      f"({pp9.speedup:.3f}x — slots hide each other's fill/pipeline)")

# Engine view: the overlapped per-token cycles feed the KV-cache budget.
serial9, overlapped9 = backend.step_pipeline(decode_contexts=(12, 20))
from repro.serve.kv_cache import plan as kv_plan
budget = kv_plan(cfg, batch=2, max_seq=64, hbm_bytes_per_chip=16e9,
                 chips=1, cycles_per_token=overlapped9 / 2,
                 freq_hz=cfg_leg.freq_hz,
                 serial_cycles_per_token=serial9 / 2)
print(f"   engine view: {budget.tokens_per_sec:,.0f} tokens/s/slot "
      f"overlapped (pipelining x{budget.pipelining_speedup:.3f} vs "
      f"serial) -> latency-aware KV-cache admission")

print("=" * 70)
print("10. Observability — timeline trace export + metrics registry")
import os
import tempfile

from repro.obs import MetricsRegistry, TimelineTracer

tracer = TimelineTracer(cfg_leg)
reg = MetricsRegistry()
obs_machine = Machine(cfg_leg, backend=PipelinedExecutor(),
                      instruments=[tracer], metrics=reg)
rep10 = obs_machine.run(merged)               # the 2-slot decode batch
# the tracer rebuilds the timeline from Instrument events alone, yet
# lands on the counter's cycles EXACTLY — serial and overlapped both
assert tracer.serial_cycles() == rep10.serial_cycles
assert tracer.overlapped_cycles() == rep10.total_cycles
path = os.path.join(tempfile.mkdtemp(), "trace.json")
tracer.export(path)
tl = tracer.programs[-1]
print(f"   traced {len(tl.cells)} round slices across "
      f"{len(tl.stage_order)} stages: serial makespan "
      f"{tracer.serial_cycles()} == counter, overlapped "
      f"{tracer.overlapped_cycles()} == pipeline report")
print(f"   Chrome trace written to {path} — open in ui.perfetto.dev "
      f"(pid 0 = serial placement, pid 1 = overlapped)")
snap = reg.snapshot()
print(f"   metrics: {len(snap)} families; "
      f"machine_cycles={snap['machine_cycles']['series']['']:.0f}, "
      f"machine_passes={snap['machine_passes']['series']['']:.0f}, "
      f"pipeline speedup p50="
      f"{reg.get('machine_pipeline_speedup').percentile(50):.3f}x")

print("=" * 70)
print("11. In-flight batching — chunked prefill merged with decode + "
      "live admission")
from repro.serve import LiveAdmission, ServeEngine

# Chunked prefill is BIT-EXACT vs whole-prompt prefill, so the in-flight
# engine emits exactly the tokens the legacy engine does — while merging
# each step's prefill chunks with the batched decode into ONE Program.
ifb_backend = LegionServeBackend(cfg_leg, cfg, params)
eng11 = ServeEngine(api, params, max_slots=3, max_seq=64,
                    prefill_chunk_tokens=8,
                    admission=LiveAdmission(ifb_backend,
                                            hbm_bytes_per_chip=8 << 30))
ifb_backend.attach(eng11)
prompts11 = [np.arange(1, 4 + 3 * i) for i in range(4)]
reqs11 = [eng11.submit(p, max_new_tokens=3 + i % 2)
          for i, p in enumerate(prompts11)]
done11 = eng11.run_until_done()

legacy11 = ServeEngine(api, params, max_slots=3, max_seq=64)
legacy_reqs = [legacy11.submit(p, max_new_tokens=3 + i % 2)
               for i, p in enumerate(prompts11)]
legacy11.run_until_done()
assert [r.output for r in reqs11] == \
    [r.output for r in legacy_reqs]                      # bit-exact

s11 = ifb_backend.summary()
mixed = sum(1 for e in eng11.step_log if e["phase"] == "prefill_chunk")
print(f"   {len(done11)} requests, {mixed} prefill chunks merged into "
      f"{s11['engine_steps']} engine steps (one Program each)")
print(f"   engine view incl. prefill: "
      f"overlapped {s11['overlapped_cycles_per_step']:.0f} <= "
      f"serial {s11['serial_cycles_per_step']:.0f} cycles/step "
      f"(x{s11['pipeline_speedup']:.3f})")
print(f"   live admission on the measured budget: "
      f"{eng11.admission.stats.admitted} admitted, "
      f"{eng11.admission.stats.deferred} deferred, "
      f"{eng11.admission.stats.refused} refused; window truncations "
      f"flagged: {sum(r.truncated for r in done11)}")

print("=" * 70)
print("12. Paged KV cache — block allocator, forced preemption, "
      "page-priced traffic")
from repro.serve import PagedKVCache

# Pool squeezed to 8 pages x 4 tokens — exactly one max_seq=32 window
# shared by three slots — forcing mid-decode evictions (pages freed, request re-queued
# for re-prefill) — yet every output stays BIT-EXACT vs the contiguous
# engine, because re-prefill replays prompt + generated-so-far.
prompts12 = [np.arange(1, 5 + 2 * i) for i in range(5)]
paged12 = PagedKVCache(total_pages=8, page_tokens=4)
pg_backend = LegionServeBackend(cfg_leg, cfg, params, page_tokens=4)
eng12 = ServeEngine(api, params, max_slots=3, max_seq=32, paged_kv=paged12)
pg_backend.attach(eng12)
reqs12 = [eng12.submit(p, max_new_tokens=6) for p in prompts12]
eng12.run_until_done()

ref12 = ServeEngine(api, params, max_slots=3, max_seq=32)
ref_reqs = [ref12.submit(p, max_new_tokens=6) for p in prompts12]
ref12.run_until_done()
assert [r.output for r in reqs12] == \
    [r.output for r in ref_reqs]                         # bit-exact

st12 = paged12.allocator.stats()
preempts = sum(1 for e in eng12.step_log if e["phase"] == "preempt")
assert preempts > 0 and st12.pinned_pages == 0
s12 = pg_backend.summary()
print(f"   {len(reqs12)} requests through {st12.total_pages} pages of "
      f"{paged12.page_tokens} tokens: {preempts} preemptions "
      f"({st12.evictions} evictions), outputs bit-exact vs contiguous")
print(f"   page-priced traffic: {s12['page_fetches']:.0f} fetches, "
      f"{s12['page_fetch_bytes'] / 1024:.1f} KiB, last-page waste "
      f"{s12['page_waste_frac']:.1%} of page bytes "
      f"(serial cycles unchanged by construction)")

print("=" * 70)
print("13. Finite bandwidth — the stall knee, a cross-validated sweep, "
      "and per-stage roofline points")
from repro.legion import (find_stall_knee, hbm_bytes_per_cycle,
                          sweep_bandwidth)
from repro.obs import RooflineTracer

# How much fetch bandwidth does this attention block actually need?
# find_stall_knee bisects for the smallest stall-free bytes/cycle; the
# paper's budget (128 GB/s per Legion) sits far above it.
wl13 = attention_workloads(spec)
knee = find_stall_knee(cfg_leg, wl13)
budget = hbm_bytes_per_cycle(cfg_leg)
sweep = sweep_bandwidth(cfg_leg, wl13, [knee / 4, knee * 2],
                        cross_validate=True)
assert sweep.worst_rel_err == 0.0      # counted stall == analytic stall
assert sweep.points[0].stalled and not sweep.points[1].stalled
print(f"   stall knee at {knee:.1f} B/cycle "
      f"({budget / knee:.0f}x headroom under the paper budget); "
      f"quarter-knee run stalls {sweep.points[0].stall_frac:.0%} "
      f"of its cycles, cross-validated at 0% error")

# A RooflineTracer rides a below-knee Machine and reduces the event
# stream to one point per stage: intensity, stall_frac, efficiency.
mach13 = Machine(cfg_leg, mem_bw_bytes_per_cycle=knee / 2)
tr13 = mach13.add_instrument(RooflineTracer())
for w in wl13:
    mach13.run(w, check_outputs=False, validate=False)
for p in tr13.rows():
    assert p.efficiency <= 1.0
    bound = "memory" if p.memory_bound else "compute"
    print(f"   {p.stage:<10} {p.mode:<6} {p.arithmetic_intensity:7.1f} "
          f"ops/B  stall {p.stall_frac:5.1%}  eff {p.efficiency:.2f} "
          f"({bound}-bound, {p.legions_used} Legions)")

print("=" * 70)
print("14. Workload zoo — MoE expert skip and the Mamba-2 SSD scan "
      "through the unified legion.lower(spec)")
from repro.legion import lower, moe_stage_names, zoo_spec
from repro.models.mamba2 import ssd_lowering_spec
from repro.models.moe import moe_lowering_spec

# A granite-MoE FFN block: the router's top-k becomes program-level ZTB
# sparsity — unchosen experts lower to fully-skipped windows, and the
# traffic delta vs the dense-E twin is EXACTLY their stationary bytes.
moe_cfg = reduced(get_config("granite-moe-1b-a400m"))
spec14 = moe_lowering_spec(moe_cfg, tokens=16)
prog14 = lower(spec14)                       # == lower(zoo_spec(moe_cfg))
rep14 = Machine(cfg_leg).run(prog14)
ref14 = reference_outputs(prog14)
assert rep14.ok
for name in ref14:                           # skipped experts included
    assert np.array_equal(rep14.outputs[name], ref14[name])
chosen14, skipped14 = spec14.routing()
dense14 = Machine(cfg_leg).run(
    lower(dataclasses.replace(spec14, top_k=spec14.n_experts, chosen=None)))
wb = lambda rep: sum(rep[n].traffic.weight_bytes for n in rep.outputs)
skipped_bytes = sum(dense14[n].traffic.weight_bytes
                    for e in skipped14 for n in moe_stage_names(e))
assert wb(rep14) == wb(dense14) - skipped_bytes          # exact identity
print(f"   MoE {spec14.n_experts} experts, top-{spec14.top_k} "
      f"(chose {list(chosen14)}): {wb(rep14) / 1024:.1f} KiB weights vs "
      f"{wb(dense14) / 1024:.1f} KiB dense — skip saves "
      f"{wb(dense14) / wb(rep14):.2f}x, bit-exact incl. skipped experts")

# The mamba2 SSD scan: chunked state/output GEMMs with the recurrent
# state threaded across chunks as a stationary multi-producer Ref.
ssm_cfg = reduced(get_config("mamba2-130m"))
prog14b = lower(ssd_lowering_spec(ssm_cfg, chunks=2))
rep14b = Machine(cfg_leg).run(prog14b)
assert rep14b.ok
ref14b = reference_outputs(prog14b)
assert all(np.array_equal(rep14b.outputs[k], ref14b[k]) for k in ref14b)
print(f"   SSD scan {ssm_cfg.ssm_heads} heads x 2 chunks of "
      f"{ssm_cfg.ssd_chunk}: {len(prog14b)} stages, bit-exact, "
      f"state carried as a cross-chunk stationary Ref")
print("quickstart complete.")
