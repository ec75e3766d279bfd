"""Serve-path Legion backend — serving steps executed through the runtime.

The serving engine runs whole-model jitted JAX steps; the accelerator models
never saw them.  This bridge closes that gap the way TensorRT-LLM routes
per-step GEMMs through an engine graph: it extracts the projection matrices
(``wq/wk/wv/wo`` and the SwiGLU ``w1/w2/w3``) from the engine's params and
lowers every prefill / decode step to **one**
:class:`~repro.legion.program.Program` — the projection stages *and* the
act-to-act attention stages, with each slot's KV-cache matrices as
stationary activation operands whose K/N depend on the slot's position
(context length ``t`` at decode) and GQA groups sharing one multicast
fetch.  The program executes through a
:class:`~repro.legion.machine.Machine` session, so traced serving traffic
produces measured **byte and cycle tallies per request covering the full
step**, cross-validatable against ``simulate()`` on the very same
workloads.  Pass ``executor=`` (any
:class:`~repro.legion.machine.ExecutorBackend`, e.g. ``ShardedExecutor``
or ``PipelinedExecutor``) to choose how the step programs run.

One representative layer executes numerically (the weights are the engine's
actual ternary codes, taken as int8); tallies scale by
the model's layer count — the same one-layer-times-L convention as
``repro.legion.trace.cross_validate``.  The streamed input and the KV cache
are synthetic int8 (the engine's real activations live inside the jitted
graph), but the intermediate activations thread through the program graph
(qkv -> score -> softmax -> output -> O-proj -> SwiGLU mlp), so the GEMMs
are numerically real — every stage output is checked against the plain
``x @ w`` reference — while the *shapes, weights, plans, dependencies,
traffic, and cycles* are the serving step's own.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.config import AcceleratorConfig
from repro.core.simulator import simulate
from repro.core.workloads import (
    ATTN_OUTPUT,
    ATTN_SCORE,
    GEMMWorkload,
    HEAD_PER_UNIT,
    MLP_DOWN,
    MLP_UP,
    N_PARTITION,
    OUT_PROJ,
    QKV_PROJ,
    decode_attention_workloads,
)
from repro.legion.latency import (
    CycleBreakdown,
    CycleValidation,
    merge_round_criticals,
)
from repro.legion.lowering import ServeStepSpec, lower
from repro.legion.machine import ExecutorBackend, Machine
from repro.legion.program import (
    STATIONARY_ACT,
    Program,
    ProgramStage,
    Ref,
    compute_pipeline,
    softmax_int8,
)
from repro.legion.trace import StageValidation, TrafficTotals
from repro.quant.bitnet import TernaryWeight, ternary_weight

# Serve-side stage names beyond the paper's four attention stages (the
# SwiGLU MLP projections are GEMMs too, and at decode they dominate bytes)
# now live in core.workloads — MLP_UP / MLP_DOWN are imported above and
# stay re-exported here for existing call sites.

PREFILL = "prefill"      # the other engine events: "decode" and "step"


@dataclasses.dataclass(frozen=True)
class ProjectionOp:
    """One serve-step GEMM family: template workload + stationary weights.

    ``workload.m`` is a placeholder (1); the backend replaces it with the
    step's row count (1 per decode token, prompt length for prefill).
    """

    workload: GEMMWorkload
    weights: np.ndarray          # [count, K, N] int8 (ternary)


@dataclasses.dataclass
class StageTally:
    traffic: TrafficTotals
    cycles: int = 0


@dataclasses.dataclass
class StepTally:
    """Measured totals of one serving step (all layers) through the runtime."""

    m: int                                # activation rows (tokens) executed
    gemms: int = 0                        # GEMM workloads lowered
    weight_bytes: float = 0.0
    act_bytes: float = 0.0
    psum_bytes: float = 0.0
    cycles: int = 0
    # Exposed weight-prefetch cycles (inside ``cycles``) — nonzero only
    # when the backend's Machine runs a finite mem_bw_bytes_per_cycle.
    stall_cycles: int = 0
    executed_passes: int = 0
    skipped_passes: int = 0
    # Paged-KV fetch accounting (zero for contiguous backends); the waste
    # is also folded into weight_bytes — see repro.legion.trace.
    page_fetches: float = 0.0
    page_bytes: float = 0.0
    page_waste_bytes: float = 0.0
    stages: Dict[str, StageTally] = dataclasses.field(default_factory=dict)

    @property
    def mem_bytes(self) -> float:
        return self.weight_bytes + self.act_bytes

    @property
    def stall_frac(self) -> float:
        """Exposed-prefetch share of the step's cycles (0 = fully hidden)."""
        return self.stall_cycles / self.cycles if self.cycles else 0.0

    def seconds(self, freq_hz: float) -> float:
        return self.cycles / freq_hz

    def merge(self, other: "StepTally") -> None:
        """Fold another step into this one (engine-level accumulation)."""
        self.m += other.m
        self.gemms += other.gemms
        self.weight_bytes += other.weight_bytes
        self.act_bytes += other.act_bytes
        self.psum_bytes += other.psum_bytes
        self.cycles += other.cycles
        self.stall_cycles += other.stall_cycles
        self.executed_passes += other.executed_passes
        self.skipped_passes += other.skipped_passes
        self.page_fetches += other.page_fetches
        self.page_bytes += other.page_bytes
        self.page_waste_bytes += other.page_waste_bytes
        for stage, st in other.stages.items():
            agg = self.stages.setdefault(
                stage, StageTally(traffic=TrafficTotals()))
            agg.traffic.add(st.traffic)
            agg.cycles += st.cycles


@dataclasses.dataclass
class RequestTally:
    """Per-request accumulation across the request's prefill + decode steps."""

    uid: int
    prefill_tokens: int = 0
    decode_tokens: int = 0
    weight_bytes: float = 0.0
    act_bytes: float = 0.0
    psum_bytes: float = 0.0
    cycles: int = 0

    @property
    def mem_bytes(self) -> float:
        return self.weight_bytes + self.act_bytes

    def add(self, t: StepTally) -> None:
        self.weight_bytes += t.weight_bytes
        self.act_bytes += t.act_bytes
        self.psum_bytes += t.psum_bytes
        self.cycles += t.cycles


def _round_key(w: GEMMWorkload) -> Tuple[str, int, int, int, int]:
    """A node's rounds depend only on its workload's shape."""
    return (w.stage, w.m, w.k, w.n, w.count)


def _ternary_int8(w, layer: int) -> np.ndarray:
    """Layer ``layer`` of a stacked engine weight as int8 ternary codes:
    the served :class:`~repro.quant.bitnet.TernaryWeight`'s own (a latent
    weight is quantized first, as the engine would)."""
    if not isinstance(w, TernaryWeight):
        w = ternary_weight(w)
    return np.asarray(w.q[layer], np.int8)


def extract_projection_ops(
    model_cfg, params, *, layer: int = 0,
) -> List[ProjectionOp]:
    """Pull one layer's projection GEMMs out of stacked serve params.

    Returns the four serve-side GEMM families (qkv_proj, out_proj, mlp_up,
    mlp_down) with per-instance stationary matrices — per produced head for
    qkv (the scheduler's head-per-Legion unit of work), per SwiGLU branch
    for mlp_up — and ``layers=model_cfg.layers`` so downstream accounting
    scales one executed layer to the whole model.
    """
    blocks = params["blocks"]
    if "attn" not in blocks or "mlp" not in blocks:
        raise ValueError(
            "legion serve backend needs a dense transformer (attn + mlp "
            f"blocks); got block params {sorted(blocks)}"
        )
    if model_cfg.quantization != "bitnet":
        # _ternary_int8 would collapse real-valued served weights to
        # {-1, 0, 1} — tallies for a model the engine does not serve
        raise ValueError(
            "legion serve backend models ternary (BitNet) projections; "
            f"got quantization={model_cfg.quantization!r}"
        )
    hd = model_cfg.head_dim_
    heads, kv_heads = model_cfg.n_heads, model_cfg.kv_heads
    d_model, d_ff, layers = model_cfg.d_model, model_cfg.d_ff, model_cfg.layers

    attn = {k: _ternary_int8(blocks["attn"][k], layer)
            for k in ("wq", "wk", "wv", "wo")}
    mlp = {k: _ternary_int8(blocks["mlp"][k], layer)
           for k in ("w1", "w2", "w3")}

    def split_heads(w: np.ndarray, n: int) -> List[np.ndarray]:
        return [w[:, h * hd:(h + 1) * hd] for h in range(n)]

    qkv = np.stack(
        split_heads(attn["wq"], heads)
        + split_heads(attn["wk"], kv_heads)
        + split_heads(attn["wv"], kv_heads)
    )
    bits = 2
    return [
        ProjectionOp(
            GEMMWorkload(stage=QKV_PROJ, m=1, k=d_model, n=hd,
                         weight_bits=bits, count=heads + 2 * kv_heads,
                         shared_input=True, mapping=HEAD_PER_UNIT,
                         layers=layers),
            qkv,
        ),
        ProjectionOp(
            GEMMWorkload(stage=OUT_PROJ, m=1, k=heads * hd, n=d_model,
                         weight_bits=bits, count=1, mapping=N_PARTITION,
                         layers=layers),
            attn["wo"][None],
        ),
        ProjectionOp(
            GEMMWorkload(stage=MLP_UP, m=1, k=d_model, n=d_ff,
                         weight_bits=bits, count=2, shared_input=True,
                         mapping=N_PARTITION, layers=layers),
            np.stack([mlp["w1"], mlp["w3"]]),
        ),
        ProjectionOp(
            GEMMWorkload(stage=MLP_DOWN, m=1, k=d_ff, n=d_model,
                         weight_bits=bits, count=1, mapping=N_PARTITION,
                         layers=layers),
            mlp["w2"][None],
        ),
    ]


class LegionServeBackend:
    """Drives a ServeEngine's per-step GEMMs through the runtime.

    Attach to an engine (``backend.attach(engine)``) and every prefill /
    decode step is lowered to one :class:`~repro.legion.program.Program`
    (projections AND, with ``attention=True``, the act-to-act attention
    stages over each slot's KV context) and executed.  Two views
    accumulate:

    * :attr:`totals` — **batch-accurate** engine-level totals: a batched
      decode over A active slots executes as one ``m=A`` step (stationary
      weights fetched once for the whole batch, like the hardware would),
      with one per-slot attention pair at each slot's own context length;
    * :attr:`per_request` — per-request **standalone** costs: each decode
      token is attributed its own ``m=1`` step at that token's context,
      as if the request were served alone.  Summing per-request tallies
      therefore *exceeds* ``totals`` whenever requests share a decode
      batch — that headroom is exactly the batching win, not
      double-counted hardware work.

    A third, **engine view** rides on the batched one: every engine
    step's merged graph (shared projections, per-slot attention
    antichain, prefill chunks beside the decode — a
    :class:`~repro.legion.lowering.ServeStepSpec`) is scheduled through
    the pipelined overlap model, composed from the cached sub-program
    round criticals without re-executing anything.  ``summary()``
    reports ``overlapped_cycles_per_step`` (<= the serial sum, asserted)
    and the per-token overlapped cycles that :meth:`cache_budget` feeds
    into ``serve.kv_cache.plan``.

    Every pricing method (:meth:`workloads`, :meth:`step_program`,
    :meth:`step_tally`, :meth:`step_pipeline`, :meth:`cross_validate`)
    takes the step's shape: its prefill ``chunks`` as ``(rows,
    context)`` and its ``decode_contexts``.  A decode-only step has no
    chunks; a whole-prompt prefill is the one chunk ``(tokens, tokens)``.

    Tallies are cached compositionally: the context-independent
    projection part by row count ``m``, the attention pair by
    ``(rows, context)``, and each part (one chunk, or the batched decode)
    by ``(m, contexts)`` — byte/cycle identical to executing the step's
    single Program (fresh per-stage instruments mean nothing dedups
    across stages), but a decode stream whose context advances every
    token re-executes only the two attention GEMMs, not the dominant
    projection/MLP stages.
    """

    def __init__(
        self,
        accel_cfg: AcceleratorConfig,
        model_cfg,
        params,
        *,
        layer: int = 0,
        seed: int = 0,
        check_outputs: bool = True,
        mem_bw_bytes_per_cycle: float = math.inf,
        executor: Optional[ExecutorBackend] = None,
        attention: bool = True,
        metrics=None,
        page_tokens: int = 0,
    ) -> None:
        self.cfg = accel_cfg
        self.model_cfg = model_cfg
        self.ops = extract_projection_ops(model_cfg, params, layer=layer)
        self.seed = seed
        self.check_outputs = check_outputs
        self.mem_bw = mem_bw_bytes_per_cycle
        self.attention = attention
        # Paged-KV pricing: annotate every attention stage's stationary
        # K/V operand as block-allocated in page_tokens-token pages, so
        # the runtime fires per-page fetch events and tallies the
        # last-page padding as traffic waste (0 = contiguous pricing).
        # Match the engine's PagedKVCache page size.
        if page_tokens < 0:
            raise ValueError(f"page_tokens must be >= 0, got {page_tokens}")
        self.page_tokens = page_tokens
        self.heads = model_cfg.n_heads
        self.kv_heads = model_cfg.kv_heads
        self.head_dim = model_cfg.head_dim_
        self.layers = model_cfg.layers
        # Duck-typed metrics registry (see repro.obs.metrics
        # .MetricsRegistry); None disables serve_backend_* / kv_* metrics.
        self.metrics = metrics
        # One Machine session serves every step; swap `executor` for e.g.
        # repro.legion.ShardedExecutor to run steps device-parallel.
        self.machine = Machine(
            accel_cfg, backend=executor,
            mem_bw_bytes_per_cycle=mem_bw_bytes_per_cycle,
        )
        self.per_request: Dict[int, RequestTally] = {}
        self.totals = StepTally(m=0)     # batch-accurate engine totals
        self.prefill_steps = 0
        self.decode_steps = 0
        # by part: (m, contexts)
        self._part_cache: Dict[Tuple[int, Tuple[int, ...]], StepTally] = {}
        self._proj_cache: Dict[int, StepTally] = {}          # by m
        self._attn_cache: Dict[Tuple[int, int], StepTally] = {}  # (rows, t)
        self._decode_cycles = 0          # standalone per-token accumulation
        self._decode_tokens = 0
        # Engine-view pipelining: per-node round criticals captured from the
        # cached sub-program executions (keyed by workload shape), and the
        # merged step graph's serial/overlapped cycles per step shape.
        self._rounds: Dict[Tuple[str, int, int, int, int],
                           List[CycleBreakdown]] = {}
        self._pipeline_cache: Dict[tuple, Tuple[int, int]] = {}
        # Engine-view accumulators.  ``engine_steps`` counts the steps the
        # merged-graph schedule priced: every batched decode step in
        # legacy mode, every mixed (chunks + decode) step in in-flight
        # mode — so *_cycles_per_step covers prefill once chunks merge in.
        self.engine_steps = 0
        self._engine_serial_cycles = 0       # engine-view steps, serial
        self._engine_overlapped_cycles = 0   # same steps, pipelined
        # Decode-only engine view: the per-decode-token overlapped rate
        # (what cache_budget feeds kv_cache.plan) must not absorb prefill
        # cycles when mixed steps carry both phases.
        self._decode_serial_cycles = 0
        self._decode_overlapped_cycles = 0

    # ------------------------------------------------------------------ #
    def attach(self, engine) -> "LegionServeBackend":
        engine.step_observers.append(self.on_step)
        return self

    def on_step(self, event: dict) -> None:
        """Price one engine event.  Each event becomes one step shape —
        its prefill chunks and its batched decode — priced on one path:
        a ``prefill`` event is the one chunk ``(tokens, tokens)`` and
        counts no engine step, a ``decode`` event is a step without
        chunks, and a ``step`` event is itself."""
        kind = event["kind"]
        if kind == PREFILL:
            tokens = event["tokens"]
            chunks = ((event["uid"], tokens, tokens),)
        else:
            # a chunk attends its prefix too: context = offset + rows
            chunks = tuple((ch["uid"], ch["tokens"], ch["pos0"] + ch["tokens"])
                           for ch in event.get("chunks", ()))
        uids = event.get("uids", ())
        positions = event.get("positions", ())
        # context at this step: the cache holds pos entries and the
        # step writes + attends position pos -> t = pos + 1
        contexts = tuple(p + 1 for p in positions) \
            if len(positions) == len(uids) else (1,) * len(uids)
        # canonical slot order so permuted batches share a cache entry
        batch_ctx = tuple(sorted(contexts))
        # totals and per-request tallies accumulate part by part (the
        # parts' caches also hold every round the merged schedule needs)
        for uid, rows, t in chunks:
            self.prefill_steps += 1
            tally = self._part_tally(rows, self._ctx((t,)))
            self.totals.merge(tally)
            req = self._request(uid)
            req.prefill_tokens += rows
            req.add(tally)
            if self.metrics is not None:
                self.metrics.counter("serve_backend_prefill_cycles") \
                    .inc(tally.cycles)
        if uids:
            self.decode_steps += 1
            self.totals.merge(
                self._part_tally(len(uids), self._ctx(batch_ctx)))
            serial, overlapped = self.step_pipeline(
                decode_contexts=batch_ctx)
            self._decode_serial_cycles += serial
            self._decode_overlapped_cycles += overlapped
            self._attribute_decode(uids, contexts)
        if kind == PREFILL:
            return
        # engine view: the whole step as ONE merged graph through the
        # pipelined schedule — per-slot attention and chunk rounds
        # interleave, so the engine-view latency is the overlapped one
        serial, overlapped = self.step_pipeline(
            tuple((rows, t) for _uid, rows, t in chunks), batch_ctx)
        self.engine_steps += 1
        self._engine_serial_cycles += serial
        self._engine_overlapped_cycles += overlapped
        self._record_step_metrics(serial, overlapped)

    def _attribute_decode(self, uids, contexts) -> None:
        """Per-request standalone attribution: each decode token's own
        m=1 step cost at its context."""
        for uid, t in zip(uids, contexts):
            tally = self._part_tally(1, self._ctx((t,)))
            req = self._request(uid)
            req.decode_tokens += 1
            req.add(tally)
            self._decode_cycles += tally.cycles
            self._decode_tokens += 1
        if self.metrics is not None and self._decode_tokens:
            self.metrics.gauge("serve_cycles_per_decode_token").set(
                self._decode_cycles / self._decode_tokens)

    def _record_step_metrics(self, serial: int, overlapped: int) -> None:
        if self.metrics is None:
            return
        m = self.metrics
        m.counter("serve_backend_serial_cycles").inc(serial)
        m.counter("serve_backend_overlapped_cycles").inc(overlapped)
        m.histogram("serve_step_overlap_x").observe(
            serial / overlapped if overlapped else 1.0)

    def _request(self, uid: int) -> RequestTally:
        return self.per_request.setdefault(uid, RequestTally(uid=uid))

    def _ctx(self, contexts: Tuple[int, ...]) -> Tuple[int, ...]:
        return contexts if self.attention else ()

    # ------------------------------------------------------------------ #
    # One step's shape: ``chunks`` are the prefill chunks' ``(rows,
    # context)`` (context = the chunk's offset + its rows; a whole-prompt
    # prefill is the one chunk ``(tokens, tokens)``), ``decode_contexts``
    # the batched decode slots' contexts, one row each.
    def _parts(
        self, chunks: Sequence[Tuple[int, int]],
        decode_contexts: Sequence[int],
    ) -> List[Tuple[int, Tuple[int, ...]]]:
        """The step's row groups ``(m, contexts)``: each chunk, then the
        batched decode — attention contexts dropped when the backend
        prices projections only."""
        parts = [(int(rows), self._ctx((int(t),))) for rows, t in chunks]
        if decode_contexts:
            parts.append((len(decode_contexts),
                          self._ctx(tuple(int(t) for t in decode_contexts))))
        return parts

    def _spec(self, chunks, decode_contexts, **kw) -> ServeStepSpec:
        """The step as a :class:`~repro.legion.lowering.ServeStepSpec`
        (no attention heads when the backend prices projections only)."""
        return ServeStepSpec(
            projections=self.ops, chunks=chunks, m=len(decode_contexts),
            contexts=self._ctx(tuple(decode_contexts)),
            heads=self.heads if self.attention else 0,
            kv_heads=self.kv_heads, head_dim=self.head_dim,
            layers=self.layers, seed=self.seed,
            page_tokens=self.page_tokens, **kw,
        )

    def workloads(
        self, chunks: Sequence[Tuple[int, int]] = (),
        decode_contexts: Sequence[int] = (),
    ) -> List[GEMMWorkload]:
        """The step's GEMM workloads (projections + per-slot attention,
        part by part) — what :meth:`cross_validate` simulates."""
        out: List[GEMMWorkload] = []
        for m, contexts in self._parts(chunks, decode_contexts):
            out.extend(dataclasses.replace(op.workload, m=m)
                       for op in self.ops)
            rows = m // len(contexts) if contexts else m
            for t in contexts:
                out.extend(decode_attention_workloads(
                    heads=self.heads, kv_heads=self.kv_heads,
                    head_dim=self.head_dim, context=t, m=rows,
                    layers=self.layers, page_tokens=self.page_tokens,
                ))
        return out

    def step_program(
        self, chunks: Sequence[Tuple[int, int]] = (),
        decode_contexts: Sequence[int] = (), *, explicit_layers: int = 1,
    ) -> Program:
        """Lower one serving step to an executable Program: projections
        and attention as one dependency graph (chunk parts merged beside
        the decode part) — what a
        :class:`~repro.legion.machine.PipelinedExecutor` runs and a
        TimelineTracer measures.  ``explicit_layers > 1`` spans a step
        without chunks over explicit transformer layers (layer ``l+1``'s
        QKV streams layer ``l``'s MLP output)."""
        return lower(self._spec(chunks, decode_contexts,
                                explicit_layers=explicit_layers))

    def _tally_program(self, program: Program, m: int) -> StepTally:
        """Execute a (sub-)program and fold its stage reports into a tally."""
        report = self.machine.run(program,
                                  check_outputs=self.check_outputs,
                                  validate=False)
        tally = StepTally(m=m)
        for name, rep in report.stage_reports.items():
            w = rep.workload
            # capture the node's per-round critical paths by workload shape
            # — step_pipeline composes merged-graph schedules from these
            # without re-executing (rounds depend only on plan geometry);
            # cycle cells key by the node name (plan_stage stage= override)
            self._rounds[_round_key(w)] = rep.cycles.round_criticals()[name]
            cycles = rep.cycles.total_cycles * w.layers
            traffic = rep.trace.totals.scaled(w.layers)
            tally.gemms += 1
            tally.weight_bytes += traffic.weight_bytes
            tally.act_bytes += traffic.act_bytes
            tally.psum_bytes += traffic.psum_bytes
            tally.cycles += cycles
            tally.stall_cycles += \
                rep.cycles.stage_breakdown()[name].stall * w.layers
            tally.executed_passes += rep.cycles.executed_passes * w.layers
            tally.skipped_passes += rep.cycles.skipped_passes * w.layers
            tally.page_fetches += traffic.page_fetches
            tally.page_bytes += traffic.page_bytes
            tally.page_waste_bytes += traffic.page_waste_bytes
            # tallies aggregate by workload stage family ("attn_score"),
            # not per-slot node name ("attn_score[2]")
            agg = tally.stages.setdefault(
                w.stage, StageTally(traffic=TrafficTotals()))
            agg.traffic.add(traffic)
            agg.cycles += cycles
        return tally

    def _attention_program(self, rows: int, t: int) -> Program:
        """The score -> softmax -> output pair alone, at context ``t``:
        synthetic Q rows and per-group K/V stationary activations — the
        same shapes, plans, and threading as the full step's attention
        stages, executable without re-running the projections."""
        score_wl, out_wl = decode_attention_workloads(
            heads=self.heads, kv_heads=self.kv_heads,
            head_dim=self.head_dim, context=t, m=rows, layers=self.layers,
            page_tokens=self.page_tokens,
        )
        rng = np.random.default_rng((self.seed, rows, t))
        q = rng.integers(-8, 9, size=(self.heads, rows, self.head_dim)) \
            .astype(np.int8)
        kv = rng.integers(
            -8, 9, size=(2, self.kv_heads, t, self.head_dim)).astype(np.int8)
        group = np.arange(self.heads) // max(self.heads // self.kv_heads, 1)
        scale = 1.0 / (8.0 * 8.0 * math.sqrt(self.head_dim))
        return Program([
            ProgramStage(
                name=ATTN_SCORE, workload=score_wl, x=q,
                w=np.transpose(kv[0], (0, 2, 1))[group],
                w_source=STATIONARY_ACT,
            ),
            ProgramStage(
                name=ATTN_OUTPUT, workload=out_wl,
                x=Ref(ATTN_SCORE, lambda o: softmax_int8(o, scale=scale)),
                w=kv[1][group], w_source=STATIONARY_ACT,
            ),
        ])

    def _part_tally(self, m: int, contexts: Tuple[int, ...]) -> StepTally:
        """One group of ``m`` rows over its slot ``contexts``, composed
        from cached sub-program executions (projections by ``m``,
        attention by ``(rows, context)``) — identical bytes/cycles to
        running the part's single graph, without re-executing the
        context-independent stages every time a context advances."""
        key = (m, contexts)
        if key in self._part_cache:
            return self._part_cache[key]
        if m not in self._proj_cache:
            self._proj_cache[m] = self._tally_program(
                lower(ServeStepSpec(projections=self.ops, m=m,
                                    seed=self.seed)), m)
        parts = [self._proj_cache[m]]
        rows = m // len(contexts) if contexts else m
        for t in contexts:
            akey = (rows, t)
            if akey not in self._attn_cache:
                self._attn_cache[akey] = self._tally_program(
                    self._attention_program(rows, t), rows)
            parts.append(self._attn_cache[akey])
        tally = StepTally(m=0)
        for part in parts:
            tally.merge(part)
        tally.m = m
        self._part_cache[key] = tally
        return tally

    def step_tally(
        self, chunks: Sequence[Tuple[int, int]] = (),
        decode_contexts: Sequence[int] = (),
    ) -> StepTally:
        """One serving step's measured totals: its parts' cached tallies
        merged — byte/cycle identical to executing
        :meth:`step_program`'s graph."""
        tally = StepTally(m=0)
        for m, contexts in self._parts(chunks, decode_contexts):
            tally.merge(self._part_tally(m, contexts))
        return tally

    def step_pipeline(
        self, chunks: Sequence[Tuple[int, int]] = (),
        decode_contexts: Sequence[int] = (),
    ) -> Tuple[int, int]:
        """One step's engine-view ``(serial, overlapped)`` cycles — the
        step's merged graph scheduled through the pipelined model, scaled
        to all model layers (``(0, 0)`` for an empty step).

        The serial side equals :meth:`step_tally`'s ``cycles`` exactly
        (both sum the same per-stage round criticals); the overlapped
        side is what the step actually costs when dependency-independent
        rounds — different slots' attention, chunk rounds against decode
        rounds — interleave (``repro.legion.program.compute_pipeline``).
        Nothing re-executes: the parts' cached executions hold every
        round, and the step's skeleton graph (no synthesized operands —
        this runs on the per-step hot path) only re-*schedules* them.
        """
        chunks = tuple((int(r), int(t)) for r, t in chunks)
        decode_contexts = tuple(int(t) for t in decode_contexts)
        if not chunks and not decode_contexts:
            return (0, 0)
        key = (chunks, len(decode_contexts), self._ctx(decode_contexts))
        cached = self._pipeline_cache.get(key)
        if cached is None:
            # populate the round caches
            for m, contexts in self._parts(chunks, decode_contexts):
                self._part_tally(m, contexts)
            program = lower(self._spec(chunks, decode_contexts,
                                       operands=False))
            rounds = merge_round_criticals(
                {st.name: self._rounds[_round_key(st.workload)]}
                for st in program
            )
            pp = compute_pipeline(program, rounds)
            if not pp.ok:
                raise AssertionError(
                    f"engine-view pipeline broke overlapped <= serial: {pp}"
                )
            cached = (pp.serial_cycles * self.layers,
                      pp.overlapped_cycles * self.layers)
            self._pipeline_cache[key] = cached
        return cached

    # ------------------------------------------------------------------ #
    def cross_validate(
        self, chunks: Sequence[Tuple[int, int]] = (),
        decode_contexts: Sequence[int] = (), *, rtol: float = 0.05,
    ) -> Tuple[List[StageValidation], List[CycleValidation]]:
        """Compare a step's measured per-stage tallies against
        ``simulate()`` on the same workload list — the serve-path
        falsifiability check, attention stages included (``simulate``
        aggregates by stage family, so both sides sum the step's parts).
        E.g. ``decode_contexts=(64,)`` is one decode token at context 64,
        ``chunks=((24, 24),)`` a 24-token prompt's prefill."""
        tally = self.step_tally(chunks, decode_contexts)
        report = simulate(self.cfg, self.workloads(chunks, decode_contexts))
        traffic_vals: List[StageValidation] = []
        cycle_vals: List[CycleValidation] = []
        for stage, st in tally.stages.items():
            sim = report.stages[stage]
            traffic_vals.append(StageValidation(
                stage=stage, measured=st.traffic,
                analytic=TrafficTotals(
                    weight_bytes=sim.weight_bytes, act_bytes=sim.act_bytes,
                    psum_bytes=sim.psum_bytes,
                    page_fetches=sim.page_fetches,
                    page_bytes=sim.page_bytes,
                    page_waste_bytes=sim.page_waste_bytes,
                ),
                rtol=rtol,
            ))
            cycle_vals.append(CycleValidation(
                stage=stage, measured=st.cycles, analytic=sim.cycles,
                rtol=rtol, analytic_breakdown=sim.cycle_breakdown,
            ))
        return traffic_vals, cycle_vals

    # ------------------------------------------------------------------ #
    def cache_budget(
        self, *, batch: int, max_seq: int, hbm_bytes_per_chip: float,
        chips: int, dtype_bytes: int = 2,
        page_tokens: Optional[int] = None,
    ):
        """Latency-aware KV budget from the *measured* serve path.

        The engine-view overlapped per-token cycles (what a pipelined
        batch actually sustains) set the budget's tokens/sec; the serial
        per-token cycles ride along so the
        :class:`~repro.serve.kv_cache.CacheBudget` carries the pipelining
        speedup.  Needs at least one observed decode step.

        ``page_tokens`` defaults to the backend's own page size (paged
        backends plan page-granular capacity; contiguous ones don't);
        pass explicitly to override.
        """
        from repro.serve.kv_cache import plan as kv_plan

        if page_tokens is None:
            page_tokens = self.page_tokens or None

        s = self.summary()
        overlapped = s["overlapped_cycles_per_decode_token"]
        if not overlapped:
            raise ValueError(
                "cache_budget needs measured decode steps; attach the "
                "backend to an engine and decode first"
            )
        serial = s["serial_cycles_per_decode_token"] or None
        budget = kv_plan(
            self.model_cfg, batch=batch, max_seq=max_seq,
            hbm_bytes_per_chip=hbm_bytes_per_chip, chips=chips,
            dtype_bytes=dtype_bytes, cycles_per_token=overlapped,
            freq_hz=self.cfg.freq_hz, serial_cycles_per_token=serial,
            page_tokens=page_tokens,
        )
        if self.metrics is not None:
            m = self.metrics
            m.gauge("kv_cache_utilization").set(
                budget.total_bytes / (hbm_bytes_per_chip * chips))
            if budget.tokens_per_sec:
                m.gauge("kv_tokens_per_sec").set(budget.tokens_per_sec)
            if budget.pipelining_speedup:
                m.gauge("kv_pipelining_speedup").set(
                    budget.pipelining_speedup)
        return budget

    # ------------------------------------------------------------------ #
    def summary(self) -> Dict[str, float]:
        """Batch-accurate engine totals (``self.totals``) + request counts.

        ``cycles``/``*_bytes`` count each batched decode step once at its
        true batch size — the hardware-level total, smaller than the sum of
        the standalone per-request tallies whenever decode steps batched.
        ``cycles_per_decode_token`` is the mean *standalone* per-token cost
        over every decoded token (position-dependent attention included).

        The engine view rides alongside: every batched decode step also
        runs as one merged batch graph through the pipelined schedule, so
        ``overlapped_cycles_per_step`` <= ``serial_cycles_per_step``
        (asserted per step) is the step latency with per-slot attention
        rounds interleaved, and ``overlapped_cycles_per_decode_token`` is
        the number to feed — with ``AcceleratorConfig.freq_hz`` — into
        ``repro.serve.kv_cache.plan`` (or just call :meth:`cache_budget`)
        for the tokens/sec the fleet actually sustains.
        """
        reqs = self.per_request.values()
        decode_tokens = sum(r.decode_tokens for r in reqs)
        decode_cycles = (self._decode_cycles / self._decode_tokens
                         if self._decode_tokens else 0.0)
        # per-step numbers average over the engine-view steps (== decode
        # steps in legacy mode; in-flight mixed steps count once each and
        # carry prefill too); per-token numbers stay decode-only so the
        # cache_budget rate never absorbs prefill cycles
        steps = self.engine_steps
        serial_step = self._engine_serial_cycles / steps if steps else 0.0
        overlap_step = (self._engine_overlapped_cycles / steps
                        if steps else 0.0)
        overlap_token = (self._decode_overlapped_cycles / self._decode_tokens
                         if self._decode_tokens else 0.0)
        serial_token = (self._decode_serial_cycles / self._decode_tokens
                        if self._decode_tokens else 0.0)
        return {
            "requests": len(self.per_request),
            "prefill_steps": self.prefill_steps,
            "decode_steps": self.decode_steps,
            "engine_steps": self.engine_steps,
            "prefill_tokens": sum(r.prefill_tokens for r in reqs),
            "decode_tokens": decode_tokens,
            "weight_bytes": self.totals.weight_bytes,
            "act_bytes": self.totals.act_bytes,
            "psum_bytes": self.totals.psum_bytes,
            # paged-KV pricing (zero for contiguous backends): distinct
            # page fetches, whole-page bytes, and the padding share of
            # them (waste is also inside weight_bytes — the delta vs a
            # contiguous backend on the same trace)
            "page_fetches": self.totals.page_fetches,
            "page_fetch_bytes": self.totals.page_bytes,
            "page_waste_bytes": self.totals.page_waste_bytes,
            "page_waste_frac": (
                self.totals.page_waste_bytes / self.totals.page_bytes
                if self.totals.page_bytes else 0.0),
            "cycles": self.totals.cycles,
            # finite-bandwidth serving: the exposed weight-prefetch share
            # of every step's cycles (0 at the default infinite mem_bw)
            "stall_cycles": self.totals.stall_cycles,
            "stall_frac": self.totals.stall_frac,
            "cycles_per_decode_token": decode_cycles,
            "us_per_decode_token": decode_cycles / self.cfg.freq_hz * 1e6,
            # engine view: the merged batch graph, pipelined
            "serial_cycles_per_step": serial_step,
            "overlapped_cycles_per_step": overlap_step,
            "serial_cycles_per_decode_token": serial_token,
            "overlapped_cycles_per_decode_token": overlap_token,
            "pipeline_speedup": (serial_step / overlap_step
                                 if overlap_step else 1.0),
            "overlapped_us_per_decode_token":
                overlap_token / self.cfg.freq_hz * 1e6,
        }
