"""Every Legion lowering builder, behind one front door: ``lower(spec)``.

A :class:`LoweringSpec` dataclass names one block of work; ``lower``
hands it to its family's builder and returns the validated
:class:`~repro.legion.program.Program`.  Specs validate at
*construction* (``__post_init__``), so a bad combination (attention with
page tables, zero experts, a chosen set wider than top-k, a page table
of the wrong length) raises before any lowering work starts, and the
builders below check nothing twice.

* :func:`lower_attention` (:class:`AttentionLoweringSpec`) — the paper's
  attention block: QKV -> act-to-act score (KV multicast across GQA
  groups) -> softmax -> act-to-act output -> O-projection.  It keeps its
  own ``AttentionSpec`` signature for the callers that lower a plain
  workload spec.

* :class:`ServeStepSpec` — one engine step: the step's projections AND
  its act-to-act attention, each slot's KV-cache matrices as stationary
  activation operands with position-dependent K/N.  A step without
  prefill chunks is one graph (optionally spanning explicit transformer
  layers); a step with chunks merges each chunk's graph and the batched
  decode's with ``{p<i>}`` / ``{d}`` name tags — the in-flight batching
  shape.

* :func:`lower_moe` — a token-choice MoE FFN block (router + ``E``
  experts' SwiGLU pairs) where the router's top-k decision becomes
  **program-level sparsity**: chosen experts' up/down GEMM stages execute
  normally, while each unchosen expert's stages carry zeroed weights with
  ``ztb=True`` — the runtime's self-derived ZeroTileBooks then gate every
  window, so `TrafficTracer`/`CycleCounter` measure the paper's
  fully-sparse-window skip at expert granularity (the AxLLM
  computation-reuse angle riding the ADiP adaptive cores).  Traffic for a
  k-of-E step equals the dense-E step minus the skipped experts'
  stationary bytes, exactly — and because the gated windows hold only
  zeros, the program still matches the dense NumPy
  :func:`~repro.legion.program.reference_outputs` bit for bit (an
  unchosen expert contributes zeros on both sides).

* :func:`lower_ssd` — the Mamba-2 SSD scan's chunked state/output GEMMs
  (``kernels/ssd`` shapes: score ``C_c B_c^T`` computed once per chunk,
  per-head intra-chunk output, chunk-state, and inter-chunk output) as
  ``ProgramStage``\\ s, with the recurrent state threaded as a
  **cross-chunk stationary Ref**: chunk ``c``'s inter stage holds
  ``h_{c-1}`` stationary, produced from every earlier chunk's state stage
  through the decay recurrence folded into the Ref transform.  All decay
  factors are deterministic NumPy transforms between int8 GEMMs, so the
  whole scan stays bit-exact against ``reference_outputs`` and every
  stage cross-validates against ``simulate()`` at exactly 0%.

* :func:`lower_hybrid` — the Zamba2-style interleaving: one shared
  attention block's program sequenced before an SSD block's chunk
  stages (control dependencies on the SSD roots), merged with
  ``{attn}`` / ``{ssm}`` name tags.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.workloads import (
    ATTN_OUTPUT,
    ATTN_SCORE,
    K_PROJ,
    MLP_DOWN,
    MLP_UP,
    OUT_PROJ,
    Q_PROJ,
    QKV_PROJ,
    ROUTER,
    SSD_INTER,
    SSD_INTRA,
    SSD_SCORE,
    SSD_STATE,
    V_PROJ,
    AttentionSpec,
    GEMMWorkload,
    attention_workloads,
    decode_attention_workloads,
    moe_ffn_workloads,
    ssd_chunk_workloads,
)
from repro.legion.program import (
    STATIONARY_ACT,
    Program,
    ProgramStage,
    Ref,
    requantize_int8,
    softmax_int8,
    swiglu_int8,
)

_WEIGHT_RANGES = {2: (-1, 2), 4: (-8, 8), 8: (-8, 9)}


# --------------------------------------------------------------------------- #
# Spec family
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True, kw_only=True)
class LoweringSpec:
    """Shared fields of every lowering spec.

    ``weight_bits`` is the stationary-weight precision of the lowered
    projection stages (act-to-act stages are always int8); ``layers``
    replicates whole-model tallies the usual scalar way; ``tag``
    optionally suffixes every stage name (:meth:`Program.merge` tagging,
    for composing lowered blocks); ``page_tokens``/``page_tables``
    annotate paged stationary KV operands — only the serve specs have
    any, so setting them elsewhere raises at construction.
    """

    weight_bits: int = 2
    layers: int = 1
    seed: int = 0
    tag: str = ""
    page_tokens: int = 0
    page_tables: Optional[Sequence[Sequence[int]]] = None

    _PAGED = False      # ClassVar by convention: which specs accept paging

    def __post_init__(self) -> None:
        if self.weight_bits not in (2, 4, 8):
            raise ValueError(
                f"{type(self).__name__}: weight_bits must be 2, 4, or 8, "
                f"got {self.weight_bits}"
            )
        if self.layers < 1:
            raise ValueError(
                f"{type(self).__name__}: layers must be >= 1, got "
                f"{self.layers}"
            )
        if self.page_tokens < 0:
            raise ValueError(
                f"{type(self).__name__}: page_tokens must be >= 0, got "
                f"{self.page_tokens}"
            )
        if (self.page_tokens or self.page_tables is not None) \
                and not self._PAGED:
            raise ValueError(
                f"{type(self).__name__} has no paged stationary operands; "
                f"page_tokens/page_tables apply to ServeStepSpec only"
            )
        if self.page_tables is not None and not self.page_tokens:
            raise ValueError(
                f"{type(self).__name__}: page_tables given without "
                f"page_tokens"
            )

    def _finish(self, prog: Program) -> Program:
        """Apply the spec's ``tag`` suffix (if any) and validate."""
        if self.tag:
            prog = Program.merge([prog], tags=[self.tag])
        prog.validate()
        return prog


@dataclasses.dataclass(frozen=True, kw_only=True)
class AttentionLoweringSpec(LoweringSpec):
    """One prefill attention block, lowered by :func:`lower_attention`.

    ``split_qkv``: three independent q/k/v projection stages instead of
    the fused qkv stage.
    """

    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    hidden: int = 0
    seq_len: int = 0
    name: str = "attention"
    x: Optional[np.ndarray] = None
    split_qkv: bool = False

    def __post_init__(self) -> None:
        super().__post_init__()
        for field in ("heads", "kv_heads", "head_dim", "hidden", "seq_len"):
            if getattr(self, field) < 1:
                raise ValueError(
                    f"AttentionLoweringSpec: {field} must be >= 1, got "
                    f"{getattr(self, field)}"
                )
        if self.heads % self.kv_heads:
            raise ValueError(
                f"AttentionLoweringSpec: heads={self.heads} not divisible "
                f"by kv_heads={self.kv_heads}"
            )

    def attention_spec(self) -> AttentionSpec:
        return AttentionSpec(
            name=self.name, layers=self.layers, hidden=self.hidden,
            heads=self.heads, kv_heads=self.kv_heads,
            head_dim=self.head_dim, seq_len=self.seq_len,
            weight_bits=self.weight_bits,
        )


@dataclasses.dataclass(frozen=True, kw_only=True)
class ServeStepSpec(LoweringSpec):
    """One engine step: in-flight prefill chunks beside a batched decode.

    ``chunks`` gives each prefill chunk's ``(rows, context)``: ``rows``
    prompt tokens written this step, attending ``context`` cache entries
    (the chunk's start offset plus its rows).  A whole-prompt prefill is
    the one chunk ``(tokens, tokens)``.  The decode part is ``m`` rows
    split evenly over the slots of ``contexts`` (each slot's KV context
    length), or ``m`` rows of projections alone when ``contexts`` is
    empty; ``m=0`` leaves it out.  ``projections`` are the serve
    backend's ``(workload, weights)`` ProjectionOp records, whose
    template ``m`` each part replaces with its own rows; ``seed`` seeds
    the synthesized activations and KV caches.

    With ``heads=0`` the step has no attention: each part lowers to its
    projections alone (decode ``contexts`` then need heads and raise).

    ``explicit_layers`` spans a step without chunks over that many
    *explicit* transformer layers; ``operands=False`` builds the
    schedulable skeleton (no synthesized arrays, data edges as ``after``
    control deps).  ``page_tokens`` prices each slot's stationary K/V
    page-granularly; ``page_tables`` pins the decode slots' physical page
    ids and ``chunk_page_tables`` one table per chunk, each exactly
    ``ceil(context / page_tokens)`` pages long (the allocator and the
    lowered graph must agree on how many pages back each context).
    """

    projections: Sequence[Any] = ()
    chunks: Sequence[Tuple[int, int]] = ()
    m: int = 0
    contexts: Sequence[int] = ()
    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    explicit_layers: int = 1
    operands: bool = True
    chunk_page_tables: Optional[Sequence[Sequence[int]]] = None

    _PAGED = True

    def __post_init__(self) -> None:
        super().__post_init__()
        chunks = tuple((int(r), int(t)) for r, t in self.chunks)
        contexts = tuple(int(t) for t in self.contexts)
        object.__setattr__(self, "chunks", chunks)
        object.__setattr__(self, "contexts", contexts)
        stages = {op.workload.stage for op in self.projections}
        if not stages:
            raise ValueError("ServeStepSpec needs projection ops")
        if self.m < 0 or not (chunks or self.m):
            raise ValueError(
                "ServeStepSpec needs at least one prefill chunk, or "
                "m >= 1 decode rows over its slot contexts"
            )
        if contexts and (not self.m or self.m % len(contexts)):
            raise ValueError(
                f"ServeStepSpec: {self.m} step rows cannot split over "
                f"{len(contexts)} slots"
            )
        for rows, t in chunks:
            if rows < 1 or t < rows:
                raise ValueError(
                    f"ServeStepSpec: chunk ({rows}, {t}) needs rows >= 1 "
                    f"and context >= rows (a chunk attends its own rows)"
                )
        if contexts or (chunks and self.heads):
            if not (self.heads and self.kv_heads and self.head_dim):
                raise ValueError(
                    "ServeStepSpec: attention lowering needs "
                    "heads/kv_heads/head_dim"
                )
            if self.heads % self.kv_heads:
                raise ValueError(
                    f"ServeStepSpec: heads={self.heads} not divisible by "
                    f"kv_heads={self.kv_heads}"
                )
            if QKV_PROJ not in stages:
                raise ValueError(
                    "ServeStepSpec: attention lowering threads Q rows out "
                    "of a qkv_proj projection; none among the given ops"
                )
        self._check_layers(stages)
        self._check_page_tables(self.page_tables, contexts, "slot")
        self._check_page_tables(
            self.chunk_page_tables, tuple(t for _r, t in chunks), "chunk")

    def _check_layers(self, stages) -> None:
        n = self.explicit_layers
        if n < 1:
            raise ValueError(
                f"ServeStepSpec: explicit_layers must be >= 1, got {n}"
            )
        if n == 1:
            return
        if self.chunks:
            raise ValueError(
                "ServeStepSpec: explicit_layers > 1 spans a step without "
                "prefill chunks"
            )
        if MLP_DOWN not in stages and OUT_PROJ not in stages:
            raise ValueError(
                "explicit_layers > 1 chains layer l+1's qkv off layer l's "
                "mlp_down (or out_proj) output; neither among the given ops"
            )
        if QKV_PROJ not in stages:
            raise ValueError(
                "explicit_layers > 1 needs a qkv_proj op to stream the "
                "previous layer's output into"
            )
        if self.layers % n:
            raise ValueError(
                f"{self.layers} attention layers cannot split into {n} "
                f"explicit layers"
            )
        for op in self.projections:
            if op.workload.layers % n:
                raise ValueError(
                    f"{op.workload.stage}: {op.workload.layers} model "
                    f"layers cannot split into {n} explicit layers"
                )

    def _check_page_tables(self, tables, contexts, part: str) -> None:
        """One table per ``part`` (slot or chunk), each exactly as many
        pages as its context needs."""
        if tables is None:
            return
        if not self.page_tokens:
            raise ValueError(
                f"ServeStepSpec: {part} page tables given without "
                f"page_tokens"
            )
        if len(tables) != len(contexts):
            raise ValueError(
                f"{len(tables)} {part} page tables for {len(contexts)} "
                f"{part}s"
            )
        for j, (tab, t) in enumerate(zip(tables, contexts)):
            need = -(-t // self.page_tokens)
            if len(tab) != need:
                raise ValueError(
                    f"{part} {j}: page table has {len(tab)} pages, context "
                    f"{t} at {self.page_tokens} tokens/page needs {need}"
                )


@dataclasses.dataclass(frozen=True, kw_only=True)
class MoESpec(LoweringSpec):
    """A token-choice MoE FFN block for :func:`lower_moe`.

    ``tokens`` rows route over ``n_experts`` experts, ``top_k`` chosen per
    step.  ``chosen`` pins the routed expert set explicitly (exactly
    ``top_k`` distinct ids); by default the routing decision is derived
    from the lowered router GEMM's own logits (:meth:`routing`).
    """

    d_model: int = 0
    d_ff: int = 0
    n_experts: int = 0
    top_k: int = 0
    tokens: int = 0
    chosen: Optional[Tuple[int, ...]] = None
    name: str = "moe"

    def __post_init__(self) -> None:
        super().__post_init__()
        for field in ("d_model", "d_ff", "n_experts", "top_k", "tokens"):
            if getattr(self, field) < 1:
                raise ValueError(
                    f"MoESpec: {field} must be >= 1, got "
                    f"{getattr(self, field)}"
                )
        if self.top_k > self.n_experts:
            raise ValueError(
                f"MoESpec: top_k={self.top_k} > n_experts={self.n_experts}"
            )
        if self.chosen is not None:
            chosen = tuple(self.chosen)
            if len(set(chosen)) != len(chosen):
                raise ValueError(f"MoESpec: duplicate chosen ids {chosen}")
            if len(chosen) != self.top_k:
                raise ValueError(
                    f"MoESpec: {len(chosen)} chosen experts for "
                    f"top_k={self.top_k}"
                )
            if any(e < 0 or e >= self.n_experts for e in chosen):
                raise ValueError(
                    f"MoESpec: chosen ids {chosen} outside "
                    f"[0, {self.n_experts})"
                )

    # ------------------------------------------------------------------ #
    def operands(self) -> Dict[str, np.ndarray]:
        """Deterministic operand synthesis, independent of the routing
        decision — a k-of-E spec and its dense-E twin (``top_k ==
        n_experts``) share identical tokens and expert weights."""
        rng = np.random.default_rng(self.seed)
        d, f, e = self.d_model, self.d_ff, self.n_experts
        lo, hi = _WEIGHT_RANGES[self.weight_bits]
        return {
            "x": rng.integers(-8, 9, size=(self.tokens, d)).astype(np.int8),
            "router": rng.integers(-8, 9, size=(1, d, e)).astype(np.int8),
            "w1": rng.integers(lo, hi, size=(e, d, f)).astype(np.int8),
            "w3": rng.integers(lo, hi, size=(e, d, f)).astype(np.int8),
            "w2": rng.integers(lo, hi, size=(e, f, d)).astype(np.int8),
        }

    def routing(self) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        """(chosen, skipped) expert ids for this step.

        Step-granular top-k: router logits summed over the step's tokens,
        the ``top_k`` highest-scoring experts chosen (ties break to the
        lower id).  The expert-parallel view of ``models/moe.py``'s
        per-token routing — an expert with no routed tokens is a
        fully-sparse window.  ``chosen`` overrides the derivation.
        """
        if self.chosen is not None:
            chosen = tuple(sorted(self.chosen))
        else:
            ops = self.operands()
            logits = ops["x"].astype(np.int64) @ \
                ops["router"][0].astype(np.int64)
            score = logits.sum(axis=0)
            order = sorted(range(self.n_experts),
                           key=lambda e: (-score[e], e))
            chosen = tuple(sorted(order[:self.top_k]))
        skipped = tuple(e for e in range(self.n_experts) if e not in chosen)
        return chosen, skipped


@dataclasses.dataclass(frozen=True, kw_only=True)
class SSDSpec(LoweringSpec):
    """A Mamba-2 SSD scan segment for :func:`lower_ssd`.

    ``chunks`` chunks of ``chunk`` timesteps over ``heads`` heads with
    state width ``state`` and head dim ``head_dim`` — the ``kernels/ssd``
    geometry.  The scan is act-to-act int8 throughout, so
    ``weight_bits`` is pinned to 8 (the surrounding in/out projections
    are separate BitLinear stages, not part of the scan program).
    """

    heads: int = 0
    chunk: int = 0
    state: int = 0
    head_dim: int = 0
    chunks: int = 1
    name: str = "ssd"
    weight_bits: int = 8

    def __post_init__(self) -> None:
        super().__post_init__()
        for field in ("heads", "chunk", "state", "head_dim", "chunks"):
            if getattr(self, field) < 1:
                raise ValueError(
                    f"SSDSpec: {field} must be >= 1, got "
                    f"{getattr(self, field)}"
                )
        if self.weight_bits != 8:
            raise ValueError(
                f"SSDSpec: the SSD scan is int8 act-to-act; weight_bits "
                f"must be 8, got {self.weight_bits}"
            )


@dataclasses.dataclass(frozen=True, kw_only=True)
class HybridSpec(LoweringSpec):
    """A Zamba2-style hybrid period: one shared attention block sequenced
    before an SSD block (:func:`lower_hybrid`).  ``attention.layers``
    carries the shared block's application count, ``ssd.layers`` the SSM
    block count — the ``attn_every`` interleaving collapsed into the two
    sub-specs' layer multipliers."""

    attention: Optional[AttentionLoweringSpec] = None
    ssd: Optional[SSDSpec] = None

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.attention is None or self.ssd is None:
            raise ValueError(
                "HybridSpec needs both an attention and an ssd sub-spec"
            )


# --------------------------------------------------------------------------- #
# Attention lowering
# --------------------------------------------------------------------------- #

def _grouped(arr: np.ndarray, heads: int, group_size: int) -> np.ndarray:
    """Replicate per-KV-head matrices across their GQA group: instance i
    (query head) uses group i // group_size — the data behind the KV
    multicast (the tracer fetches each group's tiles once)."""
    return arr[np.arange(heads) // max(group_size, 1)]


def lower_attention(
    spec: AttentionSpec,
    *,
    x: Optional[np.ndarray] = None,
    seed: int = 0,
    split_qkv: bool = False,
) -> Program:
    """Lower a full attention block to a Program: QKV projection(s) ->
    act-to-act scores (Q @ K^T, KV multicast across GQA groups) ->
    softmax -> act-to-act output (A @ V) -> output projection.

    With ``split_qkv`` the projections become three independent stages
    (q_proj / k_proj / v_proj) sharing the streamed input — V is not
    needed until attn_output, so the graph's first level is a real
    antichain and a pipelining executor has rounds to overlap.  The
    default keeps the paper's fused qkv_proj stage, making the graph a
    pure chain; even there the attn_output and out_proj boundaries hide
    their fill as cross-level weight prefetch (their stationary operands
    — V written back at qkv time, the O-weights — exist before the
    streamed input does), while qkv -> attn_score hides nothing (its
    stationary K IS qkv's output).
    """
    h, g, hd, s = spec.heads, spec.kv_heads, spec.head_dim, spec.seq_len
    gs = spec.group_size
    if h % max(g, 1):
        raise ValueError(f"heads={h} not divisible by kv_heads={g}")
    rng = np.random.default_rng(seed)
    if x is None:
        x = rng.integers(-8, 9, size=(s, spec.hidden)).astype(np.int8)
    lo, hi = {2: (-1, 2), 4: (-8, 8)}.get(spec.weight_bits, (-8, 9))
    wqkv = rng.integers(lo, hi, size=(h + 2 * g, spec.hidden, hd)) \
        .astype(np.int8)
    wo = rng.integers(lo, hi, size=(1, h * hd, spec.hidden)).astype(np.int8)
    wl = attention_workloads(spec)   # [qkv, score, output, out_proj]

    # int8 Q times int8 K^T: map raw scores into softmax's active range
    score_scale = 1.0 / (127.0 * 127.0 * math.sqrt(hd))

    def k_transposed(out: np.ndarray) -> np.ndarray:
        """KV-head K outputs -> per-query-head stationary [h, hd, s]."""
        kq = requantize_int8(out)
        return _grouped(np.transpose(kq, (0, 2, 1)), h, gs)

    def v_grouped(out: np.ndarray) -> np.ndarray:
        return _grouped(requantize_int8(out), h, gs)

    def concat_heads(out: np.ndarray) -> np.ndarray:
        """[h, s, hd] -> requantized [s, h*hd] rows for the O-projection."""
        return requantize_int8(
            np.transpose(out, (1, 0, 2)).reshape(out.shape[1], h * hd)
        )

    prog = Program()
    if split_qkv:
        per = dict(m=s, k=spec.hidden, weight_bits=spec.weight_bits,
                   shared_input=True, mapping=wl[0].mapping,
                   layers=spec.layers)
        prog.add(ProgramStage(
            name=Q_PROJ, x=x, w=wqkv[:h],
            workload=GEMMWorkload(stage=Q_PROJ, n=hd, count=h, **per),
        ))
        prog.add(ProgramStage(
            name=K_PROJ, x=x, w=wqkv[h:h + g],
            workload=GEMMWorkload(stage=K_PROJ, n=hd, count=g, **per),
        ))
        prog.add(ProgramStage(
            name=V_PROJ, x=x, w=wqkv[h + g:],
            workload=GEMMWorkload(stage=V_PROJ, n=hd, count=g, **per),
        ))
        q_src, k_src, v_src = Q_PROJ, K_PROJ, V_PROJ
        q_of = requantize_int8
        k_of, v_of = k_transposed, v_grouped
    else:
        prog.add(ProgramStage(name=QKV_PROJ, workload=wl[0], x=x, w=wqkv))
        q_src = k_src = v_src = QKV_PROJ

        def q_of(out):
            return requantize_int8(out[:h])

        def k_of(out):
            return k_transposed(out[h:h + g])

        def v_of(out):
            return v_grouped(out[h + g:])

    prog.add(ProgramStage(
        name=ATTN_SCORE, workload=wl[1],
        x=Ref(q_src, q_of), w=Ref(k_src, k_of), w_source=STATIONARY_ACT,
    ))
    prog.add(ProgramStage(
        name=ATTN_OUTPUT, workload=wl[2],
        x=Ref(ATTN_SCORE, lambda o: softmax_int8(o, scale=score_scale)),
        w=Ref(v_src, v_of), w_source=STATIONARY_ACT,
    ))
    prog.add(ProgramStage(
        name=OUT_PROJ, workload=wl[3],
        x=Ref(ATTN_OUTPUT, concat_heads), w=wo,
    ))
    prog.validate()
    return prog


def _lower_attention_block(spec: AttentionLoweringSpec) -> Program:
    return spec._finish(lower_attention(
        spec.attention_spec(), x=spec.x, seed=spec.seed,
        split_qkv=spec.split_qkv,
    ))


# --------------------------------------------------------------------------- #
# Serve-step lowering
# --------------------------------------------------------------------------- #

def _next_layer_rows(out: np.ndarray) -> np.ndarray:
    """The cross-layer link: layer ``l``'s final ``[1, m, d_model]``
    output requantized into the int8 rows layer ``l+1``'s QKV streams."""
    return requantize_int8(out[0])


def _lower_step_layer(
    spec: ServeStepSpec,
    prog: Program,
    by_stage: Dict[str, Any],
    *,
    m: int,
    contexts: Tuple[int, ...],
    layer: int,
    x_link: Optional[str],
) -> str:
    """Add one explicit transformer layer of one step part to ``prog``.

    Stage names carry the layer tag ``@l`` (none for layer 0); per-slot
    attention subgraphs are built standalone and folded in via
    :meth:`Program.merge`, hanging off the shared (batched) projection
    stages.  ``x_link`` names the previous layer's final stage — its
    requantized output rows stream into this layer's QKV (the explicit
    cross-layer dependency).  Without ``spec.operands`` the graph is a
    *skeleton*: no arrays are synthesized and every data edge becomes an
    ``after`` control dependency — same names, workloads, levels, and
    ancestry, but only schedulable, not executable (the serve backend's
    per-step overlap computation needs nothing more).  Returns the name
    of the layer's final stage (the next layer's link target).
    """
    heads, kv_heads, head_dim = spec.heads, spec.kv_heads, spec.head_dim
    operands, seed = spec.operands, spec.seed
    ltag = "" if layer == 0 else f"@{layer}"
    rows = m // len(contexts) if contexts else m
    gs = max(heads // max(kv_heads, 1), 1)
    rng = np.random.default_rng(seed if layer == 0 else (seed, layer)) \
        if operands else None

    def synth_x(k: int) -> Optional[np.ndarray]:
        if not operands:
            return None
        return rng.integers(-8, 9, size=(m, k)).astype(np.int8)

    def sized(op) -> GEMMWorkload:
        return dataclasses.replace(
            op.workload, m=m,
            layers=op.workload.layers // spec.explicit_layers,
        )

    def stage(name, workload, x, w, deps, **kw) -> ProgramStage:
        """Concrete stage, or its skeleton twin (deps as ``after``)."""
        if operands:
            return ProgramStage(name=name, workload=workload, x=x, w=w,
                                **kw)
        return ProgramStage(name=name, workload=workload,
                            after=tuple(deps), **kw)

    qkv = by_stage.get(QKV_PROJ)
    qkv_name = QKV_PROJ + ltag
    if qkv is not None:
        prog.add(stage(
            qkv_name, sized(qkv),
            (synth_x(qkv.workload.k) if x_link is None
             else Ref(x_link, _next_layer_rows)),
            qkv.weights,
            (x_link,) if x_link is not None else (),
        ))

    out_names: List[str] = []
    score_scale = 1.0 / (127.0 * 8.0 * math.sqrt(max(head_dim, 1)))
    slot_progs: List[Program] = []
    for j, t in enumerate(contexts):
        # per-slot KV cache: one K/V matrix per KV head, synthetic int8
        # (the engine's real cache lives inside the jitted graph)
        if operands:
            slot_rng = np.random.default_rng(
                (seed, j, t) if layer == 0 else (seed, layer, j, t))
            k_cache = slot_rng.integers(
                -8, 9, size=(kv_heads, t, head_dim)).astype(np.int8)
            v_cache = slot_rng.integers(
                -8, 9, size=(kv_heads, t, head_dim)).astype(np.int8)
        score_wl, out_wl = decode_attention_workloads(
            heads=heads, kv_heads=kv_heads, head_dim=head_dim,
            context=t, m=rows, layers=spec.layers // spec.explicit_layers,
            page_tokens=spec.page_tokens,
        )
        lo_row, hi_row = j * rows, (j + 1) * rows

        def q_rows(out: np.ndarray, lo=lo_row, hi=hi_row) -> np.ndarray:
            return requantize_int8(out[:heads, lo:hi, :])

        # standalone slot subgraph: bare stage names, external ref to the
        # shared projection — Program.merge retags it into the batch graph
        slot_progs.append(Program([
            stage(
                ATTN_SCORE, score_wl,
                Ref(qkv_name, q_rows),
                (_grouped(np.transpose(k_cache, (0, 2, 1)), heads, gs)
                 if operands else None),
                (qkv_name,), w_source=STATIONARY_ACT,
            ),
            stage(
                ATTN_OUTPUT, out_wl,
                Ref(ATTN_SCORE,
                    lambda o, sc=score_scale: softmax_int8(o, scale=sc)),
                _grouped(v_cache, heads, gs) if operands else None,
                (ATTN_SCORE,), w_source=STATIONARY_ACT,
            ),
        ]))
    if slot_progs:
        tags = [ltag] if len(slot_progs) == 1 else \
            [f"[{j}]{ltag}" for j in range(len(slot_progs))]
        for st in Program.merge(slot_progs, tags=tags):
            prog.add(st)
        out_names = [ATTN_OUTPUT + tag for tag in tags]

    def concat_slots(*outs: np.ndarray) -> np.ndarray:
        rows_ = [np.transpose(o, (1, 0, 2)).reshape(o.shape[1],
                                                    heads * head_dim)
                 for o in outs]
        return requantize_int8(np.concatenate(rows_, axis=0))

    last = QKV_PROJ
    o_proj = by_stage.get(OUT_PROJ)
    if o_proj is not None:
        prog.add(stage(
            OUT_PROJ + ltag, sized(o_proj),
            (Ref(tuple(out_names), concat_slots) if contexts
             else synth_x(o_proj.workload.k)),
            o_proj.weights,
            tuple(out_names),
        ))
        last = OUT_PROJ

    # SwiGLU MLP: up branches share the post-attention rows, down consumes
    # the combined gate*value
    mlp_up = by_stage.get(MLP_UP)
    mlp_down = by_stage.get(MLP_DOWN)
    if mlp_up is not None:
        prog.add(stage(
            MLP_UP + ltag, sized(mlp_up),
            (Ref(OUT_PROJ + ltag, lambda o: requantize_int8(o[0]))
             if o_proj is not None else synth_x(mlp_up.workload.k)),
            mlp_up.weights,
            (OUT_PROJ + ltag,) if o_proj is not None else (),
        ))
    if mlp_down is not None:
        prog.add(stage(
            MLP_DOWN + ltag, sized(mlp_down),
            (Ref(MLP_UP + ltag, swiglu_int8) if mlp_up is not None
             else synth_x(mlp_down.workload.k)),
            mlp_down.weights,
            (MLP_UP + ltag,) if mlp_up is not None else (),
        ))
        last = MLP_DOWN
    return last + ltag


def _lower_step_part(spec: ServeStepSpec, m: int,
                     contexts: Tuple[int, ...]) -> Program:
    """One group of ``m`` rows over its slot ``contexts`` (none: the
    projections alone), spanning ``spec.explicit_layers`` layers, each
    layer's QKV streaming the previous layer's final output."""
    by_stage = {op.workload.stage: op for op in spec.projections}
    prog = Program()
    link: Optional[str] = None
    for layer in range(spec.explicit_layers):
        link = _lower_step_layer(spec, prog, by_stage, m=m,
                                 contexts=contexts, layer=layer,
                                 x_link=link)
    return prog


def _lower_engine_step(spec: ServeStepSpec) -> Program:
    """Lower one engine step — projections AND attention — to a Program.

    Per slot, the KV-cache matrices become *stationary activation*
    operands with position-dependent K/N (score ``[rows, hd] @ [hd, t]``,
    output ``[rows, t] @ [t, hd]``), shared across each GQA group.
    Outputs thread through the graph: qkv -> score -> softmax -> output
    -> O-proj -> SwiGLU mlp, so each part is one dependency graph.

    A step without chunks is its decode part alone, with bare stage
    names; explicit layers ``l >= 1`` add an ``@l`` suffix, and every
    stage workload's ``layers`` multiplier divides by
    ``explicit_layers``, so whole-model tallies are unchanged while the
    graph exposes the layer structure to a
    :class:`~repro.legion.machine.PipelinedExecutor`.  A step with
    chunks lowers each chunk as one slot attending its context, merged
    with the decode part via :meth:`Program.merge` under ``{p<i>}`` /
    ``{d}`` tags — the TensorRT-LLM in-flight batching shape: context-
    and generation-phase work share one scheduled graph, so
    :func:`~repro.legion.program.compute_pipeline` overlaps chunk rounds
    against decode rounds exactly as it does across decode slots.
    """
    if not spec.chunks:
        return spec._finish(_lower_step_part(spec, spec.m, spec.contexts))
    parts = [_lower_step_part(spec, rows, (t,) if spec.heads else ())
             for rows, t in spec.chunks]
    tags = [f"{{p{i}}}" for i in range(len(parts))]
    if spec.m:
        parts.append(_lower_step_part(spec, spec.m, spec.contexts))
        tags.append("{d}")
    return spec._finish(Program.merge(parts, tags=tags))


# --------------------------------------------------------------------------- #
# MoE lowering
# --------------------------------------------------------------------------- #

def moe_stage_names(expert: int) -> Tuple[str, str]:
    """The (up, down) stage names of one expert's SwiGLU pair."""
    return f"{MLP_UP}[e{expert}]", f"{MLP_DOWN}[e{expert}]"


def lower_moe(spec: MoESpec) -> Program:
    """Lower a token-choice MoE FFN block to a Program.

    Graph: ``router`` (int8, all tokens) -> per expert ``e`` a SwiGLU
    pair ``mlp_up[e{e}]`` (w1 & w3, shared streamed tokens) ->
    ``mlp_down[e{e}]`` (consuming the combined gate*value).  Every
    expert's up stage carries an ``after`` control dependency on the
    router — expert execution waits on the routing decision — and the
    decision itself lowers to program-level sparsity: an unchosen
    expert's stages hold *zeroed* weights with ``ztb=True``, so the
    runtime's self-derived ZeroTileBooks gate every window (no stationary
    fetch, no activation stream, no psum — only the per-assignment drain
    remains, cross-validated against ``simulate()``'s full-skip limit at
    exactly 0%).  The k-of-E step's measured weight traffic therefore
    equals the dense-E step's minus the skipped experts' stationary
    bytes, and outputs stay bit-exact against the dense NumPy reference
    (zero weights -> zero outputs on both sides).
    """
    ops = spec.operands()
    chosen, _ = spec.routing()
    chosen_set = set(chosen)
    router_wl, up_wl, down_wl = moe_ffn_workloads(
        tokens=spec.tokens, d_model=spec.d_model, d_ff=spec.d_ff,
        n_experts=spec.n_experts, weight_bits=spec.weight_bits,
        layers=spec.layers,
    )
    prog = Program()
    prog.add(ProgramStage(name=ROUTER, workload=router_wl, x=ops["x"],
                          w=ops["router"]))
    for e in range(spec.n_experts):
        up_name, down_name = moe_stage_names(e)
        up_w = np.stack([ops["w1"][e], ops["w3"][e]])
        down_w = ops["w2"][e][None]
        skipped = e not in chosen_set
        if skipped:
            up_w = np.zeros_like(up_w)
            down_w = np.zeros_like(down_w)
        ztb = True if skipped else None
        prog.add(ProgramStage(
            name=up_name, workload=up_wl, x=ops["x"], w=up_w, ztb=ztb,
            after=(ROUTER,),
        ))
        prog.add(ProgramStage(
            name=down_name, workload=down_wl,
            x=Ref(up_name, swiglu_int8), w=down_w, ztb=ztb,
        ))
    return spec._finish(prog)


# --------------------------------------------------------------------------- #
# SSD lowering
# --------------------------------------------------------------------------- #

def ssd_stage_names(chunk: int) -> Tuple[str, str, str, str]:
    """The (score, intra, state, inter) stage names of one chunk (the
    inter name exists for chunks >= 1 only — chunk 0 has no prior state)."""
    return tuple(f"{s}[c{chunk}]" for s in
                 (SSD_SCORE, SSD_INTRA, SSD_STATE, SSD_INTER))


def lower_ssd(spec: SSDSpec) -> Program:
    """Lower a chunked Mamba-2 SSD scan segment to a Program.

    Per chunk ``c`` (``kernels/ssd``'s chunked decomposition, decays
    precomputed from a seeded per-head ``dt`` and folded into the
    inter-stage transforms):

    * ``ssd_score[c{c}]``  — ``C_c @ B_c^T`` (``[q,n] @ [n,q]``), computed
      ONCE per chunk: B/C are group-shared across heads in Mamba-2
      (``n_groups=1``), the same reuse ``ssd_grouped_scan`` exploits;
    * ``ssd_intra[c{c}]`` — ``(scores * decay_c) @ dtx_c`` per head
      (``[q,q] @ [q,p]``), the scores Ref'd from the score stage with the
      per-head causal decay mask applied in the transform;
    * ``ssd_state[c{c}]`` — ``(B_c * decay_out)^T @ dtx_c`` per head
      (``[n,q] @ [q,p]``): the chunk's contribution to the recurrent
      state;
    * ``ssd_inter[c{c}]`` (``c >= 1``) — ``(C_c * exp(la)) @ h_{c-1}``
      per head (``[q,n] @ [n,p]``), whose stationary operand is **the
      recurrent state as a cross-chunk Ref**: every earlier chunk's state
      stage feeds a multi-producer Ref whose transform applies the
      chunk-to-chunk decay products and requantizes — the graph-level
      image of ``h = exp(la_tot) * h + chunk_state``.

    The per-chunk output is ``y_c = intra_c + inter_c`` (host-side
    combine); within the program every stage is a plain int8 GEMM, so
    ``Machine.run`` reproduces ``reference_outputs`` bit for bit and each
    stage cross-validates against ``simulate()`` at 0%.
    """
    h, q, n, p, nc = (spec.heads, spec.chunk, spec.state, spec.head_dim,
                      spec.chunks)
    rng = np.random.default_rng(spec.seed)
    c_in = rng.integers(-8, 9, size=(nc, q, n)).astype(np.int8)   # C
    b_in = rng.integers(-8, 9, size=(nc, q, n)).astype(np.int8)   # B
    dtx = rng.integers(-8, 9, size=(h, nc, q, p)).astype(np.int8)
    dta = rng.uniform(0.02, 0.2, size=(h, nc, q))                 # dt * -A

    # decay precomputation — ssd_chunked_ref's la/seg/decay_out, A < 0
    la = np.cumsum(-dta, axis=-1)                    # [h, nc, q], decreasing
    ii = np.arange(q)[:, None]
    jj = np.arange(q)[None, :]
    seg = np.where(ii >= jj, la[..., :, None] - la[..., None, :], -np.inf)
    decay = np.exp(seg)                              # [h, nc, q, q] causal
    la_tot = la[..., -1]                             # [h, nc]
    decay_out = np.exp(la_tot[..., None] - la)       # [h, nc, q]

    score_w, intra_w, state_w, inter_w = ssd_chunk_workloads(
        heads=h, chunk=q, state=n, head_dim=p, layers=spec.layers,
    )

    prog = Program()
    state_names = []
    for c in range(nc):
        score_name, intra_name, state_name, inter_name = ssd_stage_names(c)

        # score: one group-shared GEMM per chunk (stationary B^T)
        prog.add(ProgramStage(
            name=score_name, workload=score_w,
            x=c_in[c], w=b_in[c].T.copy()[None], w_source=STATIONARY_ACT,
        ))

        # intra-chunk output: per-head decay mask folded into the Ref
        def masked_scores(out: np.ndarray, dc=decay[:, c]) -> np.ndarray:
            return requantize_int8(out[0].astype(np.float64)[None] * dc)

        prog.add(ProgramStage(
            name=intra_name, workload=intra_w,
            x=Ref(score_name, masked_scores),
            w=dtx[:, c], w_source=STATIONARY_ACT,
        ))

        # chunk state: (B_c * decay_out)^T per head, streamed
        bt = np.transpose(
            b_in[c].astype(np.float64)[None] * decay_out[:, c, :, None],
            (0, 2, 1),
        )
        prog.add(ProgramStage(
            name=state_name, workload=state_w,
            x=requantize_int8(bt), w=dtx[:, c], w_source=STATIONARY_ACT,
        ))

        # inter-chunk output: recurrent state stationary, Ref'd across
        # every earlier chunk's state stage through the decay recurrence
        if c > 0:
            def h_prev(*states: np.ndarray, c=c) -> np.ndarray:
                acc = np.zeros((h, n, p), np.float64)
                for j, st in enumerate(states):
                    factor = np.exp(la_tot[:, j + 1:c].sum(axis=1))
                    acc += st.astype(np.float64) * factor[:, None, None]
                return requantize_int8(acc)

            x_inter = requantize_int8(
                c_in[c].astype(np.float64)[None]
                * np.exp(la[:, c])[:, :, None]
            )
            prog.add(ProgramStage(
                name=inter_name, workload=inter_w, x=x_inter,
                w=Ref(tuple(state_names), h_prev),
                w_source=STATIONARY_ACT,
            ))
        state_names.append(state_name)
    return spec._finish(prog)


# --------------------------------------------------------------------------- #
# Hybrid lowering + the front door
# --------------------------------------------------------------------------- #

def lower_hybrid(spec: HybridSpec) -> Program:
    """Lower one hybrid period: the shared attention block's program
    merged with the SSD block's, tagged ``{attn}`` / ``{ssm}``, with the
    Zamba2 sequencing (shared attention before the SSM blocks) expressed
    as control dependencies from the SSD graph's root stages onto the
    attention block's final stage."""
    attn_prog = lower(dataclasses.replace(spec.attention, tag=""))
    ssd_prog = lower(dataclasses.replace(spec.ssd, tag=""))
    attn_last = attn_prog.topo_order()[-1].name + "{attn}"
    merged = Program.merge([attn_prog, ssd_prog], tags=["{attn}", "{ssm}"])
    prog = Program()
    for st in merged:
        if st.name.endswith("{ssm}") and not st.deps:
            st = dataclasses.replace(st, after=(attn_last,))
        prog.add(st)
    return spec._finish(prog)


_BUILDERS = {
    AttentionLoweringSpec: _lower_attention_block,
    ServeStepSpec: _lower_engine_step,
    MoESpec: lower_moe,
    SSDSpec: lower_ssd,
    HybridSpec: lower_hybrid,
}


def lower(spec: LoweringSpec) -> Program:
    """THE lowering entry point: hand any :class:`LoweringSpec` to its
    family's builder, which returns the validated (and tagged) Program."""
    builder = _BUILDERS.get(type(spec))
    if builder is None:
        raise TypeError(
            f"lower() takes a LoweringSpec, got {type(spec).__name__}"
        )
    return builder(spec)


def zoo_spec(cfg, *, seq_len: int = 64, tokens: int = 16, chunks: int = 2,
             seed: int = 0) -> LoweringSpec:
    """A registry :class:`~repro.configs.base.ModelConfig`'s workload-zoo
    spec — the family-appropriate block lowered for the CI matrix:

    * ``moe``    -> the MoE FFN block (:class:`MoESpec`, expert-skip ZTB);
    * ``ssm``    -> the chunked SSD scan (:class:`SSDSpec`);
    * ``hybrid`` -> shared attention + SSD period (:class:`HybridSpec`);
    * everything else (dense / encoder / vlm) -> the attention block
      (:class:`AttentionLoweringSpec`).

    Model-family knowledge lives with the models (the helpers in
    ``repro.models.{moe,mamba2,hybrid}``); this wrapper only dispatches,
    so ``legion`` stays import-light until a zoo spec is actually built.
    """
    family = getattr(cfg, "family", "dense")
    if family == "moe":
        from repro.models.moe import moe_lowering_spec
        return moe_lowering_spec(cfg, tokens=tokens, seed=seed)
    if family == "ssm":
        from repro.models.mamba2 import ssd_lowering_spec
        return ssd_lowering_spec(cfg, chunks=chunks, seed=seed)
    if family == "hybrid":
        from repro.models.hybrid import hybrid_lowering_spec
        return hybrid_lowering_spec(cfg, seq_len=seq_len, chunks=chunks,
                                    seed=seed)
    return AttentionLoweringSpec(
        heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim_,
        hidden=cfg.d_model, seq_len=seq_len, weight_bits=cfg.weight_bits,
        layers=cfg.layers, seed=seed, name=cfg.name,
    )
