"""`Program` — multi-stage dependency graphs for the Legion runtime.

The paper's headline latencies come from running *whole attention blocks*
through the Legions — QKV projections, the act-to-act score/output GEMMs
with KV multicast, the output projection — with tiles multicast and rounds
overlapped.  A single :class:`~repro.core.scheduler.StagePlan` cannot
express that: the right unit of execution is the stage *graph* (the same
conclusion TensorRT-LLM's engine graphs and ADiP's pipelined core reach).
This module makes the graph first-class:

* :class:`ProgramStage` — one named node: a
  :class:`~repro.core.workloads.GEMMWorkload` (or an explicit plan),
  operands that are concrete arrays, synthesized, or :class:`Ref`\\ s to
  earlier stages' outputs (optionally transformed — requantization,
  softmax, head concat), and an operand-source tag distinguishing
  stationary *weights* from stationary *activations* (the K/V matrices of
  act-to-act attention);

* :class:`Program` — a validated DAG of stages with topological order and
  dependency levels (antichains), executed by
  :meth:`repro.legion.machine.Machine.run`.  :meth:`Program.merge` folds
  *independent* programs into one batch graph (per-slot decode attention
  interleaved as an antichain — the continuous-batching shape vLLM-style
  schedulers produce).  The builders that lower attention blocks and
  serving steps to Programs live in :mod:`repro.legion.lowering`;

* :func:`compute_pipeline` — the overlapped-round timing model behind
  :class:`~repro.legion.machine.PipelinedExecutor`: rounds of
  dependency-independent stages interleave, and each round boundary whose
  two sides have no dependency path hides the incoming round's systolic
  fill + pipeline ramp under the outgoing round's streaming + drain
  (:func:`repro.core.analytical.boundary_overlap_cycles`) — within a
  level *and* across level boundaries (the outgoing level's last round
  may belong to a stage the incoming stage never consumes, e.g. another
  slot of a merged batch).  A *data-dependent* boundary whose stationary
  operand is independent of the outgoing stage still hides the incoming
  fill as a cross-level weight prefetch — the stationary tiles already
  exist in memory while the streamed input is being produced
  (:func:`repro.core.analytical.weight_prefetch_overlap_cycles`).
  Overlapped cycles are always <= the serial per-stage sum, with exact
  equality when every adjacent round pair is same-stage or
  stationary-blocked (the incoming stationary operand produced by the
  outgoing stage) — the program-level cross-validation invariant;

* :func:`reference_outputs` — a pure-NumPy execution of the whole graph
  (no plans, no kernels, no machine) that program runs are checked
  against end to end.
"""
from __future__ import annotations

import dataclasses
import math
from typing import (
    TYPE_CHECKING,
    Callable,
    Dict,
    List,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import numpy as np

from repro.core.analytical import (
    boundary_overlap_cycles,
    weight_prefetch_overlap_cycles,
)
from repro.core.scheduler import StagePlan
from repro.core.sparsity import ZeroTileBook
from repro.core.workloads import GEMMWorkload
from repro.legion.latency import CycleBreakdown
from repro.legion.modes import ModeSpec
from repro.legion.runtime import exact_int_matmul

if TYPE_CHECKING:  # pragma: no cover
    from repro.legion.machine import RunReport

# Stationary-operand sources: the paper's weight-stationary projections
# vs the act-to-act attention stages whose stationary operand is itself
# an activation (K/V — in serving, the KV-cache matrices).
WEIGHT = "weight"
STATIONARY_ACT = "stationary_act"


# --------------------------------------------------------------------------- #
# Operand references + transforms
# --------------------------------------------------------------------------- #

class Ref:
    """An operand sourced from earlier stage outputs, optionally transformed.

    ``Ref("qkv_proj")`` is the producer's raw ``[count, M, N]`` outputs;
    ``Ref("qkv_proj", f)`` applies ``f`` to them (slice heads, requantize,
    transpose K, softmax...).  A multi-producer ref —
    ``Ref(("a", "b"), f)`` — passes every producer's outputs to ``f``
    positionally (e.g. concatenating per-slot attention rows).
    """

    def __init__(
        self,
        stage: Union[str, Sequence[str]],
        transform: Optional[Callable[..., np.ndarray]] = None,
    ) -> None:
        self.producers: Tuple[str, ...] = (
            (stage,) if isinstance(stage, str) else tuple(stage)
        )
        if not self.producers:
            raise ValueError("Ref needs at least one producer stage")
        if len(self.producers) > 1 and transform is None:
            raise ValueError(
                "a multi-producer Ref needs a transform combining the "
                f"outputs; got producers {self.producers}"
            )
        self.transform = transform

    def resolve(self, outputs: Dict[str, np.ndarray]) -> np.ndarray:
        vals = [outputs[p] for p in self.producers]
        if self.transform is None:
            return vals[0]
        return np.asarray(self.transform(*vals))

    def __repr__(self) -> str:
        t = getattr(self.transform, "__name__", None) if self.transform \
            else None
        return f"Ref({', '.join(self.producers)}{f', {t}' if t else ''})"


def requantize_int8(arr: np.ndarray, *, magnitude: int = 127) -> np.ndarray:
    """Symmetric per-tensor requantization to int8.

    The inter-stage link of a program: stage outputs are int32 partial
    sums; the next stage streams int8 activations.  Deterministic, so the
    pure-NumPy :func:`reference_outputs` reproduces runtime results
    bit-for-bit.
    """
    a = np.asarray(arr, np.float64)
    peak = float(np.abs(a).max()) if a.size else 0.0
    if peak == 0.0:
        return np.zeros(a.shape, np.int8)
    return np.clip(np.rint(a / peak * magnitude), -127, 127).astype(np.int8)


def softmax_int8(
    scores: np.ndarray, *, scale: Optional[float] = None,
) -> np.ndarray:
    """Row softmax over the key axis of attention scores, requantized to
    int8 probabilities — the score -> output link of the attention graph.

    ``scale`` maps raw int32 scores into softmax's active range (the
    lowering builders pass ``1 / (qmax * kmax * sqrt(head_dim))``);
    default is ``1/sqrt(num_keys)``.
    """
    s = np.asarray(scores, np.float64)
    if scale is None:
        scale = 1.0 / math.sqrt(max(s.shape[-1], 1))
    z = s * scale
    z = z - z.max(axis=-1, keepdims=True)
    p = np.exp(z)
    p = p / p.sum(axis=-1, keepdims=True)
    return np.rint(p * 127.0).astype(np.int8)


def swiglu_int8(up: np.ndarray) -> np.ndarray:
    """SwiGLU combine of the two mlp_up branches: silu(w1 x) * (w3 x),
    requantized to int8 for the mlp_down stage (gate normalized into
    sigmoid's active range first — raw int32 magnitudes would saturate)."""
    a = np.asarray(up, np.float64)
    gate, value = a[0], a[1]
    peak = float(np.abs(gate).max()) or 1.0
    z = gate / peak * 4.0
    return requantize_int8(z / (1.0 + np.exp(-z)) * value)


# --------------------------------------------------------------------------- #
# Program graph
# --------------------------------------------------------------------------- #

Operand = Union[None, np.ndarray, Ref]


@dataclasses.dataclass
class ProgramStage:
    """One node of a :class:`Program`.

    Exactly one of ``workload`` (lowered to a plan by the machine) or
    ``plan`` must be set.  Operands: ``x`` streams, ``w`` is stationary;
    each is a concrete array, a :class:`Ref` to earlier outputs, or
    ``None`` — a workload stage with both operands ``None`` synthesizes
    them (the legacy single-workload behaviour).  ``w_source`` tags
    whether the stationary operand is a weight matrix or a stationary
    *activation* (K/V).  ``after`` adds control dependencies beyond the
    operand refs.
    """

    name: str
    workload: Optional[GEMMWorkload] = None
    plan: Optional[StagePlan] = None
    x: Operand = None
    w: Operand = None
    w_source: str = WEIGHT
    mode: Optional[ModeSpec] = None
    ztb: Union[None, bool, ZeroTileBook, Sequence[ZeroTileBook]] = None
    ztb_sparsity: float = 0.0
    after: Tuple[str, ...] = ()

    @property
    def deps(self) -> Tuple[str, ...]:
        """Producer stages this node waits on (operand refs + ``after``)."""
        seen: List[str] = []
        for op in (self.x, self.w):
            if isinstance(op, Ref):
                for p in op.producers:
                    if p not in seen:
                        seen.append(p)
        for p in self.after:
            if p not in seen:
                seen.append(p)
        return tuple(seen)

def _rename_ref(op: "Operand", mapping: Dict[str, str]) -> "Operand":
    """A Ref with producers renamed through ``mapping`` (external names —
    not in the mapping — pass through); non-Ref operands unchanged."""
    if not isinstance(op, Ref):
        return op
    return Ref(tuple(mapping.get(p, p) for p in op.producers), op.transform)


def _retagged(stage: "ProgramStage", mapping: Dict[str, str]) \
        -> "ProgramStage":
    """A copy of ``stage`` with its name, refs, and after-edges renamed."""
    return dataclasses.replace(
        stage,
        name=mapping.get(stage.name, stage.name),
        x=_rename_ref(stage.x, mapping),
        w=_rename_ref(stage.w, mapping),
        after=tuple(mapping.get(a, a) for a in stage.after),
    )


class ProgramError(ValueError):
    """A Program's graph is malformed (dup names, bad refs, cycles...)."""


class Program:
    """A validated DAG of :class:`ProgramStage` nodes.

    Execute with ``Machine(cfg).run(program)`` -> :class:`ProgramReport`.
    """

    def __init__(self, stages: Sequence[ProgramStage] = ()) -> None:
        self.stages: List[ProgramStage] = []
        self._by_name: Dict[str, ProgramStage] = {}
        for s in stages:
            self.add(s)

    # ------------------------------------------------------------------ #
    def add(self, stage: ProgramStage) -> ProgramStage:
        if not isinstance(stage, ProgramStage):
            raise TypeError(f"expected ProgramStage, got "
                            f"{type(stage).__name__}")
        if stage.name in self._by_name:
            raise ProgramError(f"duplicate stage name {stage.name!r}")
        if (stage.workload is None) == (stage.plan is None):
            raise ProgramError(
                f"stage {stage.name!r}: set exactly one of workload / plan"
            )
        self.stages.append(stage)
        self._by_name[stage.name] = stage
        return stage

    def __len__(self) -> int:
        return len(self.stages)

    def __iter__(self):
        return iter(self.stages)

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def __getitem__(self, name: str) -> ProgramStage:
        return self._by_name[name]

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(s.name for s in self.stages)

    # ------------------------------------------------------------------ #
    def validate(self) -> None:
        """Refs resolve, operands are coherent, and the graph is acyclic
        (:meth:`topo_order` raises on cycles)."""
        if not self.stages:
            raise ProgramError("empty program")
        for s in self.stages:
            for dep in s.deps:
                if dep not in self._by_name:
                    raise ProgramError(
                        f"stage {s.name!r} references unknown stage {dep!r}"
                    )
                if dep == s.name:
                    raise ProgramError(f"stage {s.name!r} depends on itself")
            if s.workload is None and (s.x is None or s.w is None):
                raise ProgramError(
                    f"stage {s.name!r}: explicit-plan stages need explicit "
                    f"x and w operands"
                )
            if s.workload is not None and (s.x is None) != (s.w is None):
                raise ProgramError(
                    f"stage {s.name!r}: pass both x and w, or neither "
                    f"(neither = synthesized operands)"
                )
            if s.ztb_sparsity and s.x is not None:
                raise ProgramError(
                    f"stage {s.name!r}: ztb_sparsity prunes *synthesized* "
                    f"operands; with explicit operands prune the weights "
                    f"yourself and pass ztb="
                )
        self.topo_order()

    def topo_order(self) -> List[ProgramStage]:
        """Stages in dependency order (stable: insertion order breaks
        ties).  Raises :class:`ProgramError` on cycles."""
        done: Dict[str, bool] = {}
        order: List[ProgramStage] = []

        def visit(s: ProgramStage, chain: Tuple[str, ...]) -> None:
            state = done.get(s.name)
            if state is True:
                return
            if state is False:
                raise ProgramError(
                    f"dependency cycle: {' -> '.join(chain + (s.name,))}"
                )
            done[s.name] = False
            for dep in s.deps:
                if dep in self._by_name:
                    visit(self._by_name[dep], chain + (s.name,))
            done[s.name] = True
            order.append(s)

        for s in self.stages:
            visit(s, ())
        return order

    def levels(self) -> List[List[ProgramStage]]:
        """Dependency levels (antichains): stages in the same level have no
        path between them and may overlap; levels serialize."""
        depth: Dict[str, int] = {}
        for s in self.topo_order():
            depth[s.name] = 1 + max(
                (depth[d] for d in s.deps if d in depth), default=-1,
            )
        out: List[List[ProgramStage]] = [[] for _ in
                                         range(max(depth.values()) + 1)]
        for s in self.stages:       # insertion order within a level
            out[depth[s.name]].append(s)
        return out

    @property
    def is_chain(self) -> bool:
        """Every level holds exactly one stage — nothing to overlap."""
        return all(len(level) == 1 for level in self.levels())

    def ancestors(self) -> Dict[str, frozenset]:
        """Transitive dependency closure: ``name -> every stage reachable
        through deps``.  The independence test behind the pipelined
        schedule — two stages with no ancestry either way may overlap."""
        anc: Dict[str, frozenset] = {}
        for s in self.topo_order():
            a: set = set()
            for dep in s.deps:
                a.add(dep)
                a |= anc.get(dep, frozenset())
            anc[s.name] = frozenset(a)
        return anc

    def stationary_blockers(self) -> Dict[str, frozenset]:
        """Stages a node's *stationary* operand transitively depends on:
        ``name -> {producer stages of w} ∪ their ancestors`` (empty when
        ``w`` is a concrete array, synthesized, or ``None``).

        The cross-level weight-prefetch test behind the pipelined
        schedule: a round may start fetching its stationary tiles under a
        data-dependent predecessor round as long as that predecessor is
        NOT among the stationary operand's own producers — the weights
        (or an earlier-written K-V cache) already exist in memory even
        though the streamed input does not yet
        (:func:`repro.core.analytical.weight_prefetch_overlap_cycles`).
        """
        anc = self.ancestors()
        out: Dict[str, frozenset] = {}
        for s in self.stages:
            blockers: set = set()
            if isinstance(s.w, Ref):
                for p in s.w.producers:
                    blockers.add(p)
                    blockers |= anc.get(p, frozenset())
            out[s.name] = frozenset(blockers)
        return out

    # ------------------------------------------------------------------ #
    @classmethod
    def merge(
        cls,
        programs: Sequence["Program"],
        *,
        tags: Optional[Sequence[str]] = None,
    ) -> "Program":
        """Merge *independent* programs into one batch graph.

        Every program's stage names gain its ``tags`` entry as a suffix
        (default ``[i]`` when merging more than one program, empty for a
        single one); :class:`Ref`\\ s and ``after`` edges between a
        program's *own* stages are renamed along, while refs to names
        outside it pass through untouched — so lowering builders can
        merge per-slot subgraphs that hang off shared stages (the batched
        projections) added around the merged result.

        The merged graph holds the inputs' stages side by side: their
        dependency levels align, so same-level stages of different slots
        form exactly the antichain a
        :class:`~repro.legion.machine.PipelinedExecutor` interleaves —
        batch-level pipelining of one decode step's per-slot attention
        programs.  The result is NOT validated here (callers with
        external refs validate after adding the surrounding stages);
        colliding names (e.g. duplicate tags) raise :class:`ProgramError`
        at ``add`` time.
        """
        programs = list(programs)
        if tags is None:
            tags = [""] if len(programs) == 1 else \
                [f"[{i}]" for i in range(len(programs))]
        tags = list(tags)
        if len(tags) != len(programs):
            raise ValueError(
                f"{len(tags)} tags for {len(programs)} programs"
            )
        merged = cls()
        for prog, tag in zip(programs, tags):
            mapping = {name: name + tag for name in prog.names}
            for st in prog:
                merged.add(_retagged(st, mapping))
        return merged

    # ------------------------------------------------------------------ #
    @classmethod
    def single(
        cls,
        work: Union[GEMMWorkload, StagePlan],
        x: Optional[np.ndarray] = None,
        w: Optional[np.ndarray] = None,
        *,
        mode: Optional[ModeSpec] = None,
        ztb: Union[None, bool, ZeroTileBook, Sequence[ZeroTileBook]] = None,
        ztb_sparsity: float = 0.0,
    ) -> "Program":
        """One-node program — what the legacy ``Machine.run(workload)`` /
        ``Machine.run(plan, x, w)`` calls become (same validation, same
        error messages)."""
        if isinstance(work, GEMMWorkload):
            if (x is None) != (w is None):
                raise ValueError("pass both x and w, or neither")
            if x is not None and ztb_sparsity:
                raise ValueError(
                    "ztb_sparsity prunes *synthesized* operands; with "
                    "explicit x and w, prune the weights yourself and pass "
                    "ztb=True (or pre-built books)"
                )
            stage = ProgramStage(
                name=work.stage, workload=work, x=x, w=w, mode=mode,
                ztb=ztb, ztb_sparsity=ztb_sparsity,
            )
        elif isinstance(work, StagePlan):
            if ztb_sparsity:
                raise ValueError(
                    "ztb_sparsity synthesizes operands and only applies to "
                    "workload runs; pass ztb= for an explicit plan"
                )
            if x is None or w is None:
                raise ValueError("Machine.run(plan, ...) needs explicit "
                                 "x and w operands")
            stage = ProgramStage(name=work.stage, plan=work, x=x, w=w,
                                 mode=mode, ztb=ztb)
        else:
            raise TypeError(
                f"expected GEMMWorkload, StagePlan, or Program, got "
                f"{type(work).__name__}"
            )
        return cls([stage])


# --------------------------------------------------------------------------- #
# Pure-NumPy reference execution
# --------------------------------------------------------------------------- #

def reference_outputs(program: Program) -> Dict[str, np.ndarray]:
    """Execute the whole graph in plain NumPy — no plans, kernels, or
    machine — and return per-stage ``[count, M, N]`` outputs.

    The end-to-end check for program runs: every operand resolves through
    the same refs/transforms, so a ``Machine.run(program)`` must reproduce
    these outputs exactly (int path) for the threading, instance wiring,
    and per-stage numerics all at once.  Requires concrete operands (no
    synthesis).  ``ztb=True`` stages are allowed: self-derived books gate
    only windows whose weights are entirely zero, so the dense reference
    is still exact (the MoE expert-skip lowering rides this — an unchosen
    expert carries zeroed weights, and both sides produce zeros).
    Caller-passed books may gate nonzero data and have no dense reference.
    """
    program.validate()
    outs: Dict[str, np.ndarray] = {}
    for st in program.topo_order():
        if st.x is None or st.w is None:
            raise ProgramError(
                f"stage {st.name!r}: reference execution needs concrete "
                f"operands (synthesized stages have no reference)"
            )
        if st.ztb not in (None, False, True):
            raise ProgramError(
                f"stage {st.name!r}: reference execution is dense; "
                f"caller-passed ZTB books would gate contributions "
                f"(ztb=True is fine — self-derived books gate only "
                f"all-zero windows)"
            )
        x = st.x.resolve(outs) if isinstance(st.x, Ref) else np.asarray(st.x)
        w = st.w.resolve(outs) if isinstance(st.w, Ref) else np.asarray(st.w)
        count = st.workload.count if st.workload is not None else (
            max(a.instance for a in st.plan.assignments) + 1
        )
        x = x if x.ndim == 2 else x[:count]
        w = w if w.ndim == 2 else w[:count]
        int_path = (np.issubdtype(x.dtype, np.integer)
                    and np.issubdtype(w.dtype, np.integer))
        if int_path:
            res, _ = exact_int_matmul(x, w)
        else:
            res = np.matmul(x.astype(np.float64), w.astype(np.float64))
        outs[st.name] = np.broadcast_to(
            res, (count,) + res.shape[-2:]
        ).astype(np.int32 if int_path else np.float32)
    return outs


# --------------------------------------------------------------------------- #
# Pipelined timing model
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class LevelTiming:
    """One dependency level's serial vs overlapped cycles."""

    stages: Tuple[str, ...]
    serial_cycles: int
    overlapped_cycles: int

    @property
    def hidden_cycles(self) -> int:
        return self.serial_cycles - self.overlapped_cycles


@dataclasses.dataclass
class PipelineReport:
    """The pipelined executor's overlapped schedule vs the serial sum.

    Invariants (the program-level cross-validation): ``overlapped_cycles
    <= serial_cycles`` always, with equality exactly when every adjacent
    round pair is same-stage or *stationary-blocked* (the incoming
    round's stationary operand is produced by the outgoing stage —
    attention's S = Q.K^T after its K).  Dependency-independent
    boundaries hide fill + pipeline; data-dependent boundaries whose
    stationary operand already exists (weights, earlier-written K-V)
    still hide the fill as a cross-level weight prefetch.
    ``serial_cycles`` itself equals the per-stage counted totals, which
    each cross-validate against ``simulate()``.  Hidden cycles at a
    *level boundary* are attributed to the incoming round's level, so
    single-stage levels may legitimately report ``overlapped < serial``.
    """

    levels: List[LevelTiming]

    @property
    def serial_cycles(self) -> int:
        return sum(lv.serial_cycles for lv in self.levels)

    @property
    def overlapped_cycles(self) -> int:
        return sum(lv.overlapped_cycles for lv in self.levels)

    @property
    def hidden_cycles(self) -> int:
        return self.serial_cycles - self.overlapped_cycles

    @property
    def speedup(self) -> float:
        if self.overlapped_cycles == 0:
            return 1.0
        return self.serial_cycles / self.overlapped_cycles

    @property
    def ok(self) -> bool:
        return all(0 <= lv.overlapped_cycles <= lv.serial_cycles
                   for lv in self.levels)

    def __str__(self) -> str:
        return (f"Pipeline[{len(self.levels)} levels] serial="
                f"{self.serial_cycles} overlapped={self.overlapped_cycles} "
                f"({self.speedup:.3f}x, {self.hidden_cycles} hidden)")


def compute_pipeline(
    program: Program,
    rounds_by_stage: Dict[str, List[CycleBreakdown]],
) -> PipelineReport:
    """Overlapped-round schedule from per-round critical paths.

    Levels serialize for *dependent* work; within a level, the stages'
    rounds interleave round-robin.  Two overlap rules apply at every
    boundary between rounds of different stages (within a level *and*
    across level boundaries):

    * **no dependency path** from the outgoing stage to the incoming one
      — the incoming round's fill + pipeline ramp hides under the
      outgoing round's streaming + drain
      (:func:`repro.core.analytical.boundary_overlap_cycles`): in a
      merged batch graph (or a split projection the next stage never
      consumes — ``attn_score`` after ``v_proj``), the first round of a
      level can start filling while the previous level's last, unrelated
      round still streams;
    * **data-dependent, stationary operand independent** — the incoming
      stage consumes the outgoing one, but its *stationary* operand does
      not (``program.stationary_blockers()``): the stationary tiles
      already exist in memory, so their fill prefetches into the double
      buffer under the outgoing round's streaming + drain
      (:func:`repro.core.analytical.weight_prefetch_overlap_cycles`) —
      only the pipeline ramp, coupled to the not-yet-produced streamed
      input, stays exposed.

    Rounds of the same stage never overlap (they share the stage's psum
    banks and stationary buffers), and a boundary whose stationary
    operand is itself produced by the outgoing stage (attention's
    S = Q.K^T after the K it consumes) hides nothing, so the overlapped
    sum can never beat the streamed work — ``overlapped <= serial``
    stays the program-level gate.
    """
    ancestors = program.ancestors()
    w_blockers = program.stationary_blockers()
    levels: List[LevelTiming] = []
    prev: Optional[Tuple[str, CycleBreakdown]] = None
    for level in program.levels():
        names = tuple(s.name for s in level)
        seqs = [rounds_by_stage.get(n, []) for n in names]
        serial = sum(b.total for seq in seqs for b in seq)
        # round-robin interleave: stage1 r0, stage2 r0, ..., stage1 r1, ...
        order: List[Tuple[str, CycleBreakdown]] = []
        for tier in range(max((len(s) for s in seqs), default=0)):
            for name, seq in zip(names, seqs):
                if tier < len(seq):
                    order.append((name, seq[tier]))
        hidden = 0
        for name, nb in order:
            if prev is not None:
                pname, pb = prev
                if pname != name:
                    if pname not in ancestors.get(name, ()):
                        hidden += boundary_overlap_cycles(
                            pb.stream, nb.fill, nb.pipeline,
                            prev_drain=pb.drain,
                        )
                    elif pname not in w_blockers.get(name, ()):
                        hidden += weight_prefetch_overlap_cycles(
                            pb.stream, nb.fill, prev_drain=pb.drain,
                        )
            prev = (name, nb)
        levels.append(LevelTiming(names, serial, serial - hidden))
    return PipelineReport(levels=levels)


# --------------------------------------------------------------------------- #
# ProgramReport
# --------------------------------------------------------------------------- #

@dataclasses.dataclass
class ProgramReport:
    """Everything one ``Machine.run(program)`` produced."""

    program: Program
    stage_reports: Dict[str, "RunReport"]   # topological order
    backend: str
    pipeline: Optional[PipelineReport] = None

    def __getitem__(self, name: str) -> "RunReport":
        return self.stage_reports[name]

    @property
    def outputs(self) -> Dict[str, np.ndarray]:
        return {n: r.outputs for n, r in self.stage_reports.items()}

    @property
    def serial_cycles(self) -> int:
        """Counted cycles with stages strictly serialized (sum of the
        per-stage critical paths)."""
        return sum(r.total_cycles for r in self.stage_reports.values())

    @property
    def total_cycles(self) -> int:
        """Overlapped cycles under a pipelined backend, serial otherwise."""
        if self.pipeline is not None:
            return self.pipeline.overlapped_cycles
        return self.serial_cycles

    @property
    def validations(self) -> List[object]:
        return [v for r in self.stage_reports.values()
                for v in r.validations]

    @property
    def ok(self) -> bool:
        stages_ok = all(r.ok for r in self.stage_reports.values())
        return stages_ok and (self.pipeline is None or self.pipeline.ok)

    def __str__(self) -> str:
        lines = [f"ProgramReport[{len(self.stage_reports)} stages] "
                 f"backend={self.backend}"]
        lines += [f"  {r}" for r in self.stage_reports.values()]
        if self.pipeline is not None:
            lines.append(f"  {self.pipeline}")
        return "\n".join(lines)
