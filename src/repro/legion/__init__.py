"""Legion runtime — executes scheduler StagePlans through the kernels.

The subsystem that closes the loop between the repo's three models of
D-Legion (analytic simulator, orchestrator plans, Pallas kernels):

- machine:  `Machine` session facade — pluggable `Instrument` measurement
            hooks + `ExecutorBackend` numerics (in-process, sharded
            device-parallel over a JAX mesh axis, or pipelined over a
            program's dependency levels)
- program:  `Program` stage graphs — named GEMM nodes with explicit data
            dependencies and operand sources (streamed act / stationary
            weight / stationary act for K-V), the int8 transforms, the
            overlapped-round pipeline model, and a pure-NumPy reference
            execution
- lowering: every lowering builder behind one `lower(spec)` front door —
            the `LoweringSpec` dataclass family covering attention
            (`lower_attention`), the engine step (`ServeStepSpec`: prefill
            chunks beside a batched decode), MoE expert-skip
            (`lower_moe`), the Mamba-2 SSD scan (`lower_ssd`), and the
            Zamba2-style hybrid (`lower_hybrid`), plus `zoo_spec` mapping
            any registry ModelConfig to its family's spec
- runtime:  plan coverage validation, operand synthesis
- modes:    adaptive-precision mode selection (W1.58 / W4 / W8, +ZTB)
- trace:    NoC-dedup traffic measurement + simulate() cross-validation
- latency:  cycle counting (fill/stream/drain/prefetch) + eq.-2 cross-val
- roofline: finite-bandwidth sweeps — the stall knee, the paper's HBM
            budget, counted-vs-analytic stall cross-validation
"""
from repro.legion.latency import (
    CycleBreakdown,
    CycleCounter,
    CycleValidation,
    cross_validate_cycles,
    merge_round_criticals,
    total_cycle_error,
    validate_mem_bw,
)
from repro.legion.lowering import (
    AttentionLoweringSpec,
    HybridSpec,
    LoweringSpec,
    MoESpec,
    SSDSpec,
    ServeStepSpec,
    lower,
    lower_attention,
    lower_hybrid,
    lower_moe,
    lower_ssd,
    moe_stage_names,
    ssd_stage_names,
    zoo_spec,
)
from repro.legion.machine import (
    ExecContext,
    ExecutorBackend,
    InProcessExecutor,
    Instrument,
    Machine,
    PipelinedExecutor,
    RunReport,
    ShardedExecutor,
    prepare_context,
    run_assignment_loop,
    validate_options,
)
from repro.legion.modes import ModeSpec, select_mode
from repro.legion.program import (
    LevelTiming,
    PipelineReport,
    Program,
    ProgramError,
    ProgramReport,
    ProgramStage,
    Ref,
    compute_pipeline,
    reference_outputs,
    requantize_int8,
    softmax_int8,
    swiglu_int8,
)
from repro.legion.roofline import (
    BandwidthSweep,
    SweepPoint,
    find_stall_knee,
    hbm_bytes_per_cycle,
    sweep_bandwidth,
)
from repro.legion.runtime import (
    PlanCoverageError,
    synthesize_operands,
    validate_coverage,
)
from repro.legion.trace import (
    StageValidation,
    TrafficTotals,
    TrafficTracer,
    cross_validate,
)

__all__ = [
    "AttentionLoweringSpec",
    "BandwidthSweep",
    "CycleBreakdown",
    "CycleCounter",
    "CycleValidation",
    "ExecContext",
    "ExecutorBackend",
    "HybridSpec",
    "InProcessExecutor",
    "Instrument",
    "LevelTiming",
    "LoweringSpec",
    "Machine",
    "MoESpec",
    "ModeSpec",
    "PipelineReport",
    "PipelinedExecutor",
    "PlanCoverageError",
    "Program",
    "ProgramError",
    "ProgramReport",
    "ProgramStage",
    "Ref",
    "RunReport",
    "SSDSpec",
    "ServeStepSpec",
    "ShardedExecutor",
    "StageValidation",
    "SweepPoint",
    "TrafficTotals",
    "TrafficTracer",
    "compute_pipeline",
    "cross_validate",
    "cross_validate_cycles",
    "find_stall_knee",
    "hbm_bytes_per_cycle",
    "lower",
    "lower_attention",
    "lower_hybrid",
    "lower_moe",
    "lower_ssd",
    "merge_round_criticals",
    "moe_stage_names",
    "prepare_context",
    "reference_outputs",
    "requantize_int8",
    "run_assignment_loop",
    "select_mode",
    "softmax_int8",
    "ssd_stage_names",
    "sweep_bandwidth",
    "swiglu_int8",
    "synthesize_operands",
    "total_cycle_error",
    "validate_coverage",
    "validate_mem_bw",
    "validate_options",
    "zoo_spec",
]
