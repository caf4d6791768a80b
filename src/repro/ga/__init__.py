"""`repro.ga` — the public GA engine API (one spec, topology × executor).

The paper's contribution is a single full-parallel datapath (FFM→SM→CM→MM)
that scales by swapping hardware arrangements.  This package is that idea as
an API: a frozen :class:`GASpec` describes *what* to solve (problem,
encoding, operator pipeline, run policy, topology) and the :class:`Engine`
decides *how*.  Backends are compositions of an *executor* (how a block of
generations is stepped) and a *topology* (how populations are laid out and
exchanged):

    =============  ===========  ============  ===========================
    backend        executor     topology      notes
    =============  ===========  ============  ===========================
    reference      JAX scan     single        any operators, lut or arith
                                              FFM, vmapped `n_repeats`
    fused          Pallas       single        VMEM-resident state, MXU
                   kernel                     one-hot tournaments; the
                                              spec's FitnessProgram.stage
                                              is traced in as the FFM (any
                                              registered problem or
                                              blackbox); bit-identical to
                                              reference; `gens_per_epoch`
                                              generations per launch
    islands        JAX scan     island_ring   ring migration; shard_mapped
                                              over a mesh when given
    fused-islands  Pallas       island_ring   ring migration *between*
                   kernel                     kernel launches; on a mesh,
                                              one launch per shard with
                                              `ppermute` migration —
                                              bit-identical to one device
    eager          python loop  single        non-traceable fitness
                                              (operators stay jitted)
    =============  ===========  ============  ===========================

    Problems are a registry too (`repro.core.fitness.PROBLEMS`): the
    paper's F1–F3 plus the n-variable suite (sphere / rastrigin /
    rosenbrock / ackley, `problem="rastrigin:8"` picks V) and
    user-registered definitions (`ga.register_problem`); each compiles to
    a `FitnessProgram` lowering it to LUT ROMs, the XLA arith path and
    the in-kernel FFM stage.

Typical use::

    from repro import ga

    result = ga.solve(ga.GASpec(problem="F1", n=32, bits_per_var=13,
                                mode="lut", generations=100))
    result = ga.solve(ga.paper_spec("F3", n=64, m=20, mode="arith"),
                      backend="fused")

Execution knobs (mesh, interpret, cost table, plan override, the streamed
mode's tile/budget) ride in one frozen :class:`EngineOptions` shared by
`Engine`, `PackedEngine`, `GAScheduler` and the CLIs; how a run executed
comes back as typed :class:`RunTelemetry` (``result.telemetry.plan`` /
``.topology`` / ``.per_repeat``).

Operator stages are pluggable protocols with registries
(`ga.SELECTION` / `ga.CROSSOVER` / `ga.MUTATION`; see
:mod:`repro.ga.operators`), chunked streaming + checkpoint/resume live in
the one chunk loop behind :meth:`Engine.run`, :meth:`Engine.run_chunked` and
:meth:`PackedEngine.run_chunked`.

The pre-engine entry points (`core.ga.run`/`run_unjitted`,
`islands.run_local`/`run_sharded`, `kernels.ops.ga_run_kernel`) have been
REMOVED after their deprecation cycle — the mapping, for code migrating
from them:

    core.ga.run(cfg, fit, k)            -> solve(spec, backend="reference")
    core.ga.run_unjitted(cfg, fit, k)   -> solve(spec, backend="eager")
                                           (spec.jit_fitness=False)
    kernels.ops.ga_run_kernel(...)      -> solve(spec, backend="fused")
    islands.run_local/run_sharded(...)  -> solve(spec, backend="islands")
                                           (spec.n_islands>1[, mesh=...])
    core.evolve.evolve(fn, bounds)      -> unchanged signature, now a
                                           GASpec + Engine underneath
"""

from repro.core.fitness import (PROBLEMS, FitnessProgram, ProblemDef,
                                compile_program, register_problem,
                                resolve_problem)
from repro.ga.spec import GASpec, paper_spec
from repro.ga.operators import (CROSSOVER, MUTATION, PAPER_PIPELINE,
                                SELECTION, CrossoverOp, MutationOp,
                                SelectionOp, make_apply_ops, make_generation,
                                register_crossover, register_mutation,
                                register_selection)
from repro.ga.options import EngineOptions, resolve_options
from repro.ga.telemetry import (TELEMETRY_VERSION, PlanInfo, ReplicaStats,
                                RunTelemetry, TopologyInfo)
from repro.ga.backends import (BACKENDS, EXECUTORS, TOPOLOGIES, Backend,
                               Executor, Segment, Topology)
from repro.ga.compile_cache import PROGRAM_CACHE, RUNNER_CACHE, CompileCache
from repro.ga.engine import (BackendUnsupported, Engine, EngineResult,
                             PackedEngine, capability_matrix,
                             repack_checkpoint, resolve_backend, solve)

__all__ = [
    "GASpec", "paper_spec",
    "PROBLEMS", "ProblemDef", "FitnessProgram", "compile_program",
    "register_problem", "resolve_problem",
    "Engine", "EngineResult", "PackedEngine", "solve", "resolve_backend",
    "capability_matrix", "BackendUnsupported", "repack_checkpoint",
    "EngineOptions", "resolve_options",
    "RunTelemetry", "PlanInfo", "TopologyInfo", "ReplicaStats",
    "TELEMETRY_VERSION",
    "RUNNER_CACHE", "PROGRAM_CACHE", "CompileCache",
    "BACKENDS", "Backend", "Segment",
    "EXECUTORS", "TOPOLOGIES", "Executor", "Topology",
    "SELECTION", "CROSSOVER", "MUTATION", "PAPER_PIPELINE",
    "SelectionOp", "CrossoverOp", "MutationOp",
    "register_selection", "register_crossover", "register_mutation",
    "make_generation", "make_apply_ops",
]
