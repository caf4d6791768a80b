"""`GASpec` — one frozen description of a GA run.

A spec bundles everything the old divergent drivers used to take through
ad-hoc plumbing: the problem (a registered benchmark or a blackbox fitness
over a box), the chromosome encoding, the operator pipeline, the run policy
(generations, repeats, islands) and the population topology.  Every
(topology × executor) backend consumes the same spec, so swapping
`"reference"` ↔ `"fused"` ↔ `"islands"` ↔ `"fused-islands"` ↔ `"eager"`
is a string, not a rewrite.

The fitness side of a spec compiles to a `repro.core.fitness.FitnessProgram`
(`spec.program()`): one object lowering the problem to the LUT ROMs, the
XLA arith path AND the Pallas in-kernel FFM stage — which is why any
registered n-variable problem (``problem="rastrigin:8"``) or traceable
blackbox runs on every executor.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import numpy as np

from repro.core import fitness as F
from repro.core import ga as G
from repro.ga import compile_cache as CC
from repro.ga import operators as OPS


@dataclasses.dataclass(frozen=True)
class GASpec:
    """Problem + encoding + operator choices + run policy (all frozen).

    Exactly one of ``problem`` (a registered benchmark name — ``"F1"``..,
    ``"sphere"``, ``"rastrigin"``, .. — optionally with a ``:V`` suffix,
    e.g. ``"rastrigin:8"``) or ``fitness`` (a batch blackbox
    ``(N, V) float32 -> (N,)`` with ``bounds``) must be set.
    """

    # ---- problem --------------------------------------------------------
    problem: Optional[str] = None
    fitness: Optional[Callable] = None
    bounds: Optional[Tuple[Tuple[float, float], ...]] = None

    # ---- encoding -------------------------------------------------------
    n: int = 32                    # population size N (even)
    bits_per_var: int = 10         # c (paper: m/2)
    n_vars: Optional[int] = None   # V; default from the problem registry
    mode: str = "arith"            # FFM mode: "lut" (ROMs) | "arith" (VPU)
    # fused-kernel tournament gather lane: "onehot" ((N, N) MXU matmul
    # gathers, N <= 1024), "gather" (jnp.take dynamic indexing, O(N·V),
    # no cap) or "auto" (onehot while legal, gather past the cap; with a
    # cost table the planner argmaxes MEASURED gens/s across both lanes).
    # Both lanes are bit-identical; this knob trades VMEM for MXU work.
    sel_lane: str = "auto"

    # ---- operators ------------------------------------------------------
    selection: str = "tournament"
    crossover: str = "single_point"
    mutation: str = "xor"
    mutation_rate: float = 0.02
    minimize: bool = True
    steps_per_draw: int = 3

    # ---- run policy -----------------------------------------------------
    generations: int = 100
    seed: int = 1
    n_repeats: int = 1             # independent vmapped replicas (Table 3)
    n_islands: int = 1             # >1 -> island model with migration
    migrate_every: int = 16
    jit_fitness: bool = True       # False -> fitness not traceable (eager)
    # generations folded INSIDE one Pallas launch (fused executors): >1
    # amortizes launch overhead at small migrate_every.  Population/LFSR
    # state and the running best individual stay bit-identical to
    # gens_per_epoch=1; only the best/mean trajectory coarsens to one
    # sample per launch.  Ignored by the reference/eager executors.
    # On an island_ring topology with migration="ring" and a fused
    # executor, >= migrate_every engages the RESIDENT epoch kernel: the
    # whole island shard stays in VMEM and the ring migration runs inside
    # the launch, folding gens_per_epoch//migrate_every migration intervals
    # per launch — so values beyond migrate_every must be a whole multiple
    # of it (validated here; migration="none" has no interval boundary and
    # is exempt — for it the planner offers the RESIDENT-FREE mode, which
    # folds the full gens_per_epoch in one VMEM-resident launch with no
    # migration pauses and no whole-multiple rule).  When the stack does
    # NOT fit the VMEM budget, the STREAMED mode tiles the island axis
    # through VMEM with a double-buffered HBM pipeline instead of giving
    # up kernel residency.  Which feasible mode actually runs is the
    # two-tier epoch-plan decision (kernels/ga_step module docstring): the
    # VMEM byte estimator gates feasibility, and an autotune cost table —
    # when one covers the spec — picks the best MEASURED gens/s among the
    # survivors (result.telemetry.plan — mode / source / fallback — reports
    # the outcome; with no table the choice is the original static
    # heuristic, bit-identically).
    gens_per_epoch: int = 1

    # ---- topology (how populations are arranged + exchanged) ------------
    # None/"auto" derives from n_islands; "single" pins one population
    # (n_repeats replicas at most), "island_ring" pins the ring-migrating
    # island layout.  `migration` picks the exchange between epochs:
    # "ring" (the [19] elite ring) or "none" (isolated islands ablation).
    topology: Optional[str] = None
    migration: str = "ring"
    # mesh policy: which mesh axes the island axis shards over when a mesh
    # is passed to the Engine.  None -> all axes of the given mesh.
    mesh_axes: Optional[Tuple[str, ...]] = None

    def __post_init__(self):
        if (self.problem is None) == (self.fitness is None):
            raise ValueError("set exactly one of problem= or fitness=")
        if self.mode not in ("lut", "arith"):
            raise ValueError(f"mode must be 'lut' or 'arith', got {self.mode!r}")
        if self.sel_lane not in ("auto", "onehot", "gather"):
            raise ValueError(f"sel_lane must be 'onehot', 'gather' or "
                             f"'auto', got {self.sel_lane!r}")
        if self.sel_lane == "onehot" and self.n > G.ONEHOT_MAX_N:
            # the lane-aware kernel gate, surfaced at spec build: an
            # explicit onehot pin past the one-hot VMEM cap can never run
            # on the fused kernel path
            raise ValueError(
                f"sel_lane='onehot' pinned with N={self.n} > "
                f"{G.ONEHOT_MAX_N}: the (N, N) one-hot tournament matrices "
                "would exceed VMEM in every fused kernel.  Fix: split the "
                "population across more islands (smaller per-island N), or "
                "switch to the O(N*V) dynamic-indexing lane with "
                "sel_lane='gather'")
        if self.problem is not None:
            # resolve "name:V" shorthand into (problem, n_vars) and validate
            # through the SAME rule set compile_program enforces
            pdef, v_suffix = F.resolve_problem(self.problem)
            if v_suffix is not None:
                if self.n_vars is not None and self.n_vars != v_suffix:
                    raise ValueError(
                        f"problem {self.problem!r} pins V={v_suffix} but "
                        f"n_vars={self.n_vars} was also given")
                object.__setattr__(self, "problem", pdef.name)
                object.__setattr__(self, "n_vars", v_suffix)
            F.resolve_vars(pdef, self.n_vars)
            F.check_bits(pdef, self.bits_per_var)
            F.check_mode(pdef, self.mode)
        if self.fitness is not None and self.bounds is None:
            raise ValueError("blackbox fitness requires bounds=")
        if self.fitness is not None and self.mode == "lut":
            raise ValueError("blackbox fitness has no LUT lowering; "
                             "run mode='arith'")
        if self.bounds is not None:
            object.__setattr__(self, "bounds",
                               tuple((float(lo), float(hi))
                                     for lo, hi in self.bounds))
        # operator names must exist — fail at spec build, not mid-run
        OPS.resolve(self.selection, self.crossover, self.mutation)
        for field, lo in (("n", 2), ("bits_per_var", 1), ("generations", 1),
                          ("n_repeats", 1), ("n_islands", 1),
                          ("migrate_every", 1), ("gens_per_epoch", 1)):
            if getattr(self, field) < lo:
                raise ValueError(f"{field} must be >= {lo}")
        if self.topology == "auto":
            object.__setattr__(self, "topology", None)
        if self.topology not in (None, "single", "island_ring"):
            raise ValueError(
                f"topology must be 'single', 'island_ring' or None/'auto', "
                f"got {self.topology!r}")
        if self.topology == "single" and self.n_islands > 1:
            raise ValueError("topology='single' is inconsistent with "
                             f"n_islands={self.n_islands}; drop one of them")
        if self.topology == "island_ring" and self.n_islands == 1:
            raise ValueError("topology='island_ring' needs n_islands > 1")
        if self.migration not in ("ring", "none"):
            raise ValueError(f"migration must be 'ring' or 'none', "
                             f"got {self.migration!r}")
        # the whole-interval rule only binds when a ring actually runs —
        # the migration='none' ablation has no interval boundary to respect
        if (self.effective_topology == "island_ring"
                and self.migration == "ring"
                and self.gens_per_epoch > self.migrate_every
                and self.gens_per_epoch % self.migrate_every):
            raise ValueError(
                f"gens_per_epoch={self.gens_per_epoch} is not a multiple of "
                f"migrate_every={self.migrate_every}: on an island_ring "
                "topology a resident launch folds WHOLE migration intervals "
                "(the ring migration runs in VMEM between them), so "
                "gens_per_epoch beyond migrate_every must be a multiple of "
                "it — round to a multiple or lower it to migrate_every")
        if self.mesh_axes is not None:
            if (not self.mesh_axes
                    or not all(isinstance(a, str) and a
                               for a in self.mesh_axes)):
                raise ValueError("mesh_axes must be a non-empty tuple of "
                                 f"axis names, got {self.mesh_axes!r}")
            object.__setattr__(self, "mesh_axes", tuple(self.mesh_axes))

    # ---- derived --------------------------------------------------------

    @property
    def v(self) -> int:
        if self.bounds is not None:
            return len(self.bounds)
        return F.resolve_vars(self.problem_def(), self.n_vars)

    @property
    def resolved_sel_lane(self) -> str:
        """The concrete kernel selection lane this spec defaults to: an
        explicit pin wins; "auto" keeps the MXU one-hot lane while it is
        legal (N <= ONEHOT_MAX_N) and switches to the dynamic-indexing
        gather lane past the cap.  With an autotune cost table the planner
        may still move an "auto" spec to the measured-faster lane at plan
        time (see IslandRingTopology._epoch_plan); this value is the
        heuristic starting point."""
        if self.sel_lane != "auto":
            return self.sel_lane
        return "onehot" if self.n <= G.ONEHOT_MAX_N else "gather"

    @property
    def effective_topology(self) -> str:
        """The topology this spec runs on: the explicit `topology` field, or
        derived from `n_islands` when left as None/'auto'."""
        if self.topology is not None:
            return self.topology
        return "island_ring" if self.n_islands > 1 else "single"

    @property
    def uses_paper_pipeline(self) -> bool:
        return (self.selection, self.crossover,
                self.mutation) == OPS.PAPER_PIPELINE

    def ga_config(self) -> G.GAConfig:
        return G.GAConfig(n=self.n, c=self.bits_per_var, v=self.v,
                          mutation_rate=self.mutation_rate,
                          minimize=self.minimize,
                          steps_per_draw=self.steps_per_draw,
                          seed=self.seed, mode=self.mode,
                          sel_lane=self.resolved_sel_lane)

    def problem_def(self) -> Optional[F.ProblemDef]:
        return F.PROBLEMS[self.problem] if self.problem is not None else None

    def program(self) -> F.FitnessProgram:
        """The spec's fitness compiled for every executor (LUT ROMs when
        mode='lut', the shared XLA/in-kernel arith stage always).

        Shared process-wide by `program_key()`: every spec whose fitness
        compiles identically — specs that differ in seed, generations,
        repeats, N, islands or operators, as a stream of solve jobs does —
        gets the SAME FitnessProgram from `compile_cache.PROGRAM_CACHE`, so
        its bound `.stage` method hashes/compares equal across specs and the
        downstream trace caches (kernels.ga_step `_ffm_jaxpr`) trace the FFM
        stage once per problem shape, not once per job.  Safe because a
        program is a pure function of the key's fields (`compile_program`
        reads nothing else) and is immutable.  A per-instance memo sits in
        front, so repeat calls on one spec skip the cache's lock."""
        cached = self.__dict__.get("_program")
        if cached is None:
            cached = CC.PROGRAM_CACHE.get_or_build(
                self.program_key(),
                lambda: F.compile_program(
                    problem=self.problem, fitness=self.fitness,
                    bounds=self.bounds, n_vars=self.v,
                    bits_per_var=self.bits_per_var, mode=self.mode,
                    minimize=self.minimize))
            object.__setattr__(self, "_program", cached)
        return cached

    def program_key(self) -> tuple:
        """Hashable identity of the compiled fitness: exactly the fields
        `compile_program` reads.  A registered problem is keyed by its
        `ProblemDef` (a frozen value that carries the name, so a name
        re-registered with another definition does not alias the old
        program); a blackbox by callable identity — safe for the reason
        `compile_key` gives: the cached program holds the callable alive,
        so its id cannot be recycled while the entry exists."""
        fit_id = (self.problem_def() if self.problem is not None
                  else ("blackbox", id(self.fitness), self.bounds))
        return (fit_id, self.v, self.bits_per_var, self.mode, self.minimize)

    def compile_key(self) -> tuple:
        """Hashable trace-shape identity: two specs with equal keys lower to
        identical traced computations — only `seed` (consumed exclusively by
        `init_state`), `generations` and `n_repeats` (loop/stack extents the
        runners re-trace by shape anyway) may differ.  This is the key the
        compiled-runner cache (repro.ga.compile_cache) and the serving
        scheduler's job packing both use.

        Blackbox fitnesses are keyed by callable identity — safe because a
        cache entry's runner closes over the fitness (keeping it alive), so
        an id can never be recycled while its entry exists."""
        fit_id = (self.problem if self.problem is not None
                  else ("blackbox", id(self.fitness), self.bounds))
        return (fit_id, self.v, self.n, self.bits_per_var, self.mode,
                self.resolved_sel_lane,
                self.selection, self.crossover, self.mutation,
                self.mutation_rate, self.minimize, self.steps_per_draw,
                self.n_islands, self.migrate_every, self.gens_per_epoch,
                self.effective_topology, self.migration, self.mesh_axes,
                self.jit_fitness)

    def fitness_fn(self) -> G.FitnessFn:
        return self.program().fitness(self.mode)

    def fitness_scale(self) -> float:
        """Raw-fitness units per real unit (lut mode is fixed-point)."""
        return self.program().scale(self.mode)

    def var_domains(self) -> Tuple[Tuple[float, float], ...]:
        """Per-variable decode range."""
        if self.bounds is not None:
            return self.bounds
        return (self.problem_def().domain,) * self.v

    def decode(self, x: np.ndarray) -> np.ndarray:
        """Decode a uint32[V] chromosome to real variable values; a
        bitstring genome to its 0/1 assignment (uint8[V * c]) in variable
        order, variable j being bit j % c of word j // c."""
        pdef = self.problem_def()
        if pdef is not None and pdef.genome == "bits":
            c = self.bits_per_var
            words = np.asarray(x, np.uint32)[:, None]
            return ((words >> np.arange(c, dtype=np.uint32)) & 1).astype(
                np.uint8).reshape(-1)
        u = np.asarray(x, np.uint64) & np.uint64((1 << self.bits_per_var) - 1)
        doms = self.var_domains()
        lo = np.array([d[0] for d in doms])
        hi = np.array([d[1] for d in doms])
        return lo + u.astype(np.float64) * (hi - lo) / \
            ((1 << self.bits_per_var) - 1)


def paper_spec(problem: str = "F3", n: int = 32, m: int = 20,
               mode: str = "lut", **kw) -> GASpec:
    """The paper's experiment grid as a spec: chromosome m = 2c bits."""
    return GASpec(problem=problem, n=n, bits_per_var=m // 2, n_vars=2,
                  mode=mode, **kw)
