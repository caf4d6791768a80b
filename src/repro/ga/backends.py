"""Engine backends — orthogonal **topology × executor** compositions.

The paper's headline result comes from running many full GA pipelines side
by side, not one fast pipeline — so "fast step" and "parallel populations"
must compose.  The engine therefore splits every backend into two
orthogonal pieces:

An **executor** advances a stack of populations a block of generations:

  reference  pure-JAX `lax.scan` over the operator pipeline
             (repro.core.ga.run_scan); any registered operators.
  fused      the Pallas `ga_step` kernel — one launch per
             `spec.gens_per_epoch` generations (default 1), the stack rides
             the kernel grid axis; paper pipeline, arith FFM (ANY traceable
             problem: the spec's FitnessProgram.stage is traced into the
             kernel as its FFM stage, so n-variable registry problems and
             blackboxes run fused), power-of-two N <= 1024.  Bit-identical
             to `reference` (state and best; the trajectory coarsens to one
             sample per launch when gens_per_epoch > 1).

A **topology** owns population layout, the epoch loop and migration:

  single       one population (or `n_repeats` vmapped replicas), no
               migration; a segment is one executor block.
  island_ring  `n_islands` populations; every `migrate_every` generations
               the best individual of each island ring-shifts to the next
               (`repro.core.islands.migrate_ring`, `lax.ppermute` on a
               mesh), replacing the recipient's worst.  By default
               migration runs *between* executor blocks — i.e. between
               Pallas kernel launches on the fused executor — so any
               executor composes; with the fused executor, ring migration
               and `gens_per_epoch >= migrate_every` the epoch planner
               instead folds the migration INTO the VMEM-resident launch
               (see IslandRingTopology's docstring — resident /
               resident-sharded / gridded modes, all bit-identical).
               `n_repeats` replicas are vmapped OUTSIDE the island axis.
               Given a mesh, the island axis is `shard_map`ped over the
               mesh axes (`spec.mesh_axes`, default all) with EITHER
               executor — one kernel launch per shard on fused — and the
               ring crosses shards via a boundary-elite `ppermute`
               (`islands.migrate_ring_sharded`), bit-identical to the
               single-device run; replicas vmap inside each shard.

The registry exposes the compositions under the familiar names:

  reference     = reference × single
  fused         = fused     × single
  islands       = reference × island_ring  (shard_mapped when mesh given)
  fused-islands = fused     × island_ring  (ring migration between
                                            launches, or in-VMEM on the
                                            resident epoch plan;
                                            shard_mapped when mesh given)
  eager         = python-loop driver for non-traceable fitness (no
                  composition — fitness cannot be traced into a block)

Each backend implements `supports(spec)` (capability check → reason string
or None), `init(spec)` (backend-native state pytree) and `segment(state,
gens)` (advance `gens` generations, returning the new state + telemetry).
The Engine composes segments into full runs, chunked streaming and
checkpoint/resume — so every composition gets those features for free.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.autotune import table as _cost
from repro.core import ga as G
from repro.core import islands as ISL
from repro.ga import compile_cache as CC
from repro.ga import operators as OPS
from repro.ga import telemetry as RT
from repro.ga.options import resolve_options
from repro.ga.spec import GASpec
from repro.kernels import ga_step as _ga_step


@dataclasses.dataclass
class Segment:
    """One contiguous block of generations (raw fitness units).

    traj arrays have one entry per generation, except island_ring topologies
    where the unit is one migration epoch (`migrate_every` generations —
    see telemetry.topology.telemetry_unit_gens).  `telemetry` is the typed
    run telemetry (ga.RunTelemetry).
    """

    state: Any
    best_y: float
    best_x: np.ndarray          # uint32[V]
    traj_best: np.ndarray
    traj_mean: np.ndarray
    gens: int
    telemetry: RT.RunTelemetry = dataclasses.field(
        default_factory=RT.RunTelemetry)


def _arg_best(y: np.ndarray, minimize: bool) -> int:
    return int(np.argmin(y) if minimize else np.argmax(y))


def _stack_states_seeded(cfg: G.GAConfig, seeds):
    """One replica per entry of `seeds`, stacked on a new leading axis.
    Replica i is bit-identical to a solo run seeded `seeds[i]` — the
    contract job packing relies on: a packed slot reproduces the job it
    came from exactly."""
    states = [G.init_state_host(dataclasses.replace(cfg, seed=s))
              for s in seeds]
    return jax.device_put(jax.tree.map(lambda *xs: np.stack(xs), *states))


def _stack_states(cfg: G.GAConfig, n_replicas: int):
    """Replica r is seeded `seed + r` — replica 0 reproduces the solo run
    bit-exactly (asserted in tests), and the splitmix seed hash decorrelates
    consecutive integers."""
    return _stack_states_seeded(cfg, [cfg.seed + r for r in range(n_replicas)])


def _stack_island_replicas_seeded(icfg: ISL.IslandConfig, seeds):
    """[R, I, ...] host stack with one island set per seed (see
    `_stack_states_seeded` for the per-slot bit-identity contract)."""
    reps = []
    for s in seeds:
        ga_r = dataclasses.replace(icfg.ga, seed=s)
        reps.append(ISL.init_islands_host(dataclasses.replace(icfg, ga=ga_r)))
    return jax.tree.map(lambda *xs: np.stack(xs), *reps)


def _stack_island_replicas(icfg: ISL.IslandConfig, n_replicas: int):
    """[R, I, ...] stack: replica r re-seeds the island seed stream with
    `seed + r` (same convention as `_stack_states`, so replica 0 reproduces
    the n_repeats=1 island run bit-exactly)."""
    return _stack_island_replicas_seeded(
        icfg, [icfg.ga.seed + r for r in range(n_replicas)])


class Backend:
    """One execution strategy for a GASpec.

    Execution knobs arrive as one frozen `ga.EngineOptions` (`options=`);
    the legacy `mesh=/interpret=/cost_table=/plan_override=` kwargs still
    work (folded into an EngineOptions via `resolve_options`, which rejects
    mixing the two styles).  cost_table feeds the measured tier of the
    epoch planner (see `repro.autotune.table.resolve_table` for accepted
    values — None discovers the ambient per-host table, False disables
    measurement and pins the pure heuristic).  plan_override forces one
    epoch mode by name ("resident" / "streamed" / "gridded" / ...; the
    autotune runner uses it to measure non-default candidates) and raises
    if the spec cannot feasibly run that mode.  vmem_budget overrides the
    PLANNER's feasibility budget (the kernels still validate against the
    real one) and stream_tile_islands pins the streamed tile.  sel_lane
    overrides the spec's fused-kernel selection lane (the spec is re-built
    with the override, so validation/compile keys stay consistent).
    Options only influence launch shapes, never results — every plan is
    bit-identical in state and best tracking.
    """

    name = "?"

    def __init__(self, spec: GASpec, *, options=None, mesh=None,
                 interpret=None, cost_table=None, plan_override=None):
        self.options = resolve_options(options, mesh=mesh,
                                       interpret=interpret,
                                       cost_table=cost_table,
                                       plan_override=plan_override)
        if (self.options.sel_lane is not None
                and self.options.sel_lane != spec.sel_lane):
            # rebuild the spec so the override flows through validation,
            # ga_config() and compile_key() like a spec-level pin would
            spec = dataclasses.replace(spec, sel_lane=self.options.sel_lane)
        self.spec = spec
        self.cfg = spec.ga_config()
        self.mesh = self.options.mesh
        self.interpret = self.options.interpret
        self.cost_table = _cost.resolve_table(self.options.cost_table)
        self.plan_override = self.options.plan_override
        self._cache: Dict[Any, Any] = {}   # gens -> jitted segment runner

    @staticmethod
    def supports(spec: GASpec, mesh=None) -> Optional[str]:
        """None if the spec can run on this backend, else the reason why not."""
        raise NotImplementedError

    def init(self):
        raise NotImplementedError

    def init_packed(self, seeds):
        """Stacked state with one replica SLOT per seed — the layout job
        packing (repro.ga.engine.PackedEngine) runs many tenants through:
        slot i is bit-identical to a solo run seeded `seeds[i]`.  Backends
        whose replica axis is a host loop (eager) cannot pack."""
        raise NotImplementedError(
            f"backend {self.name!r} does not support packed (multi-job) "
            "state initialization")

    def segment(self, state, gens: int) -> Segment:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Executors — advance a stack of populations one block of generations
# ---------------------------------------------------------------------------


class Executor:
    """Steps a leading-axis stack of populations `gens` generations.

    `block(gens)` returns a traceable function
        states[L, ...] -> (states', best_y[L], best_x[L, V],
                           traj_best[L, T], traj_mean[L, T])
    where best_* track the best individual seen across the block and traj_*
    are population best/mean per trajectory sample (fitness of the
    pre-update population, so both executors' trajectories align
    bit-for-bit).  T is one entry per generation, except the fused executor
    with `gens_per_epoch > 1` where it is one entry per kernel launch
    (best_* still fold every generation via the in-kernel best).
    `final_fitness(states)` evaluates the *current* populations ([L, N]) —
    both executors use the same XLA fitness function here, so migration
    decisions are identical whichever executor produced the states.
    """

    name = "?"
    stacked_only = True    # False -> also offers an unstacked solo path

    def __init__(self, spec: GASpec, *, interpret=None):
        self.spec = spec
        self.cfg = spec.ga_config()
        self.fit = spec.fitness_fn()

    @staticmethod
    def supports(spec: GASpec) -> Optional[str]:
        raise NotImplementedError

    def final_fitness(self, states: G.GAState) -> jax.Array:
        return jax.vmap(self.fit)(states.x)

    def block(self, gens: int):
        raise NotImplementedError


class ReferenceExecutor(Executor):
    name = "reference"
    stacked_only = False

    def __init__(self, spec: GASpec, *, interpret=None):
        super().__init__(spec, interpret=interpret)
        self.gen_fn = OPS.make_generation(spec.selection, spec.crossover,
                                          spec.mutation)

    @staticmethod
    def supports(spec: GASpec) -> Optional[str]:
        if not spec.jit_fitness:
            return "fitness is not traceable (jit_fitness=False); use 'eager'"
        return None

    def solo(self, gens: int):
        """Unstacked single-population runner (GARun) — the layout the
        reference×single backend has always exposed for n_repeats=1."""
        return lambda st: G.run_scan(self.cfg, self.fit, gens, st,
                                     self.gen_fn)

    def block(self, gens: int):
        one = self.solo(gens)

        def run_block(states: G.GAState):
            out: G.GARun = jax.vmap(one)(states)
            return (out.state, out.best_y, out.best_x,
                    out.traj_best, out.traj_mean)

        return run_block


class FusedExecutor(Executor):
    name = "fused"
    stacked_only = True

    def __init__(self, spec: GASpec, *, interpret=None):
        super().__init__(spec, interpret=interpret)
        self.gens_per_epoch = spec.gens_per_epoch
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret

    @staticmethod
    def supports(spec: GASpec) -> Optional[str]:
        if not spec.jit_fitness:
            return "fitness is not traceable (jit_fitness=False); use 'eager'"
        if spec.mode != "arith":
            return ("Pallas kernel requires mode='arith' — LUT gathers stay "
                    "on the XLA path ('reference')")
        if spec.n & (spec.n - 1):
            return f"fused kernel requires power-of-two N (got {spec.n})"
        if (spec.resolved_sel_lane == "onehot"
                and spec.n > G.ONEHOT_MAX_N):
            # only reachable through a lane pin that bypassed GASpec
            # validation; sel_lane="auto" resolves to gather past the cap
            return (f"N={spec.n} > {G.ONEHOT_MAX_N} on the 'onehot' "
                    "selection lane: the (N, N) one-hot tournament matrices "
                    "must fit VMEM; use islands/reference or "
                    "sel_lane='gather'")
        if not spec.uses_paper_pipeline:
            return ("fused kernel hardwires the paper pipeline "
                    "(tournament/single_point/xor); other operators run on "
                    "'reference'")
        # size-gate hoisted FFM closure constants: the kernel replicates
        # them into VMEM on every grid step, so a fitness capturing a large
        # array (e.g. a dataset) must stream on the reference path instead
        # of silently blowing the VMEM budget
        try:
            const_bytes = _ga_step.ffm_const_bytes(spec.program().stage,
                                                   spec.ga_config())
        except Exception as e:                   # pragma: no cover — defensive
            return f"FFM stage failed to trace for the kernel ({e!r})"
        limit = _ga_step.ffm_const_limit()
        if const_bytes > limit:
            return (f"FFM stage captures {const_bytes} bytes of array "
                    f"constants (> the {limit}-byte VMEM gate): hoisted "
                    "consts replicate into VMEM per grid step — run "
                    "'reference' (REPRO_FFM_CONST_LIMIT overrides)")
        return None

    def block(self, gens: int):
        # the FFM stage traced into the kernel is the SAME function the
        # reference executor evaluates (Executor.__init__ sets self.fit =
        # spec.fitness_fn() = FitnessProgram.stage in arith mode), so any
        # registered n-variable problem or traceable blackbox runs fused and
        # stays bit-identical to reference by construction.
        cfg, ffm, interp = self.cfg, self.fit, self.interpret
        mini = self.spec.minimize
        # generations folded inside one launch: the in-kernel best fold
        # (track_best) keeps best_y/best_x bit-identical to gens_per_epoch=1;
        # trajectories coarsen to one sample per launch.
        gpe = max(1, min(self.gens_per_epoch, gens))
        n_full, rem = divmod(gens, gpe)

        def launch(g):
            def body(carry, _):
                x, sel, cross, mut, by, bx = carry
                x2, sel2, cross2, mut2, y, lby, lbx = \
                    _ga_step.ga_generation_kernel(
                        x, sel, cross, mut, cfg=cfg, ffm=ffm,
                        interpret=interp, gens=g, track_best=True)
                # lby/lbx fold the best over all g in-kernel generations
                # with the reference tie rule; the trajectory samples both
                # come from y — the launch's LAST pre-update population —
                # so traj_best and traj_mean describe the same window.
                better = lby < by if mini else lby > by
                by2 = jnp.where(better, lby, by)
                bx2 = jnp.where(better[:, None], lbx, bx)
                carry = (x2, sel2, cross2, mut2, by2, bx2)
                gen_best = (jnp.min(y, axis=1) if mini
                            else jnp.max(y, axis=1))
                return carry, (gen_best, jnp.mean(y, axis=1))
            return body

        def run_block(states: G.GAState):
            L = states.x.shape[0]
            neutral = jnp.full((L,), jnp.inf if mini else -jnp.inf,
                               jnp.float32)
            carry = (states.x, states.sel_lfsr, states.cross_lfsr,
                     states.mut_lfsr, neutral,
                     jnp.zeros((L, cfg.v), jnp.uint32))
            tbs, tms = [], []
            if n_full:
                carry, (tb, tm) = jax.lax.scan(launch(gpe), carry, None,
                                               length=n_full)
                tbs.append(tb)
                tms.append(tm)
            if rem:
                carry, (tb1, tm1) = launch(rem)(carry, None)
                tbs.append(tb1[None])
                tms.append(tm1[None])
            x, sel, cross, mut, by, bx = carry
            tb = jnp.concatenate(tbs, axis=0)    # [launches, L]
            tm = jnp.concatenate(tms, axis=0)
            state = G.GAState(x, sel, cross, mut, states.k + gens)
            return state, by, bx, tb.T, tm.T     # traj -> [L, launches]

        return run_block


EXECUTORS: Dict[str, type] = {
    ReferenceExecutor.name: ReferenceExecutor,
    FusedExecutor.name: FusedExecutor,
}


# ---------------------------------------------------------------------------
# Topologies — population layout, epoch loop, migration
# ---------------------------------------------------------------------------


def _mesh_axes(spec: GASpec, mesh) -> tuple:
    """Mesh axes the island axis shards over: `spec.mesh_axes` or all axes
    of the given mesh (IslandConfig's default names when there is no mesh)."""
    if spec.mesh_axes is not None:
        return tuple(spec.mesh_axes)
    if mesh is not None:
        return tuple(mesh.axis_names)
    return ("data", "model")


class Topology:
    name = "?"

    def __init__(self, spec: GASpec, executor: Executor, *, mesh=None,
                 cost_table=None, plan_override=None, vmem_budget=None,
                 stream_tile_islands=None):
        self.spec = spec
        self.cfg = spec.ga_config()
        self.executor = executor
        self.mesh = mesh
        # already-resolved CostTable (or None) + forced mode + planner
        # VMEM-budget override + pinned streamed tile; only the island_ring
        # planner consults them — single has one launch shape
        self.cost_table = cost_table
        self.plan_override = plan_override
        self.vmem_budget = vmem_budget
        self.stream_tile_islands = stream_tile_islands
        self._cache: Dict[Any, Any] = {}   # instance memo over RUNNER_CACHE

    def _cached_runner(self, key, builder):
        """Instance memo in front of the process-global RUNNER_CACHE, so the
        global hit/miss counters record one resolution per topology instance
        (i.e. per Engine build) instead of one per segment launch."""
        fn = self._cache.get(key)
        if fn is None:
            fn = CC.RUNNER_CACHE.get_or_build(key, builder)
            self._cache[key] = fn
        return fn

    @staticmethod
    def supports(spec: GASpec, mesh, executor_cls) -> Optional[str]:
        raise NotImplementedError

    def init(self):
        raise NotImplementedError

    def segment(self, state, gens: int) -> Segment:
        raise NotImplementedError


class SingleTopology(Topology):
    """One population; `n_repeats` independent replicas ride the executor's
    stack axis.  A segment is exactly one executor block."""

    name = "single"

    @staticmethod
    def supports(spec: GASpec, mesh, executor_cls) -> Optional[str]:
        if spec.effective_topology != "single":
            return ("n_islands > 1; use an island_ring backend "
                    "('islands' / 'fused-islands')")
        if mesh is not None:
            return ("single topology would silently ignore the mesh; "
                    "shard over devices with an island_ring backend "
                    "(n_islands > 1)")
        return None

    def init(self):
        if self.spec.n_repeats == 1 and not self.executor.stacked_only:
            return G.init_state(self.cfg)
        return _stack_states(self.cfg, self.spec.n_repeats)

    def init_packed(self, seeds):
        if len(seeds) != self.spec.n_repeats:
            raise ValueError(f"{len(seeds)} seeds packed into a spec with "
                             f"n_repeats={self.spec.n_repeats}")
        return _stack_states_seeded(self.cfg, seeds)

    def _runner(self, gens: int, solo: bool):
        key = CC.runner_key(self.spec, self.name, self.executor.name,
                            getattr(self.executor, "interpret", None),
                            self.mesh, "block", gens, solo)
        return self._cached_runner(
            key, lambda: jax.jit(self.executor.solo(gens) if solo
                                 else self.executor.block(gens)))

    def segment(self, state, gens: int) -> Segment:
        mini = self.spec.minimize
        solo = self.spec.n_repeats == 1 and not self.executor.stacked_only
        if solo:
            out: G.GARun = self._runner(gens, True)(state)
            return Segment(state=out.state, best_y=float(out.best_y),
                           best_x=np.asarray(out.best_x),
                           traj_best=np.asarray(out.traj_best),
                           traj_mean=np.asarray(out.traj_mean), gens=gens)
        state, by, bx, tb, tm = self._runner(gens, False)(state)
        per_rep = np.asarray(by)                               # [R]
        r = _arg_best(per_rep, mini)
        tb = np.asarray(tb)                                    # [R, gens]
        reduce = np.min if mini else np.max
        tele = RT.RunTelemetry(per_repeat=RT.ReplicaStats(
            best=per_rep, best_x=np.asarray(bx), traj_best=tb,
            traj_mean=np.asarray(tm)))
        if self.executor.name == "fused":
            # the one launch shape a single population has: the gridded
            # generation kernel, on the spec's selection lane
            tele.plan = RT.PlanInfo(
                mode="gridded", lane=self.cfg.sel_lane,
                gens_per_launch=max(1, min(self.spec.gens_per_epoch, gens)))
        return Segment(state=state, best_y=float(per_rep[r]),
                       best_x=np.asarray(bx)[r],
                       traj_best=reduce(tb, axis=0),
                       traj_mean=np.asarray(tm).mean(axis=0),
                       gens=gens, telemetry=tele)


class IslandRingTopology(Topology):
    """`n_islands` populations with ring migration every `migrate_every`
    generations.  The epoch is [executor block → final fitness → ring
    migration] in one jit; `n_repeats` replicas are stacked OUTSIDE the
    island axis ([R, I, ...]) and flattened to the executor's single stack
    axis, so every executor (including the Pallas kernel, whose grid is that
    axis) composes.

    With a mesh, the SAME epoch is `shard_map`ped: the island axis is
    sharded over the mesh axes (`spec.mesh_axes`, default all), each shard
    runs its executor block — one Pallas kernel launch per shard on the
    fused executor — and migration becomes `islands.migrate_ring_sharded`
    (boundary-elite `lax.ppermute` between launches), which is bit-identical
    to the single-device `jnp.roll` ring.  Replicas vmap inside each shard,
    so `n_repeats > 1` and `migration='none'` compose with the mesh too.

    Epoch planning is TWO-TIER (see `kernels.ga_step`'s module docstring).
    Tier 1, feasibility: `epoch_candidates` asks
    `ga_step.epoch_mode_candidates` which launch shapes this spec can run,
    gated by the VMEM byte estimator:

      resident          (fused, ring, no mesh)  one launch folds
                        gens_per_epoch // migrate_every whole migration
                        intervals, full in-VMEM ring (`ring_migrate_stack`).
      resident-sharded  (fused, ring, mesh)  one launch per interval; the
                        intra-shard migrations run in VMEM and only the
                        boundary elite crosses shards via `ppermute`
                        between launches.
      resident-free     (fused, migration="none", no mesh)  no ring to run,
                        so ONE launch folds the whole gens_per_epoch (any
                        value — the whole-multiple rule is ring-only).
      streamed          (fused, resident does NOT fit)  the HBM-streaming
                        lane: `ga_streamed_epoch_kernel` tiles the island
                        axis through VMEM (`plan["tile_islands"]` islands
                        per grid step, double-buffered by the Pallas grid
                        pipeline) and the ring splice runs in XLA between
                        kernel passes inside one jitted scan over
                        gens_per_epoch // migrate_every intervals — on a
                        mesh the boundary elite `ppermute`s inside that
                        same scan, so k > 1 intervals fold per launch
                        (unlike resident-sharded).
      gridded           always feasible — the per-grid-step kernel with
                        migration between launches (the last-resort
                        fallback when not even one double-buffered streamed
                        tile fits; the estimator's reason rides in
                        plan["fallback"] either way).

    Tier 2, selection: candidates[0] is the heuristic (resident when it
    fits, else streamed with ring migration, else gridded — for
    migration="none" gridded stays the default and resident-free/streamed
    are measured choices).  When a measured cost table covers the
    spec — including the heuristic's own mode, so "measured beats
    heuristic" is provable rather than assumed — the planner instead picks
    the candidate with the best measured gens/s (`plan_source: "measured"`,
    expected rate in plan["plan_gens_per_s"]).  No table, a stale table or
    uncovered points leave the heuristic choice untouched
    (`plan_source: "heuristic"`), bit-identical to the pre-measurement
    planner.  A `plan_override` mode skips tier 2 entirely
    (`plan_source: "forced"`).

    Every plan is bit-identical in state and best tracking; resident modes
    coarsen the trajectory to one sample per launch."""

    name = "island_ring"

    def __init__(self, spec: GASpec, executor: Executor, *, mesh=None,
                 cost_table=None, plan_override=None, vmem_budget=None,
                 stream_tile_islands=None):
        super().__init__(spec, executor, mesh=mesh, cost_table=cost_table,
                         plan_override=plan_override,
                         vmem_budget=vmem_budget,
                         stream_tile_islands=stream_tile_islands)
        axis_names = _mesh_axes(spec, mesh)
        self.n_shards = (int(np.prod([mesh.shape[a] for a in axis_names]))
                         if mesh is not None else 1)
        self.icfg = ISL.IslandConfig(ga=self.cfg,
                                     n_islands=spec.n_islands,
                                     migrate_every=spec.migrate_every,
                                     axis_names=axis_names)
        self.i_local = max(1, spec.n_islands // max(1, self.n_shards))
        self.plan = self._epoch_plan()
        # the measured tier can move an "auto" spec to the OTHER selection
        # lane (cross-lane argmax); rebuild the configs every runner closes
        # over so the kernels actually run the chosen lane
        lane = self.plan.get("lane", self.cfg.sel_lane)
        if lane != self.cfg.sel_lane:
            self.cfg = dataclasses.replace(self.cfg, sel_lane=lane)
            self.icfg = dataclasses.replace(self.icfg, ga=self.cfg)
            self.executor.cfg = self.cfg

    def epoch_candidates(self) -> list:
        """Tier-1 feasible plan candidates, heuristic first (the autotune
        runner measures exactly this list, so table points and planner
        queries can never drift apart).  All candidates carry the spec's
        own resolved selection lane — the other lane's candidates are a
        separate, measured-only grid (`_lane_candidates`)."""
        return self._lane_candidates(self.cfg.sel_lane)

    def _lane_candidates(self, lane: str) -> list:
        """Feasible candidates with the selection lane forced to `lane`
        (the measured tier's (mode × lane) grid for sel_lane='auto')."""
        spec = self.spec
        cfg = (self.cfg if lane == self.cfg.sel_lane
               else dataclasses.replace(self.cfg, sel_lane=lane))
        const_vmem = (_ga_step.ffm_const_vmem_bytes(self.executor.fit, cfg)
                      if self.executor.name == "fused" else 0)
        return _ga_step.epoch_mode_candidates(
            cfg, self.i_local, const_vmem,
            executor=self.executor.name, migration=spec.migration,
            gens_per_epoch=spec.gens_per_epoch,
            migrate_every=spec.migrate_every,
            sharded=self.mesh is not None, budget=self.vmem_budget)

    def _plan_point(self, cand: Dict[str, Any]) -> Dict[str, Any]:
        return CC.plan_point(self.spec, executor=self.executor.name,
                             mode=cand["mode"], n_shards=self.n_shards,
                             lane=cand.get("lane"))

    def _epoch_plan(self) -> Dict[str, Any]:
        """Two-tier plan decision (see class docstring)."""
        cands = self.epoch_candidates()
        if self.plan_override is not None:
            want = (self.plan_override.get("mode")
                    if isinstance(self.plan_override, dict)
                    else self.plan_override)
            for c in cands:
                if c["mode"] == want:
                    plan = dict(c, plan_source="forced")
                    break
            else:
                hint = (" — streamed is only offered when the resident "
                        "stack exceeds the VMEM budget (this spec fits "
                        "resident; lower vmem_budget to force streaming)"
                        if want == "streamed" else "")
                raise ValueError(
                    f"plan_override mode {want!r} is not feasible for this "
                    f"spec (candidates: {[c['mode'] for c in cands]})"
                    + hint)
        else:
            plan = dict(cands[0], plan_source="heuristic")
            table = self.cost_table
            if table is not None:
                rated = [(c, table.lookup(self._plan_point(c),
                                          c["gens_per_launch"]))
                         for c in cands]
                # sel_lane="auto": the OTHER lane's feasible shapes join the
                # argmax as measured-only candidates — the heuristic never
                # switches lane on its own, measurement does
                if self.spec.sel_lane == "auto":
                    twin = ("gather" if self.cfg.sel_lane == "onehot"
                            else "onehot")
                    if (twin != "onehot"
                            or self.spec.n <= G.ONEHOT_MAX_N):
                        rated += [(c, table.lookup(self._plan_point(c),
                                                   c["gens_per_launch"]))
                                  for c in self._lane_candidates(twin)]
                # refine only when the heuristic's own point is measured:
                # the argmax is then provably >= the heuristic's measured
                # rate, and an uncovered spec stays bit-identical heuristic
                if len(rated) > 1 and rated[0][1] is not None:
                    best_c, best_v = rated[0]
                    for c, v in rated[1:]:
                        if v is not None and v > best_v:
                            best_c, best_v = c, v
                    plan = dict(best_c, plan_source="measured",
                                plan_gens_per_s=round(best_v, 3))
        # VMEM accounting below must price the lane the plan actually runs
        # (a measured cross-lane pick differs from self.cfg until __init__
        # re-resolves it)
        plan_cfg = self.cfg
        if plan.get("lane", plan_cfg.sel_lane) != plan_cfg.sel_lane:
            plan_cfg = dataclasses.replace(plan_cfg, sel_lane=plan["lane"])
        if plan["mode"] == "streamed":
            const_vmem = _ga_step.ffm_const_vmem_bytes(self.executor.fit,
                                                       plan_cfg)
            if self.stream_tile_islands is not None:
                t = int(self.stream_tile_islands)
                budget = (self.vmem_budget if self.vmem_budget is not None
                          else _ga_step.resident_vmem_budget())
                need = 2 * _ga_step.resident_vmem_bytes(plan_cfg, t,
                                                        const_vmem)
                if self.i_local % t or need > budget:
                    raise ValueError(
                        f"stream_tile_islands={t} is not a feasible tile: "
                        f"it must divide the local island count "
                        f"{self.i_local} and fit double-buffered "
                        f"(~{need} B vs budget {budget} B)")
                plan["tile_islands"] = t
            # the double-buffered working set of one tile — what actually
            # occupies VMEM while the grid pipeline streams the stack
            plan["vmem_estimate_bytes"] = 2 * _ga_step.resident_vmem_bytes(
                plan_cfg, plan["tile_islands"], const_vmem)
        elif plan["mode"].startswith("resident"):
            const_vmem = _ga_step.ffm_const_vmem_bytes(self.executor.fit,
                                                       plan_cfg)
            plan["vmem_estimate_bytes"] = _ga_step.resident_vmem_bytes(
                plan_cfg, self.i_local, const_vmem)
        elif self.executor.name == "fused":
            # gridded fused launches hold ONE island per program instance —
            # report its lane-aware working set so benches can show the
            # selection lane's VMEM drop, not just gens/s
            const_vmem = _ga_step.ffm_const_vmem_bytes(self.executor.fit,
                                                       plan_cfg)
            plan["vmem_estimate_bytes"] = _ga_step.resident_vmem_bytes(
                plan_cfg, 1, const_vmem)
        if self.executor.name == "fused":
            plan["ffm_const_bytes"] = _ga_step.ffm_const_bytes(
                self.executor.fit, plan_cfg)
        return plan

    @staticmethod
    def supports(spec: GASpec, mesh, executor_cls) -> Optional[str]:
        if spec.topology == "single":
            return "spec pins topology='single'; use a single backend"
        if mesh is not None:
            axes = _mesh_axes(spec, mesh)
            missing = [a for a in axes if a not in mesh.shape]
            if missing:
                return (f"mesh_axes {missing} not in the mesh "
                        f"(axes: {tuple(mesh.axis_names)})")
            n_shards = int(np.prod([mesh.shape[a] for a in axes]))
            if spec.n_islands % n_shards:
                return (f"n_islands={spec.n_islands} must divide evenly over "
                        f"the {n_shards} mesh shard(s)")
        return None

    def _place(self, states, lead: int):
        """Put a fresh host state stack on the device, its island axis
        sharded over the mesh."""
        if self.mesh is None:
            return jax.device_put(states)
        from jax.sharding import NamedSharding, PartitionSpec as P
        axes = self.icfg.axis_names
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(
                self.mesh, P(*([None] * lead), axes,
                             *([None] * (x.ndim - 1 - lead))))), states)

    def init(self):
        if self.spec.n_repeats > 1:
            states = _stack_island_replicas(self.icfg, self.spec.n_repeats)
            lead = 1
        else:
            states = ISL.init_islands_host(self.icfg)
            lead = 0
        return self._place(states, lead)

    def init_packed(self, seeds):
        if len(seeds) != self.spec.n_repeats:
            raise ValueError(f"{len(seeds)} seeds packed into a spec with "
                             f"n_repeats={self.spec.n_repeats}")
        lead = 1 if self.spec.n_repeats > 1 else 0
        if lead == 0:
            ga_s = dataclasses.replace(self.icfg.ga, seed=seeds[0])
            states = ISL.init_islands_host(
                dataclasses.replace(self.icfg, ga=ga_s))
        else:
            states = _stack_island_replicas_seeded(self.icfg, seeds)
        return self._place(states, lead)

    def _runner_key(self, *parts):
        # self.cfg.sel_lane rides along explicitly: a measured plan can move
        # an "auto" spec to the other lane without changing compile_key()
        return CC.runner_key(self.spec, self.name, self.executor.name,
                             getattr(self.executor, "interpret", None),
                             self.mesh, self.cfg.sel_lane, *parts)

    def _resident_runner(self, k: int):
        """Jitted resident launch (no mesh): ONE `ga_epoch_kernel` call
        folding k whole migration intervals (k*migrate_every generations,
        ring migration in VMEM).  Returns the same (state', by, bx, tb, tm,
        bg) contract as `_epoch`, with one trajectory sample per launch."""
        key = self._runner_key("resident", k)
        E = self.icfg.migrate_every
        R = self.spec.n_repeats
        mini = self.spec.minimize
        cfg, ffm = self.cfg, self.executor.fit
        interp = self.executor.interpret
        g4 = (lambda a: a) if R > 1 else (lambda a: a[None])
        sq = (lambda a: a) if R > 1 else (lambda a: a[0])

        def launch(states):                    # states: [R?, I, ...]
            x, sel, cross, mut, y, by, bx, bg = _ga_step.ga_epoch_kernel(
                g4(states.x), g4(states.sel_lfsr), g4(states.cross_lfsr),
                g4(states.mut_lfsr), cfg=cfg, ffm=ffm, migrate_every=E,
                intervals=k, interpret=interp)
            state = G.GAState(sq(x), sq(sel), sq(cross), sq(mut),
                              states.k + k * E)
            tb = jnp.min(y, axis=-1) if mini else jnp.max(y, axis=-1)
            return (state, sq(by), sq(bx), sq(tb)[..., None],
                    sq(jnp.mean(y, axis=-1))[..., None], sq(bg))

        return self._cached_runner(key, lambda: jax.jit(launch))

    def _resident_free_runner(self, g: int):
        """Jitted migration-free resident launch (`migration="none"`, no
        mesh): ONE `ga_epoch_kernel(migrate=False)` call folding g
        generations — no ring, so g is unconstrained by `migrate_every`.
        Same (state', by, bx, tb, tm, bg) contract as `_resident_runner`."""
        key = self._runner_key("resident-free", g)
        R = self.spec.n_repeats
        mini = self.spec.minimize
        cfg, ffm = self.cfg, self.executor.fit
        interp = self.executor.interpret
        g4 = (lambda a: a) if R > 1 else (lambda a: a[None])
        sq = (lambda a: a) if R > 1 else (lambda a: a[0])

        def launch(states):                    # states: [R?, I, ...]
            x, sel, cross, mut, y, by, bx, bg = _ga_step.ga_epoch_kernel(
                g4(states.x), g4(states.sel_lfsr), g4(states.cross_lfsr),
                g4(states.mut_lfsr), cfg=cfg, ffm=ffm, migrate_every=g,
                intervals=1, migrate=False, interpret=interp)
            state = G.GAState(sq(x), sq(sel), sq(cross), sq(mut),
                              states.k + g)
            tb = jnp.min(y, axis=-1) if mini else jnp.max(y, axis=-1)
            return (state, sq(by), sq(bx), sq(tb)[..., None],
                    sq(jnp.mean(y, axis=-1))[..., None], sq(bg))

        return self._cached_runner(key, lambda: jax.jit(launch))

    def _streamed_runner(self, k: int):
        """Jitted HBM-streaming launch: k migration intervals, each ONE
        `ga_streamed_epoch_kernel` pass tiling the island stack through
        VMEM (`plan["tile_islands"]` islands per grid step; the Pallas grid
        pipeline double-buffers the tile loads), with the ring splice
        running in XLA between passes — all inside one jitted `lax.scan`.
        The kernel emits PRE-splice elites + worst slots and the scan body
        applies the same shift-by-one/`splice_at` rule set as
        `ring_migrate_stack`, so state stays bit-identical to the resident
        and gridded plans.  On a mesh the launch is shard_mapped and the
        boundary elite crosses shards via the `ppermute` ring INSIDE the
        scan body — which is why, unlike resident-sharded, k > 1 intervals
        fold per launch.  Same (state', by, bx, tb, tm, bg) contract as
        `_resident_runner` (one trajectory sample per launch)."""
        tile = self.plan["tile_islands"]
        key = self._runner_key("streamed", k, tile)
        E = self.icfg.migrate_every
        R = self.spec.n_repeats
        mini = self.spec.minimize
        migrate = self.spec.migration == "ring"
        cfg, ffm = self.cfg, self.executor.fit
        interp = self.executor.interpret
        mesh, axes = self.mesh, self.icfg.axis_names
        g4 = (lambda a: a) if R > 1 else (lambda a: a[None])
        sq = (lambda a: a) if R > 1 else (lambda a: a[0])

        def launch(states):                    # states: [R?, I(_loc), ...]
            x0 = g4(states.x)
            n_groups, i_loc = x0.shape[0], x0.shape[1]
            init = (x0, g4(states.sel_lfsr), g4(states.cross_lfsr),
                    g4(states.mut_lfsr),
                    jnp.full((n_groups, i_loc),
                             jnp.inf if mini else -jnp.inf, jnp.float32),
                    jnp.zeros((n_groups, i_loc, cfg.v), jnp.uint32),
                    jnp.zeros((n_groups, i_loc), jnp.int32))

            def interval(carry, j):
                x, sel, cross, mut, by, bx, bg = carry
                outs = _ga_step.ga_streamed_epoch_kernel(
                    x, sel, cross, mut, cfg=cfg, ffm=ffm, migrate_every=E,
                    tile_islands=tile, migrate=migrate, interpret=interp)
                lbg = outs[-1]
                if migrate:
                    x, sel, cross, mut, ymig, lby, lbx, elite, widx = \
                        outs[:-1]
                    if mesh is None:
                        # island 0 receives island I-1's elite — the same
                        # roll `ring_migrate_stack` writes as a concat
                        incoming = jnp.concatenate(
                            [elite[:, -1:], elite[:, :-1]], axis=1)
                    else:
                        # one global ring: the last LOCAL island's elite
                        # crosses to the next shard, whose island 0 takes it
                        recv = ISL.ring_shift_sharded(elite[:, -1], mesh,
                                                      axes)
                        incoming = jnp.concatenate(
                            [recv[:, None], elite[:, :-1]], axis=1)
                    x = jax.vmap(ISL.splice_at)(x, widx, incoming)
                else:
                    x, sel, cross, mut, ymig, lby, lbx = outs[:-1]
                # fold the interval's in-kernel best into the launch best
                # (strict improvement: earlier intervals win ties, matching
                # the resident kernel's sequential per-generation fold)
                better = lby < by if mini else lby > by
                by = jnp.where(better, lby, by)
                bx = jnp.where(better[..., None], lbx, bx)
                bg = jnp.where(better, j * E + lbg, bg)
                return (x, sel, cross, mut, by, bx, bg), ymig

            carry, ys = jax.lax.scan(interval, init, jnp.arange(k))
            x, sel, cross, mut, by, bx, bg = carry
            ymig = ys[-1]                      # final interval, pre-splice
            state = G.GAState(sq(x), sq(sel), sq(cross), sq(mut),
                              states.k + k * E)
            tb = jnp.min(ymig, axis=-1) if mini else jnp.max(ymig, axis=-1)
            return (state, sq(by), sq(bx), sq(tb)[..., None],
                    sq(jnp.mean(ymig, axis=-1))[..., None], sq(bg))

        fn = launch
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from repro.sharding import shard_map
            lead = () if R == 1 else (None,)

            def pfor(extra):
                return P(*lead, axes, *([None] * extra))

            state_specs = G.GAState(x=pfor(2), sel_lfsr=pfor(2),
                                    cross_lfsr=pfor(2), mut_lfsr=pfor(2),
                                    k=pfor(0))
            fn = shard_map(
                launch, mesh, in_specs=(state_specs,),
                out_specs=(state_specs, pfor(0), pfor(1), pfor(1), pfor(1),
                           pfor(0)))

        return self._cached_runner(key, lambda: jax.jit(fn))

    def _resident_sharded_epoch(self):
        """Shard-local epoch body for the resident-sharded plan: one
        `ga_epoch_kernel(boundary=True)` launch runs `migrate_every`
        generations + the INTRA-shard migrations in VMEM, then the boundary
        elite crosses to the next shard via the `ppermute` ring and lands in
        the first island's (in-kernel decided) worst slot.  Globally
        bit-identical to `migrate_ring_sharded` — same elite/worst rules,
        same logical-coordinate ring."""
        E = self.icfg.migrate_every
        R = self.spec.n_repeats
        cfg, ffm = self.cfg, self.executor.fit
        interp = self.executor.interpret
        mesh, axes = self.mesh, self.icfg.axis_names
        mini = self.spec.minimize
        g4 = (lambda a: a) if R > 1 else (lambda a: a[None])
        sq = (lambda a: a) if R > 1 else (lambda a: a[0])

        def epoch(states):                     # states: [R?, I_loc, ...]
            x, sel, cross, mut, y, by, bx, send, w0, bg = \
                _ga_step.ga_epoch_kernel(
                    g4(states.x), g4(states.sel_lfsr),
                    g4(states.cross_lfsr), g4(states.mut_lfsr), cfg=cfg,
                    ffm=ffm, migrate_every=E, intervals=1, boundary=True,
                    interpret=interp)
            # send: [G, V] boundary elites (one ring per replica group);
            # ppermute moves the whole block to the next shard at once, and
            # the received elite lands in island 0's in-kernel-decided worst
            # slot through the same splice rule set as every other splice
            recv = ISL.ring_shift_sharded(send, mesh, axes)
            x = x.at[:, 0].set(ISL.splice_at(x[:, 0], w0, recv))
            state = G.GAState(sq(x), sq(sel), sq(cross), sq(mut),
                              states.k + E)
            tb = jnp.min(y, axis=-1) if mini else jnp.max(y, axis=-1)
            return (state, sq(by), sq(bx), sq(tb)[..., None],
                    sq(jnp.mean(y, axis=-1))[..., None], sq(bg))

        return epoch

    def _epoch(self):
        """Jitted epoch over the canonical state layout ([I,...] or
        [R, I, ...]); returns (state', by, bx, tb, tm, bg) with
        by/bx/tb/tm/bg in [R, I, ...] layout (leading R axis only when
        n_repeats > 1); bg is the launch generation each island's best was
        first seen in, for the segment's interval-first tie rule.  On a
        mesh the epoch body is shard_mapped over the island axis — the body
        sees [R?, I/n_shards, ...] blocks and the ring crosses shards via
        `ppermute`; telemetry comes back as the same global arrays."""
        key = self._runner_key("epoch", self.plan["mode"])
        if key in self._cache:
            return self._cache[key]
        E = self.icfg.migrate_every
        R = self.spec.n_repeats
        mini = self.spec.minimize
        migrate = self.spec.migration == "ring"
        mesh, axes = self.mesh, self.icfg.axis_names
        if self.plan["mode"] == "resident-sharded":
            epoch = self._resident_sharded_epoch()
        else:
            blk = self.executor.block(E)
            fit_stack = self.executor.final_fitness

            if mesh is None:
                mig = lambda s, yy: ISL.migrate_ring(s, yy, minimize=mini)
            else:
                mig = lambda s, yy: ISL.migrate_ring_sharded(
                    s, yy, minimize=mini, mesh=mesh, axis_names=axes)

            # a launch is one interval: every island's best shares its
            # epoch, so the best-generation output is all zeros
            def one(states):                   # states: [I(_loc), ...]
                states, by, bx, tb, tm = blk(states)
                if migrate:
                    y = fit_stack(states)      # [I(_loc), N]
                    states, _ex, _ey = mig(states, y)
                return states, by, bx, tb, tm, jnp.zeros(by.shape,
                                                         jnp.int32)

            if R == 1:
                epoch = one
            else:
                def epoch(states):             # states: [R, I(_loc), ...]
                    il = states.x.shape[1]
                    flat = jax.tree.map(
                        lambda a: a.reshape((R * il,) + a.shape[2:]), states)
                    flat, by, bx, tb, tm = blk(flat)
                    states = jax.tree.map(
                        lambda a: a.reshape((R, il) + a.shape[1:]), flat)
                    if migrate:
                        y = jax.vmap(fit_stack)(states)    # [R, I_loc, N]
                        states, _ex, _ey = jax.vmap(mig)(states, y)
                    return (states, by.reshape(R, il),
                            bx.reshape((R, il) + bx.shape[1:]),
                            tb.reshape((R, il) + tb.shape[1:]),
                            tm.reshape((R, il) + tm.shape[1:]),
                            jnp.zeros((R, il), jnp.int32))

        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from repro.sharding import shard_map
            lead = () if R == 1 else (None,)

            def pfor(extra):   # island axis sharded, `extra` trailing dims
                return P(*lead, axes, *([None] * extra))

            state_specs = G.GAState(x=pfor(2), sel_lfsr=pfor(2),
                                    cross_lfsr=pfor(2), mut_lfsr=pfor(2),
                                    k=pfor(0))
            epoch = shard_map(
                epoch, mesh, in_specs=(state_specs,),
                out_specs=(state_specs, pfor(0), pfor(1), pfor(1), pfor(1),
                           pfor(0)))

        return self._cached_runner(key, lambda: jax.jit(epoch))

    def segment(self, state, gens: int) -> Segment:
        E = self.icfg.migrate_every
        epochs = max(1, math.ceil(gens / E))
        mode = self.plan["mode"]
        per_launch = self.plan["epochs_per_launch"]
        R = self.spec.n_repeats
        mini = self.spec.minimize
        reduce = np.min if mini else np.max
        # launch schedule: every plan covers the SAME epochs * E total
        # generations (the rounding contract all modes share), but
        # resident-free paces in raw generations — no ring means no
        # interval boundary to respect — while resident/streamed cover
        # `per_launch` whole migration intervals per launch and the rest
        # one epoch at a time
        if mode == "resident-free":
            g_max = self.plan["gens_per_launch"]
            sched, left = [], epochs * E
            while left:
                g = min(g_max, left)
                sched.append((self._resident_free_runner(g), g))
                left -= g
            unit = g_max
        else:
            sched, left = [], epochs
            while left:
                k = min(per_launch, left)
                if mode == "resident":
                    sched.append((self._resident_runner(k), k * E))
                elif mode == "streamed":
                    sched.append((self._streamed_runner(k), k * E))
                else:
                    sched.append((self._epoch(), E))
                left -= k
            unit = E * per_launch
        # running per-replica best across launches (telemetry arrays get
        # one sample per launch)
        rep_y = np.full((R,), np.inf if mini else -np.inf, np.float32)
        rep_x = np.zeros((R, self.cfg.v), np.uint32)
        tb_ep, tm_ep = [], []          # per-launch, per-replica ([R] each)
        launches, g_start = 0, 0
        for runner, g_launch in sched:
            state, by, bx, tb, tm, bg = runner(state)
            by = np.asarray(by).reshape(R, -1)              # [R, I]
            bx = np.asarray(bx).reshape(R, -1, self.cfg.v)  # [R, I, V]
            # the reference's tie rule across islands: the first migration
            # interval the launch best shows up in, then the first island
            # (a launch folding k intervals must not let island order win)
            m = reduce(by, axis=1, keepdims=True)
            ivl = (g_start + np.asarray(bg).reshape(R, -1)) // E
            i = np.argmin(np.where(by == m, ivl, np.iinfo(np.int32).max),
                          axis=1)
            g_start += g_launch
            ep_y = by[np.arange(R), i]                      # [R]
            ep_x = bx[np.arange(R), i]
            better = ep_y < rep_y if mini else ep_y > rep_y
            rep_y = np.where(better, ep_y, rep_y)
            rep_x = np.where(better[:, None], ep_x, rep_x)
            tb_ep.append(reduce(by, axis=1))                           # [R]
            tm_ep.append(np.asarray(tm).reshape(R, -1).mean(axis=1))   # [R]
            launches += 1
        r = _arg_best(rep_y, mini)
        tb_rep = np.stack(tb_ep, axis=1)                    # [R, launches]
        tm_rep = np.stack(tm_ep, axis=1)
        tele = RT.RunTelemetry(
            plan=RT.PlanInfo.from_plan(self.plan),
            topology=RT.TopologyInfo(
                n_islands=self.icfg.n_islands,
                n_shards=self.n_shards,
                sharded=self.mesh is not None,
                launches=launches,
                migrations=(epochs if self.spec.migration == "ring" else 0),
                telemetry_unit_gens=unit),
            # per-replica views: job packing (PackedEngine) unpacks each
            # tenant's best/trajectory from its slot range here
            per_repeat=RT.ReplicaStats(best=rep_y, best_x=rep_x,
                                       traj_best=tb_rep, traj_mean=tm_rep))
        return Segment(state=state, best_y=float(rep_y[r]),
                       best_x=rep_x[r],
                       traj_best=reduce(tb_rep, axis=0),
                       traj_mean=tm_rep.mean(axis=0),
                       gens=epochs * E, telemetry=tele)


TOPOLOGIES: Dict[str, type] = {
    SingleTopology.name: SingleTopology,
    IslandRingTopology.name: IslandRingTopology,
}


# ---------------------------------------------------------------------------
# Composed backends (the registry entries)
# ---------------------------------------------------------------------------


class ComposedBackend(Backend):
    """A (topology × executor) pair behind the uniform Backend interface."""

    executor_cls: type = None
    topology_cls: type = None

    def __init__(self, spec: GASpec, *, options=None, mesh=None,
                 interpret=None, cost_table=None, plan_override=None):
        super().__init__(spec, options=options, mesh=mesh,
                         interpret=interpret, cost_table=cost_table,
                         plan_override=plan_override)
        opts = self.options
        # self.spec, not the constructor arg: Backend.__init__ may have
        # rebuilt the spec to apply an options-level sel_lane override
        self.executor: Executor = self.executor_cls(
            self.spec, interpret=opts.interpret)
        self.topology: Topology = self.topology_cls(
            self.spec, self.executor, mesh=opts.mesh,
            cost_table=self.cost_table, plan_override=opts.plan_override,
            vmem_budget=opts.vmem_budget,
            stream_tile_islands=opts.stream_tile_islands)

    @classmethod
    def supports(cls, spec: GASpec, mesh=None) -> Optional[str]:
        reason = cls.executor_cls.supports(spec)
        if reason is not None:
            return reason
        return cls.topology_cls.supports(spec, mesh, cls.executor_cls)

    def init(self):
        return self.topology.init()

    def init_packed(self, seeds):
        return self.topology.init_packed(seeds)

    def segment(self, state, gens: int) -> Segment:
        seg = self.topology.segment(state, gens)
        info = seg.telemetry.topology
        if info.executor == "-":
            info.executor = self.executor_cls.name
            info.topology = self.topology_cls.name
        return seg


def _compose(backend_name: str, executor: type, topology: type) -> type:
    cls = type(f"{backend_name.title().replace('-', '')}Backend",
               (ComposedBackend,),
               {"name": backend_name, "executor_cls": executor,
                "topology_cls": topology})
    return cls


ReferenceBackend = _compose("reference", ReferenceExecutor, SingleTopology)
FusedBackend = _compose("fused", FusedExecutor, SingleTopology)
IslandsBackend = _compose("islands", ReferenceExecutor, IslandRingTopology)
FusedIslandsBackend = _compose("fused-islands", FusedExecutor,
                               IslandRingTopology)


# ---------------------------------------------------------------------------
# eager — python generation loop for non-traceable fitness
# ---------------------------------------------------------------------------


def _pooled_fitness(fit, workers: int):
    """Population-parallel host fitness: split the (N, V) batch into
    `workers` contiguous row chunks and evaluate them on a bounded thread
    pool.  Chunks come back in submission order and are concatenated, so
    the result is bitwise identical to the serial batch call — the pool
    only overlaps the (GIL-releasing or I/O-bound) fitness work."""
    from concurrent.futures import ThreadPoolExecutor
    pool = ThreadPoolExecutor(max_workers=workers)

    def pooled(x):
        x = np.asarray(x)
        n = x.shape[0]
        chunk = max(1, -(-n // workers))
        parts = [x[i:i + chunk] for i in range(0, n, chunk)]
        outs = list(pool.map(
            lambda p: np.asarray(fit(p), np.float32), parts))
        return np.concatenate(outs, axis=0)

    return pooled


class EagerBackend(Backend):
    name = "eager"

    def __init__(self, spec, **kw):
        super().__init__(spec, **kw)
        spec = self.spec
        self.fit = spec.fitness_fn()
        if self.options.fitness_workers > 1:
            self.fit = _pooled_fitness(self.fit,
                                       self.options.fitness_workers)
        self.apply_ops = OPS.make_apply_ops(spec.selection, spec.crossover,
                                            spec.mutation)

    @staticmethod
    def supports(spec: GASpec, mesh=None) -> Optional[str]:
        if spec.effective_topology != "single":
            return "eager driver has no migration; use an island_ring backend"
        if mesh is not None:
            return ("eager driver is host-local and would silently ignore "
                    "the mesh; use an island_ring backend (n_islands > 1)")
        return None

    def init(self):
        if self.spec.n_repeats == 1:
            return G.init_state(self.cfg)
        return _stack_states(self.cfg, self.spec.n_repeats)

    def segment(self, state, gens: int) -> Segment:
        R = self.spec.n_repeats
        mini = self.spec.minimize
        if R == 1:
            out = G.run_eager(self.cfg, self.fit, gens, state,
                              apply_ops_fn=self.apply_ops)
            return Segment(state=out.state, best_y=float(out.best_y),
                           best_x=np.asarray(out.best_x),
                           traj_best=np.asarray(out.traj_best),
                           traj_mean=np.asarray(out.traj_mean), gens=gens)
        outs = []
        for r in range(R):
            st_r = jax.tree.map(lambda a: a[r], state)
            cfg_r = dataclasses.replace(self.cfg, seed=self.cfg.seed + r)
            outs.append(G.run_eager(cfg_r, self.fit, gens, st_r,
                                    apply_ops_fn=self.apply_ops))
        state = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[o.state for o in outs])
        per_rep = np.array([float(o.best_y) for o in outs])
        i = _arg_best(per_rep, mini)
        tb = np.stack([np.asarray(o.traj_best) for o in outs])
        reduce = np.min if mini else np.max
        return Segment(state=state, best_y=float(per_rep[i]),
                       best_x=np.asarray(outs[i].best_x),
                       traj_best=reduce(tb, axis=0),
                       traj_mean=np.stack([np.asarray(o.traj_mean)
                                           for o in outs]).mean(axis=0),
                       gens=gens,
                       telemetry=RT.RunTelemetry(
                           per_repeat=RT.ReplicaStats(best=per_rep)))


BACKENDS: Dict[str, type] = {
    ReferenceBackend.name: ReferenceBackend,
    FusedBackend.name: FusedBackend,
    IslandsBackend.name: IslandsBackend,
    FusedIslandsBackend.name: FusedIslandsBackend,
    EagerBackend.name: EagerBackend,
}
