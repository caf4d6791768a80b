"""The Engine: one entry point for every GA execution strategy.

    from repro import ga

    spec = ga.GASpec(problem="F3", n=64, bits_per_var=10, generations=100)
    result = ga.solve(spec)                      # auto-picks a backend
    result = ga.solve(spec, backend="fused")     # or pin one explicitly

Backends are (topology × executor) compositions (see repro.ga.backends).
Backend selection (`backend="auto"`) walks the capability matrix: eager when
the fitness is not traceable, an island_ring topology when the spec asks for
one (preferring the fused×island_ring composition on TPU when the kernel's
constraints hold), fused on TPU, reference otherwise.  Pinning an
unsupported backend warns and falls back gracefully instead of crashing.

Streaming + checkpointing:

    eng = ga.Engine(spec)
    for tele in eng.run_chunked(chunk_generations=25, ckpt_dir="/tmp/ga"):
        print(tele["gens_done"], tele["best_fitness"])

Each chunk persists the full backend-native GAState through
`repro.ckpt.checkpoint`, so a killed run resumes from the last chunk
(`resume=True`, the default).  `Engine.run`, `Engine.run_chunked` and
`PackedEngine.run_chunked` drive one chunk loop (`_Run._chunks`): `run` is
one chunk with no checkpoint, and an Engine is a pack of one job.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Iterator, Optional

import jax
import numpy as np

from repro import faults as FLT
from repro.ckpt import checkpoint as CKPT
from repro.core import fitness as F
from repro.ga import telemetry as RT
from repro.ga.backends import BACKENDS, Backend, Segment
from repro.ga.options import EngineOptions, resolve_options
from repro.ga.spec import GASpec


class BackendUnsupported(ValueError):
    """Raised when no backend can run a spec."""


def capability_matrix(spec: GASpec, mesh=None) -> Dict[str, Optional[str]]:
    """Backend name -> None (supported) or the reason it cannot run."""
    return {name: cls.supports(spec, mesh)
            for name, cls in BACKENDS.items()}


def _auto_order(spec: GASpec):
    if not spec.jit_fitness:
        return ["eager"]
    order = []
    tpu = jax.default_backend() == "tpu"
    if spec.effective_topology == "island_ring":
        if tpu:
            order.append("fused-islands")   # kernel speed × parallel pops
        order.append("islands")
    if tpu:
        order.append("fused")   # the fast path where the MXU gathers pay off
    order += ["reference", "islands", "eager"]
    return order


def resolve_backend(spec: GASpec, backend: str = "auto",
                    mesh=None) -> str:
    """Pick the backend name for a spec, with graceful fallback."""
    caps = capability_matrix(spec, mesh)
    if backend != "auto":
        if backend not in BACKENDS:
            raise BackendUnsupported(
                f"unknown backend {backend!r}; registered: {sorted(BACKENDS)}")
        reason = caps[backend]
        if reason is None:
            return backend
        fallback = next((n for n in _auto_order(spec) if caps[n] is None),
                        None)
        if fallback is None:
            raise BackendUnsupported(
                f"backend {backend!r} cannot run this spec ({reason}) and "
                f"no fallback applies: {caps}")
        warnings.warn(f"backend {backend!r} cannot run this spec ({reason}); "
                      f"falling back to {fallback!r}", stacklevel=3)
        return fallback
    for name in _auto_order(spec):
        if caps[name] is None:
            return name
    raise BackendUnsupported(f"no backend supports this spec: {caps}")


@dataclasses.dataclass
class EngineResult:
    """Uniform result across backends (fitness in real units — lut-mode
    fixed-point scaling is already divided out).  How the run executed is
    in `telemetry` (ga.RunTelemetry: .plan / .topology / .per_repeat)."""

    spec: GASpec
    backend: str
    best_fitness: float
    best_x: np.ndarray            # uint32[V] chromosome
    best_params: np.ndarray       # float64[V] decoded variables, or a
                                  # bitstring's 0/1 assignment (uint8[V*c])
    traj_best: np.ndarray
    traj_mean: np.ndarray
    generations: int
    wall_s: float
    telemetry: RT.RunTelemetry = dataclasses.field(
        default_factory=RT.RunTelemetry)


class _SoloBests:
    """The bests of one job on the unstacked layout (an `Engine`, or a pack
    of a single one-repeat job): the segment's best, replaced across chunks
    only by a strictly better one.  Checkpointed as `best_y` / `best_x`."""

    def __init__(self, spec: GASpec):
        self.specs = [spec]
        self.y: Optional[float] = None
        self.x = None

    def update(self, seg: Segment) -> None:
        mini = self.specs[0].minimize
        if self.y is None or (seg.best_y < self.y if mini
                              else seg.best_y > self.y):
            self.y, self.x = seg.best_y, np.asarray(seg.best_x)
        self.chunk_y, self.traj = seg.best_y, np.asarray(seg.traj_best)

    def job(self, j: int):
        """Job j's best and chromosome, and its best and trajectory in the
        last segment (none when a resumed run had already finished)."""
        return self.y, self.x, self.chunk_y, self.traj

    def extra(self) -> Dict[str, Any]:
        return {"best_y": float(self.y), "best_x": [int(v) for v in self.x]}

    def load(self, extra: Dict[str, Any], where: str) -> None:
        self.y = float(extra["best_y"])
        self.x = np.asarray(extra["best_x"], np.uint32)
        self.chunk_y, self.traj = self.y, np.empty((0,))

    def fill(self, y: np.ndarray, x: np.ndarray) -> None:
        """Take the bests of the one slot sliced out of a pack."""
        self.y, self.x = float(y[0]), x[0]


class _SlotBests:
    """The bests of a pack on the stacked layout: one per slot, from the
    segment's per-replica stats, each replaced only by a strictly better
    one; a job's best is the best of its slots.  Checkpointed as `slot_y` /
    `slot_x` with the slot `seeds`, which a resume must match."""

    def __init__(self, pe: "PackedEngine"):
        self.specs, self.slots, self.seeds = pe.specs, pe.slots, pe.seeds
        self.mini = pe.batch_spec.minimize
        self.y = np.full((pe.n_slots,), np.inf if self.mini else -np.inf,
                         np.float32)
        self.x = np.zeros((pe.n_slots, pe.batch_spec.v), np.uint32)

    def update(self, seg: Segment) -> None:
        rep = seg.telemetry.per_repeat
        by = np.asarray(rep.best, np.float32).reshape(self.y.shape)
        bx = np.asarray(rep.best_x, np.uint32).reshape(self.x.shape)
        better = by < self.y if self.mini else by > self.y
        self.y = np.where(better, by, self.y)
        self.x = np.where(better[:, None], bx, self.x)
        self.chunk_y = by
        self.traj = np.asarray(rep.traj_best,
                               np.float32).reshape(len(by), -1)

    def job(self, j: int):
        off, cnt = self.slots[j]
        yj = self.y[off:off + cnt]
        r = off + (int(np.argmin(yj)) if self.mini else int(np.argmax(yj)))
        reduce = np.min if self.mini else np.max
        return (float(self.y[r]), self.x[r],
                float(reduce(self.chunk_y[off:off + cnt])),
                reduce(self.traj[off:off + cnt], axis=0))

    def extra(self) -> Dict[str, Any]:
        return {"slot_y": [float(v) for v in self.y],
                "slot_x": [[int(v) for v in row] for row in self.x],
                "seeds": [int(s) for s in self.seeds]}

    def load(self, extra: Dict[str, Any], where: str) -> None:
        ck_seeds = [int(s) for s in extra.get("seeds", [])]
        if ck_seeds and ck_seeds != [int(s) for s in self.seeds]:
            raise ValueError(
                f"checkpoint in {where} holds a pack with slot seeds "
                f"{ck_seeds}, not {list(self.seeds)} — a pack must resume "
                "with the same jobs in the same order")
        self.y = np.asarray(extra["slot_y"], np.float32)
        self.x = np.asarray(extra["slot_x"], np.uint32).reshape(self.x.shape)
        self.chunk_y, self.traj = self.y, self.y[:, None]

    def fill(self, y: np.ndarray, x: np.ndarray) -> None:
        """Take the bests of the slots sliced out of a pack."""
        self.y, self.x = y, x


class _Run:
    """The backend over the replica slots of one or more jobs, and the one
    chunk loop that `Engine` and `PackedEngine` both drive: seeding or
    restoring the state, each chunk's launch, wait, readback and
    checkpoint, and the spans and fault sites around them.  How the bests
    are kept is the subclass's `_bests()`."""

    def _build(self, spec: GASpec, backend: str) -> None:
        """Resolve and construct the backend for `spec`; the seconds this
        takes are handed to the first run."""
        self._build_s: Dict[str, float] = {}
        with RT.phase("ga.engine.build", self._build_s):
            with RT.phase("ga.problem.build", self._build_s):
                build_instance = F.INSTANCES.get(spec.problem)
                if build_instance is not None:
                    with RT.phase("ga.problem.instance", self._build_s,
                                  problem=spec.problem):
                        build_instance()
                spec.program()
            self.backend_name = resolve_backend(spec, backend,
                                                self.options.mesh)
            # resolved ONCE and shared with every checkpoint write:
            # fault-rule occurrence counters live on the injector instance
            self.faults = FLT.resolve_faults(self.options.faults)
            self.backend: Backend = BACKENDS[self.backend_name](
                spec, options=self.options)

    def _take_build_phases(self) -> Dict[str, float]:
        """The construction's phases, once: the first run after it counts
        them, so a job's phases add up to its host time."""
        phases, self._build_s = self._build_s, {}
        return phases

    def _restore(self, ckpt_dir: str, step: int, state, bests):
        """Load checkpoint `step` into `state` and `bests`; returns the state
        and the run's counters (gens done, chunk index, migrations)."""
        state, extra = CKPT.restore(ckpt_dir, step, state)
        ck_backend = extra.get("backend")
        if ck_backend is not None and ck_backend != self.backend_name:
            raise ValueError(
                f"checkpoint in {ckpt_dir} was written by the "
                f"{ck_backend!r} backend; resuming it with "
                f"{self.backend_name!r} would load a mismatched state "
                "layout — rerun with the original backend or a fresh "
                "ckpt_dir")
        bests.load(extra, ckpt_dir)
        return (state, int(extra["gens_done"]),
                int(extra.get("chunk_idx", 0)),
                int(extra.get("migrations", 0)))

    def _extra(self, done: int, chunk_idx: int, migrations: int,
               bests) -> Dict[str, Any]:
        return {"gens_done": done, "chunk_idx": chunk_idx,
                "migrations": migrations, **bests.extra(),
                "backend": self.backend_name}

    def _chunks(self, total: int, chunk_generations: Optional[int], *,
                ckpt_dir: Optional[str] = None, resume: bool = True,
                fault_tag: str = "", state=None):
        """Run `total` generations chunk by chunk.  Yields `(pack, jobs,
        seg)` per chunk: the pack-level telemetry, one telemetry dict per
        job (the pack's fields and the job's bests) and the chunk's
        Segment.  A resumed run that had already finished yields its stored
        result once, with `seg` None."""
        # the default chunk never undercuts gens_per_epoch: a chunk smaller
        # than one resident launch would cap the interval folding the spec
        # asked for (an explicit chunk_generations is honored as given)
        chunk = chunk_generations or max(1, total // 10,
                                         self.backend.spec.gens_per_epoch)
        span = _span_args(fault_tag)
        phases = self._take_build_phases()
        bests = self._bests()
        done = chunk_idx = migrations = 0
        resumed_from: Optional[int] = None
        if state is None:
            with RT.phase("ga.engine.seed", phases, **span):
                state = self.init_state()
                step = (CKPT.latest_step(ckpt_dir) if ckpt_dir and resume
                        else None)
                if step is not None:
                    resumed_from = int(step)
                    state, done, chunk_idx, migrations = self._restore(
                        ckpt_dir, step, state, bests)

        def pack_tele(gens, dt, rate):
            return {"chunk": chunk_idx, "resumed_from": resumed_from,
                    "gens_done": done, "gens_total": total,
                    "chunk_gens": gens, "wall_s": dt, "gens_per_s": rate,
                    "backend": self.backend_name, "phases": phases}

        if done >= total:
            # resumed a finished run: surface the stored result instead of
            # yielding nothing
            pack = dict(pack_tele(0, 0.0, 0.0), already_complete=True)
            yield pack, self._jobs(bests, pack, None, migrations), None
            return

        while done < total:
            tag = f"{fault_tag}|{self.backend_name}|chunk={chunk_idx + 1}"
            with RT.phase("ga.chunk", chunk=chunk_idx + 1, **span):
                if self.faults is not None:
                    self.faults.inject("slow_chunk", tag)
                with RT.phase("ga.chunk.launch", phases, **span):
                    seg = self.backend.segment(state, min(chunk, total - done))
                with RT.phase("ga.chunk.wait", phases, **span):
                    jax.block_until_ready(jax.tree.leaves(seg.state))
                dt = phases["launch"] + phases["wait"]
                if self.faults is not None:
                    # crash AFTER the compute, BEFORE the checkpoint: the
                    # chunk's work is lost, earlier checkpoints are not, and
                    # a retry recomputes it deterministically
                    self.faults.inject("chunk_crash", tag)
                with RT.phase("ga.chunk.readback", phases, **span):
                    state = seg.state
                    done += seg.gens
                    chunk_idx += 1
                    migrations += seg.telemetry.topology.migrations
                    if resumed_from is not None:
                        seg.telemetry.resumed_from = resumed_from
                    bests.update(seg)
                    pack = pack_tele(seg.gens, dt, seg.gens / dt if dt > 0
                                     else float("inf"))
                    jobs = self._jobs(bests, pack, seg, migrations)
                if ckpt_dir:
                    with RT.phase("ga.ckpt.save", phases, **span):
                        CKPT.save(ckpt_dir, step=done, tree=state,
                                  extra=self._extra(done, chunk_idx,
                                                    migrations, bests),
                                  faults=self.faults, fault_tag=fault_tag)
            yield pack, jobs, seg
            phases = {}
            resumed_from = None    # only the first post-resume chunk carries it

    @staticmethod
    def _jobs(bests, pack, seg: Optional[Segment], migrations: int):
        """One telemetry dict per job: the pack's fields and its bests."""
        rt = seg.telemetry if seg is not None else None
        jobs = []
        for j, spec in enumerate(bests.specs):
            scale = spec.fitness_scale()
            y, x, chunk_y, traj = bests.job(j)
            jobs.append({
                **pack,
                "chunk_best": chunk_y / scale,
                "best_fitness": y / scale,
                "best_params": spec.decode(x),
                "traj_best": traj / scale,
                "problem": spec.problem or "blackbox",
                "n_vars": spec.v,
                "migrations": migrations,
                "telemetry_unit_gens": (rt.topology.telemetry_unit_gens
                                        if rt is not None else 1),
                "telemetry": rt,
            })
        return jobs


class Engine(_Run):
    """A spec bound to a backend, with cached compiled runners: a pack of
    one job on the unstacked layout.

    Execution knobs ride in one frozen `ga.EngineOptions` (`options=`);
    the legacy `mesh= / interpret= / cost_table= / plan_override=` kwargs
    still work and build one internally.  cost_table / plan_override steer
    the measured epoch planner (see `Backend` and `repro.autotune`): the
    default cost_table=None discovers the ambient per-host table, False
    pins the pure heuristic, and plan_override forces one epoch mode by
    name.  None of these change results — plans differ only in launch
    shape."""

    def __init__(self, spec: GASpec, backend: str = "auto", *,
                 options: Optional[EngineOptions] = None,
                 mesh=None, interpret: Optional[bool] = None,
                 cost_table=None, plan_override=None):
        self.spec = spec
        self.options = resolve_options(options, mesh=mesh,
                                       interpret=interpret,
                                       cost_table=cost_table,
                                       plan_override=plan_override)
        self._build(spec, backend)

    def init_state(self):
        return self.backend.init()

    def _bests(self):
        return _SoloBests(self.spec)

    def run(self, generations: Optional[int] = None,
            state=None) -> EngineResult:
        """Run `generations` (default the spec's) as one chunk with no
        checkpoint.  The result's `telemetry.phase_s` holds the host seconds
        of the run's spans, under the names `run_chunked` uses, and of the
        engine's construction on its first run."""
        gens = generations or self.spec.generations
        [(_, [tele], seg)] = self._chunks(gens, gens, state=state)
        rt = seg.telemetry
        if rt.problem is None:
            rt.problem, rt.n_vars = tele["problem"], tele["n_vars"]
        rt.phase_s = tele["phases"]
        return EngineResult(
            spec=self.spec, backend=self.backend_name,
            best_fitness=tele["best_fitness"],
            best_x=np.asarray(seg.best_x, np.uint32),
            best_params=tele["best_params"], traj_best=tele["traj_best"],
            traj_mean=np.asarray(seg.traj_mean) / self.spec.fitness_scale(),
            generations=seg.gens,
            wall_s=rt.phase_s.get("seed", 0.0) + tele["wall_s"],
            telemetry=rt)

    def run_chunked(self, *, chunk_generations: Optional[int] = None,
                    generations: Optional[int] = None,
                    ckpt_dir: Optional[str] = None,
                    resume: bool = True,
                    fault_tag: str = "") -> Iterator[Dict[str, Any]]:
        """Stream the run chunk by chunk, yielding per-chunk telemetry.

        With `ckpt_dir`, each chunk checkpoints the backend-native state; a
        restarted run with the same spec/ckpt_dir resumes at the last chunk
        (the newest VALID one — a corrupt step falls back to its
        predecessor; the first chunk after a resume carries
        ``"resumed_from"``).  `fault_tag` rides into every `repro.faults`
        injection-site tag (the scheduler passes its job ids) so armed
        fault rules can target one run, and labels the run's spans.

        Each chunk's dict carries ``"phases"``: the host seconds of its
        `RT.phase` spans, keyed by counter (`launch`, `wait`, `readback`,
        `ckpt_save`, and `seed` on the first chunk, with `build` when the
        engine has not run before).  ``"wall_s"`` is launch plus wait.

        Telemetry granularity follows the backend's LAUNCH unit: island
        topologies sample trajectories once per launch, and a resident-epoch
        launch covers several migration intervals — UP TO
        `telemetry_unit_gens` generations per `traj_best` entry (a
        segment's final launch folds only the remaining intervals);
        `migrations` counts every ring migration including the ones folded
        inside resident launches.
        """
        for _, [tele], _ in self._chunks(
                generations or self.spec.generations, chunk_generations,
                ckpt_dir=ckpt_dir, resume=resume, fault_tag=fault_tag):
            yield tele


def _span_args(fault_tag: str) -> Dict[str, str]:
    """The job ids a run's spans carry: the scheduler's comma-joined
    `fault_tag`, space-separated (a span argument cannot hold a comma)."""
    return {"jobs": fault_tag.replace(",", " ")} if fault_tag else {}


def solve(spec: GASpec, backend: str = "auto", *,
          generations: Optional[int] = None,
          options: Optional[EngineOptions] = None, mesh=None,
          interpret: Optional[bool] = None, cost_table=None,
          plan_override=None) -> EngineResult:
    """Run a GASpec end to end and return the uniform result."""
    return Engine(spec, backend, options=options, mesh=mesh,
                  interpret=interpret, cost_table=cost_table,
                  plan_override=plan_override).run(generations)


class PackedEngine(_Run):
    """K shape-compatible GASpecs multiplexed through ONE backend run.

    The engine already vmaps `n_repeats` independent replicas down a stack
    axis; packing reuses that axis as a *tenant* axis: job j contributes
    `n_repeats` slots seeded `seed+0..seed+r-1` — exactly the seeds the job
    would use alone — so every slot, and therefore every job's result, is
    bit-identical to running that job solo (the per-replica bit-identity the
    repeat tests already pin down).  Specs must share `compile_key()` and
    `generations`; only seeds and repeat counts may differ.  A single
    one-repeat job has no stack axis to pack: it runs the unstacked layout,
    and keeps and checkpoints its bests as an `Engine` does.

        pe = PackedEngine([spec_a, spec_b, spec_c])
        for tele in pe.run_chunked(ckpt_dir="/tmp/pack"):
            for jt in tele["jobs"]:
                print(jt["job_index"], jt["best_fitness"])

    `run_chunked` is `Engine.run_chunked`'s loop (chunked telemetry +
    checkpoint/resume — the scheduler's preemption primitive) but yields a
    pack-level dict whose `"jobs"` list carries one Engine-style telemetry
    dict per job, unpacked from the segment's per-replica telemetry."""

    def __init__(self, specs, backend: str = "auto", *,
                 options: Optional[EngineOptions] = None,
                 mesh=None, interpret: Optional[bool] = None,
                 cost_table=None, plan_override=None):
        self.options = resolve_options(options, mesh=mesh,
                                       interpret=interpret,
                                       cost_table=cost_table,
                                       plan_override=plan_override)
        specs = list(specs)
        if not specs:
            raise ValueError("PackedEngine needs at least one spec")
        key0, gens0 = specs[0].compile_key(), specs[0].generations
        for s in specs[1:]:
            if s.compile_key() != key0:
                raise BackendUnsupported(
                    "specs are not shape-compatible for packing (their "
                    "compile_key()s differ); submit them separately")
            if s.generations != gens0:
                raise BackendUnsupported(
                    "packed jobs must share generations= (the pack runs the "
                    "stack lock-step); submit unequal-length jobs separately")
        self.specs = specs
        self.slots, self.seeds = [], []
        for s in specs:
            self.slots.append((len(self.seeds), s.n_repeats))
            self.seeds.extend(s.seed + r for r in range(s.n_repeats))
        self.n_slots = len(self.seeds)
        self.batch_spec = (specs[0] if len(specs) == 1 else
                           dataclasses.replace(specs[0],
                                               n_repeats=self.n_slots))
        self._build(self.batch_spec, backend)
        if self.backend_name == "eager":
            raise BackendUnsupported(
                "the eager backend steps replicas in a host loop — nothing "
                "to pack; run eager jobs singly")

    def init_state(self):
        if self.n_slots == 1:
            return self.backend.init()
        return self.backend.init_packed(list(self.seeds))

    def _bests(self):
        if self.n_slots == 1:
            return _SoloBests(self.specs[0])
        return _SlotBests(self)

    def run_chunked(self, *, chunk_generations: Optional[int] = None,
                    ckpt_dir: Optional[str] = None,
                    resume: bool = True,
                    fault_tag: str = "") -> Iterator[Dict[str, Any]]:
        """Chunked pack run: yields {"chunk", "gens_done", ..., "jobs": [...]}
        with one Engine-style telemetry dict per job.  With `ckpt_dir`, every
        chunk checkpoints the whole packed state + per-slot bests, so an
        abandoned run (preemption) resumes bit-identically — the checkpoint
        records the slot seeds and refuses a mismatched pack composition."""
        for pack, jobs, _ in self._chunks(
                self.batch_spec.generations, chunk_generations,
                ckpt_dir=ckpt_dir, resume=resume, fault_tag=fault_tag):
            for j, jt in enumerate(jobs):
                rt = jt["telemetry"]
                jt.update(job_index=j, pack_size=len(self.specs),
                          slots=self.slots[j],
                          telemetry=rt.job_view() if rt is not None else None)
            yield {**pack, "pack_size": len(self.specs), "jobs": jobs}

    def run(self, *, chunk_generations: Optional[int] = None):
        """Run the pack to completion; returns the final per-job telemetry
        list (one Engine-style dict per job)."""
        last = None
        for last in self.run_chunked(chunk_generations=chunk_generations):
            pass
        return last["jobs"]


def repack_checkpoint(old_dir: str, specs, keep, new_dir: str,
                      backend: str = "auto", *,
                      options: Optional[EngineOptions] = None) -> Optional[int]:
    """Slice a pack checkpoint down to the jobs in `keep` (indices into
    `specs`) and write it to `new_dir`, so survivors of a quarantined pack
    resume bit-identically from where the pack left off.

    Packed state leaves carry the slot stack down their leading axis (the
    replica axis `init_packed` builds); slicing that axis at the kept jobs'
    slot offsets yields exactly the state those slots would hold had they
    run alone from the same seeds — the packing bit-identity invariant run
    in reverse.  Leaves whose shape does not change between pack sizes
    (island ring buffers etc.) pass through; anything that matches neither
    pattern is a layout change and raises.  Returns the checkpointed step
    (generations done), or None when `old_dir` holds no valid step."""
    specs = list(specs)
    keep = list(keep)
    pe_old = PackedEngine(specs, backend, options=options)
    step = CKPT.latest_step(old_dir)
    if step is None:
        return None
    old = pe_old._bests()
    state, done, chunk_idx, migrations = pe_old._restore(
        old_dir, step, pe_old.init_state(), old)

    pe_new = PackedEngine([specs[j] for j in keep], backend, options=options)
    idx = []
    for j in keep:
        off, cnt = pe_old.slots[j]
        idx.extend(range(off, off + cnt))
    idx_arr = np.asarray(idx)

    def _slice(new_like, old_leaf):
        old_arr = np.asarray(jax.device_get(old_leaf))
        want = tuple(np.shape(new_like))
        if old_arr.shape == want:
            return old_arr
        if old_arr.ndim and old_arr.shape[0] == pe_old.n_slots:
            sl = old_arr[idx_arr]
            if sl.shape == want:
                return sl
            if len(idx) == 1 and sl.shape[1:] == want:
                return sl[0]        # 1-slot target runs the solo (lead=0) layout
        raise ValueError(
            f"cannot repack state leaf of shape {old_arr.shape} into "
            f"{want}: neither shape-stable nor sliceable down the "
            f"{pe_old.n_slots}-slot axis")

    new_state = jax.tree.map(_slice, pe_new.init_state(), state)
    new = pe_new._bests()
    new.fill(old.y[idx_arr], old.x[idx_arr])
    # recovery machinery is not an injection site: faults=False keeps an
    # ambient ckpt_corrupt rule from eating the repacked checkpoint
    CKPT.save(new_dir, step=done, tree=new_state,
              extra=pe_new._extra(done, chunk_idx, migrations, new),
              faults=False)
    return done
