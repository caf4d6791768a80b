"""Typed run telemetry, and the named host phases of a served job.

How a segment or engine result executed is a versioned dataclass,
`RunTelemetry`, of three facets:

  * `plan: PlanInfo` — the epoch-plan decision (mode, provenance, fallback
    reason, launch fold shape, streamed tile size, VMEM estimate);
  * `topology: TopologyInfo` — how the run was laid out (executor ×
    topology names, island/shard counts, launch and migration counters);
  * `per_repeat: ReplicaStats | None` — per-replica best/trajectory arrays
    when the run stacked `n_repeats` replicas.

`version` is bumped whenever a field changes meaning so persisted
telemetry (e.g. scheduler job streams) stays interpretable.  An engine
result's `phase_s` holds the host seconds of its run's named phases.

`phase` names where a job's host time goes, served or solved: one context
manager that opens a profiler span and adds the seconds it took to a
per-phase counter dict (`PHASES` fixes the span names and the counter each
feeds).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, Iterator, Optional

TELEMETRY_VERSION = 1


@dataclasses.dataclass
class PlanInfo:
    """The epoch-plan decision a segment ran under.

    mode: "gridded" | "resident" | "resident-sharded" | "resident-free" |
    "streamed" | "-" (no plan: reference/eager executors).  A single
    population on the fused executor always runs "gridded".
    source: "heuristic" | "measured" | "forced" | "-".  fallback carries
    the VMEM-estimator reason when the resident shape was rejected (set for
    both the gridded fallback AND the streamed lane, which exists because
    of that rejection).  tile_islands is the streamed mode's island tile.
    lane is the selection lane the fused kernels ran ("onehot" | "gather" |
    "-" for executors without one).  gens_per_s is the measured rate that
    justified a "measured" choice.  ffm_const_bytes is the bytes of array
    constants hoisted into the fused kernels, which each launch reads."""

    mode: str = "-"
    source: str = "-"
    fallback: Optional[str] = None
    epochs_per_launch: int = 1
    gens_per_launch: int = 1
    tile_islands: Optional[int] = None
    lane: str = "-"
    vmem_estimate_bytes: Optional[int] = None
    gens_per_s: Optional[float] = None
    ffm_const_bytes: Optional[int] = None

    @classmethod
    def from_plan(cls, plan: Dict[str, Any]) -> "PlanInfo":
        """Build from an `IslandRingTopology._epoch_plan` dict."""
        return cls(mode=plan.get("mode", "-"),
                   source=plan.get("plan_source", "heuristic"),
                   fallback=plan.get("fallback"),
                   epochs_per_launch=int(plan.get("epochs_per_launch", 1)),
                   gens_per_launch=int(plan.get("gens_per_launch", 1)),
                   tile_islands=plan.get("tile_islands"),
                   lane=plan.get("lane", "-"),
                   vmem_estimate_bytes=plan.get("vmem_estimate_bytes"),
                   gens_per_s=plan.get("plan_gens_per_s"),
                   ffm_const_bytes=plan.get("ffm_const_bytes"))


@dataclasses.dataclass
class TopologyInfo:
    """How the run was laid out and what it counted."""

    executor: str = "-"
    topology: str = "-"
    n_islands: int = 1
    n_shards: int = 1
    sharded: bool = False
    launches: int = 0
    migrations: int = 0
    # generations represented by ONE trajectory sample (resident/streamed
    # launches fold many generations per sample)
    telemetry_unit_gens: int = 1


@dataclasses.dataclass
class ReplicaStats:
    """Per-replica results of an `n_repeats`-stacked run (numpy arrays:
    best [R], best_x [R, V], traj_best/traj_mean [R, samples])."""

    best: Any = None
    best_x: Any = None
    traj_best: Any = None
    traj_mean: Any = None


@dataclasses.dataclass
class RunTelemetry:
    """Versioned telemetry for one segment / one engine result."""

    version: int = TELEMETRY_VERSION
    plan: PlanInfo = dataclasses.field(default_factory=PlanInfo)
    topology: TopologyInfo = dataclasses.field(default_factory=TopologyInfo)
    per_repeat: Optional[ReplicaStats] = None
    problem: Optional[str] = None
    n_vars: Optional[int] = None
    resumed_from: Optional[int] = None   # ckpt step (gens) this segment
                                         # resumed from, first chunk only
    # host seconds of an `Engine.run`'s named phases, keyed by counter
    # (`PHASES`): build, seed, launch, wait, readback
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def job_view(self) -> "RunTelemetry":
        """Plan/topology facets without the per-repeat arrays — what a
        packed job's telemetry carries after its slots are sliced out."""
        return dataclasses.replace(self, per_repeat=None)


# span name -> the per-job counter (`GAJobStats.phase_s`) it adds to
PHASES = {
    "ga.sched.submit": "submit",
    "ga.sched.build": "build",
    "ga.engine.build": "build",
    "ga.problem.build": "build",
    "ga.problem.instance": "build",
    "ga.sched.finish": "finish",
    "ga.sched.park": "park",
    "ga.journal.append": "journal",
    "ga.engine.seed": "seed",
    "ga.chunk.launch": "launch",
    "ga.chunk.wait": "wait",
    "ga.chunk.readback": "readback",
    "ga.ckpt.save": "ckpt_save",
}

_nested = threading.local()


@contextlib.contextmanager
def phase(name: str, into: Optional[Dict[str, float]] = None,
          **args) -> Iterator[None]:
    """Time one step of a run as the span `name` and, with `into`, add its
    seconds to `into[PHASES[name]]`.

    The span is a `jax.profiler.TraceAnnotation`: it lands on the host
    plane of a profiler trace, on the device trace's clock, with `args` as
    its arguments, and records nothing while no profiler session is
    active.  The counter takes the phase's own time, less that of the
    phases nested inside it on the same thread, so the counters of one job
    add up without counting a second twice."""
    from jax.profiler import TraceAnnotation   # lazy: callers stay jax-free

    stack = _nested.__dict__.setdefault("stack", [])
    stack.append(0.0)
    t0 = time.perf_counter()
    try:
        with TraceAnnotation(name, **args):
            yield
    finally:
        dt = time.perf_counter() - t0
        inner = stack.pop()
        if stack:
            stack[-1] += dt
        if into is not None:
            key = PHASES[name]
            into[key] = into.get(key, 0.0) + dt - inner
