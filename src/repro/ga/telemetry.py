"""Typed run telemetry — the structured successor of the `extras` dict.

Segments and engine results used to report how a run executed through a
stringly-keyed `extras` dict (`epoch_mode`, `plan_source`, `plan_fallback`,
`per_repeat_best`, ... scattered across every consumer).  `RunTelemetry`
replaces that contract with a versioned dataclass of three facets:

  * `plan: PlanInfo` — the epoch-plan decision (mode, provenance, fallback
    reason, launch fold shape, streamed tile size, VMEM estimate);
  * `topology: TopologyInfo` — how the run was laid out (executor ×
    topology names, island/shard counts, launch and migration counters);
  * `per_repeat: ReplicaStats | None` — per-replica best/trajectory arrays
    when the run stacked `n_repeats` replicas.

`Segment.extras` / `EngineResult.extras` remain as DEPRECATED read-only
dict views (`to_extras()`) for one release; every in-repo consumer reads
the typed fields.  `version` is bumped whenever a field changes meaning so
persisted telemetry (e.g. scheduler job streams) stays interpretable.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional

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
    justified a "measured" choice."""

    mode: str = "-"
    source: str = "-"
    fallback: Optional[str] = None
    epochs_per_launch: int = 1
    gens_per_launch: int = 1
    tile_islands: Optional[int] = None
    lane: str = "-"
    vmem_estimate_bytes: Optional[int] = None
    gens_per_s: Optional[float] = None

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
                   gens_per_s=plan.get("plan_gens_per_s"))


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

    def job_view(self) -> "RunTelemetry":
        """Plan/topology facets without the per-repeat arrays — what a
        packed job's telemetry carries after its slots are sliced out."""
        return dataclasses.replace(self, per_repeat=None)

    def to_extras(self) -> Dict[str, Any]:
        """The legacy `extras` dict (exact historical keys).  Deprecated —
        read the typed fields; this view exists for one release."""
        d: Dict[str, Any] = {}
        t, p = self.topology, self.plan
        if t.executor != "-":
            d["executor"] = t.executor
            d["topology"] = t.topology
        if self.problem is not None:
            d["problem"] = self.problem
            d["n_vars"] = self.n_vars
        if p.mode != "-":
            d["telemetry_unit_gens"] = t.telemetry_unit_gens
            d["n_islands"] = t.n_islands
            d["n_shards"] = t.n_shards
            d["epoch_mode"] = p.mode
            d["plan_source"] = p.source
            d["launches"] = t.launches
            d["migrations"] = t.migrations
            if p.tile_islands is not None:
                d["tile_islands"] = p.tile_islands
            if p.lane != "-":
                d["sel_lane"] = p.lane
            if p.fallback is not None:
                d["resident_fallback"] = p.fallback
                d["plan_fallback"] = p.fallback
            if t.sharded:
                d["sharded"] = True
        r = self.per_repeat
        if r is not None:
            if r.best is not None:
                d["per_repeat_best"] = r.best
            if r.best_x is not None:
                d["per_repeat_best_x"] = r.best_x
            if r.traj_best is not None:
                d["per_repeat_traj_best"] = r.traj_best
            if r.traj_mean is not None:
                d["per_repeat_traj_mean"] = r.traj_mean
        return d


def deprecated_extras(telemetry: RunTelemetry, owner: str) -> Dict[str, Any]:
    """The `.extras` property body: warn once per call site, return the
    legacy dict view."""
    warnings.warn(
        f"{owner}.extras is deprecated; read the typed {owner}.telemetry "
        "(ga.RunTelemetry: .plan / .topology / .per_repeat) instead — the "
        "dict view will be removed in the next release",
        DeprecationWarning, stacklevel=3)
    return telemetry.to_extras()
