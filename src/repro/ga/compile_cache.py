"""Process-wide compiled-runner cache for GA engine backends.

Each topology used to keep its jitted segment runners in a per-instance
dict, so two Engines built from identical specs each traced and compiled
their own runners — fine for a library, wasteful for a serving stack where
repeat traffic has the same handful of spec *shapes*.  This module hoists
those dicts into one process-global cache keyed by `GASpec.compile_key()`
(the spec's trace-shape identity: problem, V, N, encoding, operators,
islands, gens_per_epoch, topology, migration — everything except seed /
generations / n_repeats) plus the backend composition and mesh fingerprint.

A hit returns the SAME `jax.jit` callable the first Engine compiled, so
jax's own jit cache short-circuits tracing entirely — the second submission
of an identical spec shape pays neither trace nor compile.  Safe because
`cfg.seed` is consumed only by `init_state` (never inside a traced runner
body), so runners are seed-independent by construction.

A second instance, `PROGRAM_CACHE`, interns the compiled fitness itself:
`GASpec.program()` looks up `GASpec.program_key()` (the problem definition
or blackbox callable identity, V, bits_per_var, mode, minimize — the fields
`compile_program` reads, nothing else), so every spec of one problem shape
shares ONE `FitnessProgram` object.  Its bound `.stage` then hashes equal
across jobs, and the FFM trace cache in `kernels.ga_step` (keyed by that
bound method) traces a problem's stage once, not once per job's fresh spec.
Safe for the same reason as the runner cache: a program is a pure, frozen
function of its key, and the runners a `RUNNER_CACHE` hit hands back
already close over the first job's program.

Counters (`hits` / `misses` / `evictions`) of both are exported through the
serving scheduler's `/metrics` gauges and asserted by tests.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional, Tuple


def mesh_fingerprint(mesh) -> Optional[tuple]:
    """Hashable identity of a mesh: axis names, shape and device ids (two
    meshes over the same devices in the same layout compile identically)."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))


class CompileCache:
    """Thread-safe LRU of built objects (compiled segment runners, fitness
    programs) with hit/miss counters."""

    def __init__(self, max_entries: int = 128):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key: Hashable, builder: Callable[[], Any]) -> Any:
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                return self._entries[key]
        # build outside the lock: builders wrap jax.jit (lazy, cheap) but may
        # trace eagerly in the future; a racing duplicate build is harmless —
        # first writer wins and both callers get a working runner
        fn = builder()
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self.hits += 1
                return self._entries[key]
            self.misses += 1
            self._entries[key] = fn
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.evictions += 1
            return fn

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"entries": len(self._entries), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions}

    def reset(self) -> None:
        """Drop every entry and zero the counters (tests)."""
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0


RUNNER_CACHE = CompileCache()
# sized like the FFM trace cache in kernels.ga_step, whose keys are these
# programs' bound stages
PROGRAM_CACHE = CompileCache(max_entries=64)


def runner_key(spec, topology_name: str, executor_name: str,
               interpret, mesh, *parts: Hashable) -> Tuple:
    """Cache key for one compiled segment runner.

    `spec.n_repeats` rides along because the runner closures branch on the
    R==1 vs stacked layout (not just shapes); `parts` carries runner-local
    knobs (gens, solo flag, resident interval count, ...)."""
    return (spec.compile_key(), spec.n_repeats, topology_name,
            executor_name, interpret, mesh_fingerprint(mesh)) + parts


def stage_fingerprint(spec) -> str:
    """Problem-stage kind for autotune cost-table keying: registry problems
    are identified by name (their decode + arith stage shape is a pure
    function of it), blackboxes collapse to their variable count — two
    different user callables with the same V share timings, which is the
    right granularity for a *launch-shape* cost model."""
    if spec.problem is not None:
        return f"{spec.problem}:v{spec.v}"
    return f"blackbox:v{spec.v}"


def plan_point(spec, *, executor: str, mode: str, n_shards: int,
               lane: Optional[str] = None) -> dict:
    """The autotune cost-table identity of one epoch-plan candidate (the
    fields of `repro.autotune.table.POINT_FIELDS`).  Shares this module's
    shape-identity discipline: everything that changes the compiled launch
    is in the key, seed/generations/n_repeats are not.  `lane` is the
    selection lane the candidate runs on (defaults to the spec's resolved
    lane — pass the candidate's own "lane" when planning across lanes)."""
    i_local = max(1, spec.n_islands // max(1, n_shards))
    return {"executor": executor, "mode": mode, "migration": spec.migration,
            "n": spec.n, "i_local": i_local, "c": spec.bits_per_var,
            "stage": stage_fingerprint(spec), "shards": n_shards,
            "E": spec.migrate_every,
            "lane": spec.resolved_sel_lane if lane is None else lane}
