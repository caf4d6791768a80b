"""`repro.ga` backend matrix: generations/sec per (topology × executor).

Canonical specs run through every registered backend for EACH problem in
the sweep — the paper's F3 (V=2, closed form) and an n-variable registry
problem (rastrigin:4) so the generalized in-kernel FFM stage is always
covered; the derived column is a JSON object (with `problem`/`n_vars`
fields) so downstream tooling can scrape per-backend throughput.
Island-topology rows use 8 islands (total chromosome throughput is
islands × gens/s); on CPU the fused rows run the Pallas kernel in interpret
mode, so their absolute numbers only mean something on TPU — which is why
`scripts/check_bench.py` gates combo-vs-combo RATIOS, not absolutes.
The `fused-islands` rows run with `gens_per_epoch = 2 * migrate_every`,
i.e. the RESIDENT epoch kernel (ring migration folded into the VMEM-resident
launch; the intra-shard part on mesh rows) — their ratio row is the
regression gate for that optimization.  The `+streamed` /
`+streamed-gridded` pair runs an island stack that exceeds a (forced)
VMEM budget through the HBM-streaming lane and through the gridded
fallback respectively; `check_bench.streamed_gate` requires the streamed
row to actually stream and to be no slower than its gridded twin.  The
`+onehot` / `+gather` pair pins the fused tournament's selection lane on
an N=512 spec; `check_bench.lane_gate` requires the gather row to run the
gather lane and keep up with its onehot twin.

The island backends additionally run as mesh combos (`...@mesh{D}`): the
island axis shard_mapped over D devices with `ppermute` ring migration —
the `devices` column is the scaling sweep (full mode sweeps powers of two
up to the host's device count; point it at a TPU pod slice and the
`gens_per_s` column is the paper's speedup-vs-replication headline).

Standalone smoke mode for CI (1 tiny config per backend × problem combo,
JSON artifact so a composition regression fails fast):

    PYTHONPATH=src python -m benchmarks.engine_backends --smoke \
        --out artifacts/engine_backends.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

from benchmarks.ga_common import planned_peak_vmem, time_call
from repro import ga

K = 100
N_ISLANDS = 8

SMOKE = dict(n=16, m=16, generations=8, n_islands=2, migrate_every=4)

PROBLEM_SWEEP = ("F3", "rastrigin:4")
MESH_BACKENDS = ("islands", "fused-islands")


def _spec_for(backend: str, problem: str, *, n: int, m: int,
              generations: int, n_islands: int,
              migrate_every: int) -> ga.GASpec:
    base = ga.GASpec(problem=problem, n=n, bits_per_var=m // 2, mode="arith",
                     mutation_rate=0.02, seed=1, generations=generations,
                     migrate_every=migrate_every)
    if backend.split("@")[0] == "fused-islands":
        # fold 2 migration intervals per launch: the resident-epoch kernel
        # keeps the island stack + ring migration in VMEM (falls back to
        # gridded per-interval launches if the VMEM budget says no), so this
        # row gates the resident path's gens/s-vs-reference ratio
        return dataclasses.replace(base, n_islands=n_islands,
                                   gens_per_epoch=2 * migrate_every)
    if backend.split("@")[0] == "islands":
        return dataclasses.replace(base, n_islands=n_islands)
    return base


def _mesh_device_counts(smoke: bool):
    """Device counts the mesh combos sweep: all devices in smoke mode,
    powers of two up to the device count in full mode."""
    import jax
    n = len(jax.devices())
    if smoke:
        return [n]
    counts, d = [], 1
    while d <= n:
        counts.append(d)
        d *= 2
    return counts


def _one_row(name: str, backend: str, spec: ga.GASpec, *, smoke: bool,
             mesh=None, devices: int = 1, cost_table=False, options=None):
    # cost_table=False by default: benchmark rows must not silently flip
    # epoch plans because the host happens to have an ambient autotune
    # table — only the explicit `+measured` rows consume one
    if options is None:
        options = ga.EngineOptions(mesh=mesh, cost_table=cost_table)
    eng = ga.Engine(spec, backend, options=options)
    out = eng.run()           # compile + warm caches
    # interpret-mode Pallas and the eager loop are slow; fewer iters.  The
    # cheap XLA backends keep 3 timed iters even in smoke mode — the
    # reference row is the anchor every ratio divides by, so its noise
    # multiplies into every gated combo.
    slow = backend in ("fused", "fused-islands", "eager")
    iters = 1 if slow else 3
    dt, out = time_call(eng.run, warmup=0, iters=iters)
    gens = out.generations * max(spec.n_islands, spec.n_repeats)
    tele = out.telemetry
    payload = json.dumps({"backend": out.backend,
                          "executor": tele.topology.executor,
                          "topology": tele.topology.topology,
                          "problem": tele.problem or spec.problem,
                          "n_vars": spec.v,
                          "gens_per_s": round(gens / dt, 1),
                          "best": round(out.best_fitness, 4),
                          "n": spec.n,
                          "islands": spec.n_islands,
                          "devices": devices,
                          "epoch_mode": tele.plan.mode,
                          "plan_source": tele.plan.source,
                          "tile_islands": tele.plan.tile_islands,
                          "sel_lane": tele.plan.lane,
                          "planned_vmem_bytes": planned_peak_vmem(eng),
                          "migrations": tele.topology.migrations},
                         separators=(",", ":"))
    # island epochs round K up to whole migration epochs — divide by
    # what actually ran
    return (name, dt / out.generations * 1e6, payload)


def _streamed_rows(problem: str, sizes: dict, *, smoke: bool):
    """The oversized-stack pair: an island stack past a (forced) VMEM
    budget, once through the HBM-streaming lane (the planner's heuristic
    pick for oversized ring specs) and once forced through the gridded
    per-interval fallback.  The kernels still validate tiles against the
    REAL budget, so the forced budget only steers the plan."""
    from repro.kernels import ga_step as KS
    isl = max(8, sizes["n_islands"])
    spec = dataclasses.replace(
        _spec_for("fused-islands", problem, **sizes), n_islands=isl)
    probe = ga.Engine(spec, "fused-islands",
                      options=ga.EngineOptions(cost_table=False))
    cfg = probe.backend.topology.cfg
    # below the full stack, but a 2-island tile fits the tile rule:
    # the heuristic plans streamed with tile_islands=2
    budget = 2 * KS.resident_vmem_bytes(cfg, 2)
    return [
        _one_row(f"engine_fused-islands[{problem}]+streamed",
                 "fused-islands", spec, smoke=smoke,
                 options=ga.EngineOptions(cost_table=False,
                                          vmem_budget=budget)),
        _one_row(f"engine_fused-islands[{problem}]+streamed-gridded",
                 "fused-islands", spec, smoke=smoke,
                 options=ga.EngineOptions(cost_table=False,
                                          vmem_budget=budget,
                                          plan_override="gridded")),
    ]


LANE_N = 512     # the lane pair's population: large enough that the onehot
                 # lane's (N, N) working set dominates and gather should win


def _lane_rows(problem: str, sizes: dict, *, smoke: bool):
    """The selection-lane pair: one fused-islands spec at N=512 pinned to
    each tournament lane.  `check_bench.lane_gate` requires the gather row
    to actually run the gather lane and to keep up with (noise margin) or
    beat its onehot twin — the O(N·V) working set must not cost speed."""
    spec = dataclasses.replace(
        _spec_for("fused-islands", problem, **sizes),
        n=LANE_N, n_islands=2)
    rows = []
    for lane in ("onehot", "gather"):
        rows.append(_one_row(
            f"engine_fused-islands[{problem}]+{lane}", "fused-islands",
            dataclasses.replace(spec, sel_lane=lane), smoke=smoke))
    return rows


def run(smoke: bool = False, cost_table=None):
    sizes = SMOKE if smoke else dict(n=64, m=20, generations=K,
                                     n_islands=N_ISLANDS, migrate_every=16)
    rows = []
    for problem in PROBLEM_SWEEP:
        for backend in sorted(ga.BACKENDS):
            spec = _spec_for(backend, problem, **sizes)
            rows.append(_one_row(f"engine_{backend}[{problem}]", backend,
                                 spec, smoke=smoke))
        if cost_table is not None:
            # the measured-planner row: same spec as the static
            # fused-islands row, epoch plan chosen from the cost table —
            # check_bench gates its gens/s against the static row's
            spec = _spec_for("fused-islands", problem, **sizes)
            rows.append(_one_row(
                f"engine_fused-islands[{problem}]+measured", "fused-islands",
                spec, smoke=smoke, cost_table=cost_table))
        if problem == "F3":
            # one oversized-stack pair is enough to gate the streamed lane
            rows.extend(_streamed_rows(problem, sizes, smoke=smoke))
            # one N=512 pinned-lane pair gates the gather selection lane
            rows.extend(_lane_rows(problem, sizes, smoke=smoke))
        # mesh combos: island axis sharded over devices (device-count sweep)
        from repro.launch.mesh import make_island_mesh
        for backend in MESH_BACKENDS:
            for d in _mesh_device_counts(smoke):
                isl = sizes["n_islands"]
                isl = isl if isl % d == 0 else d * -(-isl // d)  # ceil mult
                spec = _spec_for(backend, problem,
                                 **{**sizes, "n_islands": isl})
                rows.append(_one_row(
                    f"engine_{backend}[{problem}]@mesh{d}", backend, spec,
                    smoke=smoke, mesh=make_island_mesh(d), devices=d))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 tiny config per backend x problem combo (CI "
                         "regression gate; seconds, not minutes)")
    ap.add_argument("--out", default=None,
                    help="write the rows as a JSON artifact here")
    ap.add_argument("--cost-table", default=None,
                    help="autotune cost table path: adds '+measured' "
                         "fused-islands rows planned from measurements")
    args = ap.parse_args()
    rows = run(smoke=args.smoke, cost_table=args.cost_table)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        artifact = [{"name": name, "us_per_gen": round(us, 2),
                     **json.loads(derived)} for name, us, derived in rows]
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
