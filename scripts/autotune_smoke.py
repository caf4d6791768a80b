#!/usr/bin/env python
"""CI smoke for the trace-driven autotuner: sweep, persist, consume.

Forces an 8-device host platform (same environment as scheduler_smoke),
runs a tiny autotune sweep over the exact spec shapes the engine_backends
--smoke fused-islands rows use, writes the cost table to --out, then
asserts the whole loop closes:

  * the sweep measured > 0 points, including a resident-free one
    (migration="none" folding past migrate_every without ring exchange)
    and a streamed one (an 8-island stack under a forced vmem_budget that
    only fits a double-buffered tile);
  * an Engine pointed at the written table plans with
    plan_source="measured" and its result is bit-identical to the
    heuristic plan's;
  * with the table disabled the plan is exactly the heuristic candidate
    (no table -> bit-identical pre-autotune behavior);
  * the committed fake-8 snapshot (benchmarks/autotune_snapshot_fake8.json)
    still loads and steers the planner — the F3 point prefers resident,
    the rastrigin point prefers gridded, both marked "measured".

    PYTHONPATH=src python scripts/autotune_smoke.py \
        --out artifacts/autotune_table.json
"""

import argparse
import os
import sys

# must precede the first jax import: fake an 8-device host platform
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# this smoke pins every table explicitly; never consume an ambient one
os.environ["REPRO_GA_COST_TABLE"] = "off"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import ga                                    # noqa: E402
from repro.autotune import CostTable, sweep             # noqa: E402

SNAPSHOT = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "autotune_snapshot_fake8.json")

# the engine_backends --smoke fused-islands shape (n=16, m=16, islands=2,
# E=4, gens_per_epoch=2*E) — sweeping the same shapes means the bench's
# '+measured' rows find their points in the table this smoke writes
BASE = dict(n=16, bits_per_var=8, mode="arith", mutation_rate=0.02, seed=1,
            generations=8, n_islands=2, migrate_every=4, gens_per_epoch=8)


def _plan(spec, cost_table):
    eng = ga.Engine(spec, "fused-islands", cost_table=cost_table)
    return eng.backend.topology.plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="artifacts/autotune_table.json")
    args = ap.parse_args()

    specs = [ga.GASpec(problem=p, **BASE) for p in ("F3", "rastrigin:4")]
    # resident-free coverage: no ring exchange, the whole epoch in one launch
    free_spec = ga.GASpec(problem="F3", migration="none",
                          **{**BASE, "generations": 16,
                             "gens_per_epoch": 16})
    table = sweep(specs + [free_spec], backend="fused-islands", log=print)
    # streamed coverage: an 8-island stack under a forced budget that only
    # fits a 2-island tile -> candidates [streamed, gridded]
    from repro.kernels import ga_step as K
    stream_spec = ga.GASpec(problem="F3", **{**BASE, "n_islands": 8})
    probe = ga.Engine(stream_spec, "fused-islands", cost_table=False)
    budget = 2 * K.resident_vmem_bytes(probe.backend.topology.cfg, 2)
    sweep([stream_spec], backend="fused-islands",
          options=ga.EngineOptions(cost_table=False, vmem_budget=budget),
          table=table, log=print)
    table.save(args.out)
    print(f"wrote {len(table)} measured point(s) -> {args.out}")

    assert len(table) > 0, "sweep measured nothing"
    modes = {e["mode"] for e in table.entries()}
    assert "resident-free" in modes, f"no resident-free point (got {modes})"
    assert "streamed" in modes, f"no streamed point (got {modes})"

    # planner consumes the table it just wrote (path form, trusted load)
    plan = _plan(specs[0], args.out)
    assert plan["plan_source"] == "measured", plan
    assert plan.get("plan_gens_per_s"), plan
    print(f"measured plan: {plan['mode']} "
          f"({plan['plan_gens_per_s']:.1f} gens/s expected)")

    # measured vs heuristic plans differ only in launch shape, never results
    out_meas = ga.solve(specs[0], backend="fused-islands",
                        cost_table=args.out)
    out_heur = ga.solve(specs[0], backend="fused-islands", cost_table=False)
    assert out_meas.best_fitness == out_heur.best_fitness, \
        (out_meas.best_fitness, out_heur.best_fitness)
    assert out_heur.telemetry.plan.source == "heuristic"

    # no table -> exactly the heuristic candidate (bit-identical pre-PR plan)
    eng = ga.Engine(specs[0], "fused-islands", cost_table=False)
    heur = eng.backend.topology.epoch_candidates()[0]
    got = {k: eng.backend.topology.plan[k] for k in heur}
    assert got == heur, (got, heur)

    # the committed snapshot still steers the planner as encoded
    snap = CostTable.load(SNAPSHOT)
    assert snap is not None, f"unusable snapshot {SNAPSHOT}"
    p_f3 = _plan(specs[0], snap)
    p_ras = _plan(specs[1], snap)
    assert (p_f3["plan_source"], p_f3["mode"]) == ("measured", "resident"), \
        p_f3
    assert (p_ras["plan_source"], p_ras["mode"]) == ("measured", "gridded"), \
        p_ras
    print(f"snapshot plans: F3 -> {p_f3['mode']}, "
          f"rastrigin:4 -> {p_ras['mode']}")
    print("autotune smoke OK")


if __name__ == "__main__":
    main()
