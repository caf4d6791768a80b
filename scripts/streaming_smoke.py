#!/usr/bin/env python
"""CI smoke for the HBM-streaming epoch lane: plan, stream, bit-match.

Runs an 8-island F3 spec whose resident stack exceeds a forced VMEM
budget (`EngineOptions.vmem_budget`), so the planner's heuristic picks the
STREAMED epoch mode — the double-buffered HBM→VMEM pipeline that tiles
the island stack through VMEM instead of falling back to gridded
per-interval launches.  Asserts:

  * the plan really is streamed (mode, tile size, double-buffered VMEM
    estimate within the forced budget);
  * the result is bit-identical to the `islands` reference backend —
    best fitness, best chromosome, and the best-trajectory at launch
    boundaries (streamed launches fold several migration intervals, so
    the trajectory is one sample per launch, same as resident);
  * a pinned `stream_tile_islands=1` override also bit-matches (tile
    size is a launch-shape knob, never a results knob);
  * `plan_override="streamed"` on a spec that FITS the budget raises
    with the planner's feasibility reason.

    PYTHONPATH=src python scripts/streaming_smoke.py
"""

import os
import sys

# this smoke pins every plan explicitly; never consume an ambient table
os.environ["REPRO_GA_COST_TABLE"] = "off"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np                                      # noqa: E402

from repro import ga                                    # noqa: E402
from repro.kernels import ga_step as K                  # noqa: E402

SPEC = ga.GASpec(problem="F3", n=16, bits_per_var=8, mode="arith",
                 mutation_rate=0.02, seed=1, generations=16, n_islands=8,
                 migrate_every=4, gens_per_epoch=8)


def main():
    ref = ga.solve(SPEC, backend="islands")

    probe = ga.Engine(SPEC, "fused-islands", cost_table=False)
    cfg = probe.backend.topology.cfg
    # below the 8-island stack, but a 2-island tile fits the tile rule
    budget = 2 * K.resident_vmem_bytes(cfg, 2)
    opts = ga.EngineOptions(cost_table=False, vmem_budget=budget)
    res = ga.solve(SPEC, backend="fused-islands", options=opts)

    plan = res.telemetry.plan
    assert plan.mode == "streamed", plan
    assert plan.tile_islands == 2, plan
    assert plan.vmem_estimate_bytes <= budget, plan
    print(f"streamed plan: tile={plan.tile_islands}, "
          f"~{plan.vmem_estimate_bytes} B double-buffered "
          f"(budget {budget} B); fallback: {plan.fallback}")

    assert res.best_fitness == ref.best_fitness, \
        (res.best_fitness, ref.best_fitness)
    assert np.array_equal(res.best_x, ref.best_x)
    # islands samples once per interval, streamed once per (multi-interval)
    # launch: compare at the launch boundaries
    stride = (res.telemetry.topology.telemetry_unit_gens
              // ref.telemetry.topology.telemetry_unit_gens)
    assert np.array_equal(res.traj_best,
                          ref.traj_best[stride - 1::stride]), \
        (res.traj_best, ref.traj_best)
    print(f"bit-identical to islands reference: best={res.best_fitness}")

    pinned = ga.solve(SPEC, backend="fused-islands",
                      options=ga.EngineOptions(cost_table=False,
                                               vmem_budget=budget,
                                               stream_tile_islands=1))
    assert pinned.telemetry.plan.tile_islands == 1, pinned.telemetry.plan
    assert pinned.best_fitness == ref.best_fitness
    assert np.array_equal(pinned.best_x, ref.best_x)
    print("pinned tile=1 bit-identical too")

    try:
        ga.solve(SPEC, backend="fused-islands",
                 options=ga.EngineOptions(cost_table=False,
                                          plan_override="streamed"))
    except ValueError as e:
        print(f"fitting spec refuses forced streaming: {e}")
    else:
        raise AssertionError("plan_override='streamed' on a fitting spec "
                             "should raise")
    print("streaming smoke OK")


if __name__ == "__main__":
    main()
