"""Time the resident epoch kernel with one fitness stage against another.

    python scripts/ffm_split.py                      # on a TPU
    python scripts/ffm_split.py --problems maxsat_uf250 sphere:25

For each problem: one `ga.solve` at the MAX-3SAT cell's shape (16 islands of
N=256, 10 bits a word, 64 generations a launch, `fused-islands`) to compile,
then `--solves` more under the profiler.  Prints one JSON line a problem:
the plan, and `ga_epoch_kernel`'s device time per launch (median over the
traced launches) and per island-generation.  The difference between two
problems at one V is the difference between their fitness stages, since
every other part of the kernel (selection, crossover, mutation, migration)
is the same at the same N, V and plan.
"""

import argparse
import dataclasses
import glob
import json
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def split(problem: str, solves: int, seed: int) -> dict:
    import jax
    from repro import ga
    from bench import trace as TR
    spec = ga.GASpec(problem=problem, n=256, n_islands=16, bits_per_var=10,
                     migrate_every=16, gens_per_epoch=64, generations=1024,
                     mode="arith", mutation_rate=0.02, seed=seed)
    opts = ga.EngineOptions(cost_table=False)
    first = ga.solve(spec, "fused-islands", options=opts)
    plan = first.telemetry.plan
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for k in range(solves):
                ga.solve(dataclasses.replace(spec, seed=seed + 1 + k),
                         "fused-islands", options=opts)
        trace = TR.load(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0])
    launches = [e.dur_ns / 1e3 for ops in trace.device_ops.values()
                for e in ops if TR.op_name(e.name) == "ga_epoch_kernel"]
    per_launch = statistics.median(launches)
    return {"problem": problem, "plan": [plan.mode, plan.lane,
                                         plan.gens_per_launch],
            "launches": len(launches), "launch_us": per_launch,
            "island_gen_us": per_launch / (16 * 64),
            "best_fitness": first.best_fitness}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--problems", nargs="+",
                    default=["maxsat_uf250", "sphere:25"])
    ap.add_argument("--solves", type=int, default=2)
    ap.add_argument("--seed", type=int, default=2147483905)
    args = ap.parse_args()
    import jax
    if jax.default_backend() != "tpu":
        print("ffm_split: needs a TPU", file=sys.stderr)
        return 1
    for problem in args.problems:
        print(json.dumps(split(problem, args.solves, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
