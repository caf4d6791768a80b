"""The control of a cell's correctness check: what the check reads when
the plain reference, computed one precision lower than the configuration
states (bfloat16 for float32), stands in the system's place.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed it takes the job seeds that a run with that seed would
offer first, as many as the cell's check compares, computes their results
with the reference in the configuration's precision and in the control's,
and prints `jobs_differing`, the number the check holds to 0.  A sound
check reads every job as differing here.  The benchmark's own runs never
run this; it is the upper reading the limit is set against.
"""

import time

STARTED = time.monotonic()

import json                     # noqa: E402
import sys                      # noqa: E402
from pathlib import Path        # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)

from bench import harness as H  # noqa: E402
from bench import traffic       # noqa: E402

LOWER = {"float32": "bfloat16"}


def readings(root: Path, config: dict, seed: int) -> dict:
    """jobs_differing of the control, and of the reference against itself,
    on the first `check_jobs` job seeds of a run seeded `seed`."""
    import jax.numpy as jnp
    job_seeds = traffic.job_seeds(seed, int(config["check_jobs"]))
    want = H.reference_results(root, config, job_seeds)
    again = H.reference_results(root, config, job_seeds)
    low = H.reference_results(root, config, job_seeds,
                              dtype=getattr(jnp, LOWER[config["precision"]]))
    return {"seed": seed, "jobs": len(job_seeds),
            "control_jobs_differing": sum(
                1 for g, w in zip(low, want) if not H.same(g, w)),
            "reference_jobs_differing": sum(
                1 for g, w in zip(again, want) if not H.same(g, w))}


def main(argv, root: Path, platform: str = "tpu") -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        bench = H.load_benchmark(root)
        cell = H.find(bench["workloads"], args.workload, "workload")
        config = H.load_config(root, bench, cell["config"])
        jax = H.setup_jax(root)
        dev = jax.devices()[0]
        if dev.platform != platform:
            raise H.BenchError(f"no {platform.upper()}: JAX sees "
                               f"{dev.platform!r}")
    except H.BenchError as e:
        H.log(f"control: {e}")
        return 1
    for seed in args.seeds:
        t = time.monotonic()
        out = readings(root, config, seed)
        out.update(workload=args.workload, kind=dev.device_kind,
                   seconds=time.monotonic() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], ROOT))
