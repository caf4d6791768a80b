"""Find the highest arrival rate a serving cell sustains: one sweep.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> \
        --rates 6 8 10 12 14

In one process: warm up the cell as a run does, then offer an open-loop
Poisson window at each rate in turn and print, per rate, the jobs offered
and completed inside the window, the queue left at its close, and the
latency quantiles.  A rate is sustained while the window completes about
what it offers and the queue at the close stays near one pack.  The
benchmark's open-loop cells run at a fixed share of the knee found here;
this script is not one of the benchmark's runs.
"""

import time

STARTED = time.monotonic()

import json                     # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
from pathlib import Path        # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)

from bench import harness as H  # noqa: E402
from bench import stats, traffic  # noqa: E402


def sweep(argv, root: Path):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = H.load_benchmark(root)
    cell = H.find(bench["workloads"], args.workload, "workload")
    config = H.load_config(root, bench, cell["config"])
    Entry = H.load_module(root, "entries", config["entry"]).Entry
    jax = H.setup_jax(root)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise H.BenchError(f"no TPU: JAX sees {devices[0].platform!r}")
    seeds = iter(traffic.job_seeds(args.seed, H.JOB_SEEDS))
    workdir = tempfile.mkdtemp(prefix="bench-knee-")
    entry = Entry(config, devices[:int(cell["chips"])], workdir)
    try:
        top = traffic.Mix(loop="closed", clients=entry.max_pack)
        entry.warmup(top, seeds)
        H.log(f"set-up {time.monotonic() - STARTED:.3f} s")
        for rate in args.rates:
            mix = traffic.Mix(loop="open", rate_per_s=rate)
            t0 = time.monotonic()
            jobs, waiter = H.open_loop(entry, mix, seeds, args.seed, t0,
                                       args.seconds, H.annotator(jax, False))
            left = entry.sched.stats()["queue_depth"]
            waiter.join()
            t1 = t0 + args.seconds
            done = [j for j in jobs if j.done is not None]
            lat = [j.latency for j in done]
            print(json.dumps({
                "rate_per_s": rate, "offered": len(jobs),
                "completed_in_window": sum(1 for j in done if j.done <= t1),
                "queue_at_close": left,
                "failed": sum(1 for j in jobs if j.error is not None),
                "job_p50_s": stats.quantile(lat, 0.5) if lat else None,
                "job_p95_s": stats.quantile(lat, 0.95) if lat else None,
                "drained_s": max((j.done for j in done), default=t1) - t1}),
                flush=True)
    finally:
        entry.close()
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    try:
        sweep(sys.argv[1:], ROOT)
    except H.BenchError as e:
        H.log(f"knee: {e}")
        sys.exit(1)
