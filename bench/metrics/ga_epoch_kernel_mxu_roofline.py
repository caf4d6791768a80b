"""ga_epoch_kernel_mxu_roofline: percent of the chip's bf16 matrix peak
(`bench/peaks.py`) that `ga_epoch_kernel` reached on its fitness stage's
dense contraction in the traced window: the least time the contraction's
operations need at that peak over the time the kernel's launches took.

The operations come from the configuration, not from the kernel: its
`ffm_flops_per_eval` (for MAX-3SAT, 2 x 250 variables x 1065 clauses, the
unpadded clause-incidence product), times each launch's populations (read
from the launch's output in the trace), N and `expect.gens_per_launch`.
So the yardstick stays the same whatever implements the clause test.
None where the configuration counts no such operations or the kernel did
not run in the window."""

from bench import work


def read(run):
    per_eval = run.config.get("ffm_flops_per_eval")
    events = run.reduction.kernel("ga_epoch_kernel")
    if not per_eval or not events:
        return None
    shape = run.config["reference"]["shape"]
    n, v = int(shape["n"]), int(shape["v"])
    gens = int(run.config["expect"]["gens_per_launch"])
    flops = sum(work.populations(e.name, n, v) for e in events) \
        * n * gens * int(per_eval)
    seconds = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * flops / run.peaks["bf16_flop_per_s"] / seconds
