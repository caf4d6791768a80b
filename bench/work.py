"""Work of each GA kernel launch, counted from shapes.

A launch steps a stack of populations (N individuals of V genes each).
Whatever lane, mode or body implements it, it has to read each
population's state from HBM once and write it back once, write the
population's best, and read the objective's hoisted constants once:

    state per population   x (N, V) + selection LFSRs (2, N)
                           + crossover LFSRs (V, N/2) + mutation LFSRs (V, N)
                           uint32 words
    best per population    fitness (f32) + chromosome (V x u32)
                           + generation (i32)

These are the bytes the HBM roofline bounds.  The VPU's peak is not
published, so the bound is HBM's alone and the share reads small.
"""

from __future__ import annotations

from typing import Optional

from bench import trace as TR

WORD_BYTES = 4
KERNELS = ("ga_generation_kernel", "ga_epoch_kernel",
           "ga_streamed_epoch_kernel")


def population_words(n: int, v: int) -> int:
    return n * v + 2 * n + v * (n // 2) + v * n


def best_words(v: int) -> int:
    return 1 + v + 1


def launch_bytes(populations: int, n: int, v: int,
                 const_bytes: int = 0) -> int:
    """HBM bytes one launch over `populations` populations must move."""
    per = 2 * population_words(n, v) + best_words(v)
    return populations * per * WORD_BYTES + int(const_bytes)


def spec_launch_bytes(spec: dict, populations: int,
                      const_bytes: int = 0) -> int:
    """`launch_bytes` of a spec: only N and V count, not the selection
    lane or the epoch mode that runs it."""
    return launch_bytes(populations, int(spec["n"]), int(spec["v"]),
                        const_bytes)


def populations(hlo_text: str, n: int, v: int) -> int:
    """Populations a launch steps, from its first output (the population
    stack x, N x V genes per population) in the trace."""
    elements = TR.first_output_elements(hlo_text)
    if elements is None or elements % (n * v):
        raise ValueError(f"cannot read a population stack of N={n}, V={v} "
                         f"from {hlo_text[:120]!r}")
    return elements // (n * v)


def roofline_share(run, kernel: str) -> Optional[float]:
    """Percent of the HBM roofline one kernel reached in the traced window:
    the least time its launches' bytes need at peak bandwidth over the
    time they took.  None when the kernel did not run in the window."""
    if kernel not in KERNELS:
        raise ValueError(f"no work count for kernel {kernel!r}")
    events = run.reduction.kernel(kernel)
    if not events:
        return None
    shape = run.config["reference"]["shape"]
    n, v = int(shape["n"]), int(shape["v"])
    total = sum(launch_bytes(populations(e.name, n, v), n, v,
                             run.config["ffm_const_bytes"]) for e in events)
    seconds = sum(e.dur_ns for e in events) / 1e9
    return 100.0 * total / run.peaks["hbm_bytes_per_s"] / seconds
