"""The window's jobs' host seconds per named phase, as the system's own
registry (`repro.serve.engine.GA_METRICS`) counts them in each job's
`phase_s`.  The registry outlives the scheduler's shutdown, so the
readers of `bench/metrics` find it after the run.

A pack's phases are charged whole to every job of the pack, so a share
divides each job's seconds by its `pack_size`; a job's `journal` is
already its share of the pack's events.  Every reader returns None where
the registry holds no phases for the window's jobs: a cell whose entry
does not go through the scheduler, or a system that counts no phases.
"""

from __future__ import annotations

from typing import List, Optional


def window_jobs(run) -> List[dict]:
    """The registry's record of each job of the window that counts phases."""
    from repro.serve.engine import GA_METRICS
    jobs = GA_METRICS.metrics()["jobs"]
    found = [jobs.get(j.handle) for j in run.jobs
             if isinstance(j.handle, str)]
    return [m for m in found if m is not None and "phase_s" in m]


def share(run, phase: str, per_pack: bool = True) -> Optional[float]:
    """Percent of the window that the worker spent in `phase`."""
    jobs = window_jobs(run)
    if not jobs:
        return None
    seconds = sum(m["phase_s"].get(phase, 0.0)
                  / (m["pack_size"] if per_pack else 1) for m in jobs)
    return 100.0 * seconds / run.window_s


def mean_ms(run, phase: str) -> Optional[float]:
    """Mean milliseconds a job of the window spent in `phase`."""
    jobs = window_jobs(run)
    if not jobs:
        return None
    return 1e3 * sum(m["phase_s"].get(phase, 0.0) for m in jobs) / len(jobs)
