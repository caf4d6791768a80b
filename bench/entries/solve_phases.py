"""Entry `solve_phases`: one study at a time through `ga.solve`, as entry
`solve` runs it, adding up the host seconds of each job's named phases
(`RunTelemetry.phase_s`: build, seed, launch, wait, readback) for the
per-layer metrics.  A system whose results carry no `phase_s` counts
nothing, and those metrics then read None.
"""

from __future__ import annotations

from bench.entries.solve import Entry as SolveEntry


class Entry(SolveEntry):
    def __init__(self, config, devices, workdir):
        super().__init__(config, devices, workdir)
        self.jobs = 0
        self.phase_s = {}

    def submit(self, seed):
        res = self.ga.solve(self.spec(seed), self.backend,
                            options=self.options)
        self.jobs += 1
        for k, v in getattr(res.telemetry, "phase_s", {}).items():
            self.phase_s[k] = self.phase_s.get(k, 0.0) + v
        return self.summary(res)

    def counters(self):
        return {"jobs": self.jobs, "phase_s": dict(self.phase_s)}
