"""solve_build_share: percent of the window spent building each study's
`Engine` (the `ga.engine.build` span, with `ga.problem.build` inside it:
the problem's program, backend resolution, the FFM trace and its hoisted
constants, the plan and the backend)."""

from bench import counters


def read(run):
    return counters.share(run, "build")
