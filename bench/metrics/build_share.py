"""build_share: percent of the window spent building each dispatch's
`PackedEngine` (the `ga.sched.build` span: backend resolution, plan,
backend and runner construction), a pack's time shared by its jobs."""

from bench import phases


def read(run):
    return phases.share(run, "build")
