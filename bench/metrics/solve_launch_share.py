"""solve_launch_share: percent of the window spent inside each study's
`backend.segment` call (the `ga.chunk.launch` span of `Engine.run`: every
kernel launch of the run and the per-launch reads of its bests, which
wait for the device)."""

from bench import counters


def read(run):
    return counters.share(run, "launch")
