"""launch_share: percent of the window spent inside the backend's
`segment` call of each chunk (the `ga.chunk.launch` span: tracing,
lowering, cache reads, enqueue, and the host reads `segment` makes
itself), a pack's time shared by its jobs.  With the `wait` counter it
makes up `segment_share`."""

from bench import phases


def read(run):
    return phases.share(run, "launch")
