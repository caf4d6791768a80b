"""readback_share: percent of the window spent reading each chunk's
results on the host and building its telemetry (the `ga.chunk.readback`
span), a pack's time shared by its jobs."""

from bench import phases


def read(run):
    return phases.share(run, "readback")
