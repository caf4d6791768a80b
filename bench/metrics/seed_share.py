"""seed_share: percent of the window spent seeding each run before its
first chunk (the `ga.engine.seed` span: the initial state and the
checkpoint lookup or restore), a pack's time shared by its jobs."""

from bench import phases


def read(run):
    return phases.share(run, "seed")
