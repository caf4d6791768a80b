"""journal_share: percent of the window spent appending to the
scheduler's journal (the `ga.journal.append` span: write, flush, fsync),
each job's own events whole and a pack's events split among its jobs."""

from bench import phases


def read(run):
    return phases.share(run, "journal", per_pack=False)
