"""segment_share: percent of the traced window that the scheduler's
worker spent computing chunks (the engine's per-chunk segment wall time,
which the registry adds up per job; a pack's time is shared by its
jobs).  The rest is checkpoint saves, journal fsyncs, packing and
bookkeeping between chunks, or waiting for work."""


def read(run):
    jobs = run.counters["after"].get("jobs")
    if jobs is None:
        return None
    seconds = sum(jobs[j.handle]["wall_s"] / jobs[j.handle]["pack_size"]
                  for j in run.jobs if j.handle in jobs)
    return 100.0 * seconds / run.window_s
