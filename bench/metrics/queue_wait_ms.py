"""queue_wait_ms: mean milliseconds a job of the window waited in the
scheduler's queue, from each enqueue to the dispatch that took it (the
`queue` counter)."""

from bench import phases


def read(run):
    return phases.mean_ms(run, "queue")
