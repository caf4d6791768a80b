"""ckpt_save_share: percent of the window spent writing each chunk's
checkpoint (the `ga.ckpt.save` span), a pack's time shared by its
jobs."""

from bench import phases


def read(run):
    return phases.share(run, "ckpt_save")
