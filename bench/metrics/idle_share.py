"""Percent of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over chips."""


def read(run):
    return 100.0 * run.reduction.idle_share
