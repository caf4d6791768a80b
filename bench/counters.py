"""Shares of the window read from an entry's own summed phase counters
(`Entry.counters()["phase_s"]`, host seconds by phase), taken at the
window's start and end.  A reader returns None where the entry counts no
phases: a system whose results carry none."""

from __future__ import annotations

from typing import Optional


def share(run, phase: str) -> Optional[float]:
    """Percent of the window the entry's jobs spent in `phase`."""
    before = run.counters["before"].get("phase_s")
    after = run.counters["after"].get("phase_s")
    if not after:
        return None
    seconds = after.get(phase, 0.0) - before.get(phase, 0.0)
    return 100.0 * seconds / run.window_s
