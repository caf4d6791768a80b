"""The readers of the system's named phases (`bench/phases.py` and the
metrics that use it): on a tiny served cell driven through the harness on
the CPU each gives a number; on a cell whose jobs never pass through the
scheduler each gives None; and a share is the window's seconds in its
phase, a pack's shared by its jobs."""

import pytest

from bench import harness as H
from bench.tests import tiny

READERS = ("queue_wait_ms", "build_share", "journal_share", "seed_share",
           "launch_share", "readback_share", "ckpt_save_share")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The `Run` the harness hands its readers, for a served and a solved
    tiny cell."""
    root = tiny.make_root(tmp_path_factory.mktemp("bench-root"))
    seen = {}
    report = H.report

    def keep(root_, run, *args):
        seen[run.cell["name"]] = run
        return report(root_, run, *args)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(H, "report", keep)
        for cell in ("serve.closed4", "islands.solo"):
            tiny.run(root, cell, monkeypatch=mp)
    return root, seen


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_number_for_the_served_cell(runs, name):
    root, seen = runs
    value = H.load_module(root, "metrics", name).read(seen["serve.closed4"])
    # the tiny window is shorter than its jobs, so a share may pass 100
    assert value is not None
    assert value > 0.0 if name.endswith("_share") else value >= 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_where_no_job_was_served(runs, name):
    root, seen = runs
    assert H.load_module(root, "metrics", name).read(
        seen["islands.solo"]) is None


def test_share_divides_a_pack_among_its_jobs(monkeypatch):
    from bench import phases
    from repro.serve import engine as SE
    reg = SE.GAMetricsRegistry()
    monkeypatch.setattr(SE, "GA_METRICS", reg)
    for jid in ("a", "b", "c"):
        reg.queue_job(jid)
    # a and b share one pack; c ran alone
    for jid, pack in (("a", 2), ("b", 2), ("c", 1)):
        reg.record_chunk(jid, {"pack_size": pack,
                               "phases": {"launch": 0.4, "seed": 0.1}})
    reg.add_phases("a", {"journal": 0.05, "queue": 0.002})
    reg.add_phases("c", {"journal": 0.1, "queue": 0.004})
    run = H.Run(cell={}, config={}, mix=None, window=(10.0, 12.0),
                jobs=[H.JobRecord(seed=0, due=0.0, submitted=0.0, handle=h)
                      for h in ("a", "b", "c", "gone")],
                counters={})
    # launch: (0.4/2 + 0.4/2 + 0.4) over a 2 s window
    assert phases.share(run, "launch") == pytest.approx(40.0)
    assert phases.share(run, "seed") == pytest.approx(10.0)
    # the journal counter is already each job's share
    assert phases.share(run, "journal", per_pack=False) == pytest.approx(7.5)
    assert phases.share(run, "ckpt_save") == 0.0
    assert phases.mean_ms(run, "queue") == pytest.approx(2.0)
