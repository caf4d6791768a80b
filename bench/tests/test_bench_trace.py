"""The trace reduction, on a recorded TPU v5e trace and on made-up events.

The fixture is a profiler trace of three `ga.solve` jobs of a 16-island
ring (256 individuals, Rastrigin over 20 genes) on one v5e chip, each inside a
`bench.job` host span, cut down to the device planes and the runtime's
host threads.  Reading it needs no TPU.
"""

from pathlib import Path

import pytest

from bench import trace as TR

FIXTURE = Path(__file__).parent / "fixtures" / "islands_v5e.xplane.pb"


@pytest.fixture(scope="module")
def fixture():
    return TR.load(str(FIXTURE))


def job_spans(tr):
    return sorted((e.start_ns, e.end_ns) for evs in tr.host.values()
                  for e in evs if e.name == "bench.job")


def test_fixture_planes(fixture):
    assert sorted(fixture.device_ops) == [0]
    assert len(job_spans(fixture)) == 3


def test_kernel_time_is_the_sum_of_its_launches(fixture):
    spans = job_spans(fixture)
    window = (spans[0][0], spans[-1][1])
    red = TR.reduce(fixture, window, [0], skip_host=("bench.job",))
    launches = red.kernel("ga_epoch_kernel")
    # 512 generations, 64 folded into each launch: 8 launches a job
    assert len(launches) == 24
    total = sum(e.dur_ns for e in launches)
    assert dict(red.device_ops)["ga_epoch_kernel"] == pytest.approx(
        total / 1e9)
    assert red.device_ops[0][0] == "ga_epoch_kernel"
    assert red.busy_ns >= total
    assert 0.0 < red.idle_share < 1.0
    assert red.idle_share == pytest.approx(1 - red.busy_ns / red.window_ns)


def test_busy_is_the_union_of_op_intervals(fixture):
    spans = job_spans(fixture)
    window = (spans[0][0], spans[-1][1])
    red = TR.reduce(fixture, window, [0])
    # a plain sweep over the clipped intervals, written apart from `union`
    ev = sorted((max(e.start_ns, window[0]), min(e.end_ns, window[1]))
                for e in fixture.device_ops[0]
                if e.end_ns > window[0] and e.start_ns < window[1])
    busy, end = 0.0, -1.0
    for s, t in ev:
        if t <= end:
            continue
        busy += t - max(s, end)
        end = t
    assert red.busy_ns == pytest.approx(busy)


def test_each_job_window_holds_its_own_launches(fixture):
    for span in job_spans(fixture):
        red = TR.reduce(fixture, span, [0])
        assert len(red.kernel("ga_epoch_kernel")) == 8


def test_gaps_name_what_the_host_did(fixture):
    spans = job_spans(fixture)
    red = TR.reduce(fixture, (spans[0][0], spans[-1][1]), [0],
                    skip_host=("bench.job",))
    assert 1 <= len(red.gaps) <= 10
    seconds = [s for _, s in red.gaps]
    assert seconds == sorted(seconds, reverse=True)
    assert all(name != "bench.job" for name, _ in red.gaps)


def test_kernel_launch_reads_its_population_stack(fixture):
    from bench import work
    launch = TR.reduce(fixture, (0, float("inf")), [0]).kernel(
        "ga_epoch_kernel")[0]
    assert work.populations(launch.name, n=256, v=20) == 16


def E(name, s, t):
    return TR.Event(name, float(s), float(t))


def test_union_idle_gaps_and_self_times():
    ev = [E("%while.1 = (...)", 0, 100), E("%ga_generation_kernel.4 = (..)",
                                             10, 40),
          E("%copy.2 = u32[4]", 50, 60), E("%fusion = f32[2]", 150, 170)]
    assert TR.union([(0, 100), (10, 40), (150, 170)]) == [(0, 100),
                                                         (150, 170)]
    assert TR.busy_ns(ev, (0, 200)) == 120
    assert TR.idle_gaps(ev, (0, 200)) == [(100, 150), (170, 200)]
    assert TR.busy_ns(ev, (20, 160)) == 90
    assert TR.self_times(ev) == {"while": 60.0, "ga_generation_kernel": 30.0,
                                 "copy": 10.0, "fusion": 20.0}


def test_missing_chip_plane_is_an_error(fixture):
    with pytest.raises(ValueError, match="no 'XLA Ops' line"):
        TR.reduce(fixture, (0, 1), [0, 1])


def test_op_names_and_output_shapes():
    text = "%ga_epoch_kernel.1 = (u32[1,16,20,256]{3,2,1,0}, u32[2]) custom"
    assert TR.op_name(text) == "ga_epoch_kernel"
    assert TR.first_output_elements(text) == 16 * 20 * 256
    assert TR.op_name("%copy-start.3 = (u32[8]) copy-start") == "copy-start"
