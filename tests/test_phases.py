"""Named host phases of a served job: the `ga.*` spans on the profiler's
host plane and the per-job `phase_s` counters on /metrics, through the
scheduler and the engine's one chunk loop, for a pack of one and a pack of
two."""

import glob
import time

import pytest

from repro import ga
from repro.ga import telemetry as RT
from repro.serve.engine import GAMetricsRegistry
from repro.serve.metrics_http import render_prometheus
from repro.serve.scheduler import GAScheduler

# every counter a job that runs to the end without preemption records
PHASES = ("submit", "queue", "build", "journal", "seed", "launch", "wait",
          "readback", "ckpt_save", "finish")


def _spec(seed):
    return ga.GASpec(problem="F3", n=16, bits_per_var=10, mode="arith",
                     mutation_rate=0.05, seed=seed, generations=20)


def _serve_two(tmp_path, packed):
    """Two jobs on the fused backend (interpret mode off a TPU), with a
    checkpoint root: one pack of two, or two packs of one.  Returns the
    registry, the job ids and each job's submit-to-result seconds."""
    reg = GAMetricsRegistry()
    sched = GAScheduler(registry=reg, backend="fused",
                        max_pack=2 if packed else 1, chunk_generations=10,
                        ckpt_root=str(tmp_path / "ckpt"),
                        options=ga.EngineOptions(cost_table=False))
    try:
        t0 = time.perf_counter()
        with sched._cv:     # hold dispatch so both jobs wait in the queue
            ids = [sched.submit(_spec(seed)) for seed in (3, 4)]
        latency = {}
        for jid in ids:
            sched.result(jid, timeout=300)
            latency[jid] = time.perf_counter() - t0
    finally:
        sched.shutdown()
    return reg, ids, latency


@pytest.mark.parametrize("packed", [True, False], ids=["pack2", "solo"])
def test_every_phase_is_counted_and_they_fit_the_job(tmp_path, packed):
    reg, ids, latency = _serve_two(tmp_path, packed)
    jobs = reg.metrics()["jobs"]
    for jid in ids:
        m = jobs[jid]
        assert m["pack_size"] == (2 if packed else 1)
        assert "park" not in m["phase_s"]
        for ph in PHASES:
            assert m["phase_s"].get(ph, 0.0) > 0.0, (jid, ph, m["phase_s"])
        # the counters are disjoint: together no longer than the job
        assert sum(m["phase_s"].values()) <= latency[jid], m["phase_s"]
        # a chunk's wall time is its launch plus its wait
        assert m["wall_s"] == pytest.approx(
            m["phase_s"]["launch"] + m["phase_s"]["wait"], abs=1e-3)
    text = render_prometheus(reg.metrics())
    assert (f'repro_ga_job_phase_seconds{{job_id="{ids[0]}",backend="fused"'
            f',problem="F3",phase="launch"}}') in text
    assert 'repro_ga_phase_seconds_total{phase="seed"}' in text


def _host_spans(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("ga."):
                    spans.append((e.name, i, e.start_ns, e.end_ns,
                                  {k: v for k, v in e.stats}))
    return spans


def test_spans_land_on_the_host_plane_with_job_ids(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path / "trace")):
        _, ids, _ = _serve_two(tmp_path, packed=True)
    path = glob.glob(f"{tmp_path}/trace/**/*.xplane.pb", recursive=True)
    assert path
    spans = _host_spans(path[0])
    names = {s[0] for s in spans}
    for name in ("ga.sched.submit", "ga.sched.dispatch", "ga.sched.build",
                 "ga.engine.seed", "ga.chunk", "ga.chunk.launch",
                 "ga.chunk.wait", "ga.chunk.readback", "ga.ckpt.save",
                 "ga.sched.finish", "ga.journal.append"):
        assert name in names, name
        for _, _, _, _, args in (s for s in spans if s[0] == name):
            who = str(args.get("job", args.get("jobs", ""))).split()
            assert set(who) & set(ids), (name, args)
    dispatch = [s for s in spans if s[0] == "ga.sched.dispatch"]
    assert len(dispatch) == 1 and dispatch[0][4]["jobs"].split() == ids
    launches = [s for s in spans if s[0] == "ga.chunk.launch"]
    assert len(launches) == 2          # 20 generations in chunks of 10
    for _, line, start, end, _ in launches:
        assert any(d[1] == line and d[2] <= start and end <= d[3]
                   for d in dispatch)


def test_phase_counts_own_time_less_nested_phases():
    outer, inner = {}, {}
    t0 = time.perf_counter()
    with RT.phase("ga.sched.finish", outer, job="j"):
        time.sleep(0.01)
        with RT.phase("ga.journal.append", inner, ev="done", job="j"):
            time.sleep(0.02)
    total = time.perf_counter() - t0
    assert inner["journal"] >= 0.02
    assert outer["finish"] >= 0.01
    assert outer["finish"] + inner["journal"] <= total
    # a span without a counter still passes its time up as nested
    counted = {}
    t0 = time.perf_counter()
    with RT.phase("ga.sched.build", counted):
        t1 = time.perf_counter()
        with RT.phase("ga.chunk"):
            time.sleep(0.01)
        nested = time.perf_counter() - t1
    total = time.perf_counter() - t0
    assert counted["build"] <= total - nested + 1e-4
