"""GA-as-a-service: replica-axis packing (PackedEngine), the spec-keyed
compile cache, FFM single-trace sharing, preemption via run_chunked
checkpoint/resume, the GAScheduler end-to-end, registry thread safety and
the streaming HTTP endpoints."""

import dataclasses
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import ga
from repro.ga.compile_cache import PROGRAM_CACHE, RUNNER_CACHE
from repro.serve.engine import GAMetricsRegistry
from repro.serve.scheduler import (DONE, PREEMPTED, GAScheduler)


def _spec(**kw):
    base = dict(problem="F3", n=32, bits_per_var=10, mode="arith",
                mutation_rate=0.05, seed=11, generations=20)
    base.update(kw)
    return ga.GASpec(**base)


# ---------------------------------------------------------------------------
# Replica-axis packing: PackedEngine results are bit-identical to solo runs
# ---------------------------------------------------------------------------


def test_packed_engine_bit_identical_to_solo_reference():
    """Acceptance: K shape-compatible jobs packed down the n_repeats axis
    produce per-job results bit-identical to running each job alone —
    slot seeding follows the solo convention exactly."""
    specs = [_spec(seed=11), _spec(seed=40), _spec(seed=7, n_repeats=2)]
    packed = ga.PackedEngine(specs, "reference").run()
    assert len(packed) == 3
    for spec, jt in zip(specs, packed):
        solo = ga.solve(spec, backend="reference")
        assert jt["best_fitness"] == solo.best_fitness
        np.testing.assert_array_equal(np.asarray(jt["best_params"]),
                                      np.asarray(solo.best_params))
        assert jt["pack_size"] == 3


def test_packed_engine_bit_identical_to_solo_islands():
    specs = [_spec(seed=11, n_islands=4, migrate_every=5, generations=15),
             _spec(seed=23, n_islands=4, migrate_every=5, generations=15)]
    packed = ga.PackedEngine(specs, "islands").run()
    for spec, jt in zip(specs, packed):
        solo = ga.solve(spec, backend="islands")
        assert jt["best_fitness"] == solo.best_fitness
        np.testing.assert_array_equal(np.asarray(jt["best_params"]),
                                      np.asarray(solo.best_params))
        assert jt["migrations"] == solo.telemetry.topology.migrations


def test_packed_engine_single_job_delegates():
    """A one-job pack runs the chunk loop as an Engine does: its job dict
    matches Engine.run_chunked's last one on every key a journaled result
    keeps but the host timings, and its best is ga.solve's."""
    spec = _spec(seed=3)
    packed = ga.PackedEngine([spec], "reference").run(chunk_generations=5)
    last = list(ga.Engine(spec, "reference").run_chunked(
        chunk_generations=5))[-1]
    keys = [k for k in GAScheduler._RESULT_JSON_KEYS
            if k in last and k not in ("wall_s", "gens_per_s")]
    assert "best_fitness" in keys and "migrations" in keys
    for k in keys:
        assert packed[0][k] == last[k], k
    assert packed[0]["pack_size"] == 1 and packed[0]["job_index"] == 0
    solo = ga.solve(spec, backend="reference")
    assert packed[0]["best_fitness"] == solo.best_fitness


def test_packed_engine_rejects_incompatible():
    with pytest.raises(ga.BackendUnsupported):
        ga.PackedEngine([_spec(), _spec(n=64)], "reference")
    with pytest.raises(ga.BackendUnsupported):
        ga.PackedEngine([_spec(), _spec(generations=40)], "reference")
    with pytest.raises(ga.BackendUnsupported):
        ga.PackedEngine([_spec(), _spec()], "eager")


# ---------------------------------------------------------------------------
# Compile cache: identical spec shapes share one jitted runner
# ---------------------------------------------------------------------------


def test_compile_cache_hit_on_identical_shape():
    """Acceptance: the second engine with the same spec shape (differing
    only in seed — a trace-invariant field) is a cache hit, not a retrace."""
    RUNNER_CACHE.reset()
    a = ga.Engine(_spec(seed=1), "reference")
    a.backend.segment(a.init_state(), 20)
    after_first = RUNNER_CACHE.stats()
    b = ga.Engine(_spec(seed=999), "reference")
    b.backend.segment(b.init_state(), 20)
    after_second = RUNNER_CACHE.stats()
    assert after_second["misses"] == after_first["misses"]
    assert after_second["hits"] == after_first["hits"] + 1


def test_compile_cache_miss_on_different_shape():
    RUNNER_CACHE.reset()
    a = ga.Engine(_spec(), "reference")
    a.backend.segment(a.init_state(), 20)
    b = ga.Engine(_spec(n=64), "reference")
    b.backend.segment(b.init_state(), 20)
    stats = RUNNER_CACHE.stats()
    assert stats["misses"] == 2 and stats["hits"] == 0


def test_spec_compile_key_excludes_run_policy():
    assert _spec(seed=1).compile_key() == _spec(seed=2).compile_key()
    assert (_spec(generations=20).compile_key()
            == _spec(generations=99).compile_key())
    assert _spec().compile_key() != _spec(n=64).compile_key()
    assert _spec().compile_key() != _spec(mutation_rate=0.2).compile_key()


# ---------------------------------------------------------------------------
# FFM trace sharing: one fused build traces the fitness stage exactly once
# ---------------------------------------------------------------------------


def test_ffm_stage_traced_once_per_fused_build():
    """The const-gate, the epoch-plan budget check and the kernel hoist all
    consume one shared jaxpr (kernels.ga_step._ffm_jaxpr) — a blackbox
    fitness's FFM stage is traced exactly once per fused-islands engine
    build + run (was up to 3x before the shared trace cache)."""
    from repro.kernels.ga_step import _ffm_jaxpr, ffm_trace_cache_info

    calls = []

    def fit(x):
        calls.append(1)
        return -((x[:, 0] - 0.5) ** 2 + (x[:, 1] + 0.25) ** 2)

    # migration="none" isolates the FFM stage: ring migration additionally
    # evaluates fitness on the stacked state inside the epoch jit, which is
    # a different computation, not a redundant FFM-stage trace
    spec = _spec(fitness=fit, problem=None,
                 bounds=((-1.0, 1.0), (-1.0, 1.0)),
                 n_islands=2, migrate_every=4, migration="none",
                 generations=8)
    _ffm_jaxpr.cache_clear()
    eng = ga.Engine(spec, "fused-islands")
    eng.backend.segment(eng.init_state(), 8)
    assert sum(calls) == 1, f"fitness traced {sum(calls)}x, expected 1"
    info = ffm_trace_cache_info()
    assert info.misses == 1        # one real trace ...
    assert info.hits >= 1          # ... shared by every other consumer


# ---------------------------------------------------------------------------
# Preemption primitive: run_chunked checkpoint/resume is bit-identical
# ---------------------------------------------------------------------------


def _final_ckpt_arrays(ckpt_dir):
    from repro.ckpt import checkpoint as CKPT
    step = CKPT.latest_step(ckpt_dir)
    assert step is not None
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "shard_0.npz")
    return step, dict(np.load(path))


@pytest.mark.parametrize("backend,kw", [
    ("reference", {}),
    ("fused-islands", dict(n_islands=2, migrate_every=4)),
])
def test_preempt_resume_bit_identical(tmp_path, backend, kw):
    """Interrupt after the first chunk, resume in a fresh engine: the final
    checkpointed state (every population + LFSR bank) and the final best are
    bit-identical to the uninterrupted run."""
    spec = _spec(generations=16, **kw)
    ck_full = str(tmp_path / "full")
    ck_cut = str(tmp_path / "cut")
    full = list(ga.Engine(spec, backend).run_chunked(
        chunk_generations=8, ckpt_dir=ck_full))

    it = ga.Engine(spec, backend).run_chunked(chunk_generations=8,
                                              ckpt_dir=ck_cut)
    next(it)            # 8 generations, then "preempt"
    del it
    resumed = list(ga.Engine(spec, backend).run_chunked(
        chunk_generations=8, ckpt_dir=ck_cut))
    assert [t["gens_done"] for t in resumed] == [16]
    assert resumed[-1]["best_fitness"] == full[-1]["best_fitness"]
    np.testing.assert_array_equal(np.asarray(resumed[-1]["best_params"]),
                                  np.asarray(full[-1]["best_params"]))
    step_f, arr_f = _final_ckpt_arrays(ck_full)
    step_c, arr_c = _final_ckpt_arrays(ck_cut)
    assert step_f == step_c
    assert set(arr_f) == set(arr_c)
    for key in arr_f:
        np.testing.assert_array_equal(arr_f[key], arr_c[key], err_msg=key)


def test_packed_preempt_resume_bit_identical(tmp_path):
    """The scheduler's actual primitive: a PackedEngine pack interrupted
    mid-run resumes bit-identically from its checkpoint."""
    specs = [_spec(seed=11, generations=16), _spec(seed=40, generations=16)]
    ck = str(tmp_path / "pack")
    full = ga.PackedEngine(specs, "reference").run(chunk_generations=8)

    it = ga.PackedEngine(specs, "reference").run_chunked(
        chunk_generations=8, ckpt_dir=ck)
    next(it)
    del it
    resumed = list(ga.PackedEngine(specs, "reference").run_chunked(
        chunk_generations=8, ckpt_dir=ck))
    for jt_full, jt_res in zip(full, resumed[-1]["jobs"]):
        assert jt_res["best_fitness"] == jt_full["best_fitness"]
        np.testing.assert_array_equal(np.asarray(jt_res["best_params"]),
                                      np.asarray(jt_full["best_params"]))


def test_packed_ckpt_rejects_mismatched_pack(tmp_path):
    ck = str(tmp_path / "pack")
    it = ga.PackedEngine([_spec(seed=11, generations=16),
                          _spec(seed=40, generations=16)],
                         "reference").run_chunked(chunk_generations=8,
                                                  ckpt_dir=ck)
    next(it)
    del it
    other = ga.PackedEngine([_spec(seed=40, generations=16),
                             _spec(seed=11, generations=16)], "reference")
    with pytest.raises(ValueError, match="same jobs in the same order"):
        next(other.run_chunked(chunk_generations=8, ckpt_dir=ck))


# ---------------------------------------------------------------------------
# GAScheduler end-to-end
# ---------------------------------------------------------------------------


def test_scheduler_packs_and_matches_solo(tmp_path):
    """Acceptance: >= 2 shape-compatible jobs get packed onto one launch
    and every per-job result is bit-identical to its solo run."""
    reg = GAMetricsRegistry()
    sched = GAScheduler(registry=reg, ckpt_root=str(tmp_path),
                        chunk_generations=10)
    try:
        sa, sb = _spec(seed=11, generations=40), _spec(seed=40,
                                                       generations=40)
        sc = _spec(problem="rastrigin:4", seed=5, generations=40)
        with sched._cv:     # hold dispatch so a and b are packable together
            a = sched.submit(sa)
            b = sched.submit(sb)
            c = sched.submit(sc)
        ra, rb, rc = (sched.result(i, timeout=120) for i in (a, b, c))
        assert ra["pack_size"] == 2 and rb["pack_size"] == 2
        assert rc["pack_size"] == 1
        for spec, res in ((sa, ra), (sb, rb), (sc, rc)):
            solo = ga.solve(spec, backend="reference")
            assert res["best_fitness"] == solo.best_fitness
        stats = sched.stats()
        assert stats["jobs_packed"] == 2
        assert stats["cache_misses"] >= 1
        assert sched.job(a).state == DONE
        snap = reg.metrics()
        assert snap["jobs_done"] == 3
        assert snap["scheduler"]["packs_launched"] == stats["packs_launched"]
    finally:
        sched.shutdown()


def test_scheduler_compile_cache_hit_on_resubmit(tmp_path):
    RUNNER_CACHE.reset()
    reg = GAMetricsRegistry()
    sched = GAScheduler(registry=reg, ckpt_root=str(tmp_path))
    try:
        sched.result(sched.submit(_spec(seed=1)), timeout=120)
        h0 = sched.stats()["cache_hits"]
        sched.result(sched.submit(_spec(seed=2)), timeout=120)
        assert sched.stats()["cache_hits"] == h0 + 1
    finally:
        sched.shutdown()


def test_scheduler_reports_program_cache_gauges(tmp_path):
    """Two jobs of one problem shape compile one FitnessProgram: one miss,
    and every later lookup a hit, reported by `stats()`."""
    PROGRAM_CACHE.reset()
    sched = GAScheduler(registry=GAMetricsRegistry(),
                        ckpt_root=str(tmp_path))
    try:
        sched.result(sched.submit(_spec(seed=1)), timeout=120)
        sched.result(sched.submit(_spec(seed=2)), timeout=120)
        stats = sched.stats()
        assert stats["program_cache_misses"] == 1
        assert stats["program_cache_hits"] >= 1
    finally:
        sched.shutdown()


def test_scheduler_preempts_and_resumes_bit_identically(tmp_path):
    """A higher-priority arrival parks the running pack between chunks; the
    parked job reports PREEMPTED, resumes from its checkpoint, and finishes
    with the same result as an undisturbed run."""
    reg = GAMetricsRegistry()
    sched = GAScheduler(registry=reg, ckpt_root=str(tmp_path),
                        chunk_generations=5)
    try:
        lo_spec = _spec(seed=11, generations=80)
        lo = sched.submit(lo_spec, priority=0)
        saw_preempted = False
        hot = None
        for event in sched.stream(lo, timeout=120):
            if event.get("event") == "chunk" and hot is None:
                hot = sched.submit(_spec(problem="rastrigin:4", seed=5,
                                         generations=10), priority=10)
            if sched.job(lo).state == PREEMPTED:
                saw_preempted = True
            if event.get("event") == "end":
                break
        rlo = sched.result(lo, timeout=120)
        sched.result(hot, timeout=120)
        assert saw_preempted or sched.stats()["preemptions"] >= 1
        assert reg.metrics()["jobs"][lo]["preemptions"] >= 1
        solo = ga.solve(lo_spec, backend="reference")
        assert rlo["best_fitness"] == solo.best_fitness
    finally:
        sched.shutdown()


def test_scheduler_failed_job_raises(tmp_path):
    reg = GAMetricsRegistry()
    sched = GAScheduler(registry=reg, ckpt_root=str(tmp_path))
    try:
        def boom(x):
            raise ValueError("bad fitness")

        bad = sched.submit(_spec(fitness=boom, problem=None,
                                 bounds=((-1.0, 1.0),)))
        with pytest.raises(RuntimeError, match="failed"):
            sched.result(bad, timeout=120)
        assert reg.metrics()["jobs"][bad]["status"] == "failed"
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# Registry thread safety
# ---------------------------------------------------------------------------


def test_registry_thread_safe_under_concurrent_writers():
    """N writer threads hammering start/record/finish against concurrent
    metrics() readers: no exceptions, no lost chunks."""
    reg = GAMetricsRegistry()
    n_threads, n_chunks = 8, 50
    errors = []

    def writer(i):
        try:
            job_id = reg.allocate_job_id(f"w{i}")
            reg.start_job(job_id, backend="reference",
                          gens_total=n_chunks, problem="F3", n_vars=2)
            for c in range(n_chunks):
                reg.record_chunk(job_id, {
                    "gens_done": c + 1, "chunk_gens": 1, "wall_s": 1e-4,
                    "best_fitness": float(c), "migrations": 0})
                reg.metrics()
            reg.finish_job(job_id)
        except Exception as e:      # noqa: BLE001 — collected for the assert
            errors.append(repr(e))

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errors, errors
    snap = reg.metrics()
    assert snap["job_count"] == n_threads
    assert snap["jobs_done"] == n_threads
    assert all(j["chunks"] == n_chunks for j in snap["jobs"].values())
    assert snap["generations_total"] == n_threads * n_chunks


def test_registry_pubsub_delivers_chunks_and_end():
    reg = GAMetricsRegistry()
    job_id = reg.allocate_job_id("F3")
    reg.start_job(job_id, backend="reference", gens_total=2,
                  problem="F3", n_vars=2)
    sub = reg.subscribe(job_id)
    reg.record_chunk(job_id, {"gens_done": 1, "chunk_gens": 1,
                              "wall_s": 1e-4, "best_fitness": 1.0})
    reg.finish_job(job_id)
    events = [sub.get(timeout=5), sub.get(timeout=5)]
    assert events[0]["event"] == "chunk"
    assert events[0]["gens_done"] == 1
    assert events[1]["event"] == "end"
    assert events[1]["status"] == "done"
    reg.unsubscribe(job_id, sub)


# ---------------------------------------------------------------------------
# Streaming HTTP endpoints (SSE + long-poll + scheduler gauges)
# ---------------------------------------------------------------------------


def test_metrics_http_streaming_endpoints(tmp_path):
    """Acceptance: per-chunk telemetry streams to an HTTP client WHILE the
    job runs (SSE), the long-poll endpoint blocks until new chunks land,
    and /metrics exports the scheduler + compile-cache gauges."""
    from repro.serve.metrics_http import start_metrics_server

    reg = GAMetricsRegistry()
    sched = GAScheduler(registry=reg, ckpt_root=str(tmp_path),
                        chunk_generations=8)
    server = start_metrics_server(0, registry=reg, host="127.0.0.1")
    port = server.server_address[1]
    try:
        a = sched.submit(_spec(seed=3, generations=48))
        events = []

        def read_sse():
            req = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/jobs/{a}/stream", timeout=60)
            buf = b""
            while True:
                line = req.readline()
                if not line:
                    return
                buf += line
                if line == b"\n":
                    for ln in buf.split(b"\n"):
                        if ln.startswith(b"data: "):
                            events.append(json.loads(ln[len(b"data: "):]))
                    if b"event: end" in buf:
                        return
                    buf = b""

        t = threading.Thread(target=read_sse)
        t.start()
        sched.result(a, timeout=120)
        t.join(30)
        assert events and events[-1].get("event") == "end"
        assert any(e.get("event") == "chunk" for e in events)

        b = sched.submit(_spec(seed=99, generations=48))
        lp = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/jobs/{b}?after=0&timeout=30",
            timeout=60).read())
        assert lp["chunks"] > 0
        sched.result(b, timeout=120)

        jobs = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/jobs", timeout=10).read())
        assert a in jobs["jobs"] and b in jobs["jobs"]
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        for gauge in ("repro_ga_sched_queue_depth",
                      "repro_ga_sched_packs_launched",
                      "repro_ga_compile_cache_hits",
                      "repro_ga_job_status", "repro_ga_pack_size"):
            assert gauge in text, gauge
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/jobs/nope",
                                   timeout=10)
        assert err.value.code == 404
    finally:
        server.shutdown()
        sched.shutdown()
