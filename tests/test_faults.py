"""Fault tolerance: the deterministic injection registry (`repro.faults`),
checkpoint shard checksums + fallback, engine crash/corrupt resume
bit-identity, `ga.repack_checkpoint` pack slicing, and the scheduler's
retry/backoff, pack-isolation quarantine, deadline and journal-recovery
paths — every failure is injected, never timed."""

import json
import os
import threading

import numpy as np
import pytest

from repro import faults as FLT
from repro import ga
from repro.ckpt import checkpoint as CKPT
from repro.serve import journal as JRN
from repro.serve.engine import GAMetricsRegistry
from repro.serve.scheduler import (DEADLINE_EXCEEDED, DONE, FAILED, QUEUED,
                                   GAScheduler, retry_backoff)


def _spec(**kw):
    base = dict(problem="F3", n=32, bits_per_var=10, mode="arith",
                mutation_rate=0.05, seed=11, generations=20)
    base.update(kw)
    return ga.GASpec(**base)


class FakeClock:
    """Injectable monotonic clock: deadline/backoff tests advance time
    explicitly instead of sleeping."""

    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# Rule grammar + injector determinism
# ---------------------------------------------------------------------------


def test_parse_rule_fields():
    r = FLT.parse_rule("chunk_crash@ga-3:at=2,5:seed=7")
    assert r.site == "chunk_crash" and r.match == "ga-3"
    assert r.at == (2, 5) and r.seed == 7

    r = FLT.parse_rule("ckpt_corrupt:after=3:times=2")
    assert r.after == 3 and r.times == 2.0
    assert [n for n in range(1, 8) if r.decides(n)] == [4, 5]

    r = FLT.parse_rule("slow_chunk:delay=0.01:times=inf")
    assert r.delay_s == 0.01
    assert r.decides(1) and r.decides(10_000)

    with pytest.raises(ValueError, match="unknown fault site"):
        FLT.parse_rule("no_such_site")
    with pytest.raises(ValueError, match="unknown fault rule field"):
        FLT.parse_rule("chunk_crash:bogus=1")
    with pytest.raises(ValueError, match="p must be"):
        FLT.parse_rule("chunk_crash:p=1.5")


def test_probabilistic_rules_are_seed_deterministic():
    r = FLT.parse_rule("chunk_crash:p=0.3:seed=42")
    fire1 = [n for n in range(1, 200) if r.decides(n)]
    fire2 = [n for n in range(1, 200) if r.decides(n)]
    assert fire1 == fire2 and fire1, "same seed must give same decisions"
    other = FLT.parse_rule("chunk_crash:p=0.3:seed=43")
    assert fire1 != [n for n in range(1, 200) if other.decides(n)]
    # p bounds the empirical rate loosely (deterministic, so exact replay)
    assert 0.15 < len(fire1) / 199 < 0.45


def test_injector_counts_occurrences_and_filters_by_tag():
    inj = FLT.parse_faults("chunk_crash@job-a:at=2")
    # tag without the match substring never counts toward the rule
    assert inj.fires("chunk_crash", "job-b|chunk=1") is None
    assert inj.fires("chunk_crash", "job-a|chunk=1") is None   # occurrence 1
    assert inj.fires("chunk_crash", "job-a|chunk=2") is not None
    assert inj.fires("chunk_crash", "job-a|chunk=3") is None
    assert inj.stats() == {"chunk_crash": 1}
    with pytest.raises(FLT.ChunkCrash):
        FLT.parse_faults("chunk_crash:at=1").inject("chunk_crash", "x")
    with pytest.raises(FLT.CompileFail):
        FLT.parse_faults("compile_fail:at=1").inject("compile_fail", "x")


def test_resolve_faults_semantics(monkeypatch):
    monkeypatch.delenv(FLT.ENV_VAR, raising=False)
    assert FLT.resolve_faults(False) is None
    assert FLT.resolve_faults(None) is None        # no ambient env
    inj = FLT.FaultInjector()
    assert FLT.resolve_faults(inj) is inj          # instance passes through
    assert isinstance(FLT.resolve_faults("chunk_crash:at=1"),
                      FLT.FaultInjector)
    with pytest.raises(TypeError):
        FLT.resolve_faults(123)
    # ambient env memoizes per rule string: counters survive re-resolution
    monkeypatch.setenv(FLT.ENV_VAR, "chunk_crash:at=999")
    assert FLT.resolve_faults(None) is FLT.resolve_faults(None)
    # False disarms even against an armed env
    assert FLT.resolve_faults(False) is None


def test_classify_error():
    assert FLT.classify_error(FLT.ChunkCrash("x")) == "transient"
    assert FLT.classify_error(RuntimeError("xla oom")) == "transient"
    assert FLT.classify_error(OSError("disk")) == "transient"
    for exc in (ValueError("bad"), TypeError("bad"), KeyError("bad"),
                AssertionError("bad")):
        assert FLT.classify_error(exc) == "permanent"


def test_corrupt_file_is_deterministic(tmp_path):
    p1, p2 = tmp_path / "a.bin", tmp_path / "b.bin"
    payload = bytes(range(256)) * 16
    p1.write_bytes(payload)
    p2.write_bytes(payload)
    FLT.corrupt_file(str(p1), seed=3)
    FLT.corrupt_file(str(p2), seed=3)
    assert p1.read_bytes() == p2.read_bytes() != payload


def test_retry_backoff_deterministic_and_exponential():
    d = [retry_backoff(0.05, a, token="unit-7") for a in (1, 2, 3)]
    assert d == [retry_backoff(0.05, a, token="unit-7") for a in (1, 2, 3)]
    # base doubling, jitter bounded to +25%
    for attempt, delay in enumerate(d, start=1):
        base = 0.05 * 2 ** (attempt - 1)
        assert base <= delay <= base * 1.25
    # different units decorrelate
    assert retry_backoff(0.05, 1, token="unit-8") != d[0]


# ---------------------------------------------------------------------------
# Checkpoint checksums: validation, fallback, typed corruption error
# ---------------------------------------------------------------------------


def _save_steps(ckpt_dir, steps):
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
    for s in steps:
        CKPT.save(str(ckpt_dir), step=s, tree=tree, extra={"s": s})
    return tree


def test_ckpt_validate_and_fallback(tmp_path):
    tree = _save_steps(tmp_path, [5, 10])
    assert CKPT.validate_step(str(tmp_path), 10) is None
    assert CKPT.latest_step(str(tmp_path)) == 10

    FLT.corrupt_file(os.path.join(str(tmp_path), "step_00000010",
                                  "shard_0.npz"))
    assert "checksum" in CKPT.validate_step(str(tmp_path), 10)
    with pytest.warns(UserWarning, match="failed validation"):
        assert CKPT.latest_step(str(tmp_path)) == 5   # falls back
    assert CKPT.latest_step(str(tmp_path), validate=False) == 10
    with pytest.raises(CKPT.CheckpointCorrupt):
        CKPT.restore(str(tmp_path), 10, tree)
    restored, extra = CKPT.restore(str(tmp_path), 5, tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
    assert extra["s"] == 5


def test_ckpt_legacy_manifest_without_shards_validates(tmp_path):
    _save_steps(tmp_path, [3])
    mpath = os.path.join(str(tmp_path), "step_00000003", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    del manifest["shards"]      # pre-checksum manifest format
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    assert CKPT.validate_step(str(tmp_path), 3) is None
    assert CKPT.latest_step(str(tmp_path)) == 3


def test_ckpt_corrupt_injection_site(tmp_path):
    inj = FLT.parse_faults("ckpt_corrupt:at=1")
    tree = {"w": np.arange(8, dtype=np.float32)}
    CKPT.save(str(tmp_path), step=1, tree=tree, faults=inj, fault_tag="t")
    assert inj.stats() == {"ckpt_corrupt": 1}
    # corruption lands AFTER the checksum was recorded: validation catches it
    assert "checksum" in CKPT.validate_step(str(tmp_path), 1)


# ---------------------------------------------------------------------------
# Engine: injected crash / corruption, resume stays bit-identical
# ---------------------------------------------------------------------------


def test_engine_chunk_crash_then_resume_bit_identical(tmp_path):
    spec = _spec(generations=40)
    want = ga.solve(spec, backend="reference")

    inj = FLT.parse_faults("chunk_crash:at=3")
    eng = ga.Engine(spec, "reference",
                    options=ga.EngineOptions(faults=inj))
    seen = []
    with pytest.raises(FLT.ChunkCrash):
        for tele in eng.run_chunked(chunk_generations=10,
                                    ckpt_dir=str(tmp_path)):
            seen.append(tele["gens_done"])
    assert seen == [10, 20]     # chunk 3's work was lost pre-checkpoint

    eng2 = ga.Engine(spec, "reference")    # a "restarted process": no faults
    last = None
    for tele in eng2.run_chunked(chunk_generations=10,
                                 ckpt_dir=str(tmp_path)):
        if last is None:
            assert tele["resumed_from"] == 20
        else:
            assert tele["resumed_from"] is None   # first chunk only
        last = tele
    assert last["gens_done"] == 40
    assert last["best_fitness"] == want.best_fitness
    np.testing.assert_array_equal(np.asarray(last["best_params"]),
                                  np.asarray(want.best_params))


def test_engine_corrupt_ckpt_falls_back_a_step(tmp_path):
    spec = _spec(generations=40)
    want = ga.solve(spec, backend="reference")

    inj = FLT.parse_faults("ckpt_corrupt:at=2")
    eng = ga.Engine(spec, "reference", options=ga.EngineOptions(faults=inj))
    for _ in eng.run_chunked(chunk_generations=10, ckpt_dir=str(tmp_path),
                             generations=20):
        pass
    assert inj.stats() == {"ckpt_corrupt": 1}   # step 20's shard is rotten

    eng2 = ga.Engine(spec, "reference")
    last = None
    with pytest.warns(UserWarning, match="failed validation"):
        for tele in eng2.run_chunked(chunk_generations=10,
                                     ckpt_dir=str(tmp_path)):
            if last is None:
                assert tele["resumed_from"] == 10   # fell back past step 20
            last = tele
    assert last["gens_done"] == 40
    assert last["best_fitness"] == want.best_fitness


def test_repack_checkpoint_slices_bit_identically(tmp_path):
    specs = [_spec(seed=11, generations=40), _spec(seed=40, generations=40),
             _spec(seed=7, generations=40)]
    pack_dir = str(tmp_path / "pack")
    pe = ga.PackedEngine(specs, "reference")
    for tele in pe.run_chunked(chunk_generations=10, ckpt_dir=pack_dir):
        if tele["gens_done"] >= 20:
            break                       # pack parked at generation 20

    solo_dir = str(tmp_path / "solo1")
    step = ga.repack_checkpoint(pack_dir, specs, [1], solo_dir, "reference")
    assert step == 20
    last = None
    for tele in ga.Engine(specs[1], "reference").run_chunked(
            chunk_generations=10, ckpt_dir=solo_dir):
        last = tele
    want = ga.solve(specs[1], backend="reference")
    assert last["best_fitness"] == want.best_fitness
    np.testing.assert_array_equal(np.asarray(last["best_params"]),
                                  np.asarray(want.best_params))

    pair_dir = str(tmp_path / "pair")
    assert ga.repack_checkpoint(pack_dir, specs, [0, 2], pair_dir,
                                "reference") == 20
    pe2 = ga.PackedEngine([specs[0], specs[2]], "reference")
    last = None
    for tele in pe2.run_chunked(chunk_generations=10, ckpt_dir=pair_dir):
        last = tele
    for spec, jt in zip((specs[0], specs[2]), last["jobs"]):
        assert jt["best_fitness"] == ga.solve(
            spec, backend="reference").best_fitness


def _engine_chunks(spec, ckpt_dir):
    return ga.Engine(spec, "reference").run_chunked(chunk_generations=10,
                                                    ckpt_dir=ckpt_dir)


def _pack_chunks(spec, ckpt_dir):
    for tele in ga.PackedEngine([spec], "reference").run_chunked(
            chunk_generations=10, ckpt_dir=ckpt_dir):
        yield dict(tele["jobs"][0], resumed_from=tele["resumed_from"])


@pytest.mark.parametrize("first,then", [(_engine_chunks, _pack_chunks),
                                        (_pack_chunks, _engine_chunks)],
                         ids=["engine-then-pack", "pack-then-engine"])
def test_solo_checkpoint_resumes_across_entries(tmp_path, first, then):
    """An Engine and a one-job pack write the same checkpoint: either one
    resumes the other's and finishes bit-identical to ga.solve."""
    spec = _spec(generations=40)
    want = ga.solve(spec, backend="reference")
    it = first(spec, str(tmp_path))
    next(it), next(it)      # 20 generations, then "crash"
    del it
    teles = list(then(spec, str(tmp_path)))
    assert [t["gens_done"] for t in teles] == [30, 40]
    assert teles[0]["resumed_from"] == 20
    assert teles[-1]["best_fitness"] == want.best_fitness
    np.testing.assert_array_equal(np.asarray(teles[-1]["best_params"]),
                                  np.asarray(want.best_params))


def test_solo_checkpoint_with_exactly_the_solo_keys_resumes(tmp_path):
    """A checkpoint whose extra holds only the solo keys still resumes."""
    spec = _spec(generations=40)
    want = ga.solve(spec, backend="reference")
    eng = ga.Engine(spec, "reference")
    seg = eng.backend.segment(eng.init_state(), 20)
    CKPT.save(str(tmp_path), step=20, tree=seg.state,
              extra={"gens_done": 20, "chunk_idx": 2, "migrations": 0,
                     "best_y": float(seg.best_y),
                     "best_x": [int(v) for v in seg.best_x],
                     "backend": "reference"})
    teles = list(_engine_chunks(spec, str(tmp_path)))
    assert [(t["chunk"], t["gens_done"]) for t in teles] == [(3, 30),
                                                             (4, 40)]
    assert teles[0]["resumed_from"] == 20
    assert teles[-1]["best_fitness"] == want.best_fitness
    np.testing.assert_array_equal(np.asarray(teles[-1]["best_params"]),
                                  np.asarray(want.best_params))


def test_solve_reaches_the_chunk_crash_site():
    with pytest.raises(FLT.ChunkCrash):
        ga.solve(_spec(), backend="reference",
                 options=ga.EngineOptions(faults="chunk_crash:at=1"))


# ---------------------------------------------------------------------------
# Scheduler: retry, quarantine, deadlines, recovery
# ---------------------------------------------------------------------------


def _sched(tmp_path, **kw):
    kw.setdefault("registry", GAMetricsRegistry())
    kw.setdefault("backend", "reference")
    kw.setdefault("ckpt_root", str(tmp_path / "root"))
    return GAScheduler(**kw)


def test_scheduler_retries_transient_crash(tmp_path):
    inj = FLT.FaultInjector()
    sched = _sched(tmp_path, chunk_generations=10, paused=True,
                   options=ga.EngineOptions(faults=inj))
    try:
        spec = _spec(seed=11, generations=40)
        job = sched.submit(spec)
        inj.add_rule(f"chunk_crash@{job}:at=2")
        sched.resume_dispatch()
        res = sched.result(job, timeout=120)
        assert res["best_fitness"] == ga.solve(
            spec, backend="reference").best_fitness
        assert sched.job(job).retries == 1
        assert sched.stats()["retries"] == 1
        assert sched.registry.metrics()["jobs"][job]["retries"] == 1
    finally:
        sched.shutdown()


def test_scheduler_quarantines_poison_job_pack_survives(tmp_path):
    inj = FLT.FaultInjector()
    sched = _sched(tmp_path, chunk_generations=10, paused=True,
                   max_retries=1, options=ga.EngineOptions(faults=inj))
    try:
        specs = [_spec(seed=11, generations=40), _spec(seed=40,
                                                       generations=40),
                 _spec(seed=7, generations=40)]
        jobs = [sched.submit(s) for s in specs]
        poison = jobs[1]
        # fires on EVERY chunk after the first: the first chunk checkpoints,
        # so the split resumes survivors from the sliced pack state
        inj.add_rule(f"chunk_crash@{poison}:after=1:times=inf")
        sched.resume_dispatch()

        for job, spec in zip(jobs, specs):
            if job == poison:
                continue
            res = sched.result(job, timeout=120)
            want = ga.solve(spec, backend="reference")
            assert res["best_fitness"] == want.best_fitness
            np.testing.assert_array_equal(np.asarray(res["best_params"]),
                                          np.asarray(want.best_params))
        with pytest.raises(RuntimeError, match="injected chunk crash"):
            sched.result(poison, timeout=120)
        pj = sched.job(poison)
        assert pj.state == FAILED and pj.quarantined
        assert sched.stats()["quarantined"] == 1
        assert sched.registry.metrics()["jobs"][poison]["quarantined"] == 1
    finally:
        sched.shutdown()


def test_scheduler_permanent_error_fails_without_retry(tmp_path):
    sched = _sched(tmp_path)
    try:
        # BackendUnsupported is a ValueError: the work is wrong, not the
        # world — the job must fail immediately without burning retries
        job = sched.submit(_spec(generations=10), backend="no_such_backend")
        with pytest.raises(RuntimeError, match="unknown backend"):
            sched.result(job, timeout=120)
        assert sched.job(job).state == FAILED
        assert sched.job(job).retries == 0
        assert sched.stats()["retries"] == 0
    finally:
        sched.shutdown()


def test_scheduler_deadline_exceeded_before_dispatch(tmp_path):
    clock = FakeClock()
    sched = _sched(tmp_path, paused=True, clock=clock)
    try:
        job = sched.submit(_spec(generations=40), deadline_s=10.0)
        clock.advance(11.0)          # blows the budget while still queued
        sched.resume_dispatch()
        with pytest.raises(RuntimeError, match="deadline"):
            sched.result(job, timeout=60)
        assert sched.job(job).state == DEADLINE_EXCEEDED
        assert sched.stats()["deadline_exceeded"] == 1
        assert (sched.registry.metrics()["jobs"][job]["status"]
                == DEADLINE_EXCEEDED)
    finally:
        sched.shutdown()


def test_scheduler_journal_records_lifecycle(tmp_path):
    sched = _sched(tmp_path)
    try:
        job = sched.submit(_spec(generations=20))
        sched.result(job, timeout=120)
    finally:
        sched.shutdown()
    events = JRN.read_journal(sched._journal_path)
    kinds = [e["ev"] for e in events]
    assert kinds[0] == "submit" and "dispatch" in kinds and "done" in kinds
    done = [e for e in events if e["ev"] == "done"][0]
    assert done["job_id"] == job
    assert "best_fitness" in done["result"]


def test_scheduler_recovery_restores_done_and_requeues_pending(tmp_path):
    reg = GAMetricsRegistry()
    root = str(tmp_path / "root")
    spec_done = _spec(seed=11, generations=20)
    spec_pend = _spec(seed=40, generations=20)
    sched = GAScheduler(registry=reg, backend="reference", ckpt_root=root)
    done_id = sched.submit(spec_done)
    res = sched.result(done_id, timeout=120)
    sched.shutdown()

    # simulate a crash mid-life: journal a submit the old process never ran
    j = JRN.SchedulerJournal(os.path.join(root, JRN.JOURNAL_NAME))
    pend_id = "ga-99-F3"
    j.append({"ev": "submit", "job_id": pend_id,
              "spec": JRN.spec_to_json(spec_pend), "backend": "reference",
              "priority": 0, "deadline_s": None, "max_retries": None})
    j.close()

    reg2 = GAMetricsRegistry()
    sched2 = GAScheduler(registry=reg2, backend="reference", ckpt_root=root,
                         recover=True)
    try:
        assert sched2.recovered_total == 1
        # terminal job: result restored without recomputation
        got = sched2.result(done_id, timeout=5)
        assert got["best_fitness"] == res["best_fitness"]
        # pending job: re-enqueued, runs to the solo-identical answer
        got2 = sched2.result(pend_id, timeout=120)
        assert got2["best_fitness"] == ga.solve(
            spec_pend, backend="reference").best_fitness
        assert sched2.job(pend_id).recovered
        # new ids never collide with journaled ones
        fresh = sched2.submit(_spec(seed=7, generations=10))
        assert fresh not in (done_id, pend_id)
        sched2.result(fresh, timeout=120)
    finally:
        sched2.shutdown()


def test_scheduler_recovery_fails_blackbox_jobs_clearly(tmp_path):
    root = str(tmp_path / "root")
    os.makedirs(root, exist_ok=True)
    j = JRN.SchedulerJournal(os.path.join(root, JRN.JOURNAL_NAME))
    j.append({"ev": "submit", "job_id": "ga-1-blackbox", "spec": None,
              "backend": "reference", "priority": 0, "deadline_s": None,
              "max_retries": None})
    j.close()
    sched = GAScheduler(registry=GAMetricsRegistry(), backend="reference",
                        ckpt_root=root, recover=True)
    try:
        job = sched.job("ga-1-blackbox")
        assert job.state == FAILED
        assert "not recoverable" in job.error
    finally:
        sched.shutdown()


def test_journal_replay_folds_last_event_wins(tmp_path):
    events = [
        {"ev": "submit", "job_id": "a", "spec": {"problem": "F3"}},
        {"ev": "submit", "job_id": "b", "spec": {"problem": "F3"}},
        {"ev": "dispatch", "seq": 0, "job_ids": ["a", "b"],
         "ckpt_dir": "/x/pack-0"},
        {"ev": "park", "seq": 0, "job_ids": ["a", "b"],
         "ckpt_dir": "/x/pack-0"},
        {"ev": "done", "job_id": "a", "result": {"best_fitness": 1.0}},
    ]
    jobs, units, job_unit, max_seq = JRN.replay(events)
    assert jobs["a"].terminal and jobs["a"].result == {"best_fitness": 1.0}
    assert jobs["b"].state == "preempted" and not jobs["b"].terminal
    assert units[0]["ckpt_dir"] == "/x/pack-0" and max_seq == 0
    assert job_unit["b"] == 0


def test_journal_torn_tail_is_end_of_log(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    with open(path, "w") as f:
        f.write('{"ev":"submit","job_id":"a","spec":null}\n')
        f.write('{"ev":"dispatch","seq":0,"job_ids":["a"')   # torn mid-append
    events = JRN.read_journal(path)
    assert [e["ev"] for e in events] == ["submit"]


def test_scheduler_worker_alive_and_stream_abort(tmp_path):
    sched = _sched(tmp_path, paused=True)
    assert sched.stats()["worker_alive"] is True
    job = sched.submit(_spec(generations=40))
    got = {}

    def consume():
        try:
            for _ in sched.stream(job, timeout=60):
                pass
        except RuntimeError as e:
            got["err"] = str(e)

    t = threading.Thread(target=consume)
    t.start()
    sched.shutdown()            # job never dispatched: no organic end event
    t.join(timeout=30)
    assert not t.is_alive()
    assert "aborted" in got["err"] and "shut down" in got["err"]
    assert sched.stats()["worker_alive"] is False
    assert sched.job(job).state == QUEUED    # survives for recover=True
