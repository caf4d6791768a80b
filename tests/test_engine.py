"""The unified `repro.ga` Engine API: backend parity, operator registry,
capability checks / fallback, vmapped repeats, chunked checkpoint/resume."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ga
from repro.core import ga as G
from repro.core import lfsr


def _spec(**kw):
    base = dict(problem="F3", n=32, bits_per_var=10, mode="arith",
                mutation_rate=0.05, seed=11, generations=20)
    base.update(kw)
    return ga.GASpec(**base)


# ---------------------------------------------------------------------------
# Backend parity: the fused Pallas kernel must be bit-identical to the
# pure-JAX reference scan (interpret mode on CPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem", ["F1", "F3", "rastrigin:6", "ackley:4",
                                     "rosenbrock:5"])
def test_reference_vs_fused_bit_exact(problem):
    """Paper problems AND the n-variable suite: the kernel's pluggable FFM
    stage is the same traced function the reference executor evaluates."""
    spec = _spec(problem=problem, n=64, generations=4)
    ref = ga.Engine(spec, "reference")
    fus = ga.Engine(spec, "fused")
    seg_r = ref.backend.segment(ref.init_state(), 4)
    seg_f = fus.backend.segment(fus.init_state(), 4)
    # populations and every LFSR bank after 4 generations: bit-exact
    np.testing.assert_array_equal(np.asarray(seg_f.state.x)[0],
                                  np.asarray(seg_r.state.x))
    np.testing.assert_array_equal(np.asarray(seg_f.state.sel_lfsr)[0],
                                  np.asarray(seg_r.state.sel_lfsr))
    np.testing.assert_array_equal(np.asarray(seg_f.state.cross_lfsr)[0],
                                  np.asarray(seg_r.state.cross_lfsr))
    np.testing.assert_array_equal(np.asarray(seg_f.state.mut_lfsr)[0],
                                  np.asarray(seg_r.state.mut_lfsr))
    # identical trajectories and best chromosome
    np.testing.assert_array_equal(seg_f.traj_best, seg_r.traj_best)
    np.testing.assert_array_equal(seg_f.best_x, seg_r.best_x)
    assert seg_f.best_y == seg_r.best_y


def test_every_backend_from_one_spec():
    """Acceptance: one spec object runs F1 and F3 on every registered
    (topology × executor) backend — island_ring backends get the spec's
    island variant, everything else the single-population variant."""
    for problem, thresh in (("F1", -6.0e10), ("F3", 3.0)):
        spec = _spec(problem=problem, n=64, generations=60)
        ispec = dataclasses.replace(spec, n_islands=4, migrate_every=10)
        results = {}
        for b in sorted(ga.BACKENDS):
            cls = ga.BACKENDS[b]
            this = spec if cls.supports(spec) is None else ispec
            assert cls.supports(this) is None, (b, cls.supports(this))
            results[b] = ga.solve(this, backend=b)
        for b, r in results.items():
            assert r.backend == b
            assert np.isfinite(r.best_fitness), (problem, b)
            assert r.best_fitness < thresh, (problem, b, r.best_fitness)
            assert r.best_params.shape == (2,)
        # the jitted paths agree exactly; eager fitness runs op-by-op so
        # XLA's fusion/FMA choices may differ by float ulps
        assert results["reference"].best_fitness == \
            results["fused"].best_fitness
        assert results["islands"].best_fitness == \
            results["fused-islands"].best_fitness
        assert results["reference"].best_fitness == pytest.approx(
            results["eager"].best_fitness, rel=1e-4)


def test_blackbox_runs_fused_bit_exact():
    """Acceptance: a traceable blackbox (no closed form, captures its own
    arrays) is no longer rejected by the fused backend and runs the Pallas
    kernel bit-identical to the reference executor."""
    import jax.numpy as jnp
    target = jnp.asarray([0.25, -1.5, 2.0], jnp.float32)
    spec = ga.GASpec(fitness=lambda p: jnp.sum((p - target) ** 2, axis=-1),
                     bounds=((-4.0, 4.0),) * 3, n=32, bits_per_var=12,
                     mutation_rate=0.05, seed=13, generations=12)
    assert ga.capability_matrix(spec)["fused"] is None
    r = ga.solve(spec, backend="reference")
    f = ga.solve(spec, backend="fused")
    assert f.backend == "fused"
    assert r.best_fitness == f.best_fitness
    np.testing.assert_array_equal(r.best_x, f.best_x)
    np.testing.assert_array_equal(r.traj_best, f.traj_best)
    assert r.best_params.shape == (3,)


def test_problem_registry_spec_plumbing():
    """'name:V' shorthand, registry validation and per-problem telemetry."""
    spec = _spec(problem="rastrigin:8")
    assert spec.problem == "rastrigin" and spec.v == 8
    assert spec.program().modes == ("lut", "arith")
    r = ga.solve(spec, backend="reference")
    assert r.telemetry.problem == "rastrigin" and r.telemetry.n_vars == 8
    assert r.best_params.shape == (8,)
    with pytest.raises(ValueError, match="unknown problem"):
        _spec(problem="nope")
    with pytest.raises(ValueError, match="V=2"):
        _spec(problem="F3:4")
    with pytest.raises(ValueError, match="at least 2"):
        _spec(problem="rosenbrock:1")
    with pytest.raises(ValueError, match="separable"):
        _spec(problem="ackley", mode="lut")
    # custom problems register and run end to end (on the fused kernel too)
    import jax.numpy as jnp
    ga.register_problem(ga.ProblemDef(
        name="_test_tilted",
        fn=lambda v: jnp.sum(v * v + 0.5 * v, axis=-1),
        domain=(-3.0, 3.0)))
    try:
        r = ga.solve(_spec(problem="_test_tilted:3", generations=10),
                     backend="fused")
        assert r.backend == "fused" and np.isfinite(r.best_fitness)
    finally:
        del ga.PROBLEMS["_test_tilted"]


# ---------------------------------------------------------------------------
# Operator registry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(ga.SELECTION))
def test_every_selection_variant_runs_under_solve(name):
    r = ga.solve(_spec(selection=name, generations=30), backend="reference")
    assert np.isfinite(r.best_fitness)
    assert r.best_fitness < 10.0   # all schemes make progress on F3


def test_custom_registered_selection_runs():
    @ga.register_selection("_test_random")
    def random_selection(x, y, sel_lfsr, cfg):
        from repro.core import lfsr
        sel_lfsr, r = lfsr.draw(sel_lfsr, cfg.steps_per_draw)
        i = lfsr.truncate(r[0], cfg.idx_bits).astype(np.int32) % cfg.n
        return x[i], sel_lfsr

    try:
        r = ga.solve(_spec(selection="_test_random"), backend="reference")
        assert np.isfinite(r.best_fitness)
    finally:
        del ga.SELECTION["_test_random"]


def test_unknown_operator_rejected_at_spec_build():
    with pytest.raises(ValueError, match="unknown selection"):
        _spec(selection="nope")


def test_uniform_crossover_conserves_bits():
    spec = _spec(crossover="uniform", mutation="none", generations=5)
    eng = ga.Engine(spec, "reference")
    st = eng.init_state()
    y = eng.backend.executor.fit(st.x)
    cfg = spec.ga_config()
    w, _ = ga.SELECTION["tournament"](st.x, y, st.sel_lfsr, cfg)
    z, _ = ga.CROSSOVER["uniform"](w, st.cross_lfsr, cfg)
    w1, w2 = np.asarray(w[0::2]), np.asarray(w[1::2])
    z1, z2 = np.asarray(z[0::2]), np.asarray(z[1::2])
    np.testing.assert_array_equal(w1 ^ w2, z1 ^ z2)


# ---------------------------------------------------------------------------
# Capability checks and fallback
# ---------------------------------------------------------------------------


def test_capability_matrix_and_fallback():
    lut = _spec(mode="lut")
    caps = ga.capability_matrix(lut)
    assert caps["reference"] is None
    assert "arith" in caps["fused"]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = ga.solve(lut, backend="fused")
    assert r.backend == "reference"
    assert any("falling back" in str(x.message) for x in w)

    # non-pow2 N is fused-incompatible on every lane; N past the onehot
    # cap resolves to the gather lane (sel_lane="auto") and stays fused,
    # while an explicit onehot pin is rejected
    assert ga.capability_matrix(_spec(n=30))["fused"] is not None
    assert ga.capability_matrix(_spec(n=2048))["fused"] is None
    assert _spec(n=2048).resolved_sel_lane == "gather"
    with pytest.raises(ValueError, match="sel_lane='gather'"):
        _spec(n=2048, sel_lane="onehot")
    # non-paper pipeline routes off the fused kernel
    assert ga.capability_matrix(_spec(selection="rank"))["fused"] is not None
    # eager fitness only runs on the eager backend
    caps = ga.capability_matrix(_spec(jit_fitness=False))
    assert caps["eager"] is None and caps["reference"] is not None
    assert ga.resolve_backend(_spec(jit_fitness=False)) == "eager"


def test_unknown_backend_raises():
    with pytest.raises(ga.BackendUnsupported):
        ga.solve(_spec(), backend="gpu_farm")


def test_large_captured_consts_route_off_the_kernel():
    """A fitness closing over a big array (> the hoisted-const VMEM gate)
    is fused-incompatible with an actionable reason and falls back to the
    reference path instead of replicating the array per grid step."""
    import jax.numpy as jnp

    big = jnp.arange(1024 * 1024, dtype=jnp.float32)     # 4 MiB of consts
    spec = ga.GASpec(fitness=lambda p: jnp.sum(p * p, axis=-1) + big[0],
                     bounds=((-1.0, 1.0),) * 2, n=16, bits_per_var=8,
                     generations=5, seed=3)
    caps = ga.capability_matrix(spec)
    assert caps["fused"] is not None and "VMEM gate" in caps["fused"]
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = ga.solve(spec, backend="fused")
    assert r.backend == "reference"
    assert any("falling back" in str(x.message) for x in w)
    # a small captured const stays fused-eligible
    small = jnp.asarray([0.5, -0.5], jnp.float32)
    ok = ga.GASpec(fitness=lambda p: jnp.sum((p - small) ** 2, axis=-1),
                   bounds=((-1.0, 1.0),) * 2, n=16, bits_per_var=8,
                   generations=5, seed=3)
    assert ga.capability_matrix(ok)["fused"] is None


# ---------------------------------------------------------------------------
# Vmapped multi-seed repeats (paper Table 3 methodology)
# ---------------------------------------------------------------------------


def test_repeats_replica_zero_matches_solo_run():
    spec = _spec(generations=25)
    solo = ga.solve(spec, backend="reference")
    rep = ga.solve(dataclasses.replace(spec, n_repeats=4),
                   backend="reference")
    per = rep.telemetry.per_repeat.best
    assert per.shape == (4,)
    assert float(per[0]) == solo.best_fitness
    assert rep.best_fitness == float(np.min(per))
    # replicas are decorrelated — not all identical
    assert len(np.unique(per)) > 1


def test_repeats_match_across_backends():
    spec = _spec(n=32, generations=10, n_repeats=3)
    r_ref = ga.solve(spec, backend="reference")
    r_fus = ga.solve(spec, backend="fused")
    np.testing.assert_array_equal(r_ref.telemetry.per_repeat.best,
                                  r_fus.telemetry.per_repeat.best)


# ---------------------------------------------------------------------------
# Seeding: the initial state is built on the host, bit-identical to the
# device formula it replaced, and a warm process seeds without tracing
# ---------------------------------------------------------------------------


def _device_seeded(seed, n, v, c, n_islands=None):
    """The device formula seeding used before it moved to the host: one
    seed stream sliced into banks, the init bank warmed 8 clocks on the
    device (`lfsr.steps`, a fori_loop), MSB-truncated to c bits."""
    lead = () if n_islands is None else (n_islands,)
    per = 2 * n + v * (n // 2) + 2 * v * n
    s = lfsr.seeds(seed, per * (n_islands or 1)).reshape(*lead, per)
    a, b, d = 2 * n, 2 * n + v * (n // 2), 2 * n + v * (n // 2) + v * n
    sel = s[..., :a].reshape(*lead, 2, n)
    cross = s[..., a:b].reshape(*lead, v, n // 2)
    mut = s[..., b:d].reshape(*lead, v, n)
    init_bank = s[..., -v * n:].reshape(*lead, n, v)
    x = lfsr.truncate(lfsr.steps(init_bank, 8), c)
    k = jnp.int32(0) if n_islands is None else jnp.zeros(lead, jnp.int32)
    return G.GAState(x=x, sel_lfsr=sel, cross_lfsr=cross, mut_lfsr=mut, k=k)


@pytest.mark.parametrize("kind,n,v,c,seed", [
    ("solo", 64, 2, 10, 0),
    ("solo", 64, 2, 10, 1),
    ("solo", 64, 2, 10, 2147483704),
    ("solo", 64, 2, 10, 2**32 - 1),
    ("solo", 48, 5, 28, 7),
    ("packed", 64, 2, 10, 2147483704),
    ("islands", 64, 2, 10, 13),
])
def test_host_seeding_matches_device_formula(kind, n, v, c, seed):
    from repro.core import islands as ISL
    from repro.ga import backends as B
    cfg = G.GAConfig(n=n, c=c, v=v, seed=seed, mode="arith")
    if kind == "solo":
        got, want = G.init_state(cfg), _device_seeded(seed, n, v, c)
    elif kind == "packed":
        seeds = [seed, seed + 1, seed + 7]
        got = B._stack_states_seeded(cfg, seeds)
        want = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[_device_seeded(s, n, v, c) for s in seeds])
    else:
        got = ISL.init_islands_fast(ISL.IslandConfig(ga=cfg, n_islands=8))
        want = _device_seeded(seed, n, v, c, n_islands=8)
    for name, g, w in zip(G.GAState._fields, got, want):
        assert isinstance(g, jax.Array), name
        assert g.dtype == w.dtype and g.shape == w.shape, name
        # the warm segment executables are keyed on these: a committed or
        # weak-typed state would build new cache entries
        assert not g.committed and not g.weak_type, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def test_second_engine_seeds_without_tracing():
    spec = _spec(n=64, generations=100)
    ga.Engine(spec, "fused").init_state()
    eng = ga.Engine(dataclasses.replace(spec, seed=12), "fused")
    traces = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            traces.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        jax.block_until_ready(eng.init_state())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert traces == []


# ---------------------------------------------------------------------------
# One FitnessProgram per problem shape (compile_cache.PROGRAM_CACHE)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("field,value", [
    ("seed", 12), ("generations", 7), ("n_repeats", 3), ("n", 64),
    ("n_islands", 2)])
def test_specs_of_one_problem_shape_share_a_program(field, value):
    spec = _spec()
    other = dataclasses.replace(spec, **{field: value})
    assert other.program() is spec.program()


@pytest.mark.parametrize("a,b", [
    (dict(problem="F3"), dict(problem="F1")),
    (dict(problem="rastrigin:4"), dict(problem="rastrigin:6")),
    (dict(bits_per_var=10), dict(bits_per_var=12)),
    (dict(problem="rastrigin:4", mode="arith"),
     dict(problem="rastrigin:4", mode="lut")),
    (dict(minimize=True), dict(minimize=False))])
def test_specs_of_another_problem_shape_get_their_own_program(a, b):
    assert _spec(**a).program() is not _spec(**b).program()


def test_distinct_blackboxes_get_their_own_programs():
    def f(x):
        return jnp.sum(x * x, axis=-1)

    def g(x):
        return jnp.sum(x * x, axis=-1)

    box = ((-1.0, 1.0), (-1.0, 1.0))
    a = _spec(problem=None, fitness=f, bounds=box)
    assert dataclasses.replace(a, seed=3).program() is a.program()
    assert _spec(problem=None, fitness=g, bounds=box).program() \
        is not a.program()


def test_second_fused_islands_build_reuses_the_ffm_trace():
    """A fresh seed-only spec (one job of a solve stream) finds its FFM
    stage already traced: no `_ffm_jaxpr` miss, the blackbox traced once
    across both builds."""
    from repro.kernels.ga_step import ffm_trace_cache_info

    calls = []

    def fit(x):
        calls.append(1)
        return -((x[:, 0] - 0.5) ** 2 + (x[:, 1] + 0.25) ** 2)

    spec = _spec(fitness=fit, problem=None,
                 bounds=((-1.0, 1.0), (-1.0, 1.0)),
                 n_islands=2, migrate_every=4, migration="none",
                 generations=8)
    ga.Engine(spec, "fused-islands")
    misses = ffm_trace_cache_info().misses
    ga.Engine(dataclasses.replace(spec, seed=12), "fused-islands")
    assert ffm_trace_cache_info().misses == misses
    assert sum(calls) == 1


def test_shared_program_runs_bit_identical_to_a_fresh_one():
    spec = _spec(problem="rastrigin:4", n_islands=2, migrate_every=4,
                 generations=8)
    ga.Engine(spec, "fused-islands")
    again = dataclasses.replace(spec, seed=12)
    shared = ga.Engine(again, "fused-islands").run()
    assert again.program() is spec.program()
    ga.PROGRAM_CACHE.reset()
    ga.RUNNER_CACHE.reset()
    fresh_spec = dataclasses.replace(spec, seed=12)
    fresh = ga.Engine(fresh_spec, "fused-islands").run()
    assert fresh_spec.program() is not spec.program()
    assert ga.PROGRAM_CACHE.stats()["misses"] == 1
    assert fresh.best_fitness == shared.best_fitness
    np.testing.assert_array_equal(fresh.best_x, shared.best_x)
    np.testing.assert_array_equal(fresh.traj_best, shared.traj_best)


# ---------------------------------------------------------------------------
# Chunked streaming + checkpoint/resume
# ---------------------------------------------------------------------------


def test_chunked_equals_straight_run(tmp_path):
    spec = _spec(generations=40)
    eng = ga.Engine(spec, "reference")
    teles = list(eng.run_chunked(chunk_generations=10))
    assert [t["gens_done"] for t in teles] == [10, 20, 30, 40]
    straight = ga.solve(spec, backend="reference")
    assert teles[-1]["best_fitness"] == straight.best_fitness


def test_checkpoint_resume(tmp_path):
    spec = _spec(generations=40)
    ckpt = str(tmp_path / "ga_ck")
    full = list(ga.Engine(spec, "reference").run_chunked(
        chunk_generations=10))

    it = ga.Engine(spec, "reference").run_chunked(chunk_generations=10,
                                                  ckpt_dir=ckpt)
    next(it), next(it)      # 20 generations, then "crash"
    del it
    resumed = list(ga.Engine(spec, "reference").run_chunked(
        chunk_generations=10, ckpt_dir=ckpt))
    assert [t["gens_done"] for t in resumed] == [30, 40]
    assert resumed[-1]["best_fitness"] == full[-1]["best_fitness"]


def test_islands_backend_chunks_by_epoch():
    spec = _spec(n_islands=4, migrate_every=8, generations=32)
    r = ga.solve(spec)   # auto routes to islands
    assert r.backend == "islands"
    assert r.generations == 32
    assert len(r.traj_best) == 4   # one telemetry entry per migration epoch


@pytest.mark.parametrize("backend,kw", [
    ("reference", {}),
    ("islands", dict(n_islands=4, migrate_every=5)),
    ("reference", dict(n_repeats=2)),
], ids=["reference", "islands", "reference-repeats2"])
def test_every_entry_runs_the_same_chunk_loop(backend, kw):
    """Engine.run, Engine.run_chunked and a pack of one or of two jobs agree
    on a job's best, its chromosome and its last chunk's trajectory."""
    spec = _spec(generations=30, **kw)
    other = dataclasses.replace(spec, seed=spec.seed + 100)
    straight = ga.Engine(spec, backend).run()
    chunked = list(ga.Engine(spec, backend).run_chunked(
        chunk_generations=10))[-1]
    alone = ga.PackedEngine([spec], backend).run(chunk_generations=10)[0]
    paired = ga.PackedEngine([spec, other], backend).run(
        chunk_generations=10)[0]
    n_last = len(chunked["traj_best"])
    assert 0 < n_last < len(straight.traj_best)
    want = (straight.best_fitness, straight.best_params,
            straight.traj_best[-n_last:])
    for got in (chunked, alone, paired):
        assert got["best_fitness"] == want[0]
        np.testing.assert_array_equal(got["best_params"], want[1])
        np.testing.assert_array_equal(got["traj_best"], want[2])


# ---------------------------------------------------------------------------
# Result semantics
# ---------------------------------------------------------------------------


def test_lut_fixed_point_descaled():
    spec = ga.paper_spec("F1", n=32, m=26, mode="lut", mutation_rate=0.05,
                         seed=7, generations=100)
    r = ga.solve(spec, backend="reference")
    # real units, not fixed-point: the paper's global minimum ~ -6.897e10
    assert r.best_fitness == pytest.approx(-6.897e10, rel=0.01)
    assert r.best_params[1] == pytest.approx(-4096.0, abs=2.0)


def test_deprecated_entry_points_folded():
    """Deprecation clock part 2: the old shim drivers are gone — the engine
    is the only entry point — while the engine-internal building blocks
    (`run_scan`) still agree with `ga.solve` bit-for-bit."""
    from repro.core import islands as ISL
    from repro.kernels import ops
    for mod, name in ((G, "run"), (G, "run_unjitted"),
                      (ISL, "run_local"), (ISL, "run_sharded"),
                      (ops, "ga_run_kernel")):
        assert not hasattr(mod, name), f"{mod.__name__}.{name} should be gone"

    spec = _spec(generations=30)
    old = G.run_scan(spec.ga_config(), spec.fitness_fn(), 30)
    new = ga.solve(spec, backend="reference")
    assert float(old.best_y) == new.best_fitness
    np.testing.assert_array_equal(np.asarray(old.best_x), new.best_x)
