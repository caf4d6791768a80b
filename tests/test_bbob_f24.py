"""BBOB f24 (the rotated Lunacek bi-Rastrigin function) through the system:
the registered problem against RR-6829 in float64, the hoisted (D, D)
rotation inside the kernels, the island path bit-identical to the
reference executor, and the named phases of `ga.solve`."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ga
from repro.core import fitness as F
from repro.core import ga as G
from repro.core import islands as ISL
from repro.kernels import ga_step as K
from repro.kernels import ops, ref


def _f24_float64(x, d):
    """RR-6829's f24 in float64, its instance drawn as the system's is."""
    rng = np.random.default_rng(1)

    def haar():
        q, r = np.linalg.qr(rng.standard_normal((d, d)))
        return q * np.sign(np.diag(r))

    r_mat, q_mat = haar(), haar()
    sign = np.sign(rng.standard_normal(d))
    lam = 100.0 ** (0.5 * np.arange(d) / (d - 1))
    m = q_mat @ np.diag(lam) @ r_mat
    mu0, dd = 2.5, 1.0
    s = 1.0 - 1.0 / (2.0 * np.sqrt(d + 20.0) - 8.2)
    mu1 = -np.sqrt((mu0 ** 2 - dd) / s)
    xh = 2.0 * sign * x
    z = (xh - mu0) @ m.T
    return (np.minimum(((xh - mu0) ** 2).sum(-1),
                       dd * d + s * ((xh - mu1) ** 2).sum(-1))
            + 10.0 * (d - np.cos(2.0 * np.pi * z).sum(-1))
            + 1e4 * (np.maximum(0.0, np.abs(x) - 5.0) ** 2).sum(-1))


@pytest.mark.parametrize("d", [2, 10, 40])
def test_f24_matches_rr6829_in_float64(d):
    x = np.random.default_rng(d).uniform(-5.0, 5.0, (512, d))
    x32 = x.astype(np.float32)
    got = np.asarray(jax.jit(F.bbob_f24(d))(x32), np.float64)
    want = _f24_float64(x32.astype(np.float64), d)
    # float32 rounding, not a wrong term: f is 10^2-10^3 here, and z (up to
    # a few hundred) carries a relative error of ~D ulps into cos(2 pi z),
    # each term's phase then off by ~1e-4 rad; 40 such terms times 10 move
    # f by up to ~1e-2, a relative 1e-5.  A term left out or a sign flipped
    # moves it by far more than rtol 1e-4.
    np.testing.assert_allclose(got, want, rtol=1e-4)


@pytest.mark.parametrize("d", [2, 40])
def test_f24_is_f_opt_at_x_opt(d):
    inst = F.bbob_f24_instance(d)
    x_opt = (F.BBOB_MU0 / 2.0) * (inst.a / 2.0)
    assert float(F.bbob_f24(d)(jnp.asarray(x_opt))[0]) == F.BBOB_F_OPT


def test_f24_registered_with_its_instance():
    spec = ga.GASpec(problem="bbob_f24:40", n=32, bits_per_var=16)
    assert spec.v == 40 and spec.var_domains()[0] == (-5.0, 5.0)
    closed = K._ffm_jaxpr(spec.program().stage, 32, 40)
    assert [np.shape(c) for c in closed.consts] == [(40, 40), (1, 40)]
    assert K.ffm_const_bytes(spec.program().stage, spec.ga_config()) == 6560
    with pytest.raises(ValueError, match="no separable form"):
        ga.GASpec(problem="bbob_f24:40", mode="lut")


def test_2d_hoisted_constant_is_bound_exactly_in_the_kernel():
    """A (V, V) constant rides into the kernel as its own 2-D block and is
    read back element for element: the fitness contracts the genes with
    every row of W through static slices and adds the element W[V-1, 0],
    which the kernel and the oracle must see alike."""
    v = 8
    w = np.random.default_rng(3).standard_normal((v, v)).astype(np.float32)

    def fit(p):
        wm = jnp.asarray(w)
        acc = p[..., 0:1] * wm[0:1]
        for j in range(1, v):
            acc = acc + p[..., j:j + 1] * wm[j:j + 1]
        return F.vsum(acc) + wm[v - 1, 0]

    prog = F.compile_program(fitness=fit, bounds=((-1.0, 1.0),) * v,
                             bits_per_var=10)
    cfg = G.GAConfig(n=32, c=10, v=v, mutation_rate=0.05, seed=2,
                     mode="arith")
    _, shapes, blocks, nbytes = K._hoist_ffm(prog.stage, cfg.n, cfg.v)
    assert shapes == ((v, v),) and blocks[0].shape == (v, v)
    np.testing.assert_array_equal(np.asarray(blocks[0]), w)
    assert nbytes == w.nbytes
    assert K.ffm_const_vmem_bytes(prog.stage, cfg) == 4 * 8 * 128
    st = ISL.init_islands_fast(ISL.IslandConfig(ga=cfg, n_islands=2))
    k = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=prog.stage)
    r = ref.ga_generation_ref(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                              cfg=cfg, ffm=prog.stage)
    for a, b in zip(k[:4], r[:4]):       # uint32 state: bit-exact
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the fitness row to rounding, as the other kernel sweeps compare it:
    # off the chip the interpreted kernel and the oracle are two XLA:CPU
    # programs, which may contract a*b + c into fused multiply-adds
    # differently
    np.testing.assert_allclose(np.asarray(k[4]), np.asarray(r[4]),
                               rtol=1e-5, atol=1e-5)


def test_resident_islands_bit_identical_to_reference():
    spec = ga.GASpec(problem="bbob_f24:8", n=32, bits_per_var=16,
                     generations=32, n_islands=4, migrate_every=4,
                     gens_per_epoch=8, seed=11)
    opts = ga.EngineOptions(cost_table=False)
    fused = ga.solve(spec, "fused-islands", options=opts)
    plain = ga.solve(spec, "islands", options=opts)
    assert fused.backend == "fused-islands"
    assert fused.telemetry.plan.mode == "resident"
    assert fused.best_fitness == plain.best_fitness
    np.testing.assert_array_equal(fused.best_x, plain.best_x)
    # one trajectory sample a launch: the best of its two intervals
    np.testing.assert_array_equal(
        fused.traj_best, np.minimum(plain.traj_best[0::2],
                                    plain.traj_best[1::2]))


def test_solve_counts_its_phases_once():
    spec = ga.GASpec(problem="bbob_f24:4", n=16, bits_per_var=10,
                     generations=8, n_islands=2, migrate_every=4,
                     gens_per_epoch=8)
    eng = ga.Engine(spec, "fused-islands",
                    options=ga.EngineOptions(cost_table=False))
    first = eng.run()
    assert set(first.telemetry.phase_s) == {"build", "seed", "launch",
                                            "wait", "readback"}
    assert all(v > 0.0 for v in first.telemetry.phase_s.values())
    # seeding, launch and wait are disjoint parts of the run's wall time
    timed = sum(first.telemetry.phase_s[k] for k in ("seed", "launch",
                                                     "wait"))
    assert 0.9 * first.wall_s <= timed <= first.wall_s
    # the construction is counted by the first run only
    assert "build" not in eng.run().telemetry.phase_s


def test_solve_spans_land_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData
    spec = ga.GASpec(problem="bbob_f24:4", n=16, bits_per_var=10,
                     generations=8, n_islands=2, migrate_every=4,
                     gens_per_epoch=8, seed=3)
    with jax.profiler.trace(str(tmp_path / "trace")):
        ga.solve(spec, "fused-islands",
                 options=ga.EngineOptions(cost_table=False))
    path = glob.glob(f"{tmp_path}/trace/**/*.xplane.pb", recursive=True)
    names = {e.name for plane in ProfileData.from_file(path[0]).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    for name in ("ga.engine.build", "ga.problem.build", "ga.engine.seed",
                 "ga.chunk.launch", "ga.chunk.wait", "ga.chunk.readback"):
        assert name in names, name
