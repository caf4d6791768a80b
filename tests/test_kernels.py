"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

The FFM stage is pluggable (`FitnessProgram.stage` traced into the kernel),
so the sweeps cover the paper problems, the n-variable registry suite AND a
user blackbox closing over its own arrays (the closure-constant hoisting
path)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fitness as F
from repro.core import ga as G
from repro.core import islands as ISL
from repro.core import lfsr
from repro.kernels import ops, ref


def _states(cfg, n_islands=2):
    icfg = ISL.IslandConfig(ga=cfg, n_islands=n_islands)
    return ISL.init_islands_fast(icfg)


def _ffm(problem: str, cfg: G.GAConfig):
    return F.compile_program(problem=problem, n_vars=cfg.v,
                             bits_per_var=cfg.c).stage


@pytest.mark.parametrize("n", [16, 64, 256, 1024])
@pytest.mark.parametrize("problem", ["F1", "F2", "F3"])
def test_ga_step_matches_ref_population_sweep(n, problem):
    cfg = G.GAConfig(n=n, c=10, v=2, mutation_rate=0.03, seed=n, mode="arith")
    ffm = _ffm(problem, cfg)
    st = _states(cfg)
    k = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=ffm)
    r = ref.ga_generation_ref(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                              cfg=cfg, ffm=ffm)
    for a, b in zip(k[:4], r[:4]):       # uint32 state: bit-exact
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(k[4]), np.asarray(r[4]), rtol=2e-5)


@pytest.mark.parametrize("problem,v", [("sphere", 4), ("rastrigin", 6),
                                       ("rosenbrock", 4), ("ackley", 8)])
def test_ga_step_nvar_suite_matches_ref(problem, v):
    """The V-variable decode + suite objectives run inside the kernel and
    stay bit-exact with the oracle (which evaluates the same stage)."""
    cfg = G.GAConfig(n=64, c=10, v=v, mutation_rate=0.03, seed=v,
                     mode="arith")
    ffm = _ffm(problem, cfg)
    st = _states(cfg, n_islands=3)
    k = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=ffm)
    r = ref.ga_generation_ref(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                              cfg=cfg, ffm=ffm)
    for a, b in zip(k[:4], r[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(k[4]), np.asarray(r[4]), rtol=2e-5)


def test_ga_step_blackbox_closure_constants():
    """A user fitness closing over its own arrays runs in-kernel: the
    captured constants are hoisted into kernel inputs (Pallas forbids
    implicit array captures), bit-exact with the XLA evaluation."""
    cfg = G.GAConfig(n=32, c=12, v=5, mutation_rate=0.05, seed=9,
                     mode="arith")
    target = jnp.asarray(np.linspace(-1.0, 1.0, 5), jnp.float32)
    weight = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0], jnp.float32)
    prog = F.compile_program(
        fitness=lambda p: jnp.sum(weight * (p - target) ** 2, axis=-1),
        bounds=((-2.0, 2.0),) * 5, bits_per_var=cfg.c)
    st = _states(cfg, n_islands=2)
    k = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=prog.stage)
    r = ref.ga_generation_ref(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                              cfg=cfg, ffm=prog.stage)
    for a, b in zip(k[:4], r[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(k[4]), np.asarray(r[4]), rtol=2e-5)


@pytest.mark.parametrize("c", [6, 10, 14, 15])
@pytest.mark.parametrize("mr", [0.01, 0.1])
def test_ga_step_matches_ref_width_sweep(c, mr):
    cfg = G.GAConfig(n=64, c=c, v=2, mutation_rate=mr, seed=c, mode="arith")
    ffm = _ffm("F3", cfg)
    st = _states(cfg, n_islands=3)
    k = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=ffm)
    r = ref.ga_generation_ref(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                              cfg=cfg, ffm=ffm)
    for a, b in zip(k[:4], r[:4]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("minimize", [True, False])
def test_ga_step_minimize_maximize(minimize):
    cfg = G.GAConfig(n=128, c=10, v=2, mutation_rate=0.02, seed=5,
                     minimize=minimize, mode="arith")
    ffm = _ffm("F2", cfg)
    st = _states(cfg)
    k = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=ffm)
    r = ref.ga_generation_ref(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                              cfg=cfg, ffm=ffm)
    np.testing.assert_array_equal(np.asarray(k[0]), np.asarray(r[0]))


def test_ga_kernel_multi_generation_converges():
    """One launch, 100 in-kernel generations (gens>1 VMEM residency), with
    the in-kernel best fold — converges near the F3 optimum."""
    cfg = G.GAConfig(n=64, c=10, v=2, mutation_rate=0.05, seed=11, mode="arith")
    ffm = _ffm("F3", cfg)
    st = _states(cfg, n_islands=4)
    out = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                            cfg=cfg, ffm=ffm, gens=100, track_best=True)
    best_y = out[5]
    assert best_y.shape == (4,)
    assert float(jnp.min(best_y)) < 1.0  # near the F3 optimum


@pytest.mark.parametrize("gens", [1, 7])
def test_ga_kernel_track_best_matches_oracle(gens):
    """track_best folds the running best inside the kernel with the
    reference argmin tie rule: re-running generation by generation and
    folding outside must give bit-identical (best_y, best_x)."""
    cfg = G.GAConfig(n=32, c=10, v=2, mutation_rate=0.05, seed=3, mode="arith")
    ffm = _ffm("F1", cfg)
    st = _states(cfg, n_islands=3)
    out = ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                            cfg=cfg, ffm=ffm, gens=gens, track_best=True)
    by_k, bx_k = np.asarray(out[5]), np.asarray(out[6])

    x, sel, cross, mut = st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr
    by = np.full((3,), np.inf, np.float32)
    bx = np.zeros((3, cfg.v), np.uint32)
    for _ in range(gens):
        x2, sel, cross, mut, y = ops.ga_generation(x, sel, cross, mut,
                                                   cfg=cfg, ffm=ffm)
        y = np.asarray(y)
        idx = np.argmin(y, axis=1)
        gb = y[np.arange(3), idx]
        better = gb < by
        by = np.where(better, gb, by)
        bx = np.where(better[:, None], np.asarray(x)[np.arange(3), idx], bx)
        x = x2
    np.testing.assert_array_equal(by_k, by)
    np.testing.assert_array_equal(bx_k, bx)
    # and the state outputs are unchanged by the extra best outputs
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x))


@pytest.mark.parametrize("shape", [(7,), (128,), (3, 5), (2, 130)])
@pytest.mark.parametrize("steps", [1, 3, 13, 40])
def test_lfsr_kernel_matches_ref(shape, steps):
    s = lfsr.seeds(99, int(np.prod(shape))).reshape(shape)
    got = ops.lfsr_advance(s, steps)
    want = ref.lfsr_advance_ref(s, steps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ga_epoch_kernel_matches_local_step_oracle():
    """The resident-epoch kernel (islands in one VMEM block, ring migration
    inside the fori_loop) reproduces repro.core.islands.make_local_step —
    the independent between-launch oracle — bit-for-bit over 3 migration
    intervals in a SINGLE launch."""
    cfg = G.GAConfig(n=32, c=10, v=2, mutation_rate=0.05, seed=11,
                     mode="arith")
    ffm = _ffm("F3", cfg)
    icfg = ISL.IslandConfig(ga=cfg, n_islands=4, migrate_every=5)
    states = ISL.init_islands_fast(icfg)
    oracle = states
    epoch = ISL.make_local_step(icfg, ffm)
    for _ in range(3):
        oracle, _ex, _ey = epoch(oracle)

    x, sel, cross, mut, y, by, bx, bg = ops.ga_epoch(
        states.x[None], states.sel_lfsr[None], states.cross_lfsr[None],
        states.mut_lfsr[None], cfg=cfg, ffm=ffm, migrate_every=5,
        intervals=3)
    np.testing.assert_array_equal(np.asarray(x[0]), np.asarray(oracle.x))
    np.testing.assert_array_equal(np.asarray(sel[0]),
                                  np.asarray(oracle.sel_lfsr))
    np.testing.assert_array_equal(np.asarray(cross[0]),
                                  np.asarray(oracle.cross_lfsr))
    np.testing.assert_array_equal(np.asarray(mut[0]),
                                  np.asarray(oracle.mut_lfsr))
    assert by.shape == (1, 4) and bx.shape == (1, 4, 2)
    assert y.shape == (1, 4, cfg.n)
    assert bg.shape == (1, 4) and 0 <= int(bg.min()) <= int(bg.max()) < 15


def test_ga_epoch_kernel_boundary_is_partial_ring():
    """boundary=True leaves island 0 for the between-launch ppermute: the
    intra-shard splices match the full in-kernel ring everywhere but island
    0, and (send elite, island-0 worst slot) equal what the full ring would
    have used."""
    cfg = G.GAConfig(n=32, c=10, v=2, mutation_rate=0.05, seed=7,
                     mode="arith")
    ffm = _ffm("F1", cfg)
    st = _states(cfg, n_islands=4)
    full = ops.ga_epoch(st.x[None], st.sel_lfsr[None], st.cross_lfsr[None],
                        st.mut_lfsr[None], cfg=cfg, ffm=ffm,
                        migrate_every=3, intervals=1)
    part = ops.ga_epoch(st.x[None], st.sel_lfsr[None], st.cross_lfsr[None],
                        st.mut_lfsr[None], cfg=cfg, ffm=ffm,
                        migrate_every=3, intervals=1, boundary=True)
    xf, xp = np.asarray(full[0][0]), np.asarray(part[0][0])
    send, w0 = np.asarray(part[7][0]), int(np.asarray(part[8][0]))
    np.testing.assert_array_equal(xp[1:], xf[1:])       # intra-shard splices
    # island 0: splicing send (the wrap elite on a 1-shard ring) at w0
    # reproduces the full ring
    xp0 = xp[0].copy()
    xp0[w0] = send
    np.testing.assert_array_equal(xp0, xf[0])
    # migration fitness + best tracking identical either way
    np.testing.assert_array_equal(np.asarray(part[4]), np.asarray(full[4]))
    np.testing.assert_array_equal(np.asarray(part[5]), np.asarray(full[5]))


def test_kernel_ffm_const_size_gate():
    """Hoisted FFM closure constants above the VMEM gate are rejected with
    an actionable error instead of silently replicating per grid step."""
    cfg = G.GAConfig(n=16, c=8, v=2, seed=1, mode="arith")
    big = jnp.zeros((1024, 1024), jnp.float32)          # 4 MiB > 2 MiB gate
    prog = F.compile_program(
        fitness=lambda p: jnp.sum(p, axis=-1) + big[0, 0],
        bounds=((-1.0, 1.0),) * 2, bits_per_var=cfg.c)
    st = _states(cfg, 1)
    with pytest.raises(ValueError, match="VMEM gate"):
        ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=prog.stage)


def test_kernel_rejects_oversize_population_on_onehot_lane():
    """The onehot lane's (N, N) tournament matrices cap N; the error names
    the gather lane as the fix, and the gather lane actually runs there."""
    cfg = G.GAConfig(n=2048, c=10, v=2, seed=1, mode="arith")
    ffm = _ffm("F3", cfg)
    st = _states(cfg, 1)
    with pytest.raises(ValueError, match="sel_lane='gather'"):
        ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=ffm)
    out = ops.ga_generation(
        st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
        cfg=dataclasses.replace(cfg, sel_lane="gather"), ffm=ffm)
    assert out[0].shape == st.x.shape


def test_kernel_rejects_non_pow2_population():
    cfg = G.GAConfig(n=30, c=10, v=2, seed=1, mode="arith",
                     sel_lane="gather")
    ffm = _ffm("F3", cfg)
    st = _states(cfg, 1)
    with pytest.raises(ValueError, match="power-of-two"):
        ops.ga_generation(st.x, st.sel_lfsr, st.cross_lfsr, st.mut_lfsr,
                          cfg=cfg, ffm=ffm)
