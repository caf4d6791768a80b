"""The topology × executor decomposition: fused×island_ring is bit-identical
to reference×island_ring, replicas vmap outside the island axis, migration
math is shared with repro.core.islands, the mesh path (shard_map +
ppermute) is bit-identical to the single-device run, and serve-side GA job
telemetry."""

import dataclasses
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro import ga
from repro.core import islands as ISL


def _spec(**kw):
    base = dict(problem="F3", n=32, bits_per_var=10, mode="arith",
                mutation_rate=0.05, seed=11, generations=15,
                n_islands=4, migrate_every=5)
    base.update(kw)
    return ga.GASpec(**base)


def _segment(spec, backend, gens):
    eng = ga.Engine(spec, backend)
    return eng.backend.segment(eng.init_state(), gens)


# ---------------------------------------------------------------------------
# Acceptance: the fused Pallas executor under the island ring is bit-identical
# to the reference executor under the island ring (same seeds, same migration)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem", ["F1", "F2", "F3"])
def test_fused_islands_bit_identical_to_reference_islands(problem):
    spec = _spec(problem=problem)
    seg_r = _segment(spec, "islands", 15)
    seg_f = _segment(spec, "fused-islands", 15)
    # island-stacked populations and every LFSR bank after 3 migration
    # epochs: bit-exact (migration runs between kernel launches on the
    # same elite/worst decisions)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(seg_f.state, field)),
                                      np.asarray(getattr(seg_r.state, field)),
                                      err_msg=field)
    np.testing.assert_array_equal(seg_f.traj_best, seg_r.traj_best)
    np.testing.assert_array_equal(seg_f.best_x, seg_r.best_x)
    assert seg_f.best_y == seg_r.best_y
    assert (seg_f.telemetry.topology.migrations
            == seg_r.telemetry.topology.migrations == 3)
    assert seg_f.telemetry.topology.executor == "fused"
    assert seg_r.telemetry.topology.executor == "reference"
    assert (seg_f.telemetry.topology.topology
            == seg_r.telemetry.topology.topology == "island_ring")


@pytest.mark.parametrize("problem", ["rastrigin:4", "ackley:6"])
def test_fused_islands_nvar_bit_identical(problem):
    """Acceptance: n-variable registry problems run fused-islands (the
    pluggable in-kernel FFM stage) bit-identical to reference islands."""
    spec = _spec(problem=problem)
    seg_r = _segment(spec, "islands", 15)
    seg_f = _segment(spec, "fused-islands", 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(seg_f.state, field)),
                                      np.asarray(getattr(seg_r.state, field)),
                                      err_msg=field)
    assert seg_f.best_y == seg_r.best_y
    np.testing.assert_array_equal(seg_f.best_x, seg_r.best_x)


def test_fused_islands_blackbox_bit_identical():
    """Acceptance: a blackbox fitness (captured arrays and all) runs
    fused-islands bit-identical to reference islands — the old
    'fused FFM needs a closed-form paper problem' gate is gone."""
    import jax.numpy as jnp
    t = jnp.asarray([1.0, -0.5, 0.25], jnp.float32)
    spec = ga.GASpec(fitness=lambda p: jnp.sum(jnp.abs(p - t), axis=-1),
                     bounds=((-2.0, 2.0),) * 3, n=32, bits_per_var=10,
                     mutation_rate=0.05, seed=11, generations=15,
                     n_islands=4, migrate_every=5)
    assert ga.capability_matrix(spec)["fused-islands"] is None
    seg_r = _segment(spec, "islands", 15)
    seg_f = _segment(spec, "fused-islands", 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(seg_f.state, field)),
                                      np.asarray(getattr(seg_r.state, field)),
                                      err_msg=field)
    assert seg_f.best_y == seg_r.best_y
    np.testing.assert_array_equal(seg_f.traj_best, seg_r.traj_best)


def test_fused_islands_end_to_end_solve():
    """`ga.solve(spec, backend="fused-islands")` runs the Pallas step kernel
    under an island ring with migration and converges on the paper problem."""
    spec = _spec(generations=40, migrate_every=8)
    r = ga.solve(spec, backend="fused-islands")
    assert r.backend == "fused-islands"
    assert r.telemetry.topology.migrations == 5
    assert np.isfinite(r.best_fitness) and r.best_fitness < 3.0
    assert r.generations == 40
    assert len(r.traj_best) == 5   # telemetry unit = migration epoch


# ---------------------------------------------------------------------------
# Replica axis outside the island axis (n_repeats × n_islands)
# ---------------------------------------------------------------------------


def test_islands_n_repeats_per_replica_bests():
    solo = ga.solve(_spec(), backend="islands")
    rep = ga.solve(_spec(n_repeats=3), backend="islands")
    per = rep.telemetry.per_repeat.best
    assert per.shape == (3,)
    # replica 0 re-runs the n_repeats=1 island stack bit-exactly
    assert float(per[0]) == solo.best_fitness
    assert rep.best_fitness == float(np.min(per))
    # replicas are seeded distinctly — not all identical
    assert len(np.unique(per)) > 1


def test_fused_islands_n_repeats_matches_reference():
    spec = _spec(n_repeats=2, generations=10)
    r_ref = ga.solve(spec, backend="islands")
    r_fus = ga.solve(spec, backend="fused-islands")
    np.testing.assert_array_equal(r_ref.telemetry.per_repeat.best,
                                  r_fus.telemetry.per_repeat.best)
    assert r_ref.best_fitness == r_fus.best_fitness


# ---------------------------------------------------------------------------
# Shared migration math: the engine's island_ring == core/islands.py
# ---------------------------------------------------------------------------


def test_islands_backend_state_matches_core_local_step():
    """The engine's island_ring epoch == repro.core.islands.make_local_step
    (the independent oracle), state bit-for-bit after 3 epochs."""
    spec = _spec()
    icfg = ISL.IslandConfig(ga=spec.ga_config(), n_islands=4, migrate_every=5)
    epoch = ISL.make_local_step(icfg, spec.fitness_fn())
    old_states = ISL.init_islands_fast(icfg)
    for _ in range(3):
        old_states, _ex, _ey = epoch(old_states)
    seg = _segment(spec, "islands", 15)
    for a, b in zip(old_states, seg.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_migration_none_ablation():
    """migration='none' evolves isolated islands: epochs still chunk the
    run but no elites are exchanged."""
    ring = ga.solve(_spec(), backend="islands")
    none = ga.solve(_spec(migration="none"), backend="islands")
    assert none.telemetry.topology.migrations == 0
    assert ring.telemetry.topology.migrations == 3
    assert np.isfinite(none.best_fitness)


# ---------------------------------------------------------------------------
# In-kernel epochs (gens_per_epoch): launch-overhead amortization that stays
# bit-identical in state and best tracking
# ---------------------------------------------------------------------------


def test_gens_per_epoch_bit_identical_state_and_best():
    """gens_per_epoch>1 folds generations inside one Pallas launch; the
    population/LFSR state AND the best individual (in-kernel fold) must be
    bit-identical to the reference islands run — only the trajectory
    coarsens to one sample per launch."""
    spec = _spec()
    seg_r = _segment(spec, "islands", 15)
    seg_g = _segment(dataclasses.replace(spec, gens_per_epoch=5),
                     "fused-islands", 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(seg_g.state, field)),
                                      np.asarray(getattr(seg_r.state, field)),
                                      err_msg=field)
    assert seg_g.best_y == seg_r.best_y
    np.testing.assert_array_equal(seg_g.best_x, seg_r.best_x)


def test_gens_per_epoch_remainder_launch_on_single_topology():
    """10 generations at gens_per_epoch=4 = two full launches + a remainder
    launch of 2; state/best equal to gens_per_epoch=1, one traj sample per
    launch."""
    spec = _spec(n_islands=1, generations=10)
    a = _segment(spec, "fused", 10)
    b = _segment(dataclasses.replace(spec, gens_per_epoch=4), "fused", 10)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(b.state, field)),
                                      np.asarray(getattr(a.state, field)),
                                      err_msg=field)
    assert a.best_y == b.best_y
    assert a.traj_best.shape[-1] == 10 and b.traj_best.shape[-1] == 3


# ---------------------------------------------------------------------------
# Resident-epoch kernel: gens_per_epoch beyond migrate_every folds the ring
# migration INTO the VMEM-resident launch — bit-identical to the
# between-launch ring at equal seeds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem", ["F1", "F2", "F3", "rastrigin:4"])
def test_resident_epoch_bit_identical_to_reference_islands(problem):
    """gens_per_epoch=10 > migrate_every=5 engages the resident kernel (2
    migration intervals per launch, in-VMEM ring).  State AND best must be
    bit-identical to reference × island_ring; 15 generations = one 2-interval
    launch + one 1-interval remainder launch, so the trajectory coarsens to
    2 samples while migrations still count every in-kernel ring."""
    spec = _spec(problem=problem, gens_per_epoch=10)
    seg_r = _segment(dataclasses.replace(spec, gens_per_epoch=1),
                     "islands", 15)
    seg_f = _segment(spec, "fused-islands", 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(seg_f.state, field)),
                                      np.asarray(getattr(seg_r.state, field)),
                                      err_msg=field)
    assert seg_f.best_y == seg_r.best_y
    np.testing.assert_array_equal(seg_f.best_x, seg_r.best_x)
    assert seg_f.telemetry.plan.mode == "resident"
    assert seg_f.telemetry.topology.launches == 2
    assert (seg_f.telemetry.topology.migrations
            == seg_r.telemetry.topology.migrations == 3)
    assert seg_f.telemetry.topology.telemetry_unit_gens == 10
    assert seg_f.traj_best.shape == (2,)


def test_resident_epoch_n_repeats_matches_reference():
    """Replica groups ride the resident kernel's grid axis — each replica's
    in-VMEM ring stays independent and bit-identical to the reference run."""
    spec = _spec(n_repeats=3, generations=10, gens_per_epoch=10)
    r_ref = ga.solve(dataclasses.replace(spec, gens_per_epoch=1),
                     backend="islands")
    r_res = ga.solve(spec, backend="fused-islands")
    np.testing.assert_array_equal(r_ref.telemetry.per_repeat.best,
                                  r_res.telemetry.per_repeat.best)
    assert r_ref.best_fitness == r_res.best_fitness
    assert r_res.telemetry.plan.mode == "resident"


def test_resident_sharded_epoch_on_one_device_mesh():
    """On a mesh the resident plan keeps one migration interval per launch
    (the boundary elite must ppermute between launches) but runs the
    intra-shard migrations in VMEM — bit-identical to the local reference
    ring even on a 1-device mesh (where the ppermute ring is the wrap)."""
    spec = _spec(gens_per_epoch=10)
    ref = _segment(dataclasses.replace(spec, gens_per_epoch=1),
                   "islands", 15)
    eng = ga.Engine(spec, "fused-islands", mesh=_mesh1())
    shard = eng.backend.segment(eng.init_state(), 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(shard.state, field)),
                                      np.asarray(getattr(ref.state, field)),
                                      err_msg=field)
    assert shard.best_y == ref.best_y
    assert shard.telemetry.plan.mode == "resident-sharded"
    assert shard.telemetry.topology.sharded is True


def test_resident_vmem_budget_fallback_decision():
    """The VMEM-budget estimator drives the fallback: an island stack whose
    working set exceeds the budget reverts to the STREAMED lane when
    a double-buffered tile fits, and all the way to the gridded per-interval
    kernel when none does (still bit-identical), never errors."""
    from repro.kernels import ga_step as K

    cfg = _spec().ga_config()
    # unit decision: the same stack fits a large budget, not a small one
    assert K.resident_fit_reason(cfg, 4, 0, budget=1 << 30) is None
    reason = K.resident_fit_reason(cfg, 4, 0, budget=1 << 10)
    assert reason is not None and "VMEM" in reason
    # big captured consts count against the same budget
    assert K.resident_fit_reason(cfg, 4, 1 << 30) is not None
    # the island blocks scale with the stack, one island's temporaries do
    # not: a budget that holds a 1-island streamed tile but not the
    # 16-island stack lets the HBM-streaming lane absorb the oversize case
    # instead of dropping kernel residency
    big = _spec(n=64, n_islands=16, gens_per_epoch=10)
    big_cfg = big.ga_config()
    budget = 2 * K.resident_vmem_bytes(big_cfg, 1)
    assert K.resident_fit_reason(big_cfg, 16, 0, budget=budget) is not None
    eng = ga.Engine(big, "fused-islands", options=ga.EngineOptions(
        cost_table=False, vmem_budget=budget))
    plan = eng.backend.topology.plan
    assert plan["mode"] == "streamed" and "VMEM" in plan["fallback"]
    assert plan["tile_islands"] == 1
    # with a budget too small for even a double-buffered 1-island tile the
    # planner still reverts to gridded
    eng_g = ga.Engine(big, "fused-islands", options=ga.EngineOptions(
        cost_table=False, vmem_budget=1 << 10))
    plan_g = eng_g.backend.topology.plan
    assert plan_g["mode"] == "gridded" and "VMEM" in plan_g["fallback"]
    # integration: the streamed fallback path still runs, matches reference
    seg_f = eng.backend.segment(eng.init_state(), 10)
    seg_r = _segment(dataclasses.replace(big, gens_per_epoch=1),
                     "islands", 10)
    np.testing.assert_array_equal(np.asarray(seg_f.state.x),
                                  np.asarray(seg_r.state.x))
    assert seg_f.telemetry.plan.fallback == plan["fallback"]


# ---------------------------------------------------------------------------
# Mesh path: shard_map over the island axis + ppermute ring migration,
# bit-identical to the single-device run (any executor, any n_repeats)
# ---------------------------------------------------------------------------


def _mesh1():
    """A 1-device mesh: exercises the whole shard_map/ppermute machinery on
    every host, so the sharded path is tier-1 everywhere."""
    return jax.make_mesh((1,), ("islands",))


def test_fused_islands_on_one_device_mesh_bit_identical():
    spec = _spec()
    local = _segment(spec, "fused-islands", 15)
    eng = ga.Engine(spec, "fused-islands", mesh=_mesh1())
    shard = eng.backend.segment(eng.init_state(), 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(shard.state, field)),
                                      np.asarray(getattr(local.state, field)),
                                      err_msg=field)
    assert shard.best_y == local.best_y
    np.testing.assert_array_equal(shard.traj_best, local.traj_best)
    assert shard.telemetry.topology.sharded is True
    assert shard.telemetry.topology.n_shards == 1


def test_mesh_capability_gates():
    mesh = _mesh1()
    caps = ga.capability_matrix(_spec(), mesh=mesh)
    # PR 2's mesh restrictions are lifted: both executors, n_repeats > 1
    # and migration='none' all compose with the mesh now
    assert caps["islands"] is None and caps["fused-islands"] is None
    assert ga.capability_matrix(_spec(n_repeats=3), mesh=mesh)["islands"] is None
    assert ga.capability_matrix(_spec(migration="none"),
                                mesh=mesh)["islands"] is None
    # 3 islands over 1 shard is fine; over 2 shards it must be rejected
    assert ga.BACKENDS["islands"].supports(_spec(n_islands=3),
                                           mesh=mesh) is None
    import types
    fake2 = types.SimpleNamespace(shape={"islands": 2},
                                  axis_names=("islands",))
    assert "divide evenly" in ga.BACKENDS["islands"].supports(
        _spec(n_islands=3), mesh=fake2)
    # a spec naming axes missing from the mesh is rejected with a reason
    bad = _spec(mesh_axes=("nope",))
    assert "not in the mesh" in ga.BACKENDS["islands"].supports(bad, mesh=mesh)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 devices (CI runs this with "
                           "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
@pytest.mark.parametrize("backend", ["islands", "fused-islands"])
def test_mesh_multi_device_bit_identical_in_process(backend):
    """On a real multi-device host (or the forced-8-device CI job) the
    sharded epoch crosses device boundaries and must still be bit-identical
    to the single-device run."""
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev,), ("islands",))
    spec = _spec(n_islands=2 * n_dev)
    local = _segment(spec, backend, 15)
    eng = ga.Engine(spec, backend, mesh=mesh)
    shard = eng.backend.segment(eng.init_state(), 15)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(shard.state, field)),
                                      np.asarray(getattr(local.state, field)),
                                      err_msg=field)
    assert shard.best_y == local.best_y
    assert shard.telemetry.topology.n_shards == n_dev


def test_fused_islands_mesh_bit_identical_subprocess_8dev():
    """Acceptance: fused-islands on a host-platform mesh of 8 devices is
    bit-identical to the single-device run at equal seeds — F1–F3, an
    n-variable registry problem (rastrigin:4) and a blackbox through the
    in-kernel FFM stage, an n_repeats>1 on-mesh case, AND a mesh built
    with a custom (reversed) device permutation, which must form the SAME
    logical ring (ring_shift_sharded orders by logical mesh coordinates,
    not physical devices).  Spawned so the forced device count doesn't
    leak into this process."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh
from repro import ga
mesh = jax.make_mesh((2, 4), ("data", "model"))

def seg(spec, backend, gens, mesh=None):
    eng = ga.Engine(spec, backend, mesh=mesh)
    return eng.backend.segment(eng.init_state(), gens)

def check(spec, mesh, tag):
    local = seg(spec, "fused-islands", 15)
    shard = seg(spec, "fused-islands", 15, mesh=mesh)
    for f in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(shard.state, f)),
                                      np.asarray(getattr(local.state, f)),
                                      err_msg=tag + " " + f)
    assert shard.best_y == local.best_y, tag
    np.testing.assert_array_equal(shard.traj_best, local.traj_best)
    ti = shard.telemetry.topology
    assert ti.sharded is True and ti.n_shards == 8

for problem in ("F1", "F2", "F3", "rastrigin:4"):
    spec = ga.GASpec(problem=problem, n=32, bits_per_var=10, mode="arith",
                     mutation_rate=0.05, seed=11, generations=15,
                     n_islands=8, migrate_every=5)
    check(spec, mesh, problem)

# blackbox (captured-array FFM stage) on the mesh
t = jnp.asarray([0.5, -1.0, 1.5], jnp.float32)
bb = ga.GASpec(fitness=lambda p: jnp.sum((p - t) ** 2, axis=-1),
               bounds=((-2.0, 2.0),) * 3, n=32, bits_per_var=10,
               mutation_rate=0.05, seed=11, generations=15,
               n_islands=8, migrate_every=5)
check(bb, mesh, "blackbox")

# custom device permutation: same LOGICAL ring, bit-identical run
perm_mesh = Mesh(np.asarray(jax.devices())[::-1].reshape(2, 4),
                 ("data", "model"))
spec = ga.GASpec(problem="F3", n=32, bits_per_var=10, mode="arith",
                 mutation_rate=0.05, seed=11, generations=15,
                 n_islands=8, migrate_every=5)
check(spec, perm_mesh, "permuted-devices")

spec = ga.GASpec(problem="F3", n=32, bits_per_var=10, mode="arith",
                 mutation_rate=0.05, seed=11, generations=10,
                 n_islands=8, migrate_every=5, n_repeats=2)
local = ga.solve(spec, backend="fused-islands")
shard = ga.solve(spec, backend="fused-islands", mesh=mesh)
np.testing.assert_array_equal(local.telemetry.per_repeat.best,
                              shard.telemetry.per_repeat.best)
assert local.best_fitness == shard.best_fitness

# RESIDENT epochs on the mesh: gens_per_epoch=10 > migrate_every=5 runs the
# boundary kernel (intra-shard migration in VMEM, elite ppermute between
# launches) — state/best bit-identical to the local reference ring, on the
# row-major mesh AND a reversed-device mesh (same logical ring), with
# n_repeats riding the kernel grid axis
def check_resident(tag, use_mesh, n_repeats=1):
    spec = ga.GASpec(problem="rastrigin:4", n=32, bits_per_var=10,
                     mode="arith", mutation_rate=0.05, seed=11,
                     generations=15, n_islands=8, migrate_every=5,
                     n_repeats=n_repeats, gens_per_epoch=10)
    ref = seg(dataclasses.replace(spec, gens_per_epoch=1), "islands", 15)
    res = seg(spec, "fused-islands", 15, mesh=use_mesh)
    assert res.telemetry.plan.mode == "resident-sharded", tag
    for f in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(res.state, f)),
                                      np.asarray(getattr(ref.state, f)),
                                      err_msg=tag + " " + f)
    assert res.best_y == ref.best_y, tag
    np.testing.assert_array_equal(res.best_x, ref.best_x)

check_resident("resident", mesh)
check_resident("resident-permuted", perm_mesh)
check_resident("resident-repeats", mesh, n_repeats=2)
print("MESH_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MESH_OK" in r.stdout


# ---------------------------------------------------------------------------
# Spec-level topology plumbing
# ---------------------------------------------------------------------------


def test_topology_field_validation():
    assert _spec().effective_topology == "island_ring"
    assert _spec(n_islands=1).effective_topology == "single"
    assert _spec(n_islands=1, topology="auto").topology is None
    with pytest.raises(ValueError, match="inconsistent"):
        _spec(topology="single")           # n_islands=4
    with pytest.raises(ValueError, match="n_islands > 1"):
        _spec(n_islands=1, topology="island_ring")
    with pytest.raises(ValueError, match="topology must be"):
        _spec(topology="torus")
    with pytest.raises(ValueError, match="migration must be"):
        _spec(migration="broadcast")
    with pytest.raises(ValueError, match="gens_per_epoch must be"):
        _spec(gens_per_epoch=0)
    with pytest.raises(ValueError, match="mesh_axes must be"):
        _spec(mesh_axes=())


def test_gens_per_epoch_beyond_migrate_every_needs_whole_intervals():
    """The gens_per_epoch <= migrate_every cap is GONE (the resident kernel
    folds ring migrations in VMEM); what remains is the whole-interval rule:
    beyond migrate_every, gens_per_epoch must be a multiple of it so every
    launch folds complete migration intervals."""
    # multiples are valid now — this used to be a spec-build error
    assert _spec(migrate_every=4, gens_per_epoch=8).gens_per_epoch == 8
    assert _spec(migrate_every=4, gens_per_epoch=4).gens_per_epoch == 4
    with pytest.raises(ValueError) as ei:
        _spec(migrate_every=4, gens_per_epoch=7)
    msg = str(ei.value)
    assert "gens_per_epoch=7" in msg and "migrate_every=4" in msg
    assert "multiple" in msg
    # single topology is uncapped and rule-free
    solo = _spec(n_islands=1, gens_per_epoch=63)
    assert solo.effective_topology == "single"
    # migration='none' has no interval boundary — no multiple rule either
    none = _spec(migrate_every=4, gens_per_epoch=7, migration="none")
    assert none.gens_per_epoch == 7


def test_auto_and_fallback_routing():
    # auto on CPU routes island specs to the reference×island_ring composition
    assert ga.resolve_backend(_spec()) == "islands"
    # fused-islands falls back to islands when the kernel can't run (lut FFM)
    lut = _spec(mode="lut")
    assert ga.capability_matrix(lut)["fused-islands"] is not None
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = ga.solve(lut, backend="fused-islands")
    assert r.backend == "islands"
    assert any("falling back" in str(x.message) for x in w)
    # pinned single topology keeps island backends off the table
    single = _spec(n_islands=1)
    caps = ga.capability_matrix(single)
    assert caps["reference"] is None
    assert caps["islands"] is None        # permissive: 1-island ring runs
    pinned = _spec(n_islands=1, topology="single")
    assert ga.capability_matrix(pinned)["islands"] is not None


def test_chunked_checkpoint_resume_on_islands(tmp_path):
    spec = _spec(generations=20, migrate_every=5)
    ckpt = str(tmp_path / "isl_ck")
    full = list(ga.Engine(spec, "islands").run_chunked(chunk_generations=5))
    assert [t["gens_done"] for t in full] == [5, 10, 15, 20]
    assert full[-1]["migrations"] == 4

    it = ga.Engine(spec, "islands").run_chunked(chunk_generations=5,
                                                ckpt_dir=ckpt)
    next(it), next(it)     # 2 epochs, then "crash"
    del it
    resumed = list(ga.Engine(spec, "islands").run_chunked(
        chunk_generations=5, ckpt_dir=ckpt))
    assert [t["gens_done"] for t in resumed] == [15, 20]
    assert resumed[-1]["best_fitness"] == full[-1]["best_fitness"]
    assert resumed[-1]["migrations"] == 4


# ---------------------------------------------------------------------------
# Serve-side GA job telemetry
# ---------------------------------------------------------------------------


def test_serve_ga_job_metrics():
    from repro.serve.engine import GAMetricsRegistry, run_ga_job

    reg = GAMetricsRegistry()
    spec = _spec(generations=10, migrate_every=5)
    out = run_ga_job(spec, backend="islands", job_id="job-a",
                     chunk_generations=5, registry=reg)
    assert out["status"] == "done"
    assert out["backend"] == "islands"
    assert out["problem"] == "F3" and out["n_vars"] == 2
    assert out["generations_done"] == 10
    assert out["migration_count"] == 2
    assert out["generations_per_s"] > 0
    # per-shard throughput: 4 islands on 1 shard -> islands x gens/s
    assert out["islands"] == 4 and out["shards"] == 1
    assert out["generations_per_s_per_shard"] == pytest.approx(
        4 * out["generations_per_s"], rel=0.01)
    assert len(out["best_fitness_trajectory"]) == 2
    assert out["best_fitness"] == min(out["best_fitness_trajectory"])

    snap = reg.metrics()
    assert snap["job_count"] == 1 and snap["jobs_done"] == 1
    assert snap["migrations_total"] == 2
    assert snap["generations_total"] == 10
    assert "job-a" in snap["jobs"]


def test_metrics_http_endpoint_scrapes_prometheus_text():
    """The stdlib /metrics endpoint serves the registry snapshot in
    Prometheus text format (and /healthz answers) while jobs run."""
    import urllib.request

    from repro.serve.engine import GAMetricsRegistry, run_ga_job
    from repro.serve.metrics_http import render_prometheus, start_metrics_server

    reg = GAMetricsRegistry()
    server = start_metrics_server(0, registry=reg, host="127.0.0.1")
    try:
        port = server.server_address[1]
        spec = _spec(problem="rastrigin:4", generations=10, migrate_every=5)
        run_ga_job(spec, backend="islands", job_id="job-m",
                   chunk_generations=5, registry=reg)
        url = f"http://127.0.0.1:{port}"
        with urllib.request.urlopen(f"{url}/metrics") as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            txt = resp.read().decode()
        line = ('repro_ga_generations_done{job_id="job-m",'
                'backend="islands",problem="rastrigin"} 10')
        assert line in txt, txt[:500]
        assert 'status="done"' in txt
        assert "repro_ga_jobs 1" in txt
        assert 'repro_ga_n_vars{job_id="job-m"' in txt
        with urllib.request.urlopen(f"{url}/healthz") as resp:
            assert resp.read() == b"ok\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{url}/nope")
        # the renderer is pure: re-rendering the snapshot reproduces the scrape
        assert render_prometheus(reg.metrics()) == txt
    finally:
        server.shutdown()


@pytest.mark.parametrize("plan", ["resident", "streamed"])
def test_multi_interval_launch_keeps_reference_best_tie_rule(plan):
    """rastrigin is symmetric, so mirrored chromosomes tie on fitness (here
    genes 15 and 16 of 5 bits).  A launch folding several migration
    intervals must still report the chromosome the reference picks: the
    first interval the best shows up in, then the first island — not the
    first island over the whole launch."""
    from repro.kernels import ga_step as K
    spec = _spec(problem="rastrigin:2", bits_per_var=5, n=16, n_islands=8,
                 migrate_every=2, gens_per_epoch=8, generations=16, seed=5)
    opts = dict(cost_table=False)
    if plan == "streamed":
        opts["vmem_budget"] = 2 * K.resident_vmem_bytes(spec.ga_config(), 2)
    eng = ga.Engine(spec, "fused-islands", options=ga.EngineOptions(**opts))
    assert eng.backend.topology.plan["mode"] == plan
    seg_f = eng.backend.segment(eng.init_state(), 16)
    seg_r = _segment(dataclasses.replace(spec, gens_per_epoch=1), "islands",
                     16)
    assert seg_f.best_y == seg_r.best_y
    np.testing.assert_array_equal(np.asarray(seg_f.best_x),
                                  np.asarray(seg_r.best_x))
