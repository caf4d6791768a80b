"""The indexed-gather selection lane vs the one-hot MXU lane.

The fused kernels grow a second, bit-identical way to realize tournament
selection: `sel_lane="gather"` reads fitness and splices winners through
dynamic indexing (`jnp.take`, O(N·V) working set) instead of one-hot
matmul contractions (O(N²)).  Because the one-hot matmuls were already
EXACT (uint32 split into 16-bit halves, f32 HIGHEST-precision dots), the
two lanes must agree bit-for-bit with each other and with the pure-jnp
reference on every shape — which is what this file pins, along with the
lifted N cap, the lane-aware rejection errors, the measured cross-lane
planner, and the eager backend's pooled host-fitness determinism.
"""

import os
import subprocess
import sys
import dataclasses

import numpy as np
import pytest

from repro import ga
from repro.core import ga as G


def _spec(**kw):
    base = dict(problem="F3", n=32, bits_per_var=8, mode="arith",
                mutation_rate=0.05, seed=7, generations=16,
                n_islands=2, migrate_every=4, gens_per_epoch=8)
    base.update(kw)
    return ga.GASpec(**base)


def _solve(spec, backend, **opt_kw):
    opts = ga.EngineOptions(cost_table=False, **opt_kw)
    return ga.solve(spec, backend=backend, options=opts)


# ---------------------------------------------------------------------------
# Bit-identity: gather == onehot == reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem", ["F1", "F2", "F3", "rastrigin:4"])
def test_lanes_bit_identical_to_reference(problem):
    """Both lanes of the fused resident epoch (gens_per_epoch > 1, ring
    migration in VMEM) reproduce the islands reference bit-for-bit."""
    spec = _spec(problem=problem)
    ref = _solve(spec, "islands")
    for lane in ("onehot", "gather"):
        res = _solve(dataclasses.replace(spec, sel_lane=lane),
                     "fused-islands")
        assert res.telemetry.plan.lane == lane
        assert res.best_fitness == ref.best_fitness, lane
        np.testing.assert_array_equal(np.asarray(res.best_x),
                                      np.asarray(ref.best_x),
                                      err_msg=lane)
        # resident launches sample the trajectory once per launch, the
        # reference once per generation — the final sample must agree
        assert res.traj_best[-1] == ref.traj_best[-1], lane


def test_lanes_bit_identical_with_stacked_repeats():
    """The replica axis (n_repeats > 1) rides both lanes identically."""
    spec = _spec(n_repeats=3, seed=5)
    ref = _solve(spec, "islands")
    for lane in ("onehot", "gather"):
        res = _solve(dataclasses.replace(spec, sel_lane=lane),
                     "fused-islands")
        assert res.best_fitness == ref.best_fitness, lane
        np.testing.assert_array_equal(
            np.asarray(res.telemetry.per_repeat.best),
            np.asarray(ref.telemetry.per_repeat.best), err_msg=lane)


def test_gather_lane_runs_past_the_onehot_cap():
    """N=2048 — impossible on the onehot lane — runs the fused kernel on
    the gather lane, and sel_lane='auto' resolves there on its own."""
    spec = ga.GASpec(problem="F1", n=2048, bits_per_var=8, mode="arith",
                     mutation_rate=0.02, seed=3, generations=4,
                     gens_per_epoch=2, n_islands=1)
    assert spec.resolved_sel_lane == "gather"
    res = _solve(spec, "fused", interpret=True)
    ref = _solve(spec, "reference")
    assert res.best_fitness == ref.best_fitness
    np.testing.assert_array_equal(np.asarray(res.best_x),
                                  np.asarray(ref.best_x))


def test_lanes_bit_identical_on_eight_fake_device_mesh():
    """Both lanes under the sharded ring (8 fake devices) agree with each
    other and the local islands reference (subprocess so the forced device
    count doesn't leak into the suite)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_GA_COST_TABLE"] = "off"
import dataclasses, jax, numpy as np
from repro import ga
mesh = jax.make_mesh((8,), ("islands",))
spec = ga.GASpec(problem="F3", n=16, bits_per_var=8, mode="arith",
                 mutation_rate=0.02, seed=2, generations=16,
                 n_islands=8, migrate_every=4, gens_per_epoch=8)
ref = ga.solve(spec, backend="islands",
               options=ga.EngineOptions(cost_table=False))
for lane in ("onehot", "gather"):
    res = ga.solve(dataclasses.replace(spec, sel_lane=lane),
                   backend="fused-islands",
                   options=ga.EngineOptions(mesh=mesh, cost_table=False))
    assert res.telemetry.topology.n_shards == 8, res.telemetry.topology
    assert res.best_fitness == ref.best_fitness, lane
    np.testing.assert_array_equal(np.asarray(res.best_x),
                                  np.asarray(ref.best_x), err_msg=lane)
print("LANES_MESH_OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "LANES_MESH_OK" in r.stdout


# ---------------------------------------------------------------------------
# Lane resolution, rejection errors and the options override
# ---------------------------------------------------------------------------


def test_auto_lane_resolution_and_compile_key():
    assert _spec(n=64).resolved_sel_lane == "onehot"
    assert _spec(n=2048, n_islands=1).resolved_sel_lane == "gather"
    # the resolved lane is part of the compiled-runner identity
    k_on = _spec(sel_lane="onehot").compile_key()
    k_ga = _spec(sel_lane="gather").compile_key()
    assert k_on != k_ga


def test_onehot_pin_past_cap_rejected_with_actionable_error():
    with pytest.raises(ValueError, match="sel_lane='gather'"):
        _spec(n=2048, n_islands=1, sel_lane="onehot")
    # the options-level override flows through the same spec validation
    with pytest.raises(ValueError, match="sel_lane='gather'"):
        ga.Engine(_spec(n=2048, n_islands=1), "fused",
                  options=ga.EngineOptions(cost_table=False,
                                           sel_lane="onehot"))


def test_options_lane_override_reaches_the_kernel():
    spec = _spec()            # sel_lane defaults to "auto" -> onehot at N=32
    eng = ga.Engine(spec, "fused-islands",
                    options=ga.EngineOptions(cost_table=False,
                                             sel_lane="gather"))
    assert eng.backend.spec.sel_lane == "gather"
    assert eng.backend.topology.cfg.sel_lane == "gather"
    ref = _solve(spec, "islands")
    out = eng.run()
    assert out.best_fitness == ref.best_fitness
    assert out.telemetry.plan.lane == "gather"


def test_bad_lane_values_rejected():
    with pytest.raises(ValueError, match="sel_lane"):
        _spec(sel_lane="mxu")
    with pytest.raises(ValueError, match="sel_lane"):
        ga.EngineOptions(sel_lane="vpu")
    with pytest.raises(AssertionError, match="RESOLVED"):
        G.GAConfig(n=16, c=8, v=2, seed=1, sel_lane="auto")


def test_gather_lane_shrinks_the_vmem_estimate():
    """The planner's per-island working set drops from O(N²) to O(N·V)."""
    from repro.kernels import ga_step as K
    cfg = _spec(n=512).ga_config()
    on = K.resident_vmem_bytes(dataclasses.replace(cfg, sel_lane="onehot"), 1)
    ga_b = K.resident_vmem_bytes(dataclasses.replace(cfg, sel_lane="gather"),
                                 1)
    # the onehot lane's four (N, N) masks/one-hots are gone; what remains
    # is O(N·V): state, the FFM stage's (N, V) temporaries, gathered rows
    assert on - ga_b >= 3 * 4 * 512 * 512
    assert ga_b < on / 2


# ---------------------------------------------------------------------------
# The measured cross-lane planner
# ---------------------------------------------------------------------------


def test_auto_spec_measured_plan_crosses_lanes():
    """With a cost table that rates the gather lane far above onehot, an
    'auto' spec's plan argmaxes ACROSS lanes, the telemetry shows the
    switch, and the run stays bit-identical to the reference."""
    from repro.autotune import runner as AR
    from repro.autotune import table as AT
    from repro.ga import compile_cache as CC

    spec = _spec()            # N=32: heuristic lane is onehot
    table = AT.CostTable(host=AT.host_fingerprint())
    for lane, rate in (("onehot", 10.0), ("gather", 1000.0)):
        for cand in AR.plan_candidates(spec, backend="fused-islands",
                                       sel_lane=lane):
            table.add(CC.plan_point(spec, executor="fused",
                                    mode=cand["mode"], n_shards=1,
                                    lane=cand["lane"]),
                      cand["gens_per_launch"], rate)
    eng = ga.Engine(spec, "fused-islands",
                    options=ga.EngineOptions(cost_table=table))
    plan = eng.backend.topology.plan
    assert plan["plan_source"] == "measured", plan
    assert plan["lane"] == "gather", plan
    assert eng.backend.topology.cfg.sel_lane == "gather"
    out = eng.run()
    assert out.telemetry.plan.lane == "gather"
    assert out.telemetry.plan.source == "measured"
    ref = _solve(spec, "islands")
    assert out.best_fitness == ref.best_fitness


def test_sweep_lanes_enumeration():
    from repro.autotune.runner import sweep_lanes
    assert sweep_lanes(_spec()) == ["onehot", "gather"]
    assert sweep_lanes(_spec(sel_lane="gather")) == ["gather"]
    assert sweep_lanes(_spec(n=2048, n_islands=1)) == ["gather"]


# ---------------------------------------------------------------------------
# Eager backend: population-parallel host fitness
# ---------------------------------------------------------------------------


def test_eager_pooled_fitness_is_deterministic():
    """fitness_workers > 1 splits the batch over a thread pool but keeps
    submission order, so results are bitwise identical to serial."""
    spec = ga.GASpec(problem="F3", n=32, bits_per_var=8, mode="arith",
                     mutation_rate=0.05, seed=9, generations=12,
                     jit_fitness=False)
    serial = ga.solve(spec, backend="eager",
                      options=ga.EngineOptions(cost_table=False))
    for workers in (2, 5):
        pooled = ga.solve(spec, backend="eager",
                          options=ga.EngineOptions(cost_table=False,
                                                   fitness_workers=workers))
        assert pooled.best_fitness == serial.best_fitness, workers
        np.testing.assert_array_equal(np.asarray(pooled.best_x),
                                      np.asarray(serial.best_x))
        np.testing.assert_array_equal(np.asarray(pooled.traj_best),
                                      np.asarray(serial.traj_best))


def test_fitness_workers_validation():
    with pytest.raises(ValueError, match="fitness_workers"):
        ga.EngineOptions(fitness_workers=0)
