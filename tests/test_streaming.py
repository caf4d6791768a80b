"""The HBM-streaming epoch lane + the typed engine API surface: planner
feasibility boundaries around the VMEM budget, bit-identity of the streamed
kernel to the `islands` reference (single device, pinned tiles, sharded
8-fake-device mesh), forced-override validation, the fused multi-bank LFSR
leap, `EngineOptions` resolution and the telemetry job view."""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import ga
from repro.ga.options import resolve_options
from repro.kernels import ga_step as K


def _spec(**kw):
    base = dict(problem="F3", n=16, bits_per_var=8, mode="arith",
                mutation_rate=0.02, seed=1, generations=16, n_islands=8,
                migrate_every=4, gens_per_epoch=8)
    base.update(kw)
    return ga.GASpec(**base)


def _budget(spec, tile=2):
    """A planning budget that holds a streamed tile of `tile` islands of
    this spec (the tile rule's 2x margin) but not the full 8-island stack,
    so the streamed lane engages with that tile."""
    cfg = spec.ga_config()
    budget = 2 * K.resident_vmem_bytes(cfg, tile)
    assert budget < K.resident_vmem_bytes(cfg, spec.n_islands)
    return budget


# ---------------------------------------------------------------------------
# Planner boundaries: at / under / far-under the budget
# ---------------------------------------------------------------------------


def test_candidate_boundaries_around_the_budget():
    """`epoch_mode_candidates` at the exact byte boundaries: resident at the
    budget, streamed one byte under (largest double-buffered tile), gridded
    only when not even one double-buffered island fits."""
    cfg = _spec().ga_config()
    kw = dict(executor="fused", migration="ring", gens_per_epoch=8,
              migrate_every=4, sharded=False)
    fit = K.resident_vmem_bytes(cfg, 8)
    cands = K.epoch_mode_candidates(cfg, 8, budget=fit, **kw)
    assert [c["mode"] for c in cands] == ["resident", "gridded"]
    # one byte under: the streamed lane IS the heuristic, and the 4-island
    # tile (double-buffered = the full 8-island stack) is exactly too big
    cands = K.epoch_mode_candidates(cfg, 8, budget=fit - 1, **kw)
    assert [c["mode"] for c in cands] == ["streamed", "gridded"]
    s = cands[0]
    assert s["tile_islands"] == 2
    assert "VMEM" in s["fallback"]
    # streamed folds whole migration intervals, exactly like resident
    assert s["epochs_per_launch"] == 2 and s["gens_per_launch"] == 8
    # below a single double-buffered island: gridded only, reason attached
    floor = 2 * K.resident_vmem_bytes(cfg, 1)
    assert K.streamed_tile_islands(cfg, 8, budget=floor) == 1
    cands = K.epoch_mode_candidates(cfg, 8, budget=floor - 1, **kw)
    assert [c["mode"] for c in cands] == ["gridded"]
    assert "VMEM" in cands[0]["fallback"]


def test_migration_none_keeps_gridded_heuristic():
    """For migration='none' the streamed candidate is offered for the table
    or an override to pick, but gridded stays the silent default."""
    cfg = _spec().ga_config()
    cands = K.epoch_mode_candidates(
        cfg, 8, executor="fused", migration="none", gens_per_epoch=16,
        migrate_every=4, sharded=False, budget=_budget(_spec()))
    assert [c["mode"] for c in cands] == ["gridded", "streamed"]


def test_plan_override_streamed_on_fitting_spec_errors():
    """Forcing the streamed lane onto a spec whose stack FITS residency is
    refused with the feasibility hint (streamed exists because of the
    budget, it is not a free-floating mode)."""
    with pytest.raises(ValueError, match="vmem_budget"):
        ga.Engine(_spec(), "fused-islands",
                  options=ga.EngineOptions(cost_table=False,
                                           plan_override="streamed"))


# ---------------------------------------------------------------------------
# Bit-identity: the streamed kernel is a launch-shape change, never a
# results change
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("problem", ["F1", "F2", "F3", "rastrigin:4"])
def test_streamed_bit_identical_to_islands_reference(problem):
    """Every paper problem + an n-variable one through the streamed lane:
    final population and all three LFSR banks bit-equal the `islands`
    reference backend after 16 generations (4 ring migrations)."""
    spec = _spec(problem=problem)
    opts = ga.EngineOptions(cost_table=False, vmem_budget=_budget(spec))
    eng = ga.Engine(spec, "fused-islands", options=opts)
    plan = eng.backend.topology.plan
    assert plan["mode"] == "streamed" and plan["tile_islands"] == 2, plan
    seg_s = eng.backend.segment(eng.init_state(), 16)
    ref = ga.Engine(dataclasses.replace(spec, gens_per_epoch=1), "islands",
                    options=ga.EngineOptions(cost_table=False))
    seg_r = ref.backend.segment(ref.init_state(), 16)
    for field in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
        np.testing.assert_array_equal(np.asarray(getattr(seg_s.state, field)),
                                      np.asarray(getattr(seg_r.state, field)),
                                      err_msg=field)
    assert seg_s.best_y == seg_r.best_y
    # the best chromosome too: on an exact fitness tie every plan picks the
    # reference's (first interval, then first island)
    np.testing.assert_array_equal(np.asarray(seg_s.best_x),
                                  np.asarray(seg_r.best_x))
    res = ga.Engine(spec, "fused-islands",
                    options=ga.EngineOptions(cost_table=False))
    assert res.backend.topology.plan["mode"] == "resident"
    seg_res = res.backend.segment(res.init_state(), 16)
    np.testing.assert_array_equal(np.asarray(seg_s.best_x),
                                  np.asarray(seg_res.best_x))


def test_pinned_tile_is_a_launch_shape_knob_only():
    """Any feasible pinned tile gives bit-identical results; infeasible
    pins (non-divisor, too big to double-buffer) are rejected with the
    byte math."""
    spec = _spec()
    budget = _budget(spec)
    base = ga.solve(spec, backend="fused-islands",
                    options=ga.EngineOptions(cost_table=False,
                                             vmem_budget=budget))
    assert base.telemetry.plan.mode == "streamed"
    for t in (1, 2):
        res = ga.solve(spec, backend="fused-islands",
                       options=ga.EngineOptions(cost_table=False,
                                                vmem_budget=budget,
                                                stream_tile_islands=t))
        assert res.telemetry.plan.tile_islands == t
        assert res.best_fitness == base.best_fitness
        np.testing.assert_array_equal(np.asarray(res.best_x),
                                      np.asarray(base.best_x))
    for bad in (3, 4):      # 3 does not divide 8; 4 won't double-buffer
        with pytest.raises(ValueError, match="feasible tile"):
            ga.Engine(spec, "fused-islands",
                      options=ga.EngineOptions(cost_table=False,
                                               vmem_budget=budget,
                                               stream_tile_islands=bad))


def test_streamed_migration_none_bit_identical_via_override():
    """The isolated-islands ablation through the streamed lane (forced —
    gridded is its heuristic) matches the gridded run bit-for-bit."""
    spec = _spec(migration="none", generations=16, gens_per_epoch=16)
    budget = _budget(spec)
    res = ga.solve(spec, backend="fused-islands",
                   options=ga.EngineOptions(cost_table=False,
                                            vmem_budget=budget,
                                            plan_override="streamed"))
    assert res.telemetry.plan.mode == "streamed"
    assert res.telemetry.plan.source == "forced"
    assert res.telemetry.topology.migrations == 0
    grid = ga.solve(spec, backend="fused-islands",
                    options=ga.EngineOptions(cost_table=False,
                                             vmem_budget=budget))
    assert grid.telemetry.plan.mode == "gridded"
    assert res.best_fitness == grid.best_fitness
    np.testing.assert_array_equal(np.asarray(res.best_x),
                                  np.asarray(grid.best_x))


def test_streamed_sharded_on_eight_fake_devices():
    """The global ring across shards INSIDE the streamed scan body: 32
    islands over 8 fake devices (4 local islands, 1-island tiles), final
    state bit-equal the local `islands` reference (subprocess so the forced
    device count doesn't leak)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["REPRO_GA_COST_TABLE"] = "off"
import dataclasses, jax, numpy as np
from repro import ga
from repro.kernels import ga_step as K
mesh = jax.make_mesh((8,), ("islands",))
spec = ga.GASpec(problem="F3", n=16, bits_per_var=8, mode="arith",
                 mutation_rate=0.02, seed=2, generations=16,
                 n_islands=32, migrate_every=4, gens_per_epoch=8)
budget = 2 * K.resident_vmem_bytes(spec.ga_config(), 1)
eng = ga.Engine(spec, "fused-islands",
                options=ga.EngineOptions(mesh=mesh, cost_table=False,
                                         vmem_budget=budget))
plan = eng.backend.topology.plan
assert plan["mode"] == "streamed" and plan["tile_islands"] == 1, plan
seg_s = eng.backend.segment(eng.init_state(), 16)
ref = ga.Engine(dataclasses.replace(spec, gens_per_epoch=1), "islands",
                options=ga.EngineOptions(cost_table=False))
seg_r = ref.backend.segment(ref.init_state(), 16)
for f in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
    np.testing.assert_array_equal(np.asarray(getattr(seg_s.state, f)),
                                  np.asarray(getattr(seg_r.state, f)),
                                  err_msg=f)
assert seg_s.best_y == seg_r.best_y
print("STREAMED_SHARDED_OK", seg_s.best_y)
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "STREAMED_SHARDED_OK" in r.stdout


# ---------------------------------------------------------------------------
# The fused multi-bank LFSR leap
# ---------------------------------------------------------------------------


def test_kernel_lfsr_leap_matches_clocked_banks():
    """`_lfsr_draw` (the in-kernel GF(2) leap, run on each LFSR bank) is
    bit-identical to clocking the same bank `steps` times one by one."""
    from repro.core import lfsr
    rng = np.random.default_rng(0)
    import jax.numpy as jnp
    banks = tuple(jnp.asarray(rng.integers(1, 1 << 32, size=s,
                                           dtype=np.uint32))
                  for s in ((2, 16), (3, 8), (5,)))
    for steps in (1, 3, 17, 45):
        for bank in banks:
            np.testing.assert_array_equal(
                np.asarray(K._lfsr_draw(bank, steps)),
                np.asarray(lfsr.steps(bank, steps)),
                err_msg=f"steps={steps}")


# ---------------------------------------------------------------------------
# EngineOptions resolution + the telemetry job view
# ---------------------------------------------------------------------------


def test_engine_options_validation_and_clash():
    with pytest.raises(ValueError, match="plan_override"):
        ga.EngineOptions(plan_override="warp")
    with pytest.raises(ValueError, match="vmem_budget"):
        ga.EngineOptions(vmem_budget=0)
    with pytest.raises(ValueError, match="stream_tile_islands"):
        ga.EngineOptions(stream_tile_islands=-1)
    opts = ga.EngineOptions(cost_table=False)
    assert resolve_options(opts) is opts
    # options= plus a non-default legacy kwarg: two sources of truth
    with pytest.raises(ValueError, match="legacy kwarg"):
        resolve_options(opts, cost_table=False)
    with pytest.raises(ValueError, match="legacy kwarg"):
        ga.Engine(_spec(), "fused-islands", options=opts,
                  plan_override="gridded")
    with pytest.raises(TypeError, match="EngineOptions"):
        resolve_options({"mesh": None})


def test_job_view_keeps_the_plan_without_replica_arrays():
    spec = _spec(n_islands=2, generations=8)
    # the job view strips the replica payload, keeps the plan
    rep = ga.solve(dataclasses.replace(spec, n_repeats=2),
                   backend="fused-islands",
                   options=ga.EngineOptions(cost_table=False))
    assert rep.telemetry.per_repeat is not None
    view = rep.telemetry.job_view()
    assert view.per_repeat is None
    assert view.plan == rep.telemetry.plan
    assert view.topology == rep.telemetry.topology
