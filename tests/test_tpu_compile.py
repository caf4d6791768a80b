"""The fused GA kernels compile for a TPU v5e, at their planned shapes.

Interpret mode cannot show Mosaic refusing a kernel (an unsupported cast,
an unaligned block, a gather it cannot express, more VMEM than the limit).
These tests hand the chip's compiler a described `v5e:2x2` topology — no
chip needed — and compile each kernel with `interpret=False`, the way the
engine launches it: under `vmem_limit_bytes=resident_vmem_budget()`.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import ga
from repro.core import fitness as F
from repro.core import ga as G
from repro.kernels import ga_step as K


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2; the persistent compilation cache
    is off meanwhile — its entries for a described chip cannot be read
    back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this environment
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _state(lead, n, v, sharding):
    u32 = lambda *s: jax.ShapeDtypeStruct(lead + s, jnp.uint32,
                                          sharding=sharding)
    return u32(n, v), u32(2, n), u32(v, n // 2), u32(v, n)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(**kw):
    base = dict(problem="rastrigin:10", bits_per_var=10, mode="arith",
                generations=64, migrate_every=16, gens_per_epoch=64)
    base.update(kw)
    return ga.GASpec(**base)


def _plan(spec):
    eng = ga.Engine(spec, "fused-islands", options=ga.EngineOptions(
        cost_table=False, interpret=False))
    return eng, eng.backend.topology.plan


def _island_states(spec, sharding, k_sharding=None):
    x, sel, cross, mut = _state((spec.n_islands,), spec.n, spec.v, sharding)
    k = jax.ShapeDtypeStruct((spec.n_islands,), jnp.int32,
                             sharding=k_sharding or sharding)
    return G.GAState(x=x, sel_lfsr=sel, cross_lfsr=cross, mut_lfsr=mut, k=k)


@pytest.mark.parametrize("lane", ["onehot", "gather"])
def test_gridded_kernel_compiles_at_paper_size(one_chip, lane):
    """F3, N=64, c=10, V=2 — the paper's own size — on both lanes, one and
    ten generations per launch."""
    prog = ga.GASpec(problem="F3", n=64, bits_per_var=10).program()
    cfg = G.GAConfig(n=64, c=10, v=2, mode="arith", sel_lane=lane)
    for gens, track in ((1, False), (10, True)):
        fn = lambda *s: K.ga_generation_kernel(
            *s, cfg=cfg, ffm=prog.stage, gens=gens, track_best=track)
        _compile(fn, *_state((1,), 64, 2, one_chip))


def test_gather_lane_compiles_past_the_onehot_cap(one_chip):
    """N=2048 single population: only the gather lane runs there."""
    spec = ga.GASpec(problem="F3", n=2048, bits_per_var=10, mode="arith",
                     gens_per_epoch=4)
    assert spec.resolved_sel_lane == "gather"
    cfg = spec.ga_config()
    fn = lambda *s: K.ga_generation_kernel(
        *s, cfg=cfg, ffm=spec.program().stage, gens=4, track_best=True)
    _compile(fn, *_state((1,), 2048, 2, one_chip))


def test_planned_resident_epoch_compiles(one_chip):
    """16 islands x N=256: the planner keeps the stack resident, and the
    resident runner (kernel + XLA glue) compiles under the budget."""
    spec = _spec(n=256, n_islands=16)
    eng, plan = _plan(spec)
    assert plan["mode"] == "resident" and plan["lane"] == "onehot"
    assert plan["vmem_estimate_bytes"] <= K.resident_vmem_budget()
    runner = eng.backend.topology._resident_runner(plan["epochs_per_launch"])
    _compile(runner, _island_states(spec, one_chip))


def test_planned_bbob_resident_epoch_compiles(one_chip):
    """BBOB f24 at D=40, 16 islands x N=256, 16 bits a variable (the
    `bbob-f24-d40` configuration): resident, and the kernel binds the
    (40, 40) rotation as a 2-D block — Mosaic refuses to reshape a
    (1, 1600) lane row back into it."""
    spec = _spec(problem="bbob_f24:40", n=256, n_islands=16,
                 bits_per_var=16, generations=1024)
    eng, plan = _plan(spec)
    assert plan["mode"] == "resident" and plan["lane"] == "onehot"
    assert plan["gens_per_launch"] == 64
    runner = eng.backend.topology._resident_runner(plan["epochs_per_launch"])
    _compile(runner, _island_states(spec, one_chip))


def test_planned_maxsat_resident_epoch_compiles(one_chip):
    """MAX-3SAT at the uf250-1065 shape (the `maxsat-uf250` configuration),
    16 islands x N=256 of 25 ten-bit words: resident, and the kernel runs
    the clause contraction on the MXU against the hoisted (1065, 250)
    bfloat16 incidence matrix — an unaligned contraction over the
    unpacked bit planes."""
    spec = _spec(problem="maxsat_uf250", n=256, n_islands=16,
                 bits_per_var=10, generations=1024)
    eng, plan = _plan(spec)
    assert plan["mode"] == "resident" and plan["lane"] == "onehot"
    assert plan["gens_per_launch"] == 64
    assert plan["ffm_const_bytes"] == 1065 * 250 * 2 + 1065 * 4
    runner = eng.backend.topology._resident_runner(plan["epochs_per_launch"])
    _compile(runner, _island_states(spec, one_chip))


def test_planned_streamed_tile_compiles(one_chip):
    """64 islands x N=2048 exceed the budget: the planner streams tiles on
    the gather lane, and the streamed runner compiles."""
    spec = _spec(n=2048, n_islands=64)
    eng, plan = _plan(spec)
    assert plan["mode"] == "streamed" and plan["lane"] == "gather"
    assert 2 * K.resident_vmem_bytes(spec.ga_config(),
                                     plan["tile_islands"]) \
        <= K.resident_vmem_budget()
    runner = eng.backend.topology._streamed_runner(plan["epochs_per_launch"])
    _compile(runner, _island_states(spec, one_chip))


def test_largest_accepted_resident_stack_compiles(one_chip):
    """At the budget's edge: the most N=2048 islands the estimator still
    accepts as resident compile under the same `vmem_limit_bytes`."""
    prog = F.compile_program(problem="rastrigin:10", bits_per_var=10)
    cfg = G.GAConfig(n=2048, c=10, v=10, mode="arith", sel_lane="gather")
    islands = max(i for i in range(1, 64)
                  if K.resident_fit_reason(cfg, i) is None)
    assert K.resident_vmem_bytes(cfg, islands + 1) > K.resident_vmem_budget()
    fn = lambda *s: K.ga_epoch_kernel(*s, cfg=cfg, ffm=prog.stage,
                                      migrate_every=2, intervals=2)
    _compile(fn, *_state((1, islands), 2048, 10, one_chip))


def test_resident_sharded_epoch_compiles_on_four_chips(topo):
    """The island ring over a 2x2 mesh: one resident-sharded kernel per
    chip plus the boundary-elite collective-permute."""
    mesh = Mesh(np.asarray(topo.devices), ("islands",))
    spec = _spec(n=256, n_islands=16, gens_per_epoch=16)
    eng = ga.Engine(spec, "fused-islands", options=ga.EngineOptions(
        mesh=mesh, cost_table=False, interpret=False))
    assert eng.backend.topology.plan["mode"] == "resident-sharded"
    shard = lambda extra: NamedSharding(mesh, P("islands",
                                                *([None] * extra)))
    st = _island_states(spec, shard(2), shard(0))
    compiled = _compile(eng.backend.topology._epoch(), st)
    assert "collective-permute" in compiled.as_text()
