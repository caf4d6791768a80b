"""MAX-3SAT at SATLIB's uf250-1065 shape through the system: the clause
contraction against a plain count of unsatisfied clauses, the bitstring
genome's registration and assignment, the resident island path
bit-identical to the `islands` executor, and the instance span."""

import glob
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ga
from repro.core import fitness as F
from repro.kernels import ga_step as K

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "bench"
                     / "configs" / "maxsat-uf250.json").read_text())


def _unsatisfied(words):
    """Unsatisfied clauses of each row of 10-bit words, literal by literal
    in numpy: variable j is bit j % 10 of word j // 10."""
    inst = F.maxsat_instance()
    w = np.asarray(words, np.uint32) & np.uint32(1023)
    bits = ((w[:, :, None] >> np.arange(10, dtype=np.uint32)) & 1)
    bits = bits.reshape(len(w), 250)
    true = bits[:, inst.var] ^ inst.neg.astype(np.uint32)       # (R, m, 3)
    return (true.max(axis=-1) == 0).sum(axis=-1).astype(np.float32)


def _program():
    return ga.GASpec(problem="maxsat_uf250", n=16).program()


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 9])
def test_stage_counts_unsatisfied_clauses_exactly(seed):
    """Random populations (words drawn over all 32 bits, so the stage's
    mask is exercised), with an all-zero and an all-one genome among
    them."""
    x = np.random.default_rng(seed).integers(0, 2**32, (256, 25),
                                             dtype=np.uint32)
    x[0], x[1] = 0, 1023
    got = np.asarray(jax.jit(_program().stage)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, _unsatisfied(x))
    assert got.dtype == np.float32 and got.min() >= 0.0


def test_instance_is_drawn_as_documented():
    inst = F.maxsat_instance()
    rng = np.random.default_rng(1)
    var = np.stack([rng.choice(250, size=3, replace=False)
                    for _ in range(1065)])
    np.testing.assert_array_equal(inst.var, var)
    np.testing.assert_array_equal(inst.neg,
                                  rng.integers(0, 2, size=(1065, 3)))
    assert all(len(set(row)) == 3 for row in inst.var.tolist())


def test_padding_does_not_change_the_count():
    """The hoisted constants are the signed incidence matrix W (1065, 250)
    and the negation counts b (1065, 1).  Padded to the (8, 128) tile as
    a layout might pad them — zero columns for the padded variables, zero
    rows with b = 1 for the padded clauses — the count is the same: a
    padded clause holds one true literal whatever the bits."""
    closed = K._ffm_jaxpr(_program().stage, 16, 25)
    w, b = (np.asarray(c, np.float32) for c in closed.consts)
    m, r = F.MAXSAT_CLAUSES, F.MAXSAT_VARS
    assert w.shape == (m, r) and b.shape == (m, 1)
    assert (np.abs(w).sum(axis=1) == 3).all()     # three literals a clause
    np.testing.assert_array_equal(b[:, 0], F.maxsat_instance().neg.sum(1))
    wp = np.zeros((1152, 256), np.float32)
    wp[:m, :r] = w
    bp = np.ones((1152, 1), np.float32)
    bp[:m] = b
    x = np.random.default_rng(4).integers(0, 1024, (64, 25),
                                          dtype=np.uint32)
    planes = [(x.T >> np.uint32(k)) & np.uint32(1) for k in range(10)]
    bits = np.concatenate(planes, axis=0).astype(np.float32)   # (250, N)
    bits_p = np.concatenate([bits, np.ones((6, 64), np.float32)])
    true_p = wp @ bits_p + bp
    assert (true_p[m:] == 1.0).all()
    np.testing.assert_array_equal((true_p < 0.5).sum(axis=0),
                                  ((w @ bits + b) < 0.5).sum(axis=0))
    np.testing.assert_array_equal(
        (true_p < 0.5).sum(axis=0).astype(np.float32), _unsatisfied(x))


def test_registration_pins_the_genome():
    spec = ga.GASpec(problem="maxsat_uf250", n=16)
    assert spec.v == 25 and spec.bits_per_var == 10
    assert spec.problem_def().genome == "bits"
    # the domain's step is exactly 1: the stage's decode is the identity
    assert spec.var_domains() == ((0.0, 1023.0),) * 25
    words = jnp.arange(1024, dtype=jnp.uint32).reshape(-1, 16)
    assert (np.asarray(spec.program().decode(words))
            == np.arange(1024, dtype=np.float32).reshape(-1, 16)).all()
    with pytest.raises(ValueError, match="10-bit words"):
        ga.GASpec(problem="maxsat_uf250", bits_per_var=16)
    with pytest.raises(ValueError, match="V=25"):
        ga.GASpec(problem="maxsat_uf250", n_vars=40)
    with pytest.raises(ValueError, match="V=25"):
        ga.GASpec(problem="maxsat_uf250:24")
    with pytest.raises(ValueError, match="no separable form"):
        ga.GASpec(problem="maxsat_uf250", mode="lut")
    with pytest.raises(ValueError, match="10-bit words"):
        F.compile_program(problem="maxsat_uf250", bits_per_var=12)
    # one genome evaluates as a batch of one does
    x = np.random.default_rng(3).integers(0, 1024, (4, 25), dtype=np.uint32)
    pdef = spec.problem_def()
    assert float(pdef.f(x[2])) == float(pdef.f(x)[2]) == _unsatisfied(x)[2]


def test_best_params_is_the_assignment():
    spec = ga.GASpec(problem="maxsat_uf250", n=16, generations=4, seed=5)
    res = ga.solve(spec, "reference",
                   options=ga.EngineOptions(cost_table=False))
    a = res.best_params
    assert a.shape == (250,) and set(np.unique(a)) <= {0, 1}
    for j in (0, 9, 10, 137, 249):
        assert a[j] == (int(res.best_x[j // 10]) >> (j % 10)) & 1
    words = (a.reshape(25, 10).astype(np.uint32)
             << np.arange(10, dtype=np.uint32)).sum(axis=1)
    np.testing.assert_array_equal(words, res.best_x)
    assert float(_unsatisfied(words[None])[0]) == res.best_fitness


def test_resident_islands_bit_identical_to_islands():
    spec = ga.GASpec(problem="maxsat_uf250", n=16, generations=32,
                     n_islands=4, migrate_every=4, gens_per_epoch=8,
                     seed=2**31 + 3)
    opts = ga.EngineOptions(cost_table=False)
    fused = ga.solve(spec, "fused-islands", options=opts)
    plain = ga.solve(spec, "islands", options=opts)
    assert fused.backend == "fused-islands"
    assert fused.telemetry.plan.mode == "resident"
    assert fused.best_fitness == plain.best_fitness
    np.testing.assert_array_equal(fused.best_x, plain.best_x)
    np.testing.assert_array_equal(
        fused.traj_best, np.minimum(plain.traj_best[0::2],
                                    plain.traj_best[1::2]))


def test_instance_span_builds_once_and_plan_reports_the_constants(
        tmp_path):
    """Each build opens `ga.problem.instance` inside `ga.problem.build`;
    only the first in a process builds the instance (W and b)."""
    from jax.profiler import ProfileData
    ga.PROGRAM_CACHE.reset()
    F.maxsat_uf250.cache_clear()
    opts = ga.EngineOptions(cost_table=False)
    spec = ga.GASpec(problem="maxsat_uf250", n=16, generations=8,
                     n_islands=2, migrate_every=4, gens_per_epoch=8)
    with jax.profiler.trace(str(tmp_path / "trace")):
        first = ga.solve(spec, "fused-islands", options=opts)
        ga.solve(ga.GASpec(**{**CONFIG["spec"], "n": 16, "n_islands": 2,
                              "generations": 8, "gens_per_epoch": 8,
                              "migrate_every": 4, "seed": 7}),
                 "fused-islands", options=opts)
    path = glob.glob(f"{tmp_path}/trace/**/*.xplane.pb", recursive=True)
    events = [e for plane in ProfileData.from_file(path[0]).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    spans = [e for e in events if e.name == "ga.problem.instance"]
    builds = [e for e in events if e.name == "ga.problem.build"]
    assert len(spans) == len(builds) == 2
    for span, build in zip(sorted(spans, key=lambda e: e.start_ns),
                           sorted(builds, key=lambda e: e.start_ns)):
        assert build.start_ns <= span.start_ns
        assert span.start_ns + span.duration_ns <= (build.start_ns
                                                    + build.duration_ns)
    assert F.maxsat_uf250.cache_info().misses == 1
    assert first.telemetry.phase_s["build"] > 0.0
    # the planner's count of the hoisted constants, as the benchmark
    # configuration states it for the roofline
    plan = first.telemetry.plan
    assert plan.ffm_const_bytes == CONFIG["ffm_const_bytes"]
    assert plan.ffm_const_bytes == K.ffm_const_bytes(spec.program().stage,
                                                     spec.ga_config())
    assert CONFIG["ffm_flops_per_eval"] == 2 * 250 * 1065


def test_planned_cell_shape_is_resident_within_the_gates():
    """The configuration's own spec plans resident, onehot, 64 generations
    a launch, with the clause matrix under the const gate and the stack
    under the VMEM budget — no override."""
    spec = ga.GASpec(**CONFIG["spec"])
    eng = ga.Engine(spec, "fused-islands",
                    options=ga.EngineOptions(cost_table=False))
    plan = eng.backend.topology.plan
    assert (plan["mode"], plan["lane"], plan["gens_per_launch"]) == (
        "resident", "onehot", 64)
    assert plan["ffm_const_bytes"] <= K.ffm_const_limit()
    assert plan["vmem_estimate_bytes"] <= K.resident_vmem_budget()
