"""The MAX-3SAT cell's pieces on the CPU at a tiny size: the reference's
copy of the instance and objective agree with the system's bit for bit, a
tiny island cell through the `solve_phases` entry is `correct`, the
bfloat16 control fails the check, and the MXU roofline reads what its
docstring says, or None where there is nothing to read."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import reference as R
from bench import trace as TR
from bench.problems import maxsat_uf250 as M
from bench.tests import tiny

CONFIG = {
    "entry": "solve_phases", "backend": "fused-islands",
    "spec": {"problem": "maxsat_uf250", "n": 16, "bits_per_var": 10,
             "mode": "arith", "generations": 32, "n_islands": 4,
             "migrate_every": 4, "gens_per_epoch": 8},
    "expect": {"backend": "fused-islands", "mode": "resident",
               "lane": "onehot", "gens_per_launch": 8},
    "precision": "float32",
    "reference": {"problem": "maxsat_uf250", "shape": {
        "n": 16, "v": 25, "c": 10, "mutation_rate": 0.02, "islands": 4,
        "migrate_every": 4}},
    "check_jobs": 3, "ffm_const_bytes": 1065 * 250 * 2 + 1065 * 4,
    "ffm_flops_per_eval": 2 * 250 * 1065}
CELL = "maxsat.solo"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench-maxsat"))
    (root / "bench" / "configs" / "tiny-maxsat.json").write_text(
        json.dumps(CONFIG))
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["configs"].append({"name": "tiny-maxsat",
                            "file": "bench/configs/tiny-maxsat.json"})
    spec["workloads"].append({"name": CELL, "config": "tiny-maxsat",
                              "traffic": "closed1", "chips": 1})
    for m in spec["end_to_end"]:
        m["workloads"].append(CELL)
    path.write_text(json.dumps(spec))
    return root


def test_instance_and_objective_are_the_systems_bit_for_bit():
    from repro import ga
    from repro.core import fitness as F
    inst = F.maxsat_instance()
    var, neg = M.instance()
    np.testing.assert_array_equal(inst.var, var)
    np.testing.assert_array_equal(inst.neg, neg)
    words = np.random.default_rng(6).integers(0, 1024, (128, 25),
                                              dtype=np.uint32)
    words[0], words[1] = 0, 1023
    g = R.GAShape(n=128, v=25, c=10, mutation_rate=0.02, domain=M.DOMAIN)
    want = np.asarray(R.evaluate(jnp.asarray(words), g, M.objective,
                                 jnp.float32))
    stage = ga.GASpec(problem="maxsat_uf250", n=128).program().stage
    np.testing.assert_array_equal(np.asarray(jax.jit(stage)(words)), want)
    # the reference's decode hands the objective each word as its value
    np.testing.assert_array_equal(np.asarray(R.decode(words, g)),
                                  words.astype(np.float32))


def test_sound_run_is_correct(root, monkeypatch):
    out = tiny.run(root, CELL, monkeypatch=monkeypatch)
    assert out["correct"], out["check"]
    assert out["check"]["jobs_checked"]["value"] >= 1
    assert set(out["metrics"]) >= {"setup_s", "evals_per_s"}


def test_bfloat16_control_fails_the_check(root, monkeypatch):
    from bench import control
    out = control.readings(root, CONFIG, seed=2**33 + 11)
    assert out["reference_jobs_differing"] == 0
    assert out["control_jobs_differing"] == CONFIG["check_jobs"]
    real_load = H.load_module

    def low(self, seed):
        return H.reference_results(root, CONFIG, [seed],
                                   dtype=jnp.bfloat16)[0]

    def load(root_, kind, name):
        mod = real_load(root_, kind, name)
        if kind == "entries":
            mod.Entry.submit = low
        return mod

    monkeypatch.setattr(H, "load_module", load)
    out = tiny.run(root, CELL, monkeypatch=monkeypatch)
    assert not out["correct"]
    assert out["check"]["jobs_differing"]["value"] == \
        out["check"]["jobs_checked"]["value"]


def _run(config, events):
    red = TR.Reduction(window=(0.0, 1e9), busy_ns=0.0, ops=events,
                       device_ops=[], gaps=[])
    return H.Run(cell={}, config=config, mix=None, window=(0.0, 1.0),
                 jobs=[], counters={}, reduction=red,
                 peaks={"bf16_flop_per_s": 197e12})


def test_mxu_roofline_reads_none_without_the_kernel():
    metric = H.load_module(tiny.REPO, "metrics",
                           "ga_epoch_kernel_mxu_roofline")
    other = TR.Event("%ga_generation_kernel.1 = (u32[1,16,25]) custom-call",
                     0.0, 1e6)
    assert metric.read(_run(CONFIG, [])) is None
    assert metric.read(_run(CONFIG, [other])) is None
    # a configuration that counts no contraction reads None too
    launch = TR.Event("%ga_epoch_kernel.2 = (u32[1,4,16,25]) custom-call",
                      0.0, 1e6)
    plain = {k: v for k, v in CONFIG.items() if k != "ffm_flops_per_eval"}
    assert metric.read(_run(plain, [launch])) is None


def test_mxu_roofline_by_hand():
    metric = H.load_module(tiny.REPO, "metrics",
                           "ga_epoch_kernel_mxu_roofline")
    # two launches of 4 populations of N=16, 8 generations each:
    # 2 x 4 x 16 x 8 x 532,500 = 545,280,000 operations in 2 ms
    launches = [TR.Event("%ga_epoch_kernel.2 = (u32[1,4,16,25]{3,2,1,0}, "
                         "u32[1,4,2,16]) custom-call(...)", t, t + 1e6)
                for t in (0.0, 5e6)]
    share = metric.read(_run(CONFIG, launches))
    assert share == pytest.approx(100.0 * 545_280_000 / 197e12 / 2e-3)
