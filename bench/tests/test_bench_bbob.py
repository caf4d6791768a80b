"""The BBOB f24 cell's pieces on the CPU at a tiny size: the reference's
copy of the objective holds the system's instance bit for bit, a tiny
island cell through the `solve_phases` entry is `correct` and counts its
phases, and the bfloat16 control fails the check."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import counters
from bench.problems import bbob_f24 as B
from bench.tests import tiny

CONFIG = {
    "entry": "solve_phases", "backend": "fused-islands",
    "spec": {"problem": "bbob_f24:8", "n": 32, "bits_per_var": 16,
             "mode": "arith", "generations": 32, "n_islands": 4,
             "migrate_every": 4, "gens_per_epoch": 8},
    "expect": {"backend": "fused-islands", "mode": "resident",
               "lane": "onehot", "gens_per_launch": 8},
    "precision": "float32",
    "reference": {"problem": "bbob_f24", "shape": {
        "n": 32, "v": 8, "c": 16, "mutation_rate": 0.02, "islands": 4,
        "migrate_every": 4}},
    "check_jobs": 3, "ffm_const_bytes": 4 * (8 * 8 + 8)}
CELL = "bbob.solo"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(tmp_path_factory.mktemp("bench-bbob"))
    (root / "bench" / "configs" / "tiny-bbob.json").write_text(
        json.dumps(CONFIG))
    path = root / "BENCHMARK.json"
    spec = json.loads(path.read_text())
    spec["configs"].append({"name": "tiny-bbob",
                            "file": "bench/configs/tiny-bbob.json"})
    spec["workloads"].append({"name": CELL, "config": "tiny-bbob",
                              "traffic": "closed1", "chips": 1})
    for m in spec["end_to_end"]:
        m["workloads"].append(CELL)
    path.write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("d", [2, 8, 40])
def test_instance_is_the_systems_bit_for_bit(d):
    from repro.core import fitness as F
    sys_inst, ref_inst = F.bbob_f24_instance(d), B.instance(d)
    np.testing.assert_array_equal(sys_inst.mt, ref_inst["mt"])
    np.testing.assert_array_equal(sys_inst.a[0], ref_inst["a"])
    for a, b in ((sys_inst.s, ref_inst["s"]), (sys_inst.mu1, ref_inst["mu1"]),
                 (sys_inst.d_dim, ref_inst["dd"])):
        assert np.float32(a).tobytes() == np.float32(b).tobytes()
    x = np.random.default_rng(d).uniform(-5.0, 5.0, (64, d))
    x = jnp.asarray(x, jnp.float32)
    np.testing.assert_array_equal(np.asarray(F.bbob_f24(d)(x)),
                                  np.asarray(B.objective(x)))


def test_sound_run_is_correct_and_counts_phases(root, monkeypatch):
    from bench import harness as H
    runs = []
    real = H.report

    def keep(root_, run, *a, **kw):
        runs.append(run)
        return real(root_, run, *a, **kw)

    monkeypatch.setattr(H, "report", keep)
    out = tiny.run(root, CELL, monkeypatch=monkeypatch)
    assert out["correct"], out["check"]
    assert out["check"]["jobs_checked"]["value"] >= 1
    assert set(out["metrics"]) >= {"setup_s", "evals_per_s"}
    after = runs[0].counters["after"]
    assert after["jobs"] > runs[0].counters["before"]["jobs"]
    assert counters.share(runs[0], "build") > 0.0
    assert counters.share(runs[0], "launch") > 0.0


def test_phase_share_reads_none_without_counters():
    from bench import harness as H
    run = H.Run(cell={}, config={}, mix=None, window=(0.0, 1.0), jobs=[],
                counters={"before": {}, "after": {}})
    assert counters.share(run, "build") is None


def test_bfloat16_control_fails_the_check(root, monkeypatch):
    from bench import control
    from bench import harness as H
    out = control.readings(root, CONFIG, seed=2**33 + 7)
    assert out["reference_jobs_differing"] == 0
    assert out["control_jobs_differing"] == CONFIG["check_jobs"]
    # the control in the system's place: the harness reads it not correct
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
