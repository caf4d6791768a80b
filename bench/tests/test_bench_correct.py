"""The check that decides `correct`, driven through the harness on the CPU
at tiny sizes: sound runs pass it; the control (the reference one
precision lower) and each fault a cell can have, planted in the timed
path underneath the harness, fail it."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench-root"))


# ---- faults, planted in every segment runner the system builds --------------

def plant(monkeypatch, wrap):
    """Wrap each compiled segment runner: it maps the state stack to
    (state', best_y, best_x, traj_best, traj_mean[, best_gen])."""
    from repro.ga import backends as B
    planted = {}

    def cached_runner(self, key, build):
        if key not in planted:
            planted[key] = wrap(build())
        return planted[key]

    monkeypatch.setattr(B.Topology, "_cached_runner", cached_runner)


def state_unchanged(fn):
    """A step that returns its state unchanged."""
    def run(state):
        return (state,) + tuple(fn(state)[1:])
    return run


def half_left_out(fn):
    """Half of the batch (the slots of a pack, the islands of a ring) left
    out: their state is not advanced."""
    def keep(new, old):
        if new.ndim == 0:
            return new
        ax = 0 if new.shape[0] > 1 or new.ndim == 1 else 1
        idx = [slice(None)] * new.ndim
        idx[ax] = slice(new.shape[ax] // 2, None)
        return new.at[tuple(idx)].set(old[tuple(idx)])

    def run(state):
        import jax
        out = fn(state)
        return (jax.tree.map(keep, out[0], state),) + tuple(out[1:])
    return run


def answer_altered(fn):
    """Each launch's best fitness altered where it is produced."""
    def run(state):
        out = fn(state)
        return (out[0], out[1] + 1.0) + tuple(out[2:])
    return run


# ---- sound runs and the control ---------------------------------------------

@pytest.mark.parametrize("cell", ["serve.open", "serve.closed4",
                                  "islands.solo"])
def test_sound_run_is_correct(root, cell, monkeypatch):
    out = tiny.run(root, cell, monkeypatch=monkeypatch)
    assert out["correct"], out["check"]
    assert out["check"]["jobs_checked"]["value"] >= 1
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "evals_per_s", "job_p50_s",
                                   "job_p95_s"}
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("config", ["tiny-serve", "tiny-islands"])
def test_control_fails_the_check(root, config):
    from bench import control
    cfg = tiny.CONFIGS[config]
    out = control.readings(root, cfg, seed=2**33 + 1)
    assert out["reference_jobs_differing"] == 0
    assert out["control_jobs_differing"] == cfg["check_jobs"]


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   answer_altered],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", ["serve.closed4", "islands.solo"])
def test_planted_fault_fails_the_check(root, cell, fault, monkeypatch):
    plant(monkeypatch, fault)
    out = tiny.run(root, cell, monkeypatch=monkeypatch)
    assert not out["correct"]
    assert out["check"]["jobs_differing"]["value"] >= 1


def test_check_counts_lost_jobs(root):
    from bench import harness as H
    jobs = [H.JobRecord(seed=3, due=0.0, submitted=0.0, error="lost")]
    numbers = H.check(root, tiny.CONFIGS["tiny-serve"], jobs, seed=1)
    assert numbers["jobs_lost"]["value"] == 1
    assert numbers["jobs_checked"]["value"] == 0
    assert not H.is_correct(numbers)


# ---- across chips: four virtual CPU devices, in a process of their own ------

X4_SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, sys.argv[2])
    from bench.tests import tiny
    from repro.core import islands
    from repro.ga.compile_cache import RUNNER_CACHE
    root = Path(sys.argv[1])
    sound = tiny.run(root, "islands-x4.solo")
    islands.ring_shift_sharded = lambda x, mesh, axis_names: x
    RUNNER_CACHE.reset()
    cut = tiny.run(root, "islands-x4.solo")
    print(json.dumps({"sound": sound, "exchange_left_out": cut}))
""")


@pytest.fixture(scope="module")
def x4(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tiny.REPO / "src"),
                                           str(tiny.REPO)]))
    proc = subprocess.run(
        [sys.executable, "-c", X4_SCRIPT, str(root), str(tiny.REPO)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_x4_sound_run_is_correct(x4):
    assert x4["sound"]["correct"], x4["sound"]["check"]
    assert x4["sound"]["device"]["count"] == 4


def test_x4_exchange_left_out_fails_the_check(x4):
    assert not x4["exchange_left_out"]["correct"]
    assert x4["exchange_left_out"]["check"]["jobs_differing"]["value"] >= 1


def test_same_compares_every_bit():
    from bench import harness as H
    a = {"best_y": np.float32(1.0), "best_x": np.array([1, 2], np.uint32)}
    b = {"best_y": np.nextafter(np.float32(1.0), np.float32(2.0)),
         "best_x": np.array([1, 2], np.uint32)}
    assert H.same(a, dict(a))
    assert not H.same(b, a)
