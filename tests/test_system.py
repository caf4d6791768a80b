"""End-to-end behaviour tests for the paper's system: the full-parallel GA
reproduces the paper's optimisation results; the island model scales it; the
multi-device shard_map path works (spawned with fake devices).

All GA runs go through the unified `repro.ga` engine API (the old
`G.run` / `ISL.run_local` drivers were folded after their deprecation
cycle)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ga
from repro.core import fitness as F
from repro.roofline import analyze_hlo


def test_f1_paper_reproduction_lut_mode():
    """Paper Fig. 11: minimise F1 with N=32, m=26 — global minimum within
    100 generations (LUT/fixed-point mode, the hardware-faithful path)."""
    spec = ga.paper_spec("F1", n=32, m=26, mode="lut", mutation_rate=0.05,
                         seed=7, generations=100)
    r = ga.solve(spec, backend="reference")
    target = float(F.F1.f(np.array([0.0, -4096.0])))
    assert r.best_fitness <= 0.98 * target   # real units (descaled)
    # decoded solution sits at the domain edge the paper reports
    assert r.best_params[1] == pytest.approx(-4096.0, abs=2.0)


def test_f3_paper_reproduction():
    """Paper Fig. 12: F3 with N=64, m=20 converges near zero in ~20 gens."""
    spec = ga.paper_spec("F3", n=64, m=20, mode="arith", mutation_rate=0.05,
                         seed=3, generations=100)
    r = ga.solve(spec, backend="reference")
    assert r.traj_best[40] < 3.0   # most of the way by gen 40
    assert r.best_fitness < 1.0


def test_islands_beat_single_population():
    """Island model with migration should match or beat one big population
    at equal total chromosome count (the multi-FPGA [19] claim)."""
    isl = ga.GASpec(problem="F3", n=32, bits_per_var=12, mode="arith",
                    mutation_rate=0.05, seed=1, generations=100,
                    n_islands=8, migrate_every=10)
    r_isl = ga.solve(isl, backend="islands")
    assert r_isl.telemetry.topology.migrations == 10

    big = ga.GASpec(problem="F3", n=256, bits_per_var=12, mode="arith",
                    mutation_rate=0.05, seed=1, generations=100)
    r_big = ga.solve(big, backend="reference")
    assert r_isl.best_fitness <= r_big.best_fitness * 1.5 + 0.2


def test_sharded_island_ga_on_multiple_devices():
    """Full shard_map island GA on 8 fake devices via the engine's
    reference×island_ring backend (subprocess so the forced device count
    doesn't leak into this process)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
from repro import ga
mesh = jax.make_mesh((2, 4), ("data", "model"))
spec = ga.GASpec(problem="F3", n=32, bits_per_var=10, mode="arith",
                 mutation_rate=0.05, seed=2, generations=48,
                 n_islands=16, migrate_every=8)
r = ga.solve(spec, backend="islands", mesh=mesh)
assert r.backend == "islands"
assert r.telemetry.topology.sharded is True
assert r.telemetry.topology.migrations == 6
assert r.best_fitness < 2.0, r.best_fitness
print("SHARDED_OK", r.best_fitness)
"""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARDED_OK" in r.stdout


def test_roofline_parser_on_known_program():
    def loss(ws, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y ** 2)

    comp = jax.jit(jax.grad(loss)).lower(
        jax.ShapeDtypeStruct((10, 128, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    res = analyze_hlo(comp.as_text())
    # fwd + 2 bwd matmuls per scanned layer, times 10 layers
    assert res["flops"] == pytest.approx(10 * 3 * 2 * 128 ** 3, rel=0.05)
    assert res["collective_bytes"] == 0.0


def test_serving_engine_end_to_end():
    from repro.configs import get_config, reduced
    from repro.models import common as C
    from repro.models import lm as LM
    from repro.serve.engine import Engine, EngineConfig

    cfg = reduced(get_config("minitron-8b"))
    params = C.init_params(LM.model_defs(cfg, max_seq=128), jax.random.key(0))
    eng = Engine(cfg, params, EngineConfig(batch=2, max_len=128))
    prompts = np.ones((2, 16), np.int32)
    toks, stats = eng.generate(prompts, max_new_tokens=8)
    assert toks.shape == (2, 8)
    assert (toks >= 0).all() and (toks < cfg.vocab_).all()
    assert stats["decode_tok_per_s"] > 0


def test_persistent_cache_location(monkeypatch):
    """`JAX_COMPILATION_CACHE_DIR` wins untouched; without it the cache sits
    at the fixed `<checkout>/.jax_cache`."""
    import jax
    from repro.launch import jax_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert jax_cache.enable_persistent_cache() == "/elsewhere/cache"
        assert {k: getattr(jax.config, k) for k in keys} == saved
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = jax_cache.enable_persistent_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
