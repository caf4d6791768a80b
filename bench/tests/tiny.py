"""Tiny cells for running the harness on the CPU, in interpret mode.

`make_root` builds a checkout-shaped directory: `BENCHMARK.json` naming
the tiny cells, `bench/` with the tiny configuration and traffic files
beside links to the real entries, problems and metrics, and `src/` linked
to the system under test.  `run` drives one cell through the harness in
this process, with JAX's persistent cache left alone.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

CONFIGS = {
    "tiny-serve": {
        "entry": "scheduler", "backend": "fused",
        "spec": {"problem": "F3", "n": 16, "bits_per_var": 10,
                 "mode": "arith", "generations": 20},
        "scheduler": {"max_pack": 4, "chunk_generations": 10},
        "expect": {"backend": "fused", "mode": "gridded", "lane": "onehot",
                   "gens_per_launch": 1},
        "precision": "float32",
        "reference": {"problem": "F3", "shape": {
            "n": 16, "v": 2, "c": 10, "mutation_rate": 0.02}},
        "check_jobs": 4, "ffm_const_bytes": 0},
    "tiny-islands": {
        "entry": "solve", "backend": "fused-islands",
        "spec": {"problem": "rastrigin:4", "n": 16, "bits_per_var": 10,
                 "mode": "arith", "generations": 32, "n_islands": 4,
                 "migrate_every": 4, "gens_per_epoch": 8},
        "expect": {"backend": "fused-islands", "mode": "resident",
                   "lane": "onehot", "gens_per_launch": 8},
        "precision": "float32",
        "reference": {"problem": "rastrigin", "shape": {
            "n": 16, "v": 4, "c": 10, "mutation_rate": 0.02, "islands": 4,
            "migrate_every": 4}},
        "check_jobs": 3, "ffm_const_bytes": 0},
    "tiny-islands-x4": {
        "entry": "solve", "backend": "fused-islands", "mesh_devices": 4,
        "spec": {"problem": "rastrigin:4", "n": 16, "bits_per_var": 10,
                 "mode": "arith", "generations": 32, "n_islands": 8,
                 "migrate_every": 4, "gens_per_epoch": 8},
        "expect": {"backend": "fused-islands", "mode": "resident-sharded",
                   "lane": "onehot", "gens_per_launch": 4},
        "precision": "float32",
        "reference": {"problem": "rastrigin", "shape": {
            "n": 16, "v": 4, "c": 10, "mutation_rate": 0.02, "islands": 8,
            "migrate_every": 4}},
        "check_jobs": 2, "ffm_const_bytes": 0},
}

MIXES = {"closed1": {"loop": "closed", "clients": 1},
         "closed4": {"loop": "closed", "clients": 4},
         "poisson-8": {"loop": "open", "arrivals": "poisson",
                       "rate_per_s": 8}}

CELLS = [("serve.open", "tiny-serve", "poisson-8", 1),
         ("serve.closed4", "tiny-serve", "closed4", 1),
         ("islands.solo", "tiny-islands", "closed1", 1),
         ("islands-x4.solo", "tiny-islands-x4", "closed1", 4)]


def make_root(root: Path) -> Path:
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True)
    (bench / "traffic").mkdir()
    for kind in ("entries", "problems", "metrics"):
        (bench / kind).symlink_to(REPO / "bench" / kind)
    (root / "src").symlink_to(REPO / "src")
    for name, cfg in CONFIGS.items():
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, mix in MIXES.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": n, "file": f"bench/configs/{n}.json"}
                       for n in CONFIGS]
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k}
                         for n, c, t, k in CELLS]
    spec["end_to_end"] = [dict(m, workloads=[n for n, *_ in CELLS])
                          for m in spec["end_to_end"]]
    spec["per_layer"] = []
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run(root: Path, cell: str, seed: int = 2**31 + 5, seconds: float = 0.5,
        monkeypatch=None) -> dict:
    """One run of a tiny cell on the CPU; returns its result line."""
    import jax
    from bench import harness as H
    if monkeypatch is not None:
        monkeypatch.setattr(H, "setup_jax", lambda root: jax)
    else:
        H.setup_jax = lambda root: jax
    return H.run_cell(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"],
                      root, time.monotonic(), platform="cpu")
