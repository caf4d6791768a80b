"""`BENCHMARK.json` and the files it names; `bench/run.py` refusing to
measure where it cannot."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness as H

REPO = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((REPO / "BENCHMARK.json").read_text())


def test_every_name_resolves_to_its_files(bench):
    for cell in bench["workloads"]:
        config = H.load_config(REPO, bench, cell["config"])
        H.load_mix(REPO, cell["traffic"])
        H.load_module(REPO, "entries", config["entry"])
        H.load_module(REPO, "problems", config["reference"]["problem"])
        e2e, layer = H.cell_metrics(bench, cell["name"])
        # every cell reports its set-up, its throughput and a layer; tails
        # only below capacity, where they do not swing with the backlog
        names = {m["name"] for m in e2e}
        assert {"setup_s", "evals_per_s"} <= names, cell["name"]
        assert (H.load_mix(REPO, cell["traffic"]).loop == "closed") == (
            {"job_p50_s", "job_p95_s"} <= names), cell["name"]
        assert layer, cell["name"]
    for m in bench["per_layer"]:
        assert callable(H.load_module(REPO, "metrics", m["name"]).read)


def test_names_and_shape_follow_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    layers = {m["layer"] for m in bench["per_layer"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert m["layer"] in layers and "\n" not in m["layer"]
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_a_cell_is_added_by_adding_files(tmp_path, bench):
    """A later cell brings a configuration, a traffic mix and a metric of
    its own and names them in BENCHMARK.json; no file the harness already
    has changes."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((REPO / bench["configs"][0]["file"]).read_text())
    cfg["name"] = "dummy-config"
    (tmp_path / "bench/configs/dummy-config.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench/traffic/dummy-mix.json").write_text(
        json.dumps({"loop": "closed", "clients": 2}))
    (tmp_path / "bench/metrics/dummy_metric.py").write_text(
        "def read(run):\n    return None\n")
    spec = dict(bench)
    spec["configs"] = bench["configs"] + [
        {"name": "dummy-config", "source": "test", "reduced": [],
         "file": "bench/configs/dummy-config.json", "why": "test"}]
    spec["workloads"] = bench["workloads"] + [
        {"name": "dummy.cell", "config": "dummy-config",
         "traffic": "dummy-mix", "chips": 1, "why": "test"}]
    spec["per_layer"] = bench["per_layer"] + [
        {"name": "dummy_metric", "unit": "%", "better": "higher",
         "source": "program_counter", "layer": "test", "moves": "evals_per_s",
         "workloads": ["dummy.cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    assert "dummy.cell" in H.list_cells(tmp_path)
    assert H.load_config(tmp_path, spec, "dummy-config")["name"] == \
        "dummy-config"
    assert H.load_mix(tmp_path, "dummy-mix").clients == 2
    e2e, layer = H.cell_metrics(spec, "dummy.cell")
    assert [m["name"] for m in layer] == ["dummy_metric"]
    assert H.load_module(tmp_path, "metrics", "dummy_metric").read(None) \
        is None
    assert all(p.read_bytes() == b for p, b in before.items())


def run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-f3.serve-closed1",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    proc = run_bench(REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_without_the_system(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_bench(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no system under test" in proc.stderr
