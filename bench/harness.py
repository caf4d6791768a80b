"""Run one benchmark cell once and print its result line.

Everything that belongs to one configuration, traffic mix, entry or
per-layer metric lives in a file of its own that this module finds by the
name `BENCHMARK.json` gives it:

    bench/configs/<config>.json   sizes, entry, expected plan, guarantees
    bench/traffic/<mix>.json      parameters of the one generator
    bench/entries/<entry>.py      how a job reaches the system (`Entry`)
    bench/problems/<name>.py      the objective, for the reference
    bench/metrics/<metric>.py     `read(run)` of one per-layer metric

A run: find the chip, warm up the cell's own shapes (set-up), offer the
traffic for `--seconds` (the window), wait for the window's jobs, read the
device's peak memory, free the system, then check a seeded sample of the
window's jobs against the plain reference (`bench/reference.py`).
`--trace 1` profiles the window and reports the per-layer metrics instead
of the end-to-end ones.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.util
import json
import queue
import shutil
import sys
import tempfile
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import stats, traffic

BENCH_DIR = "bench"
WAIT_AFTER_CLOSE_S = 60.0
JOB_SEEDS = 1 << 16


class BenchError(RuntimeError):
    """The run cannot measure: no result line is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---- finding things by name -------------------------------------------------

def load_benchmark(root: Path) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def list_cells(root: Path) -> List[str]:
    return [w["name"] for w in load_benchmark(root)["workloads"]]


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def load_config(root: Path, bench: dict, name: str) -> dict:
    path = root / find(bench["configs"], name, "configuration")["file"]
    if not path.is_file():
        raise BenchError(f"no configuration file {path}")
    return json.loads(path.read_text())


def load_mix(root: Path, name: str) -> traffic.Mix:
    path = root / BENCH_DIR / "traffic" / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no traffic mix file {path}")
    return traffic.mix_from_dict(json.loads(path.read_text()))


def load_module(root: Path, kind: str, name: str):
    path = root / BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str):
    """(end-to-end, per-layer) metric entries that apply to a cell."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", [cell]) and m["moves"] in names]
    return e2e, layer


# ---- what a run records -----------------------------------------------------

@dataclasses.dataclass
class JobRecord:
    seed: int
    due: float            # monotonic s: when the job was due (open loop) or
                          # submitted (closed loop)
    submitted: float
    done: Optional[float] = None        # the client's clock, result in hand
    summary: Optional[Dict[str, np.ndarray]] = None
    error: Optional[str] = None
    handle: Any = None

    @property
    def latency(self) -> float:
        return self.done - self.due


@dataclasses.dataclass
class Run:
    """What per-layer metric readers see."""

    cell: dict
    config: dict
    mix: traffic.Mix
    window: tuple                  # (t_start, t_end), monotonic s
    jobs: List[JobRecord]
    counters: Dict[str, Any]       # entry counters at the window's ends
    plan: Any = None               # the `Plan` the system reported
    reduction: Any = None          # bench.trace.Reduction of the window
    peaks: Optional[dict] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


@dataclasses.dataclass(frozen=True)
class Plan:
    """How the system ran the cell's jobs, as it reports it."""

    backend: str
    mode: str
    lane: str
    gens_per_launch: int
    interpret: Optional[bool]


def expect_plan(config: dict, plan: Plan, platform: str) -> Plan:
    """Fail the run when the system plans otherwise than the
    configuration states, or runs its kernels in the interpreter on a
    chip."""
    want = config["expect"]
    got = dataclasses.asdict(plan)
    wrong = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if wrong:
        raise BenchError(f"the system planned {got}, the configuration "
                         f"states {want}")
    if plan.interpret is not (platform != "tpu"):
        raise BenchError(f"the kernels run with interpret={plan.interpret} "
                         f"on {platform}")
    return plan


class CompileCounter:
    """Counts JAX's compile events while `active`: executables built
    (`backend_compile_duration`), those of them read back from the
    persistent cache, and jaxpr traces."""

    def __init__(self, jax):
        self.active = False
        self.built = self.cache_hits = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if not self.active:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event: str, **_):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.built - self.cache_hits


# ---- offering the traffic ---------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax(root: Path):
    """Import JAX with the persistent compilation cache inside the checkout,
    at a fixed path, caching every executable however quick to build."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"no system under test: {src / 'repro'} is missing")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def _finish(entry, rec: JobRecord, timeout: Optional[float]) -> None:
    """Wait for one job and stamp, on the client's clock, when its result
    was in hand."""
    try:
        rec.summary = entry.wait(rec.handle, timeout=timeout)
        rec.done = time.monotonic()
    except Exception as e:         # noqa: BLE001 — a late or failed job
        rec.error = repr(e)


def closed_loop(entry, mix, seeds, t_end, annotate) -> List[JobRecord]:
    """Each client sends its next job when its last one is done, until the
    window closes."""
    records: List[JobRecord] = []
    lock = threading.Lock()

    def client():
        while time.monotonic() < t_end:
            with lock:
                seed = next(seeds)
            t = time.monotonic()
            rec = JobRecord(seed=seed, due=t, submitted=t)
            with annotate("bench.job"):
                try:
                    rec.handle = entry.submit(seed)
                except Exception as e:     # noqa: BLE001 — a refused job
                    rec.error = repr(e)
                else:
                    _finish(entry, rec, None)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, name=f"client-{i}")
               for i in range(mix.clients - 1)]
    for th in threads:
        th.start()
    client()
    for th in threads:
        th.join()
    return records


def open_loop(entry, mix, seeds, seed, t_start, seconds, annotate
              ) -> Tuple[List[JobRecord], threading.Thread]:
    """Jobs are sent when due whatever the system does; a collector thread
    waits for them in the order sent (the order a FIFO service ends them)
    and stamps each when its result is in hand, up to a minute past the
    window's close.  Returns the jobs sent and the collector, still
    running."""
    records: List[JobRecord] = []
    sent: "queue.Queue[Optional[JobRecord]]" = queue.Queue()
    give_up = t_start + seconds + WAIT_AFTER_CLOSE_S

    def collector():
        while (rec := sent.get()) is not None:
            _finish(entry, rec, max(0.0, give_up - time.monotonic()))

    waiter = threading.Thread(target=collector, name="collector",
                              daemon=True)
    waiter.start()
    try:
        for due in traffic.open_schedule(mix, seed, seconds):
            due += t_start
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            rec = JobRecord(seed=next(seeds), due=due,
                            submitted=time.monotonic())
            with annotate("bench.submit"):
                try:
                    rec.handle = entry.submit(rec.seed)
                except Exception as e:     # noqa: BLE001 — a refused job
                    rec.error = repr(e)
            records.append(rec)
            if rec.error is None:
                sent.put(rec)
        wait = t_start + seconds - time.monotonic()
        if wait > 0:
            time.sleep(wait)
    finally:
        sent.put(None)
    return records, waiter


# ---- the run ----------------------------------------------------------------

def run_cell(argv, root: Path, started: float,
             platform: str = "tpu") -> dict:
    args = parse_args(argv)
    bench = load_benchmark(root)
    cell = find(bench["workloads"], args.workload, "workload")
    config = load_config(root, bench, cell["config"])
    mix = load_mix(root, cell["traffic"])
    e2e, layer = cell_metrics(bench, cell["name"])
    Entry = load_module(root, "entries", config["entry"]).Entry

    phases = {"start": time.monotonic() - started}
    jax = setup_jax(root)
    phases["jax_import"] = time.monotonic() - started
    devices = jax.devices()
    phases["devices"] = time.monotonic() - started
    if devices[0].platform != platform:
        raise BenchError(f"no {platform.upper()}: JAX sees "
                         f"{devices[0].platform!r}")
    chips = int(cell["chips"])
    if len(devices) < chips:
        raise BenchError(f"{cell['name']} needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    devices = devices[:chips]

    counter = CompileCounter(jax)
    workdir = tempfile.mkdtemp(prefix="bench-")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    seeds = iter(traffic.job_seeds(args.seed, JOB_SEEDS))
    entry = None
    try:
        with warnings.catch_warnings():
            # a backend that cannot run the spec warns and falls back to
            # another: here that is an error, the run measures what the
            # configuration states or nothing
            warnings.filterwarnings("error", message=r"backend .* cannot run")
            entry = Entry(config, devices, workdir)
            phases["system"] = time.monotonic() - started
            plan = entry.warmup(mix, seeds)
            phases["warmup"] = time.monotonic() - started
            log(f"plan: {dataclasses.asdict(plan)}")
            gc.collect()
            annotate = annotator(jax, bool(args.trace))
            if args.trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            before = entry.counters()
            t_start = time.monotonic()
            setup_s = t_start - started
            phases["window"] = setup_s
            log("set-up, s from process start: " + ", ".join(
                f"{k} {v:.3f}" for k, v in phases.items()))
            t_end = t_start + args.seconds
            counter.active = True
            waiter = None
            with annotate("bench.window"):
                if mix.loop == "closed":
                    jobs = closed_loop(entry, mix, seeds, t_end, annotate)
                else:
                    jobs, waiter = open_loop(entry, mix, seeds, args.seed,
                                             t_start, args.seconds, annotate)
            after = entry.counters()
            if args.trace:
                jax.profiler.stop_trace()
            if waiter is not None:
                waiter.join()
            counter.active = False
        lateness = max((j.submitted - j.due for j in jobs), default=0.0)
        log(f"window: {args.seconds} s; jobs offered {len(jobs)}; the "
            f"generator ran up to {lateness * 1e3:.3f} ms late")
        log(f"compiles in window: {counter.compiled} (executables read from "
            f"the persistent cache: {counter.cache_hits}, jaxpr traces: "
            f"{counter.traces})")
        peak = memory_peak(devices)
        entry.close()
        entry = None
        gc.collect()
        run = Run(cell=cell, config=config, mix=mix, window=(t_start, t_end),
                  jobs=jobs, counters={"before": before, "after": after},
                  plan=plan)
        return report(root, run, args, setup_s, e2e, layer, devices, peak,
                      trace_dir)
    finally:
        if entry is not None:
            entry.close()
        shutil.rmtree(workdir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def annotator(jax, on: bool):
    if on:
        return jax.profiler.TraceAnnotation

    class _Null:
        def __init__(self, *_):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    return _Null


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---- correctness ------------------------------------------------------------

def sample(jobs: List[JobRecord], k: int, seed: int) -> List[JobRecord]:
    """A sample, drawn from the run's seed, of the jobs that finished."""
    finished = [j for j in jobs if j.summary is not None]
    k = min(int(k), len(finished))
    if not k:
        return []
    rng = np.random.default_rng([int(seed), 3])
    return [finished[i] for i in
            sorted(rng.choice(len(finished), size=k, replace=False))]


def reference_results(root: Path, config: dict, job_seeds, dtype=None):
    """What the plain reference gives for these jobs, in the form an
    entry's `summary` takes (`Entry.expected`)."""
    import jax.numpy as jnp
    from bench import reference as R
    if not job_seeds:
        return []
    Entry = load_module(root, "entries", config["entry"]).Entry
    ref_cfg = config["reference"]
    prob = load_module(root, "problems", ref_cfg["problem"])
    shape = R.GAShape(**ref_cfg["shape"], domain=tuple(prob.DOMAIN))
    ref = R.run_jobs(list(job_seeds), int(config["spec"]["generations"]),
                     shape, prob.objective,
                     dtype=jnp.float32 if dtype is None else dtype)
    return [Entry.expected(config, {key: val[i] for key, val in ref.items()})
            for i in range(len(job_seeds))]


def same(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]) -> bool:
    return all(np.array_equal(np.asarray(got[k]), np.asarray(want[k]))
               for k in want)


def check(root: Path, config: dict, jobs: List[JobRecord], seed: int
          ) -> Dict[str, Dict[str, int]]:
    """The numbers that decide `correct`, each with its limit: jobs of the
    sample whose result differs from the reference's in any bit, and jobs
    of the window that never returned a result."""
    picked = sample(jobs, config["check_jobs"], seed)
    want = reference_results(root, config, [j.seed for j in picked])
    differing = sum(1 for j, w in zip(picked, want) if not same(j.summary, w))
    lost = sum(1 for j in jobs if j.summary is None)
    return {"jobs_checked": {"value": len(picked), "limit_min": 1},
            "jobs_differing": {"value": differing, "limit": 0},
            "jobs_lost": {"value": lost, "limit": 0}}


def is_correct(numbers: Dict[str, Dict[str, int]]) -> bool:
    for n in numbers.values():
        if "limit" in n and n["value"] > n["limit"]:
            return False
        if "limit_min" in n and n["value"] < n["limit_min"]:
            return False
    return True


# ---- the result line --------------------------------------------------------

def end_to_end(jobs: List[JobRecord], window, job_evals: int,
               setup_s: float) -> Dict[str, float]:
    """Evaluations of the jobs completed inside the window over the whole
    window; latency quantiles over every job of the window that returned."""
    t_start, t_end = window
    done = [j for j in jobs if j.done is not None]
    evals = job_evals * sum(1 for j in done if j.done <= t_end)
    lat = [j.latency for j in done]
    out = {"setup_s": setup_s,
           "evals_per_s": stats.rate(evals, t_end - t_start)}
    if lat:
        out["job_p50_s"] = stats.quantile(lat, 0.5)
        out["job_p95_s"] = stats.quantile(lat, 0.95)
    return out


def job_evals(config: dict) -> int:
    """Fitness evaluations in one job: population x islands x generations."""
    spec = config["spec"]
    return (int(spec["n"]) * int(spec.get("n_islands", 1))
            * int(spec["generations"]))


def traced(root: Path, run: Run, layer, devices, trace_dir, device):
    """Per-layer metrics and the breakdown, from the window's trace."""
    from bench import peaks as PK
    from bench import trace as TR
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not path:
        raise BenchError("the profiler wrote no trace")
    tr = TR.load(path[0])
    span = TR.host_span(tr, "bench.window")
    if span is None:
        raise BenchError("no bench.window span in the trace")
    # the benchmark's own spans cover every gap: name the host's work
    red = TR.reduce(tr, span, list(range(len(devices))),
                    skip_host=("bench.window", "bench.job", "bench.submit"))
    device["busy_s"] = red.busy_ns / 1e9
    device["window_s"] = red.window_ns / 1e9
    run = dataclasses.replace(run, reduction=red,
                              peaks=PK.peaks(devices[0].device_kind))
    metrics = {}
    for m in layer:
        value = load_module(root, "metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": [[n, s] for n, s in red.device_ops],
                 "idle_gaps": [[n, s] for n, s in red.gaps]}
    return metrics, breakdown


def report(root: Path, run: Run, args, setup_s, e2e, layer, devices, peak,
           trace_dir) -> dict:
    numbers = check(root, run.config, run.jobs, args.seed)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        metrics, breakdown = traced(root, run, layer, devices, trace_dir,
                                    device)
    else:
        values = end_to_end(run.jobs, run.window, job_evals(run.config),
                            setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in e2e if m["name"] in values}
    out = {"correct": is_correct(numbers), "attempted": len(run.jobs),
           "failed": sum(1 for j in run.jobs if j.error is not None),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = numbers
    return out


def main(argv, root: Path, started: float, platform: str = "tpu") -> int:
    try:
        result = run_cell(argv, root, started, platform)
    except BenchError as e:
        log(f"bench: {e}")
        return 1
    for name, n in result["check"].items():
        bound = (f"limit {n['limit']}" if "limit" in n
                 else f"at least {n['limit_min']}")
        log(f"check {name}: {n['value']} ({bound})")
    log(f"correct: {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0
