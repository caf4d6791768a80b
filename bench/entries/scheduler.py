"""Entry `scheduler`: many tenants' jobs through `serve.GAScheduler`.

`submit` queues a job and returns its id at once; `wait` blocks until the
job is finished and returns its result.  The scheduler keeps its
checkpoints and journal in the run's temporary directory.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness import Plan, expect_plan

WARMUP_TIMEOUT_S = 600


class Entry:
    def __init__(self, config, devices, workdir):
        from repro import ga
        from repro.serve.engine import GA_METRICS
        from repro.serve.scheduler import GAScheduler
        self.ga = ga
        self.config = config
        self.platform = devices[0].platform
        self.backend = config["backend"]
        self.registry = GA_METRICS
        self.options = ga.EngineOptions(cost_table=False)
        self.template = ga.GASpec(**config["spec"])
        sc = config["scheduler"]
        self.max_pack = int(sc["max_pack"])
        self.sched = GAScheduler(
            backend=self.backend, max_pack=self.max_pack,
            chunk_generations=sc.get("chunk_generations"),
            options=self.options, ckpt_root=workdir, paused=True)

    def spec(self, seed):
        return dataclasses.replace(self.template, seed=int(seed))

    def warmup(self, mix, seeds) -> Plan:
        """One pack of every size the traffic can form: each slot count is
        a launch shape of its own."""
        top = int(min(self.max_pack, mix.max_outstanding))
        res = None
        for k in range(1, top + 1):
            self.sched.pause()
            ids = [self.sched.submit(self.spec(next(seeds)))
                   for _ in range(k)]
            self.sched.resume_dispatch()
            for i in ids:
                res = self.sched.result(i, timeout=WARMUP_TIMEOUT_S)
        p = res["telemetry"].plan
        # the scheduler keeps no engine past a dispatch: an Engine of the
        # template (planned, never launched) shows whether the system's
        # executors run their kernels in the interpreter here
        eng = self.ga.Engine(self.template, self.backend,
                             options=self.options)
        return expect_plan(self.config, Plan(
            backend=res["backend"], mode=p.mode, lane=p.lane,
            gens_per_launch=int(p.gens_per_launch or 1),
            interpret=getattr(eng.backend.executor, "interpret", None)),
            self.platform)

    def submit(self, seed):
        return self.sched.submit(self.spec(seed))

    def wait(self, job_id, timeout=None):
        return self.summary(self.sched.result(job_id, timeout=timeout))

    def summary(self, res):
        """The job's best and its last chunk's per-generation bests; the
        best chromosome is recovered from the decoded parameters."""
        lo, hi = self.template.var_domains()[0]
        steps = (1 << self.template.bits_per_var) - 1
        best_x = np.rint((np.asarray(res["best_params"], np.float64) - lo)
                         * steps / (hi - lo)).astype(np.uint32)
        return {"best_y": np.float32(res["best_fitness"]),
                "best_x": best_x,
                "traj": np.asarray(res["traj_best"], np.float32)}

    @staticmethod
    def expected(config, ref):
        """The reference's run as a job's result shows it: the best, and the
        per-generation bests of the job's last chunk (the scheduler's
        chunk, by default a tenth of the job)."""
        spec = config["spec"]
        gens = int(spec["generations"])
        chunk = config["scheduler"].get("chunk_generations") or max(
            1, gens // 10, int(spec.get("gens_per_epoch", 1)))
        last = gens - ((gens - 1) // chunk) * chunk
        return {"best_y": np.float32(ref["best_y"]),
                "best_x": np.asarray(ref["best_x"], np.uint32),
                "traj": np.asarray(ref["traj_best"][-last:], np.float32)}

    def counters(self):
        jobs = self.registry.metrics()["jobs"]
        return {"packs_launched": self.sched.packs_launched,
                "max_pack": self.max_pack,
                "jobs": {j: {"status": m["status"], "wall_s": m["wall_s"],
                             "pack_size": m["pack_size"]}
                         for j, m in jobs.items()}}

    def close(self):
        self.sched.shutdown()
