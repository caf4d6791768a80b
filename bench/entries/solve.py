"""Entry `solve`: one study at a time through `ga.solve`, the library path.

`submit` runs the whole job and returns when its result is on the host;
`wait` hands that result back.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness import Plan, expect_plan


class Entry:
    def __init__(self, config, devices, workdir):
        from repro import ga
        self.ga = ga
        self.config = config
        self.platform = devices[0].platform
        mesh = None
        if config.get("mesh_devices"):
            from repro.launch.mesh import make_island_mesh
            mesh = make_island_mesh(int(config["mesh_devices"]))
        self.backend = config["backend"]
        self.options = ga.EngineOptions(cost_table=False, mesh=mesh)
        self.template = ga.GASpec(**config["spec"])

    def spec(self, seed):
        return dataclasses.replace(self.template, seed=int(seed))

    def warmup(self, mix, seeds) -> Plan:
        """One job of the cell's one shape; returns the plan it ran."""
        spec = self.spec(next(seeds))
        eng = self.ga.Engine(spec, self.backend, options=self.options)
        res = eng.run()
        p = res.telemetry.plan
        return expect_plan(self.config, Plan(
            backend=res.backend, mode=p.mode, lane=p.lane,
            gens_per_launch=int(p.gens_per_launch or 1),
            interpret=getattr(eng.backend.executor, "interpret", None)),
            self.platform)

    def submit(self, seed):
        return self.summary(self.ga.solve(self.spec(seed), self.backend,
                                          options=self.options))

    def wait(self, handle, timeout=None):
        return handle

    @staticmethod
    def summary(res):
        return {"best_y": np.float32(res.best_fitness),
                "best_x": np.asarray(res.best_x, np.uint32),
                "traj": np.asarray(res.traj_best, np.float32)}

    @staticmethod
    def expected(config, ref):
        """The reference's run in the form `summary` gives: the trajectory
        holds one best per launch of the planned `gens_per_launch`."""
        spec = config["spec"]
        per = max(1, int(config["expect"]["gens_per_launch"])
                  // int(spec.get("migrate_every", 1)))
        traj = ref["traj_best"]
        traj = np.array([traj[i:i + per].min()
                         for i in range(0, len(traj), per)], np.float32)
        return {"best_y": np.float32(ref["best_y"]),
                "best_x": np.asarray(ref["best_x"], np.uint32),
                "traj": traj}

    def counters(self):
        return {}

    def close(self):
        pass
