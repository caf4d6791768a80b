"""Run the GA main path once on a TPU and check it against the reference.

    python chip_smoke.py                # one chip: paper, resident, streamed,
                                        # bbob, maxsat, serve
    python chip_smoke.py --four-chips   # island ring over a 4-device mesh only

Every phase drives the user entry points (`ga.solve`, `ga.Engine
.run_chunked`, `GAScheduler`) with no interpret mode and no backend
fallback, and compares each fused run with the `reference` / `islands`
executor at the same seed, bit for bit: the final population and the three
LFSR banks (read back from the run's last checkpoint) and the best
chromosome and fitness.

  paper     F3 on `fused`, N in {4, 64} x m in {20, 28}, K=100,
            gens_per_epoch 1 and 10 (the corners of configs/ga_paper.py)
  resident  rastrigin:10, N=256, 16 islands on `fused-islands`: plan resident
  streamed  rastrigin:10, N=2048, 64 islands on `fused-islands`: plan streamed
  bbob      bbob_f24:40 (BBOB f24, its 40x40 rotation hoisted into the kernel
            as a 2-D constant), N=256, 16 islands, 16 bits a variable on
            `fused-islands`: plan resident
  maxsat    maxsat_uf250 (MAX-3SAT at SATLIB's uf250-1065 shape: 25 words
            of 10 bits, the clause contraction on the MXU against its
            hoisted (1065, 250) incidence matrix), N=256, 16 islands on
            `fused-islands`: plan resident
  serve     16 F3 jobs through GAScheduler(max_pack=8) on `fused`; each
            job's result equals its solo run, and every job ends DONE
  --four-chips  rastrigin:10, N=256, 16 islands over a 4-device island
            mesh: plan resident-sharded, equal to the same spec on one device

Each phase prints its backend, plan, lane, the executor's `interpret`, the
compile seconds (JAX's own compile-duration events) and wall seconds.  The
last line of stdout is {"ok": true, "device": {...}}; any failure exits
non-zero without it.  One process holds the chip throughout.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
import traceback
import warnings
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's trace/lower/compile duration events."""

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


class Smoke:
    def __init__(self, ckpt_root: str):
        from repro import ga
        from repro.ckpt import checkpoint as CKPT
        self.ga, self.CKPT = ga, CKPT
        self.ckpt_root = ckpt_root
        self.clock = CompileClock()
        self.opts = ga.EngineOptions(cost_table=False)
        self._n = 0

    def run(self, spec, backend: str, *, mesh=None, expect_plan=None):
        """One chunked run; returns (engine, final state, best_y, best_x,
        last chunk telemetry)."""
        import numpy as np
        opts = dataclasses.replace(self.opts, mesh=mesh)
        eng = self.ga.Engine(spec, backend, options=opts)
        check(eng.backend_name == backend,
              f"asked for {backend!r}, got {eng.backend_name!r}")
        self._n += 1
        ckpt = str(Path(self.ckpt_root) / f"run{self._n}")
        last = None
        for tele in eng.run_chunked(chunk_generations=spec.generations,
                                    ckpt_dir=ckpt):
            last = tele
        step = self.CKPT.latest_step(ckpt)
        state, extra = self.CKPT.restore(ckpt, step, eng.init_state())
        plan = last["telemetry"].plan
        if expect_plan is not None:
            check(plan.mode == expect_plan,
                  f"{backend}: plan {plan.mode!r}, expected {expect_plan!r}")
        interp = getattr(eng.backend.executor, "interpret", None)
        if eng.backend.executor.name == "fused":
            check(interp is False, f"{backend}: interpret={interp}")
        return (eng, state, float(extra["best_y"]),
                np.asarray(extra["best_x"], np.uint32), plan, interp)

    @staticmethod
    def same_run(a, b, what: str) -> None:
        import numpy as np
        _, sa, ya, xa, _, _ = a
        _, sb, yb, xb, _, _ = b
        for f in ("x", "sel_lfsr", "cross_lfsr", "mut_lfsr"):
            # the fused executor stacks even one population: (1, N, V)
            fa, fb = np.asarray(getattr(sa, f)), np.asarray(getattr(sb, f))
            check(fa.size == fb.size
                  and np.array_equal(fa.reshape(fb.shape), fb),
                  f"{what}: {f} differs")
        check(ya == yb, f"{what}: best_fitness {ya!r} != {yb!r}")
        check(np.array_equal(xa, xb), f"{what}: best_x {xa} != {xb}")

    def phase(self, name: str, body) -> None:
        t0, c0 = time.perf_counter(), self.clock.total
        rows = body()
        wall = time.perf_counter() - t0
        for row in rows:
            print(f"[{name}] {row}", flush=True)
        print(f"[{name}] ok compile_s={self.clock.total - c0:.3f} "
              f"wall_s={wall:.3f}", flush=True)

    @staticmethod
    def row(res, ref_backend: str, extra: str = "") -> str:
        eng, _, by, _, plan, interp = res
        return (f"backend={eng.backend_name} plan={plan.mode} "
                f"lane={plan.lane} interpret={interp} best={by!r} "
                f"match={ref_backend}{extra}")

    # ---- phases -------------------------------------------------------------

    def paper(self):
        rows = []
        for n in (4, 64):
            for m in (20, 28):
                spec = self.ga.GASpec(problem="F3", n=n, bits_per_var=m // 2,
                                      mode="arith", generations=100, seed=1)
                ref = self.run(spec, "reference")
                for gpe in (1, 10):
                    fused = self.run(
                        dataclasses.replace(spec, gens_per_epoch=gpe),
                        "fused", expect_plan="gridded")
                    self.same_run(fused, ref, f"F3 N={n} m={m} gpe={gpe}")
                    rows.append(self.row(fused, "reference",
                                         f" N={n} m={m} gpe={gpe}"))
        return rows

    def islands(self, n: int, n_islands: int, generations: int, plan: str,
                mesh=None, problem: str = "rastrigin:10", bits: int = 10):
        spec = self.ga.GASpec(problem=problem, n=n, bits_per_var=bits,
                              mode="arith", generations=generations, seed=3,
                              n_islands=n_islands, migrate_every=16,
                              gens_per_epoch=64 if mesh is None else 16)
        fused = self.run(spec, "fused-islands", mesh=mesh, expect_plan=plan)
        ref = self.run(dataclasses.replace(spec, gens_per_epoch=1),
                       "islands")
        self.same_run(fused, ref, f"{problem} N={n} I={n_islands}")
        extra = f" problem={problem} N={n} islands={n_islands}"
        if fused[4].tile_islands:
            extra += f" tile={fused[4].tile_islands}"
        if mesh is not None:
            extra += f" mesh={dict(mesh.shape)}"
        return [self.row(fused, "islands@1-device", extra)]

    def serve(self):
        from repro.serve.scheduler import DONE, GAScheduler
        import numpy as np
        specs = [self.ga.GASpec(problem="F3", n=64, bits_per_var=10,
                                mode="arith", generations=200, seed=s)
                 for s in range(16)]
        # paused while submitting, so the 16 jobs pack as two launches of 8
        sched = GAScheduler(backend="fused", max_pack=8, options=self.opts,
                            ckpt_root=str(Path(self.ckpt_root) / "sched"),
                            paused=True)
        try:
            ids = [sched.submit(s) for s in specs]
            sched.resume_dispatch()
            sched.wait_all(timeout=900)
            results = [sched.result(i) for i in ids]
            states = [sched.job(i).state for i in ids]
            packs = sched.packs_launched
        finally:
            sched.shutdown()
        check(all(s == DONE for s in states), f"job states {states}")
        for spec, res in zip(specs, results):
            check(res["backend"] == "fused", f"served on {res['backend']}")
            eng = self.ga.Engine(spec, "fused", options=self.opts)
            interp = eng.backend.executor.interpret
            check(interp is False, f"fused solo: interpret={interp}")
            solo = eng.run()
            ref = self.ga.solve(spec, backend="reference", options=self.opts)
            check(solo.best_fitness == ref.best_fitness
                  and np.array_equal(solo.best_x, ref.best_x),
                  f"seed {spec.seed}: fused solo != reference")
            check(res["best_fitness"] == solo.best_fitness
                  and np.array_equal(res["best_params"], solo.best_params),
                  f"seed {spec.seed}: served {res['best_fitness']!r} != "
                  f"solo {solo.best_fitness!r}")
        plan = results[0]["telemetry"].plan
        return [f"backend=fused plan={plan.mode} lane={plan.lane} "
                f"interpret={interp} jobs=16 done=16 packs={packs} "
                f"match=solo+reference"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the island ring over a 4-device mesh")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.jax_cache import enable_persistent_cache
    cache_dir = enable_persistent_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devs) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, have {len(devs)}",
              file=sys.stderr)
        return 1
    # a pinned backend that cannot run warns and falls back: here it fails
    warnings.filterwarnings("error", message=r"backend .* cannot run")
    print(f"device: {dev.device_kind} x{len(devs)}  cache: {cache_dir}",
          flush=True)

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        smoke = Smoke(tmp)
        try:
            if args.four_chips:
                from repro.launch.mesh import make_island_mesh
                mesh = make_island_mesh(4)
                smoke.phase("four-chips", lambda: smoke.islands(
                    256, 16, 256, "resident-sharded", mesh=mesh))
            else:
                smoke.phase("paper", smoke.paper)
                smoke.phase("resident", lambda: smoke.islands(
                    256, 16, 512, "resident"))
                smoke.phase("streamed", lambda: smoke.islands(
                    2048, 64, 256, "streamed"))
                smoke.phase("bbob", lambda: smoke.islands(
                    256, 16, 256, "resident", problem="bbob_f24:40",
                    bits=16))
                smoke.phase("maxsat", lambda: smoke.islands(
                    256, 16, 256, "resident", problem="maxsat_uf250"))
                smoke.phase("serve", smoke.serve)
        except Exception:   # any phase failure: traceback, no result line
            traceback.print_exc()
            print("chip_smoke: FAILED", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
