"""Full-parallel Genetic Algorithm — faithful JAX port of the paper's datapath.

One `generation()` call is the paper's 3-clock pipeline beat: it evaluates all
N fitness values, runs N tournaments, N/2 single-point crossovers and P
mutations, producing the next population — all as one fused tensor program
(the VPU lanes play the role of the N parallel hardware modules).

Chromosome layout: the paper packs x = px ‖ qx (m bits, two m/2-bit halves).
We generalize to V variables of c bits each, stored as uint32[N, V]
(V=2, c=m/2 reproduces the paper exactly; the paper itself notes more
variables need only "some adjustments on hardware architecture").

Module → code map (paper Sec. 3):
  FFM   -> fitness_fn (see core/fitness.py; LUT = faithful, arith = TPU-native)
  SM    -> tournament selection with per-slot LFSR pairs, MSB-truncated draws
  CM    -> mask-shift bitwise crossover, per-variable cut points (CMPQ1/CMPQ2)
  MM    -> XOR of the first P individuals with LFSR words
  SyncM -> the lax.scan over generations in `run_scan`
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fitness as F
from repro.core import lfsr


# Past this population size the onehot selection lane's (N, N) one-hot
# tournament matrices exceed a reasonable VMEM share — the gather lane
# (dynamic indexing, O(N·V)) has no such cap.
ONEHOT_MAX_N = 1024


@dataclasses.dataclass(frozen=True)
class GAConfig:
    n: int                       # population size N (even, paper uses 4..64)
    c: int                       # bits per variable (= m/2 for the paper)
    v: int = 2                   # number of variables packed per chromosome
    mutation_rate: float = 0.01  # MR; P = ceil(N * MR) individuals mutate
    minimize: bool = True        # SMMAXMIN
    steps_per_draw: int = 3      # LFSR clocks per generation (SyncM cadence)
    seed: int = 1234
    mode: str = "lut"            # "lut" (faithful ROMs) | "arith" (VPU)
    sel_lane: str = "onehot"     # "onehot" (MXU matmul gather) | "gather"
                                 # (VPU dynamic indexing); always resolved —
                                 # "auto" lives on GASpec, never here

    def __post_init__(self):
        assert self.n % 2 == 0, "N must be even (paper Sec. 2)"
        assert 1 <= self.c <= 31
        assert self.sel_lane in ("onehot", "gather"), (
            f"sel_lane={self.sel_lane!r}: GAConfig carries a RESOLVED lane "
            "('onehot' | 'gather'); 'auto' is resolved by GASpec")

    @property
    def m(self) -> int:
        return self.c * self.v

    @property
    def p(self) -> int:
        return max(1, math.ceil(self.n * self.mutation_rate))

    @property
    def idx_bits(self) -> int:
        return max(1, math.ceil(math.log2(self.n)))

    @property
    def cut_bits(self) -> int:
        return max(1, math.ceil(math.log2(self.c + 1)))

    @property
    def var_mask(self) -> int:
        return (1 << self.c) - 1


class GAState(NamedTuple):
    x: jax.Array          # uint32[N, V] population
    sel_lfsr: jax.Array   # uint32[2, N]   SMLFSR1/2 per selection slot
    cross_lfsr: jax.Array # uint32[V, N/2] CMPQLFSR per crossover submodule
    mut_lfsr: jax.Array   # uint32[V, N]   MMLFSR per mutation slot/variable
    k: jax.Array          # int32 generation counter


FitnessFn = Callable[[jax.Array], jax.Array]  # uint32[N, V] -> [N] (i32|f32)


# ---------------------------------------------------------------------------
# Fitness builders — thin wrappers over core.fitness.FitnessProgram
# ---------------------------------------------------------------------------


def make_lut_fitness(tables: F.LutTables) -> FitnessFn:
    """Faithful ROM-pipeline fitness over the whole chromosome matrix."""
    return lambda x: F.lut_fitness(x, tables)


def make_blackbox_fitness(fn: Callable[[jax.Array], jax.Array], c: int,
                          bounds) -> FitnessFn:
    """General V-variable fitness: decode each c-bit gene to its bound range
    and hand the (N, V) float matrix to `fn` (vectorized, jit-able)."""
    prog = F.compile_program(fitness=fn, bounds=bounds, bits_per_var=c)
    return prog.stage


def fitness_for_problem(problem, cfg: GAConfig) -> FitnessFn:
    """Fitness for a registry problem (name or ProblemDef) at cfg's V/c/mode."""
    name = problem.name if isinstance(problem, F.ProblemDef) else problem
    prog = F.compile_program(problem=name, n_vars=cfg.v, bits_per_var=cfg.c,
                             mode=cfg.mode, minimize=cfg.minimize)
    return prog.fitness(cfg.mode)


# ---------------------------------------------------------------------------
# State init
# ---------------------------------------------------------------------------


def seed_bank_size(n: int, v: int) -> int:
    """LFSR seeds one population draws: sel + cross + mut + init banks."""
    return 2 * n + v * (n // 2) + v * n + v * n


def split_seed_banks(s: np.ndarray, n: int, v: int, c: int):
    """Slice host seed rows (..., seed_bank_size(n, v)) into the sel, cross
    and mut banks and the initial population (x, sel, cross, mut): the init
    bank runs a few warmup clocks, then is MSB-truncated to c bits per gene."""
    lead = s.shape[:-1]
    a, b, d = 2 * n, 2 * n + v * (n // 2), 2 * n + v * (n // 2) + v * n
    sel = s[..., :a].reshape(*lead, 2, n)
    cross = s[..., a:b].reshape(*lead, v, n // 2)
    mut = s[..., b:d].reshape(*lead, v, n)
    init_bank = s[..., d:].reshape(*lead, n, v)
    x = lfsr.truncate(lfsr.np_steps(init_bank, 8), c)
    return x, sel, cross, mut


def init_state_host(cfg: GAConfig) -> GAState:
    """`init_state` as numpy arrays, computed on the host."""
    x, sel, cross, mut = split_seed_banks(
        lfsr.np_seeds(cfg.seed, seed_bank_size(cfg.n, cfg.v)),
        cfg.n, cfg.v, cfg.c)
    return GAState(x=x, sel_lfsr=sel, cross_lfsr=cross, mut_lfsr=mut,
                   k=np.int32(0))


def init_state(cfg: GAConfig) -> GAState:
    """Seed every LFSR distinctly (the paper's CCseed) and draw the initial
    random population from a dedicated LFSR bank.  Built on the host and put
    on the device in one transfer (uncommitted), so seeding a job traces and
    dispatches nothing; under a trace the values become constants."""
    return jax.device_put(init_state_host(cfg))


# ---------------------------------------------------------------------------
# The generation step (Algorithm 1, lines 3–14, fully parallel)
# ---------------------------------------------------------------------------


def _select(x, y, sel_lfsr, cfg: GAConfig):
    """SM: N parallel 2-way tournaments."""
    sel_lfsr, r = lfsr.draw(sel_lfsr, cfg.steps_per_draw)
    i1 = lfsr.truncate(r[0], cfg.idx_bits).astype(jnp.int32)
    i2 = lfsr.truncate(r[1], cfg.idx_bits).astype(jnp.int32)
    if cfg.n & (cfg.n - 1):  # non power-of-two N: fold into range
        i1 = i1 % cfg.n
        i2 = i2 % cfg.n
    y1, y2 = y[i1], y[i2]
    first_wins = jnp.where(cfg.minimize, y1 <= y2, y1 >= y2)
    w = jnp.where(first_wins[:, None], x[i1], x[i2])
    return w, sel_lfsr


def _crossover(w, cross_lfsr, cfg: GAConfig):
    """CM: N/2 parallel single-point crossovers, independent cut per variable.

    mask s = (2^c - 1) >> cut; offspring are (h1|t2, h2|t1) with
    h = w & ~s (head), t = w & s (tail) — paper Eqs. 12–20.
    """
    cross_lfsr, r = lfsr.draw(cross_lfsr, cfg.steps_per_draw)  # [V, N/2]
    cut = lfsr.truncate(r, cfg.cut_bits).astype(jnp.uint32)
    cut = jnp.minimum(cut, jnp.uint32(cfg.c))                  # clamp to c
    ones = jnp.uint32(cfg.var_mask)
    s = (ones >> cut).T                                        # [N/2, V]
    w1, w2 = w[0::2], w[1::2]                                  # [N/2, V]
    h1, t1 = w1 & ~s, w1 & s
    h2, t2 = w2 & ~s, w2 & s
    z1 = h1 | t2
    z2 = h2 | t1
    z = jnp.stack([z1, z2], axis=1).reshape(cfg.n, cfg.v)
    return z, cross_lfsr


def _mutate(z, mut_lfsr, cfg: GAConfig):
    """MM: XOR the first P offspring with LFSR words (paper Eq. 21 == XOR)."""
    mut_lfsr, r = lfsr.draw(mut_lfsr, cfg.steps_per_draw)      # [V, N]
    rbits = lfsr.truncate(r, cfg.c).T                          # [N, V]
    mut_row = (jnp.arange(cfg.n) < cfg.p)[:, None]
    return jnp.where(mut_row, z ^ rbits, z), mut_lfsr


def generation(state: GAState, cfg: GAConfig, fit: FitnessFn
               ) -> Tuple[GAState, jax.Array]:
    """One full GA generation. Returns (next_state, fitness_of_current_pop)."""
    y = fit(state.x)
    w, sel_lfsr = _select(state.x, y, state.sel_lfsr, cfg)
    z, cross_lfsr = _crossover(w, state.cross_lfsr, cfg)
    x_new, mut_lfsr = _mutate(z, state.mut_lfsr, cfg)
    return GAState(x_new, sel_lfsr, cross_lfsr, mut_lfsr, state.k + 1), y


# ---------------------------------------------------------------------------
# K-generation driver (SyncM analogue: one scan, no host round-trips)
# ---------------------------------------------------------------------------


class GARun(NamedTuple):
    state: GAState
    best_y: jax.Array      # [] best fitness ever seen
    best_x: jax.Array      # [V] its chromosome
    traj_best: jax.Array   # [K] per-generation population best
    traj_mean: jax.Array   # [K] per-generation population mean


GenerationFn = Callable[[GAState, GAConfig, FitnessFn],
                        Tuple[GAState, jax.Array]]


def run_scan(cfg: GAConfig, fit: FitnessFn, k_generations: int,
             state: Optional[GAState] = None,
             generation_fn: GenerationFn = None) -> GARun:
    """K-generation scan.  `generation_fn` swaps the operator pipeline
    (defaults to the paper's tournament/single-point/XOR `generation`).

    This is the reference *executor* of the engine (`repro.ga`); prefer
    `ga.solve(spec, backend="reference")` in new code."""
    if state is None:
        state = init_state(cfg)
    if generation_fn is None:
        generation_fn = generation

    neutral = jnp.float32(jnp.inf) if cfg.minimize else jnp.float32(-jnp.inf)

    def body(carry, _):
        st, by, bx = carry
        st2, y = generation_fn(st, cfg, fit)
        yf = y.astype(jnp.float32)
        idx = jnp.argmin(yf) if cfg.minimize else jnp.argmax(yf)
        gen_best = yf[idx]
        improved = gen_best < by if cfg.minimize else gen_best > by
        by2 = jnp.where(improved, gen_best, by)
        bx2 = jnp.where(improved, st.x[idx], bx)
        return (st2, by2, bx2), (gen_best, jnp.mean(yf))

    init = (state, neutral, jnp.zeros((cfg.v,), jnp.uint32))
    (st, by, bx), (tb, tm) = jax.lax.scan(body, init, None, length=k_generations)
    return GARun(st, by, bx, tb, tm)


def generation_with_y(state: GAState, y: jax.Array, cfg: GAConfig) -> GAState:
    """SM+CM+MM given externally-computed fitness — lets non-traceable
    fitness functions (e.g. 'train a model for 10 steps') drive the GA."""
    w, sel_lfsr = _select(state.x, y, state.sel_lfsr, cfg)
    z, cross_lfsr = _crossover(w, state.cross_lfsr, cfg)
    x_new, mut_lfsr = _mutate(z, state.mut_lfsr, cfg)
    return GAState(x_new, sel_lfsr, cross_lfsr, mut_lfsr, state.k + 1)


def run_eager(cfg: GAConfig, fit: FitnessFn, k_generations: int,
              state: Optional[GAState] = None,
              apply_ops_fn=None) -> GARun:
    """Python-loop driver for fitness functions that cannot be traced.
    The GA operators themselves stay jitted; only fitness runs eagerly.
    `apply_ops_fn(state, y, cfg) -> state` swaps the SM/CM/MM pipeline
    (defaults to `generation_with_y`)."""
    if state is None:
        state = init_state(cfg)
    step = jax.jit(functools.partial(apply_ops_fn or generation_with_y,
                                     cfg=cfg))
    sign = 1.0 if cfg.minimize else -1.0
    best_y, best_x = np.inf, np.zeros((cfg.v,), np.uint32)
    tb, tm = [], []
    for _ in range(k_generations):
        y = np.asarray(fit(state.x), np.float32)
        idx = int(np.argmin(sign * y))
        if sign * y[idx] < sign * best_y or not np.isfinite(best_y):
            best_y = float(y[idx])
            best_x = np.asarray(state.x[idx])
        tb.append(float(y[idx]))
        tm.append(float(y.mean()))
        state = step(state, jnp.asarray(y))
    return GARun(state, jnp.float32(best_y), jnp.asarray(best_x),
                 jnp.asarray(tb), jnp.asarray(tm))


def decode_best(run_out: GARun, cfg: GAConfig, domain) -> np.ndarray:
    """Decode the best chromosome's genes to real values."""
    u = np.asarray(run_out.best_x) & cfg.var_mask
    return np.asarray(F.decode(jnp.asarray(u), cfg.c, domain))
