"""Plain reference of the genetic algorithm the system serves.

Written from the algorithm's description, with no import of the system
under test, so that a run can be checked against it:

* Torquato & Fernandes 2018 (arXiv:1806.11555), Sec. 3: every generation
  evaluates all N fitness values, runs N two-way tournaments, N/2
  single-point crossovers (one cut per variable) and XORs the first
  P = ceil(N * mutation_rate) offspring with random words.
* The random source is a 32-bit Fibonacci LFSR per module, polynomial
  r^32 + r^22 + r^2 + 1, clocked 3 times per generation; a draw is
  truncated to its most significant bits.  Each module's register is seeded
  from the job's seed by a splitmix-style hash (the system's documented
  seeding convention), so a job's result is a function of its seed alone.
* A gene of c bits decodes to lo + u * (hi - lo) / (2^c - 1) in float32;
  the objective is evaluated in float32 (`bench/problems/<name>.py`).
* Island model: `migrate_every` generations on every island, then the best
  individual of island i replaces the worst of island i + 1 (mod I), first
  occurrence on ties.  The best individual of a run is the first to reach
  the lowest fitness, ordered by migration interval, then island, then
  generation, then position.

Everything is straightforward `jax.numpy`: plain indexing for the
tournament gathers, a `lax.scan` over generations, `vmap` over islands and
over jobs.  `dtype` evaluates the objective in another precision: the
control that the comparison has to reject.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GAShape:
    """The sizes and operator constants of one configuration."""

    n: int                    # population per island (power of two)
    v: int                    # variables per chromosome
    c: int                    # bits per variable
    mutation_rate: float
    domain: tuple             # (lo, hi) of every variable
    islands: int = 1
    migrate_every: int = 16
    minimize: bool = True
    steps_per_draw: int = 3

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
    def mask(self) -> int:
        return (1 << self.c) - 1


# ---- LFSR ------------------------------------------------------------------

def seeds(seed: int, count: int) -> np.ndarray:
    """`count` non-zero 32-bit register seeds derived from one job seed."""
    base = np.uint64(int(seed) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        z = np.arange(1, count + 1, dtype=np.uint64) + base * np.uint64(
            0x9E3779B9)
        z = z * np.uint64(0xBF58476D1CE4E5B9)
        z ^= z >> np.uint64(31)
        z = z * np.uint64(0x94D049BB133111EB)
        z ^= z >> np.uint64(27)
    out = (z & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return np.where(out == 0, np.uint32(0xDEADBEEF), out)


def clock(s, times: int):
    """Advance LFSR registers `times` clocks: fb = s31^s21^s1^s0, then
    shift left."""
    for _ in range(times):
        fb = ((s >> 31) ^ (s >> 21) ^ (s >> 1) ^ s) & 1
        s = (s << 1) | fb
    return s


# ---- initial state ----------------------------------------------------------

def _population(s: np.ndarray, g: GAShape) -> Dict[str, np.ndarray]:
    n, v = g.n, g.v
    a, b, c = 2 * n, 2 * n + v * (n // 2), 2 * n + v * (n // 2) + v * n
    bank = s[c:c + v * n].reshape(n, v)
    return {"x": clock(bank, 8) >> np.uint32(32 - g.c),
            "sel": s[:a].reshape(2, n),
            "cross": s[a:b].reshape(v, n // 2),
            "mut": s[b:c].reshape(v, n)}


def init_state(seed: int, g: GAShape) -> Dict[str, np.ndarray]:
    """A job's initial state; with islands every array leads with I."""
    per = 2 * g.n + g.v * (g.n // 2) + 2 * g.v * g.n
    if g.islands == 1:
        return _population(seeds(seed, per), g)
    s = seeds(seed, g.islands * per).reshape(g.islands, per)
    pops = [_population(s[i], g) for i in range(g.islands)]
    return {k: np.stack([p[k] for p in pops]) for k in pops[0]}


# ---- one generation ---------------------------------------------------------

def decode(x, g: GAShape):
    lo, hi = g.domain
    span = np.float32((hi - lo) / ((1 << g.c) - 1))
    u = (x & np.uint32(g.mask)).astype(jnp.int32).astype(jnp.float32)
    return np.float32(lo) + u * span


def evaluate(x, g: GAShape, objective: Callable, dtype) -> jax.Array:
    vals = decode(x, g)
    return objective(vals.astype(dtype)).astype(jnp.float32)


def generation(pop, y, g: GAShape):
    """Selection, crossover and mutation of one population (N, V)."""
    x = pop["x"]
    sel = clock(pop["sel"], g.steps_per_draw)
    i1 = (sel[0] >> np.uint32(32 - g.idx_bits)).astype(jnp.int32) % g.n
    i2 = (sel[1] >> np.uint32(32 - g.idx_bits)).astype(jnp.int32) % g.n
    first = y[i1] <= y[i2] if g.minimize else y[i1] >= y[i2]
    w = jnp.where(first[:, None], x[i1], x[i2])

    cross = clock(pop["cross"], g.steps_per_draw)
    cut = jnp.minimum(cross >> np.uint32(32 - g.cut_bits), np.uint32(g.c))
    tail = (np.uint32(g.mask) >> cut).T                    # (N/2, V)
    w1, w2 = w[0::2], w[1::2]
    z1 = (w1 & ~tail) | (w2 & tail)
    z2 = (w2 & ~tail) | (w1 & tail)
    z = jnp.stack([z1, z2], axis=1).reshape(g.n, g.v)

    mut = clock(pop["mut"], g.steps_per_draw)
    words = (mut >> np.uint32(32 - g.c)).T                 # (N, V)
    z = jnp.where((jnp.arange(g.n) < g.p)[:, None], z ^ words, z)
    return {"x": z, "sel": sel, "cross": cross, "mut": mut}


def _better(a, b, g: GAShape):
    return a < b if g.minimize else a > b


def _first_best(y, g: GAShape):
    return jnp.argmin(y) if g.minimize else jnp.argmax(y)


def generations(pop, gens: int, g: GAShape, objective, dtype):
    """`gens` generations of one population; returns (pop, best_y, best_x,
    per-generation best)."""
    worst = np.float32(np.inf if g.minimize else -np.inf)

    def body(carry, _):
        pop, by, bx = carry
        y = evaluate(pop["x"], g, objective, dtype)
        i = _first_best(y, g)
        up = _better(y[i], by, g)
        by = jnp.where(up, y[i], by)
        bx = jnp.where(up, pop["x"][i], bx)
        return (generation(pop, y, g), by, bx), y[i]

    init = (pop, worst, jnp.zeros((g.v,), jnp.uint32))
    (pop, by, bx), traj = jax.lax.scan(body, init, None, length=gens)
    return pop, by, bx, traj


def run_single(pop, gens: int, g: GAShape, objective, dtype=jnp.float32):
    pop, by, bx, traj = generations(pop, gens, g, objective, dtype)
    return {"best_y": by, "best_x": bx, "traj_best": traj}


def run_islands(pop, gens: int, g: GAShape, objective, dtype=jnp.float32):
    """Island ring; `traj_best` holds each migration interval's best."""
    E = g.migrate_every
    intervals = -(-gens // E)
    worst = np.float32(np.inf if g.minimize else -np.inf)

    def interval(carry, _):
        pop, by, bx = carry
        pop, iby, ibx, _ = jax.vmap(
            lambda p: generations(p, E, g, objective, dtype))(pop)
        # ring migration on the fitness of the interval's last population
        y = jax.vmap(lambda x: evaluate(x, g, objective, dtype))(pop["x"])
        best = jax.vmap(lambda r: _first_best(r, g))(y)
        worst_slot = jax.vmap(
            lambda r: jnp.argmax(r) if g.minimize else jnp.argmin(r))(y)
        isl = jnp.arange(g.islands)
        elite = pop["x"][isl, best]                          # (I, V)
        incoming = jnp.roll(elite, 1, axis=0)
        x = pop["x"].at[isl, worst_slot].set(incoming)
        pop = dict(pop, x=x)
        # interval best: lowest fitness, then the first island
        i = _first_best(iby, g)
        up = _better(iby[i], by, g)
        return ((pop, jnp.where(up, iby[i], by), jnp.where(up, ibx[i], bx)),
                iby[i])

    init = (pop, worst, jnp.zeros((g.v,), jnp.uint32))
    (pop, by, bx), traj = jax.lax.scan(interval, init, None, length=intervals)
    return {"best_y": by, "best_x": bx, "traj_best": traj}


def run_jobs(job_seeds: Sequence[int], gens: int, g: GAShape, objective,
             dtype=jnp.float32, device=None) -> Dict[str, np.ndarray]:
    """Reference results of many jobs of one shape, one seed each."""
    states = [init_state(s, g) for s in job_seeds]
    batch = {k: np.stack([s[k] for s in states]) for k in states[0]}
    run = run_single if g.islands == 1 else run_islands
    fn = jax.jit(jax.vmap(lambda p: run(p, gens, g, objective, dtype)))
    out = fn(jax.device_put(batch, device))
    return {k: np.asarray(v) for k, v in out.items()}
