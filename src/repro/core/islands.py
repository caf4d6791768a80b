"""Island-model parallel GA — how the paper's one-FPGA datapath scales to pods.

The paper instantiates the full GA once per FPGA; its cited related work [19]
(Guo et al., multi-FPGA parallel GAs) scales by running isolated populations
("islands") that periodically exchange good individuals.  We map that to the
TPU production mesh:

  * a device holds `islands_per_device` independent populations,
    vmapped over the leading axis (the VPU analogue of replicated datapaths);
  * the global island array is sharded over EVERY mesh axis with `shard_map`;
  * every `migrate_every` generations the best individual of each island is
    ring-shipped to the next device with `jax.lax.ppermute`
    (collective-permute == the inter-FPGA links of [19]), replacing the
    recipient island's worst individual.

Migration is overlapped with compute by construction: the permute is issued
on a [I_local, V]-sized elite buffer (tiny) while the next local-generation
scan runs on values that do not depend on it until the splice point.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import ga as G
from repro.core import lfsr


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    ga: G.GAConfig
    n_islands: int               # global island count I
    migrate_every: int = 16      # generations between migrations
    axis_names: tuple = ("data", "model")  # mesh axes the islands shard over


def init_islands(cfg: IslandConfig) -> G.GAState:
    """Stack of I island states with decorrelated seeds."""
    states = []
    for i in range(cfg.n_islands):
        sub = dataclasses.replace(cfg.ga, seed=cfg.ga.seed + 7919 * (i + 1))
        states.append(G.init_state(sub))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def init_islands_host(cfg: IslandConfig) -> G.GAState:
    """Vectorized init (no per-island python loop) for large I, as numpy
    arrays computed on the host: island i takes row i of one seed stream."""
    I, n, v = cfg.n_islands, cfg.ga.n, cfg.ga.v
    per = G.seed_bank_size(n, v)
    s = lfsr.np_seeds(cfg.ga.seed, I * per).reshape(I, per)
    x, sel, cross, mut = G.split_seed_banks(s, n, v, cfg.ga.c)
    return G.GAState(x=x, sel_lfsr=sel, cross_lfsr=cross, mut_lfsr=mut,
                     k=np.zeros((I,), np.int32))


def init_islands_fast(cfg: IslandConfig) -> G.GAState:
    """`init_islands_host` put on the device in one transfer."""
    return jax.device_put(init_islands_host(cfg))


# ---------------------------------------------------------------------------
# Local (single-device) island stepping
# ---------------------------------------------------------------------------


def _local_generations(states: G.GAState, cfg: IslandConfig,
                       fit: G.FitnessFn, gens: int,
                       generation_fn=None) -> Tuple[G.GAState, jax.Array]:
    """Run `gens` generations on a stack of islands; returns final fitness.
    `generation_fn` swaps the operator pipeline (default: paper ops)."""
    step = functools.partial(generation_fn or G.generation, cfg=cfg.ga,
                             fit=fit)

    def one(st, _):
        st2, y = jax.vmap(lambda s: step(s))(st)
        return st2, None

    states, _ = jax.lax.scan(one, states, None, length=gens)
    y = jax.vmap(fit)(states.x)
    return states, y


def _splice_elites(states: G.GAState, y: jax.Array, elites: jax.Array,
                   cfg: IslandConfig) -> G.GAState:
    """Replace each island's worst individual with the incoming elite."""
    return splice_elites(states, y, elites, minimize=cfg.ga.minimize)


# ---------------------------------------------------------------------------
# Migration math — THE rule set for elite/worst selection and splicing on
# island stacks (I, N, V).  Everything here is gather/scatter-free:
# first-occurrence argmin/argmax is a min-reduction over a masked 2-D iota,
# and "gather row idx" / "scatter row idx" are a masked sum / a select —
# exact for uint32 (single nonzero per mask).  The Pallas epoch kernels
# apply the same rules to one lane-major island at a time
# (kernels/ga_step._best_slot / _take_column / _splice).
# ---------------------------------------------------------------------------


def best_slot(y: jax.Array, *, minimize: bool) -> jax.Array:
    """First-occurrence best index per island: (I, N) -> int32 (I,).
    Matches jnp.argmin/argmax (which take the FIRST hit on ties) for
    finite fitness — the engine's contract.  NaN fitness is out of
    contract: the masked-iota form returns the out-of-range sentinel N
    (no slot matches), making take_slot/splice_at no-ops rather than
    propagating an argmin-style NaN index."""
    yf = y.astype(jnp.float32)
    m = (jnp.min(yf, axis=1, keepdims=True) if minimize
         else jnp.max(yf, axis=1, keepdims=True))
    iota = jax.lax.broadcasted_iota(jnp.int32, yf.shape, 1)
    return jnp.min(jnp.where(yf == m, iota, yf.shape[1]), axis=1)


def worst_slot(y: jax.Array, *, minimize: bool) -> jax.Array:
    """First-occurrence worst index per island (the slot migration fills)."""
    return best_slot(y, minimize=not minimize)


def take_slot(a: jax.Array, slot: jax.Array) -> jax.Array:
    """a[i, slot[i]] for an island-stacked array (I, N, ...) — expressed as
    a one-hot masked sum (exact: one nonzero per row, any dtype)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, a.shape[:2], 1)
    hit = iota == slot[:, None]
    hit = hit.reshape(hit.shape + (1,) * (a.ndim - 2))
    return jnp.sum(jnp.where(hit, a, jnp.zeros_like(a)), axis=1)


def splice_at(x: jax.Array, slot: jax.Array, rows: jax.Array,
              island_mask: jax.Array = None) -> jax.Array:
    """x with x[i, slot[i]] <- rows[i] (a select, no scatter).  island_mask
    (bool (I, 1), optional) disables the splice for masked-off islands —
    the sharded path uses it to leave island 0 for the boundary elite."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape[:2], 1)
    hit = iota == slot[:, None]
    if island_mask is not None:
        hit = hit & island_mask
    return jnp.where(hit[..., None], rows[:, None, :], x)


def elites_stack(x: jax.Array, y: jax.Array, *, minimize: bool
                 ) -> Tuple[jax.Array, jax.Array]:
    """Per-island elite over a raw stack: (elite_x [I, V], elite_y [I])."""
    slot = best_slot(y, minimize=minimize)
    return take_slot(x, slot), take_slot(y.astype(jnp.float32), slot)


def ring_migrate_stack(x: jax.Array, y: jax.Array, *, minimize: bool
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One full ring migration over an in-block island stack (I, N, V):
    elite extraction -> shift-by-one across the island axis (the `jnp.roll`
    ring, written as a concat so it traces into a kernel) -> worst-slot
    splice.  Returns (x', elite_x, elite_y).  `migrate_ring` runs it between
    launches; the resident-epoch kernel applies the same rules in VMEM."""
    elite_x, elite_y = elites_stack(x, y, minimize=minimize)
    shifted = jnp.concatenate([elite_x[-1:], elite_x[:-1]], axis=0)
    x2 = splice_at(x, worst_slot(y, minimize=minimize), shifted)
    return x2, elite_x, elite_y


def splice_elites(states: G.GAState, y: jax.Array, elites: jax.Array,
                  *, minimize: bool) -> G.GAState:
    """Replace each island's worst individual with the incoming elite.
    states: island-stacked [I, ...]; y: fitness of states.x [I, N]."""
    x = splice_at(states.x, worst_slot(y, minimize=minimize), elites)
    return states._replace(x=x)


def _best_of(states: G.GAState, y: jax.Array, cfg: IslandConfig):
    return best_of(states, y, minimize=cfg.ga.minimize)


def best_of(states: G.GAState, y: jax.Array, *, minimize: bool):
    """Per-island elite: (elite_x [I, V], elite_y [I]) of the current pops."""
    return elites_stack(states.x, y, minimize=minimize)


def migrate_ring(states: G.GAState, y: jax.Array, *, minimize: bool
                 ) -> Tuple[G.GAState, jax.Array, jax.Array]:
    """One on-host ring migration over an island-stacked state.

    The best individual of island i replaces the worst individual of island
    (i + 1) mod I — the `jnp.roll` analogue of the inter-FPGA elite links
    ([19]); `lax.ppermute` plays the same role on a device mesh (see
    `migrate_ring_sharded`).  This is THE migration step shared by
    `make_local_step` and the engine's island_ring topology (any executor).
    It delegates to `ring_migrate_stack`, whose elite/worst/splice rules the
    resident-epoch kernel's in-VMEM migration repeats island by island.

    Returns (new_states, elite_x [I, V], elite_y [I]).
    """
    x2, elite_x, elite_y = ring_migrate_stack(states.x, y, minimize=minimize)
    return states._replace(x=x2), elite_x, elite_y


# ---------------------------------------------------------------------------
# Sharded ring migration (inside shard_map) — bit-identical to migrate_ring
# ---------------------------------------------------------------------------


def ring_shift_sharded(x: jax.Array, mesh: Mesh,
                       axis_names: Sequence[str]) -> jax.Array:
    """Send `x` to the next shard in row-major linear order over `axis_names`.

    The inverse view: each shard receives the previous shard's `x`.  With the
    island axis sharded over several mesh axes jointly, "next shard" means
    linear index +1 over the raveled (row-major) axis tuple — i.e. exactly
    one global ring, not one ring per leading-axis slice.  Implemented as a
    `lax.ppermute` cascade: shift along the last axis, then patch the wrap
    positions (trailing indices all zero) with progressively higher-axis
    shifts.  Must be called inside `shard_map` over `axis_names`.

    Device-order canonicalization: the ring is defined over LOGICAL mesh
    coordinates (`lax.axis_index` / the ppermute permutation), and XLA
    shards global arrays by the same logical coordinates — the physical
    device array backing the mesh never enters the ordering.  A mesh built
    with a custom device permutation (`Mesh(devices[perm], ...)`) therefore
    yields the SAME island ring as the local `jnp.roll`, bit-for-bit; only
    which physical chip hosts each logical shard changes.  Asserted in
    tests/test_topology.py (permuted-device mesh vs local run).
    """
    def shift(v, a):
        s = mesh.shape[a]
        return jax.lax.ppermute(v, a,
                                perm=[(i, (i + 1) % s) for i in range(s)])

    out = shift(x, axis_names[-1])
    for j in range(len(axis_names) - 2, -1, -1):
        nxt = shift(out, axis_names[j])
        cond = jnp.bool_(True)
        for a in axis_names[j + 1:]:
            cond = cond & (jax.lax.axis_index(a) == 0)
        out = jnp.where(cond, nxt, out)
    return out


def migrate_ring_sharded(states: G.GAState, y: jax.Array, *, minimize: bool,
                         mesh: Mesh, axis_names: Sequence[str]
                         ) -> Tuple[G.GAState, jax.Array, jax.Array]:
    """`migrate_ring` for one shard of an island axis sharded over a mesh.

    states/y hold this shard's [I_local, ...] block.  Globally the effect is
    bit-identical to the single-device `migrate_ring` (`jnp.roll` by one over
    the full island axis): locally elites shift down by one island, and the
    boundary elite (this shard's last island) is `ppermute`d to the next
    shard in ring order, landing on its first island.

    Returns (new_states, elite_x [I_local, V], elite_y [I_local]).
    """
    elite_x, elite_y = best_of(states, y, minimize=minimize)
    recv = ring_shift_sharded(elite_x[-1], mesh, axis_names)   # [V] from prev
    shifted = jnp.concatenate([recv[None], elite_x[:-1]], axis=0)
    states = splice_elites(states, y, shifted, minimize=minimize)
    return states, elite_x, elite_y




# ---------------------------------------------------------------------------
# Single-host convenience (vmap only, no mesh) — used by tests/benchmarks
# ---------------------------------------------------------------------------


def make_local_step(cfg: IslandConfig, fit: G.FitnessFn, generation_fn=None):
    """Jitted epoch for a single-host island stack: `migrate_every` local
    generations + one on-host ring migration.  The independent oracle the
    engine's islands backend is asserted against.  Returns
    (states, elite_x, elite_y)."""

    @jax.jit
    def epoch(states):
        states, y = _local_generations(states, cfg, fit, cfg.migrate_every,
                                       generation_fn)
        states, elite_x, elite_y = migrate_ring(states, y,
                                                minimize=cfg.ga.minimize)
        return states, elite_x, elite_y

    return epoch
