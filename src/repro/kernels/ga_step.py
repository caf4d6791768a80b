"""Pallas TPU kernels: fused GA generations for one island or a stack.

This is the TPU re-expression of the paper's full-parallel datapath: on the
FPGA, FFM/SM/CM/MM are N physically parallel circuits clocked as one 3-cycle
pipeline; here a whole generation is one pass of a kernel whose working set
(population, fitness row, LFSR banks, selection scratch) lives in VMEM — no
HBM round-trips between GA stages.

Layout.  Inside the kernels an island is LANE-MAJOR: x (V, N), sel (2, N),
cross (V, N/2), mut (V, N), y (1, N) — the population index runs along the
128 lanes of a vreg, so every op is a 2-D (sublane, lane) op Mosaic lowers.
The launchers transpose x between the engine's (N, V) and (V, N) in XLA;
the FFM stage is evaluated on the (N, V) view, exactly the function the
reference executor evaluates.  A block holding several islands steps them
one at a time (a `fori_loop` over the refs' leading island axis): islands
are independent between migrations, and no op is ever 3-D.

Key adaptation — MUX trees → two selection lanes (``GAConfig.sel_lane``):
  the paper gathers tournament contestants through N-input multiplexer trees
  (SMMUX1..3, the source of its O(N²) LUT growth).  The kernels implement
  that gather two bit-identical ways:

  * ``"onehot"`` — (N, N) contestant masks: the fitness pick is an exact
    masked max, the chromosome pick contracts the winner one-hot against the
    population on the MXU in O(N²·V) MACs, the MUX tree's asymptotics in
    hardware we do have.  Bit-exactness: each uint32 word splits into two
    16-bit halves before the f32 matmul (≤ 2^16 is exact in f32; one nonzero
    per column), then recombines.
  * ``"gather"`` — dynamic indexing: `_lane_take` gathers lanes chunk by
    chunk (Mosaic gathers only inside one 128-lane vreg), O(N·V) working
    set, no (N, N) scratch.  This drops the one-hot term and with it the
    N ≤ 1024 cap.

  Both lanes consume the same tournament indices and tie rules, so they are
  bit-identical to each other and to the reference path.  Power-of-two N is
  required on both (the tournament indices are the top `idx_bits` of the
  LFSR draw).

The FFM stage is PLUGGABLE: the kernel takes a traceable ``ffm`` function
``uint32[N, V] bits -> f32[N]`` (normally ``FitnessProgram.stage`` from
repro.core.fitness — decode + the problem's jnp expression, a matrix
product on the MXU included, as in MAX-3SAT's clause contraction) and
traces it into the kernel body, so any n-variable registry problem or user
blackbox runs fused.  The reference executor evaluates the SAME function; the
registry problems reduce over V in one fixed order (`fitness.vsum`) so both
compilers round alike.  LUT-mode (HBM gather tables) stays in the pure-JAX
path.

Mosaic's limits shape the bodies: no uint32 <-> f32 casts (they go through
int32, exact for ≤ 31-bit genes and 16-bit halves), no unsigned min or
reductions (int32 again), no scalar stores (bests are (1, 1) / (V, 1)
blocks), and block shapes whose last two dims equal the array's.

Epoch planning & VMEM budget — the TWO-TIER decision:

  The file exposes the candidate launch shapes for the island_ring topology;
  the engine's epoch planner (`ga/backends.IslandRingTopology._epoch_plan`)
  picks among them in two tiers:

  tier 1 — FEASIBILITY (modeled, this module): `epoch_mode_candidates`
  enumerates which modes a spec can legally run, gated by the
  `resident_fit_reason` VMEM byte estimator.  The candidate modes are:

  * gridded (`ga_generation_kernel`) — one island per grid step; a launch
    folds up to `migrate_every` generations and the ring migration runs
    BETWEEN launches in XLA (`islands.migrate_ring`).  Always feasible;
    always the fallback.
  * resident (`ga_epoch_kernel`) — the island axis moves out of the grid
    into the kernel block: all (local-shard) islands live in one program
    instance's VMEM, and the launch folds `intervals × migrate_every`
    generations with the ring migration (the elite/worst tie rules of
    `islands.ring_migrate_stack`) executed INSIDE the loop.  On a mesh,
    `boundary=True` keeps one interval per launch and performs the
    intra-shard part of the migration in VMEM; the boundary elite is handed
    back for the between-launch `lax.ppermute` (mode "resident-sharded").
  * resident-free (`ga_epoch_kernel` with `migrate=False`) — the
    `migration="none"` ablation has no ring to run, so one launch folds the
    WHOLE `gens_per_epoch` with zero in-kernel migration work.
  * streamed (`ga_streamed_epoch_kernel`) — populations PAST the residency
    budget: the island axis joins the grid in tiles of `tile_islands`
    islands, and Pallas's grid pipeline double-buffers the tile loads.
    Elite/worst-slot extraction runs in-kernel per tile; the ring splice
    runs in XLA between kernel passes, inside one jitted `lax.scan` over the
    migration intervals.  `streamed_tile_islands` picks the tile.

  tier 2 — SELECTION (measured, `repro.autotune`): among feasible
  candidates the planner picks the best *measured* gens/s from a per-host
  cost table when one covers the spec, and otherwise keeps the first
  candidate — `epoch_mode_candidates` orders candidates so that index 0 IS
  the heuristic (resident when it fits, else streamed when a tile fits,
  else gridded).

  The VMEM estimator (`resident_vmem_bytes`) counts the (8, 128) tile
  padding Mosaic applies: the double-buffered island blocks of the stack,
  ONE island's generation temporaries (lane-aware: (N, N) masks on the
  onehot lane) and the hoisted FFM constants.  The budget comes from
  `VMEM_BUDGET_BYTES`, keyed by `device_kind` (REPRO_RESIDENT_VMEM_BUDGET
  overrides), and every launch hands the same number to Mosaic as
  `vmem_limit_bytes` — so a plan the estimator accepts compiles
  (tests/test_tpu_compile.py compiles the planned shapes for a v5e).

  Hoisted FFM closure constants are size-gated separately: the kernels
  refuse constants above `ffm_const_limit()` (default 2 MiB, override with
  REPRO_FFM_CONST_LIMIT) because every grid step re-reads them into VMEM —
  a large captured array (e.g. a dataset) should run on the reference path
  (the engine's capability check does that fallback automatically).
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import lfsr
from repro.core.ga import GAConfig, ONEHOT_MAX_N

# The kernel-facing FFM stage: uint32 bits (N, V) -> f32 fitness (N,).
FfmStage = Callable[[jax.Array], jax.Array]

# A TPU vreg is (8 sublanes, 128 lanes) of 32-bit words: VMEM arrays pad
# their last two dims to it, and Mosaic gathers within one vreg only.
_SUBLANES = 8
_LANES = 128
_LANE_SHIFT = 7


def _lfsr_draw(state, steps: int):
    """In-kernel LFSR-32 advance (paper polynomial r^32+r^22+r^2+1).

    Uses the precomputed GF(2) leap (`lfsr.leap_feedback_masks`): the
    register shifts `steps` bits at once and each inserted feedback bit is
    an XOR of masked original-state bits — bit-identical to `steps`
    sequential clocks, without the clock-to-clock dependency chain of the
    unrolled shift loop (the parities are independent and share their
    `s >> b` subterms)."""
    while steps > 0:                      # leap in chunks of < 32 clocks
        t = min(steps, 31)
        masks = lfsr.leap_feedback_masks(t)
        shifted = {}
        out = state << jnp.uint32(t)
        for j, m in enumerate(masks):
            acc = None
            for b in range(32):
                if not (m >> b) & 1:
                    continue
                if b not in shifted:
                    shifted[b] = state >> jnp.uint32(b) if b else state
                acc = shifted[b] if acc is None else acc ^ shifted[b]
            bit = acc & jnp.uint32(1)
            out = out | (bit << jnp.uint32(j) if j else bit)
        state = out
        steps -= t
    return state


def check_kernel_lane(cfg: GAConfig) -> None:
    """THE lane-aware validity gate for the fused kernel path — called by
    all three kernel entry points and by `GASpec` validation, replacing the
    bare asserts that used to be triplicated across the kernels.

    The tournament indices are the top `idx_bits` of the LFSR draw, so the
    kernel path requires a power-of-two N on ANY lane (the reference
    backend folds indices modulo N instead and takes any even N).  The
    onehot lane additionally caps N at `ONEHOT_MAX_N`: its (N, N) one-hot
    tournament matrices are the dominant VMEM term.  Raises ValueError —
    these conditions are reachable from user specs, not internal
    invariants."""
    if cfg.n & (cfg.n - 1):
        raise ValueError(
            f"N={cfg.n}: the fused kernel path draws tournament indices "
            "from the top idx_bits LFSR bits and requires a power-of-two N "
            "(the reference backend accepts any even N)")
    if cfg.sel_lane == "onehot" and cfg.n > ONEHOT_MAX_N:
        raise ValueError(
            f"N={cfg.n} > {ONEHOT_MAX_N} on the 'onehot' selection lane: "
            "the (N, N) one-hot tournament matrices would exceed VMEM.  "
            "Fix: split the population across more islands, or switch to "
            "the O(N*V) dynamic-indexing lane with sel_lane='gather'")


# ---------------------------------------------------------------------------
# FFM closure-constant hoisting + size gates / VMEM budget
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _ffm_jaxpr(ffm: FfmStage, n: int, v: int):
    """One shared trace of the FFM stage per (ffm, n, v).

    A fused engine build consults this trace up to three times — the
    `supports` const gate, the epoch planner's VMEM budget check and
    `_hoist_ffm` at kernel-build time — so a slow-to-trace blackbox fitness
    must not pay 3×.  `ffm` is a bound `FitnessProgram.stage` method or a
    user callable; both hash by identity.  `GASpec.program()` interns
    programs process-wide (`compile_cache.PROGRAM_CACHE`), so every spec of
    one problem shape — each job of a stream of fresh seed-only specs —
    passes the SAME bound method and the stage is traced once per problem
    shape, not once per job.  The cached jaxpr's consts keep any captured
    arrays (and the callable itself) alive, so id-keyed entries can't go
    stale."""
    return jax.make_jaxpr(lambda xx: jnp.asarray(ffm(xx), jnp.float32))(
        jax.ShapeDtypeStruct((n, v), jnp.uint32))


def ffm_trace_cache_info():
    """Hit/miss counters of the shared FFM trace cache (for tests/metrics)."""
    return _ffm_jaxpr.cache_info()


def _const_block(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """The 2-D VMEM block a hoisted FFM constant rides in: a matrix as
    itself (a rotation stays (D, D): turning a lane row back into a matrix
    would move data from lanes to sublanes), anything else flattened to one
    (1, size) lane row."""
    if len(shape) == 2:
        return tuple(int(d) for d in shape)
    return (1, max(int(np.prod(shape, dtype=np.int64)), 1))


def _hoist_ffm(ffm: FfmStage, n: int, v: int):
    """Lower the FFM stage to a jaxpr and hoist its captured array constants
    into explicit kernel inputs (Pallas kernels cannot capture non-scalar
    constants; `jax.closure_convert` only hoists autodiff-perturbed consts).
    Returns (conv_fn(x, *consts), const_shapes, blocks, const_bytes): each
    const rides in as its `_const_block` and `_bind_consts` restores its
    shape inside the kernel."""
    closed = _ffm_jaxpr(ffm, n, v)
    consts = closed.consts
    conv = lambda xx, *cs: jax.core.eval_jaxpr(closed.jaxpr, cs, xx)[0]
    const_shapes = tuple(np.shape(c) for c in consts)
    blocks = [jnp.reshape(jnp.asarray(c), _const_block(np.shape(c)))
              for c in consts]
    nbytes = int(sum(int(np.size(c)) * np.dtype(jnp.asarray(c).dtype).itemsize
                     for c in consts))
    return conv, const_shapes, blocks, nbytes


def ffm_const_bytes(ffm: FfmStage, cfg: GAConfig) -> int:
    """Total bytes of array constants the FFM stage closes over (what the
    kernels would replicate into VMEM) — the engine's capability check uses
    this to route oversized-const programs to the reference path.  Trace
    only: sizes come from the jaxpr consts' metadata, no flattening or
    device transfers (this runs at capability-check time, possibly against
    MB-scale captured arrays)."""
    closed = _ffm_jaxpr(ffm, cfg.n, cfg.v)
    return int(sum(int(np.size(c)) * np.dtype(c.dtype).itemsize
                   for c in closed.consts))


def ffm_const_vmem_bytes(ffm: FfmStage, cfg: GAConfig) -> int:
    """VMEM bytes of one copy of the FFM stage's hoisted constants, as the
    kernels lay them out: each in its `_const_block`, padded to (8, 128)
    tiles of 32-bit words."""
    closed = _ffm_jaxpr(ffm, cfg.n, cfg.v)
    return int(sum(_tile_bytes(*_const_block(np.shape(c)))
                   for c in closed.consts))


def ffm_const_limit() -> int:
    """Hoisted-const VMEM gate (bytes); REPRO_FFM_CONST_LIMIT overrides."""
    return int(os.environ.get("REPRO_FFM_CONST_LIMIT", str(2 << 20)))


def _check_const_gate(nbytes: int) -> None:
    limit = ffm_const_limit()
    if nbytes > limit:
        raise ValueError(
            f"FFM stage captures {nbytes} bytes of array constants > the "
            f"{limit}-byte VMEM gate: hoisted consts are replicated into "
            "VMEM on every grid step, so large captured arrays (datasets, "
            "big tables) should run on the 'reference' backend instead — "
            "the engine's capability check does this fallback automatically "
            "(REPRO_FFM_CONST_LIMIT overrides the gate)")


# Per-chip VMEM budget of one kernel launch, keyed by `jax.Device.device_kind`.
# TPU v5e ("TPU v5 lite") has 128 MiB of VMEM per TensorCore (JAX's
# `jax/_src/pallas/mosaic/tpu_info.py`, TPU_V5E entry).  The budget is half of
# it: every launch passes it to Mosaic as `vmem_limit_bytes`, and the other
# half stays free for Mosaic's internal scratch and for XLA.
VMEM_BUDGET_BYTES = {"TPU v5 lite": 64 << 20, "TPU v5e": 64 << 20}
# Interpret mode has no VMEM; it plans for the chip this repo targets, so a
# CPU run picks the same plan the chip would.
PLANNING_DEVICE_KIND = "TPU v5 lite"


def resident_vmem_budget() -> int:
    """VMEM byte budget of one kernel launch on this host's device — the
    planner's feasibility limit AND the `vmem_limit_bytes` every launch
    hands Mosaic.  REPRO_RESIDENT_VMEM_BUDGET overrides; a TPU kind missing
    from `VMEM_BUDGET_BYTES` is an error."""
    env = os.environ.get("REPRO_RESIDENT_VMEM_BUDGET")
    if env:
        return int(env)
    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else PLANNING_DEVICE_KIND
    if kind not in VMEM_BUDGET_BYTES:
        raise ValueError(
            f"no VMEM budget for TPU device kind {kind!r}: add its entry to "
            "kernels.ga_step.VMEM_BUDGET_BYTES (or set "
            "REPRO_RESIDENT_VMEM_BUDGET)")
    return VMEM_BUDGET_BYTES[kind]


def _tile_bytes(rows: int, cols: int) -> int:
    """Bytes of a 32-bit (rows, cols) VMEM array: Mosaic pads the
    second-minor dim to 8 sublanes and the minor dim to 128 lanes."""
    return 4 * (-(-rows // _SUBLANES) * _SUBLANES) * (-(-cols // _LANES)
                                                      * _LANES)


def _island_block_bytes(cfg: GAConfig) -> int:
    """One island's input + output blocks in the kernels' lane-major layout:
    state x (V, N), sel (2, N), cross (V, N/2), mut (V, N) in and out, plus
    the y (1, N), best (1, 1)/(V, 1) and elite/worst outputs."""
    n, v = cfg.n, cfg.v
    state = (2 * _tile_bytes(v, n) + _tile_bytes(2, n)
             + _tile_bytes(v, n // 2))
    extra = _tile_bytes(1, n) + 2 * _tile_bytes(1, 1) + 2 * _tile_bytes(v, 1)
    return 2 * state + extra


def _island_work_bytes(cfg: GAConfig) -> int:
    """Temporaries of one island's generation.  The kernels step the
    islands of a block one at a time, so one set is live per launch: the
    FFM stage on the (N, V) transpose, the LFSR/crossover/mutation rows and
    the selection lane's working set — four (N, N) masks/one-hots on the
    onehot lane, chunked (V, N) gathers on the gather lane."""
    n, v = cfg.n, cfg.v
    ffm = 8 * _tile_bytes(n, v)
    rows = 12 * _tile_bytes(v, n) + 8 * _tile_bytes(2, n)
    if cfg.sel_lane == "gather":
        sel = 4 * _tile_bytes(v, n) + 4 * _tile_bytes(2, n)
    else:
        sel = 4 * _tile_bytes(n, n) + 4 * _tile_bytes(v, n)
    return ffm + rows + sel


def resident_vmem_bytes(cfg: GAConfig, n_islands: int,
                        const_vmem: int = 0) -> int:
    """Estimated VMEM of one kernel program instance holding `n_islands`
    islands, with Mosaic's (8, 128) tile padding: the island blocks, which
    the Pallas pipeline double-buffers, one island's generation temporaries
    (see `_island_work_bytes`) and the hoisted FFM consts (`const_vmem`, one
    copy as `ffm_const_vmem_bytes` lays them out, double-buffered)."""
    return (2 * n_islands * _island_block_bytes(cfg) + _island_work_bytes(cfg)
            + 2 * const_vmem)


def resident_fit_reason(cfg: GAConfig, n_islands: int, const_vmem: int = 0,
                        budget: int = None) -> str:
    """None when `n_islands` VMEM-resident islands fit the budget, else the
    reason string — the epoch planner's fallback-to-gridded decision."""
    budget = resident_vmem_budget() if budget is None else budget
    need = resident_vmem_bytes(cfg, n_islands, const_vmem)
    if need > budget:
        return (f"resident epoch needs ~{need} B of VMEM for {n_islands} "
                f"island(s) at N={cfg.n} (> budget {budget} B); falling "
                "back to the gridded per-interval kernel "
                "(REPRO_RESIDENT_VMEM_BUDGET overrides)")
    return None


def streamed_tile_islands(cfg: GAConfig, i_local: int, const_vmem: int = 0,
                          budget: int = None) -> int:
    """The streamed lane's VMEM tile estimator: the largest island-tile size
    T (a divisor of `i_local`) with 2× its resident estimate within the
    budget (a margin over the pipeline's own double buffering, so a
    streamed tile never sits at the edge of what compiles).  None when even
    a single island won't fit — then only the gridded fallback remains."""
    budget = resident_vmem_budget() if budget is None else budget
    for t in range(i_local, 0, -1):
        if i_local % t:
            continue
        if 2 * resident_vmem_bytes(cfg, t, const_vmem) <= budget:
            return t
    return None


def epoch_mode_candidates(cfg: GAConfig, i_local: int, const_vmem: int = 0,
                          *, executor: str, migration: str,
                          gens_per_epoch: int, migrate_every: int,
                          sharded: bool, budget: int = None) -> list:
    """Tier 1 of the epoch plan: the FEASIBLE launch shapes for a spec,
    ordered so candidates[0] is the heuristic choice (what a planner with
    no cost table must pick, deterministically).

    Each candidate is a plan dict: {"mode", "lane", "epochs_per_launch",
    "gens_per_launch"} (+ "fallback" carrying the VMEM-estimator reason when
    a resident shape was rejected, + "tile_islands" for the streamed mode).
    The "lane" is `cfg.sel_lane` throughout — this function enumerates the
    launch shapes of ONE lane; the planner builds the cross-lane (mode ×
    lane) grid by calling it once per lane (see
    `IslandRingTopology._epoch_plan`), keeping the default candidate list
    (and the no-table heuristic) exactly what it was before lanes existed.
    `gens_per_launch` is the generations one kernel launch folds — the cost
    table's interpolation axis.  When the resident stack exceeds the budget
    the streamed lane — NOT gridded — is the heuristic for ring migration:
    it keeps kernel throughput at any population size, which is the lane's
    whole point.
    """
    # the gridded path launches one migrate_every-generation epoch at a
    # time; the fused executor's block folds min(gens_per_epoch, E) of those
    # generations per kernel launch, the reference executor scans all E
    g_gridded = (min(gens_per_epoch, migrate_every) if executor == "fused"
                 else migrate_every)
    gridded = {"mode": "gridded", "lane": cfg.sel_lane,
               "epochs_per_launch": 1, "gens_per_launch": g_gridded}
    if executor != "fused":
        return [gridded]
    if migration == "ring" and gens_per_epoch >= migrate_every:
        reason = resident_fit_reason(cfg, i_local, const_vmem, budget)
        if reason is not None:
            tile = streamed_tile_islands(cfg, i_local, const_vmem, budget)
            if tile is None:
                return [dict(gridded, fallback=reason)]
            k = max(1, gens_per_epoch // migrate_every)
            return [{"mode": "streamed", "lane": cfg.sel_lane,
                     "epochs_per_launch": k,
                     "gens_per_launch": k * migrate_every,
                     "tile_islands": tile, "fallback": reason},
                    dict(gridded, fallback=reason)]
        if sharded:
            return [{"mode": "resident-sharded", "lane": cfg.sel_lane,
                     "epochs_per_launch": 1,
                     "gens_per_launch": migrate_every}, gridded]
        k = max(1, gens_per_epoch // migrate_every)
        return [{"mode": "resident", "lane": cfg.sel_lane,
                 "epochs_per_launch": k,
                 "gens_per_launch": k * migrate_every}, gridded]
    if migration == "none" and gens_per_epoch > migrate_every and not sharded:
        # no ring to run: the resident kernel can fold the WHOLE epoch in
        # one launch (satellite of the autotune PR).  Gridded stays the
        # heuristic default — resident-free is selected by measurement (or
        # forced via plan_override), never silently.
        reason = resident_fit_reason(cfg, i_local, const_vmem, budget)
        if reason is not None:
            # gridded stays the heuristic for migration="none" (matching the
            # fitting case below); a feasible streamed tile is offered for
            # measurement/plan_override to pick.
            tile = streamed_tile_islands(cfg, i_local, const_vmem, budget)
            out = [dict(gridded, fallback=reason)]
            if tile is not None:
                k = max(1, gens_per_epoch // migrate_every)
                out.append({"mode": "streamed", "lane": cfg.sel_lane,
                            "epochs_per_launch": k,
                            "gens_per_launch": k * migrate_every,
                            "tile_islands": tile, "fallback": reason})
            return out
        return [gridded,
                {"mode": "resident-free", "lane": cfg.sel_lane,
                 "epochs_per_launch": max(1, gens_per_epoch // migrate_every),
                 "gens_per_launch": gens_per_epoch}]
    return [gridded]



# ---------------------------------------------------------------------------
# Kernel body: one island in the lane-major layout
# ---------------------------------------------------------------------------
#
# Inside the kernels an island is lane-major: x (V, N), sel (2, N),
# cross (V, N/2), mut (V, N), y (1, N) — the population index runs along the
# 128 lanes, so every operand is a 2-D (sublane, lane) tile.  The launchers
# transpose x between the engine's (N, V) layout and (V, N) in XLA, and the
# FFM stage still sees the (N, V) view the reference executor evaluates.
# Blocks holding several islands step them one at a time (`fori_loop` over
# the leading island axis of the refs), so no op is ever 3-D.


def _lane_take(a: jax.Array, idx: jax.Array) -> jax.Array:
    """out[:, j] = a[:, idx[:, j]] for a (R, N) array and a (1 | R, N) index
    row.  Mosaic gathers only within one 128-lane vreg, so a wider row is
    gathered chunk by chunk from every source chunk and the hit selected."""
    r, n = a.shape
    if r == 1:                          # Mosaic's gather wants R > 1
        return _lane_take(jnp.concatenate([a, a], axis=0), idx)[:1]
    idx = jnp.broadcast_to(idx, (r, n))
    if n <= _LANES:
        return jnp.take_along_axis(a, idx, axis=1)
    chunks = []
    for k in range(n // _LANES):
        ik = idx[:, k * _LANES:(k + 1) * _LANES]
        lo, src = ik & (_LANES - 1), ik >> _LANE_SHIFT
        acc = None
        for j in range(n // _LANES):
            g = jnp.take_along_axis(a[:, j * _LANES:(j + 1) * _LANES], lo,
                                    axis=1)
            acc = g if acc is None else jnp.where(src == j, g, acc)
        chunks.append(acc)
    return jnp.concatenate(chunks, axis=1)


def _pair_swap(w: jax.Array) -> jax.Array:
    """w[:, p ^ 1]: each crossover pair's partner (pairs never straddle a
    128-lane chunk, so each chunk gathers alone)."""
    n = w.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, min(n, _LANES)), 1)
    if n <= _LANES:
        return _lane_take(w, lane ^ 1)
    return jnp.concatenate(
        [_lane_take(w[:, k * _LANES:(k + 1) * _LANES], lane ^ 1)
         for k in range(n // _LANES)], axis=1)


def _pair_expand(s: jax.Array) -> jax.Array:
    """(R, N/2) per-pair values -> (R, N) with s[:, p // 2] at lane p."""
    h = s.shape[1]
    n = 2 * h
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, min(n, _LANES)), 1)
    if n <= _LANES:
        return _lane_take(jnp.concatenate([s, s], axis=1), lane >> 1)
    half = _LANES // 2
    return jnp.concatenate(
        [_lane_take(s[:, (k // 2) * _LANES:(k // 2 + 1) * _LANES],
                    (lane >> 1) + (k % 2) * half)
         for k in range(n // _LANES)], axis=1)


def _onehot_gather_u32(x: jax.Array, oh: jax.Array) -> jax.Array:
    """Exact uint32 column gather x (R, N) @ one-hot (N, N) on the MXU: two
    16-bit-half f32 matmuls (≤ 2^16 is exact in f32, one nonzero per
    column).  Casts go through int32 — Mosaic has no uint32<->f32 cast, and
    both halves fit int32 exactly."""
    hi = (x >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (x & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    ghi = jax.lax.dot(hi, oh, precision=jax.lax.Precision.HIGHEST)
    glo = jax.lax.dot(lo, oh, precision=jax.lax.Precision.HIGHEST)
    return ((ghi.astype(jnp.int32).astype(jnp.uint32) << 16)
            | glo.astype(jnp.int32).astype(jnp.uint32))


def _best_slot(y: jax.Array, minimize: bool) -> jax.Array:
    """First-occurrence best lane of a (1, N) fitness row -> int32 (1, 1):
    the reference argmin/argmax tie rule as a min over a masked iota (the
    same rule as `islands.best_slot`)."""
    m = (jnp.min(y, axis=1, keepdims=True) if minimize
         else jnp.max(y, axis=1, keepdims=True))
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    return jnp.min(jnp.where(y == m, lane, y.shape[1]), axis=1, keepdims=True)


def _take_column(x: jax.Array, slot: jax.Array) -> jax.Array:
    """x[:, slot] of a (V, N) population as a (V, 1) column: a masked int32
    sum (genes are ≤ 31 bits, so the int32 view is exact and the single
    nonzero term sums exactly; Mosaic has no unsigned reductions)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    hit = jnp.where(lane == slot, x.astype(jnp.int32), 0)
    return jnp.sum(hit, axis=1, keepdims=True).astype(jnp.uint32)


def _splice(x: jax.Array, slot: jax.Array, col: jax.Array) -> jax.Array:
    """x with column `slot` replaced by `col` (V, 1) — `islands.splice_at`
    for one lane-major island."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane == slot, col, x)


def _fitness_row(ffm: FfmStage, x: jax.Array) -> jax.Array:
    """The FFM stage on the (N, V) view of a lane-major island -> (1, N)."""
    return jnp.asarray(ffm(x.T), jnp.float32)[None, :]


def _one_generation(x, sel_in, cross_in, mut_in, *, cfg: GAConfig,
                    ffm: FfmStage):
    """One GA generation of one lane-major island.  Returns (x', sel',
    cross', mut', y) with y (1, N) the fitness of the incoming x."""
    n, c = cfg.n, cfg.c
    sel, cross, mut = (_lfsr_draw(b, cfg.steps_per_draw)
                       for b in (sel_in, cross_in, mut_in))
    y = _fitness_row(ffm, x)

    # ---- SM: tournaments on the configured selection lane -----------------
    i1 = (sel[0:1] >> jnp.uint32(32 - cfg.idx_bits)).astype(jnp.int32)
    i2 = (sel[1:2] >> jnp.uint32(32 - cfg.idx_bits)).astype(jnp.int32)
    if cfg.sel_lane == "gather":
        # dynamic-indexing lane: chunked lane gathers, O(N·V) scratch
        y12 = _lane_take(jnp.concatenate([y, y], axis=0),
                         jnp.concatenate([i1, i2], axis=0))
        y1, y2 = y12[0:1], y12[1:2]
        first_wins = (y1 <= y2) if cfg.minimize else (y1 >= y2)
        w = _lane_take(x, jnp.where(first_wins, i1, i2))
    else:
        # one-hot lane: m[c, r] says contestant c is slot r's draw; the
        # fitness pick is an exact masked max, the chromosome pick an MXU
        # contraction
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        m1, m2 = rows == i1, rows == i2
        y_col = jnp.transpose(y)
        y1 = jnp.max(jnp.where(m1, y_col, -jnp.inf), axis=0, keepdims=True)
        y2 = jnp.max(jnp.where(m2, y_col, -jnp.inf), axis=0, keepdims=True)
        first_wins = (y1 <= y2) if cfg.minimize else (y1 >= y2)
        wi = jnp.where(first_wins, i1, i2)
        w = _onehot_gather_u32(x, (rows == wi).astype(jnp.float32))

    # ---- CM: mask-shift single-point crossover, one cut per pair ----------
    # (the clamp runs in int32: Mosaic has no unsigned min; cut < 2^cut_bits)
    cut = jnp.minimum((cross >> jnp.uint32(32 - cfg.cut_bits))
                      .astype(jnp.int32), c).astype(jnp.uint32)
    s = _pair_expand(jnp.uint32(cfg.var_mask) >> cut)
    z = (w & ~s) | (_pair_swap(w) & s)

    # ---- MM: XOR-mutate the first P --------------------------------------
    rbits = mut >> jnp.uint32(32 - c)
    mutate = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1) < cfg.p
    return jnp.where(mutate, z ^ rbits, z), sel, cross, mut, y


def _island_gens(state, best, *, cfg: GAConfig, ffm: FfmStage, gens: int,
                 track_best: bool, g0=0):
    """`gens` generations of one island, folding the running best with the
    reference scan's strict-improvement + first-occurrence rule.  state is
    (x, sel, cross, mut); best is (by (1, 1), bx (V, 1), bg (1, 1)), bg
    the generation (counted from `g0`) the best was first seen in.
    Returns (state', best', y) with y the fitness of the last pre-update
    population."""
    mini = cfg.minimize

    def step(i, carry):
        x, sel, cross, mut, _y, by, bx, bg = carry
        x2, sel2, cross2, mut2, y = _one_generation(x, sel, cross, mut,
                                                    cfg=cfg, ffm=ffm)
        if track_best:
            slot = _best_slot(y, mini)                # y scores x
            gb = (jnp.min(y, axis=1, keepdims=True) if mini
                  else jnp.max(y, axis=1, keepdims=True))
            better = gb < by if mini else gb > by
            by = jnp.where(better, gb, by)
            bx = jnp.where(better, _take_column(x, slot), bx)
            bg = jnp.where(better, g0 + i, bg)
        return x2, sel2, cross2, mut2, y, by, bx, bg

    init = tuple(state) + (jnp.zeros((1, cfg.n), jnp.float32),) + tuple(best)
    out = (jax.lax.fori_loop(0, gens, step, init) if gens > 1
           else step(0, init))
    return out[:4], out[5:], out[4]


def _best_init(cfg: GAConfig):
    by = jnp.full((1, 1), jnp.inf if cfg.minimize else -jnp.inf, jnp.float32)
    return (by, jnp.zeros((cfg.v, 1), jnp.uint32),
            jnp.zeros((1, 1), jnp.int32))


def _bind_consts(ffm, const_shapes, rest):
    """Split a kernel's trailing refs into (FFM stage with its hoisted
    consts bound, output refs)."""
    n_consts = len(const_shapes)
    const_refs, out_refs = rest[:n_consts], rest[n_consts:]
    if not n_consts:
        return ffm, out_refs
    consts = [r[...] if len(s) == 2 else r[0].reshape(s)
              for r, s in zip(const_refs, const_shapes)]
    return (lambda x: ffm(x, *consts)), out_refs


def _compiler_params(interpret: bool) -> dict:
    """Every launch hands Mosaic the planner's VMEM budget, so a plan the
    estimator accepts is compiled under the same limit."""
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=resident_vmem_budget())}


def _to_lanes(x):
    """(..., N, V) engine layout -> the kernels' (..., V, N)."""
    return jnp.swapaxes(x, -1, -2)


# ---------------------------------------------------------------------------
# Gridded kernel: one island per grid step
# ---------------------------------------------------------------------------


def _kernel(x_ref, sel_ref, cross_ref, mut_ref,              # inputs
            *rest,                                           # consts + outputs
            cfg: GAConfig, ffm, const_shapes=(), gens: int = 1,
            track_best: bool = False):
    """One or MANY generations per launch for one island.

    gens > 1 is the VMEM-residency optimization: the FPGA keeps population
    + LFSRs in registers between clock beats; we keep them in VMEM between
    generations, so HBM sees one state read + one write per `gens`
    generations instead of per generation.

    `rest` leads with one VMEM ref per FFM closure constant (arrays the
    fitness captured, hoisted by `_hoist_ffm` — Pallas kernels cannot
    capture array constants directly); `const_shapes` restores their shapes.

    track_best=True adds two outputs (best_y, best_x) folding the running
    best individual *inside* the launch with the reference scan's strict
    improvement + first-occurrence tie rule — so a gens>1 launch loses no
    best-tracking fidelity, only per-generation trajectory resolution
    (y_out is the fitness of the LAST pre-update population)."""
    ffm_stage, out_refs = _bind_consts(ffm, const_shapes, rest)
    state = (x_ref[0], sel_ref[0], cross_ref[0], mut_ref[0])
    state, best, y = _island_gens(state, _best_init(cfg), cfg=cfg,
                                  ffm=ffm_stage, gens=gens,
                                  track_best=track_best)
    for ref, val in zip(out_refs, tuple(state) + (y,)
                        + (tuple(best[:2]) if track_best else ())):
        ref[0] = val


def ga_generation_kernel(x, sel, cross, mut, *, cfg: GAConfig,
                         ffm: FfmStage, interpret: bool = False,
                         gens: int = 1, track_best: bool = False
                         ) -> Tuple[jax.Array, ...]:
    """Launch the fused generation(s) over a stack of islands.

    x: uint32[I, N, V]; sel: uint32[I, 2, N]; cross: uint32[I, V, N//2];
    mut: uint32[I, V, N].  Returns (x', sel', cross', mut', y[I, N]).
    ffm: the traced FFM stage — uint32[N, V] -> f32[N] (normally
    `FitnessProgram.stage`; any traceable n-variable/blackbox objective).
    gens: generations per launch (VMEM-resident state between them).
    track_best appends (best_y[I], best_x[I, V]) — the running best over all
    `gens` in-kernel generations, reference tie rule (see `_kernel`).
    """
    check_kernel_lane(cfg)
    i_islands, n, v = x.shape
    assert (n, v) == (cfg.n, cfg.v)

    # Hoist any array constants the FFM stage closed over (decode bounds,
    # blackbox targets, ...) into explicit kernel inputs — Pallas kernels
    # cannot capture non-scalar constants.  Every const rides in replicated
    # (block index 0 on every grid step), which is why oversized consts are
    # rejected by the VMEM gate — see the module docstring.
    ffm_conv, const_shapes, consts, const_bytes = _hoist_ffm(ffm, n, v)
    _check_const_gate(const_bytes)

    blk = lambda *shape: pl.BlockSpec((1,) + shape,
                                      lambda i: (i,) + (0,) * len(shape))
    cblk = lambda c: pl.BlockSpec(c.shape, lambda i: (0, 0))
    kernel = functools.partial(_kernel, cfg=cfg, ffm=ffm_conv,
                               const_shapes=const_shapes, gens=gens,
                               track_best=track_best)
    state_blks = [blk(v, n), blk(2, n), blk(v, n // 2), blk(v, n)]
    shape = lambda *s, dt=jnp.uint32: jax.ShapeDtypeStruct((i_islands,) + s,
                                                           dt)
    state_shapes = [shape(v, n), shape(2, n), shape(v, n // 2), shape(v, n)]
    out_specs = state_blks + [blk(1, n)]
    out_shape = state_shapes + [shape(1, n, dt=jnp.float32)]
    if track_best:
        out_specs += [blk(1, 1), blk(v, 1)]
        out_shape += [shape(1, 1, dt=jnp.float32), shape(v, 1)]
    outs = pl.pallas_call(
        kernel,
        grid=(i_islands,),
        in_specs=state_blks + [cblk(c) for c in consts],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="ga_generation_kernel",
        **_compiler_params(interpret),
    )(_to_lanes(x), sel, cross, mut, *consts)
    res = (_to_lanes(outs[0]),) + tuple(outs[1:4]) + (outs[4][:, 0],)
    if track_best:
        res += (outs[5][:, 0, 0], outs[6][..., 0])
    return res


# ---------------------------------------------------------------------------
# Epoch kernel: a block of islands, migration intervals in the loop
# ---------------------------------------------------------------------------


def _epoch_body(x_ref, sel_ref, cross_ref, mut_ref,          # inputs
                *rest,                                       # consts + outputs
                cfg: GAConfig, ffm, const_shapes=(),
                migrate_every: int, intervals: int, ring: str):
    """`intervals × migrate_every` generations of a block of islands.

    The block holds an island stack [I, ...] (the whole shard for the
    resident modes, one tile for the streamed mode).  Each interval steps
    the islands one at a time through `migrate_every` generations (islands
    are independent between migrations), evaluates the migration fitness
    and applies `ring`:

      * "full"     — the in-VMEM ring migration: island i's pre-splice
        elite replaces island i+1's worst slot, island I-1's lands on
        island 0 — the rule set `islands.ring_migrate_stack` runs in XLA.
      * "boundary" — the sharded variant (intervals == 1): islands 1..I-1
        receive elites 0..I-2 and the outputs carry (boundary elite of
        island I-1, worst slot of island 0) for the between-launch
        `lax.ppermute` + splice.
      * "emit"     — the streamed variant: no splice; every island's elite
        and worst slot are outputs, the caller splices in XLA.
      * "none"     — no migration (`migration="none"`): one interval folds
        the whole launch.

    The per-island running best folds every generation with the reference
    strict-improvement/first-occurrence rule and records the generation it
    was first seen in; the y output is the migration
    fitness of the final (pre-splice) populations — one trajectory sample
    per launch."""
    ffm_stage, out_refs = _bind_consts(ffm, const_shapes, rest)
    x_o, sel_o, cross_o, mut_o, y_o, by_o, bx_o, bg_o = out_refs[:8]
    extra = out_refs[8:]
    mini = cfg.minimize
    n_isl = x_ref.shape[1]
    for dst, src in ((x_o, x_ref), (sel_o, sel_ref), (cross_o, cross_ref),
                     (mut_o, mut_ref)):
        dst[...] = src[...]
    for ref, val in zip((by_o, bx_o, bg_o), _best_init(cfg)):
        ref[...] = jnp.broadcast_to(val, ref.shape)

    def island(i, carry, g0):
        state = (x_o[0, i], sel_o[0, i], cross_o[0, i], mut_o[0, i])
        (x, sel, cross, mut), (by, bx, bg), _ = _island_gens(
            state, (by_o[0, i], bx_o[0, i], bg_o[0, i]), cfg=cfg,
            ffm=ffm_stage, gens=migrate_every, track_best=True, g0=g0)
        ymig = _fitness_row(ffm_stage, x)
        sel_o[0, i], cross_o[0, i], mut_o[0, i] = sel, cross, mut
        by_o[0, i], bx_o[0, i], bg_o[0, i] = by, bx, bg
        y_o[0, i] = ymig
        if ring == "none":
            x_o[0, i] = x
            return carry
        elite = _take_column(x, _best_slot(ymig, mini))
        worst = _best_slot(ymig, not mini)
        if ring == "emit":
            x_o[0, i] = x
            extra[0][0, i], extra[1][0, i] = elite, worst
            return carry
        # island i takes island i-1's pre-splice elite; island 0's arrives
        # after the loop (ring="full") or from the previous shard ("boundary")
        prev_elite, worst0 = carry
        x_o[0, i] = jnp.where(i > 0, _splice(x, worst, prev_elite), x)
        return elite, jnp.where(i == 0, worst, worst0)

    def interval(k, carry):
        last_elite, worst0 = jax.lax.fori_loop(
            0, n_isl, functools.partial(island, g0=k * migrate_every), carry)
        if ring == "full":
            x_o[0, 0] = _splice(x_o[0, 0], worst0, last_elite)
        return last_elite, worst0

    carry = (jnp.zeros((cfg.v, 1), jnp.uint32), jnp.zeros((1, 1), jnp.int32))
    carry = (jax.lax.fori_loop(0, intervals, interval, carry)
             if intervals > 1 else interval(0, carry))
    if ring == "boundary":
        extra[0][0], extra[1][0] = carry


def _epoch_call(x, sel, cross, mut, *, cfg: GAConfig, ffm: FfmStage,
                migrate_every: int, intervals: int, ring: str,
                tile_islands: int, interpret: bool, name: str):
    """Launch `_epoch_body` over a (G, I, ...) replica-group stack with
    `tile_islands` islands per block (grid (G, I // tile_islands)) and
    return the outputs in the engine's layout."""
    g_grid, i_islands, n, v = x.shape
    assert (n, v) == (cfg.n, cfg.v)
    ffm_conv, const_shapes, consts, const_bytes = _hoist_ffm(ffm, n, v)
    _check_const_gate(const_bytes)
    t = tile_islands

    def blk(*shape, per_island=True):
        lead = (1, t) if per_island else (1,)
        if per_island:
            return pl.BlockSpec(lead + shape,
                                lambda g, j: (g, j) + (0,) * len(shape))
        return pl.BlockSpec(lead + shape, lambda g, j: (g,) + (0,) * len(shape))

    shape = lambda *s, dt=jnp.uint32: jax.ShapeDtypeStruct(
        (g_grid, i_islands) + s, dt)
    cblk = lambda c: pl.BlockSpec(c.shape, lambda g, j: (0, 0))
    state_blks = [blk(v, n), blk(2, n), blk(v, n // 2), blk(v, n)]
    out_specs = state_blks + [blk(1, n), blk(1, 1), blk(v, 1), blk(1, 1)]
    out_shape = [shape(v, n), shape(2, n), shape(v, n // 2), shape(v, n),
                 shape(1, n, dt=jnp.float32), shape(1, 1, dt=jnp.float32),
                 shape(v, 1), shape(1, 1, dt=jnp.int32)]
    if ring == "emit":
        out_specs += [blk(v, 1), blk(1, 1)]
        out_shape += [shape(v, 1), shape(1, 1, dt=jnp.int32)]
    elif ring == "boundary":
        out_specs += [blk(v, 1, per_island=False),
                      blk(1, 1, per_island=False)]
        out_shape += [jax.ShapeDtypeStruct((g_grid, v, 1), jnp.uint32),
                      jax.ShapeDtypeStruct((g_grid, 1, 1), jnp.int32)]
    kernel = functools.partial(_epoch_body, cfg=cfg, ffm=ffm_conv,
                               const_shapes=const_shapes,
                               migrate_every=migrate_every,
                               intervals=intervals, ring=ring)
    outs = pl.pallas_call(
        kernel,
        grid=(g_grid, i_islands // t),
        in_specs=state_blks + [cblk(c) for c in consts],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
        **_compiler_params(interpret),
    )(_to_lanes(x), sel, cross, mut, *consts)
    res = (_to_lanes(outs[0]),) + tuple(outs[1:4]) + (
        outs[4][..., 0, :], outs[5][..., 0, 0], outs[6][..., 0])
    if ring == "emit":
        res += (outs[8][..., 0], outs[9][..., 0, 0])
    elif ring == "boundary":
        res += (outs[8][..., 0], outs[9][:, 0, 0])
    return res + (outs[7][..., 0, 0],)


def ga_epoch_kernel(x, sel, cross, mut, *, cfg: GAConfig, ffm: FfmStage,
                    migrate_every: int, intervals: int = 1,
                    boundary: bool = False, migrate: bool = True,
                    interpret: bool = False) -> Tuple[jax.Array, ...]:
    """Launch the resident-epoch kernel over replica-stacked island shards.

    x: uint32[G, I, N, V]; sel: uint32[G, I, 2, N]; cross: uint32[G, I, V,
    N//2]; mut: uint32[G, I, V, N] — G independent replica groups ride the
    grid, each program instance keeps its I islands VMEM-resident for
    `intervals × migrate_every` generations with the ring migration folded
    into the loop (see `_epoch_body`; `boundary=True` for the sharded
    intra-shard variant, which requires intervals == 1).

    Returns (x', sel', cross', mut', y[G, I, N], best_y[G, I],
    best_x[G, I, V]) — y is the final migration fitness (pre-splice) —
    plus (send_elite[G, V], worst0[G]) when boundary=True, and last
    best_gen[G, I]: the launch generation each island's best was first
    seen in (the engine's cross-island tie rule needs it).

    migrate=False (migration-free resident mode) skips the in-loop ring
    splice; pass the full generation fold as `migrate_every` with
    intervals=1.

    Callers should consult `resident_fit_reason` first; this function
    raises on a stack over the budget (and on the hoisted-const gate)
    rather than handing Mosaic a block it would refuse.
    """
    check_kernel_lane(cfg)
    assert intervals >= 1 and migrate_every >= 1
    assert not (boundary and intervals != 1), \
        "boundary (sharded) epochs exchange elites between launches: one " \
        "migration interval per launch"
    assert migrate or not boundary, \
        "boundary epochs exist to exchange elites: migrate=False has none"
    i_islands = x.shape[1]
    reason = resident_fit_reason(cfg, i_islands,
                                 ffm_const_vmem_bytes(ffm, cfg))
    if reason is not None:
        raise ValueError(reason)
    ring = "boundary" if boundary else ("full" if migrate else "none")
    return _epoch_call(x, sel, cross, mut, cfg=cfg, ffm=ffm,
                       migrate_every=migrate_every, intervals=intervals,
                       ring=ring, tile_islands=i_islands,
                       interpret=interpret, name="ga_epoch_kernel")


def ga_streamed_epoch_kernel(x, sel, cross, mut, *, cfg: GAConfig,
                             ffm: FfmStage, migrate_every: int,
                             tile_islands: int, migrate: bool = True,
                             interpret: bool = False,
                             ) -> Tuple[jax.Array, ...]:
    """One migration interval streamed through VMEM in island tiles.

    x: uint32[G, I, N, V] (+ the sel/cross/mut LFSR banks, same leading
    axes): G replica groups × I islands, tiled through the kernel
    `tile_islands` islands at a time over grid (G, I // tile_islands).
    Pallas's grid pipeline double-buffers the block loads — the next tile's
    HBM→VMEM copy overlaps the current tile's `migrate_every` generations —
    so populations far past `resident_vmem_budget()` keep kernel throughput.

    Returns (x', sel', cross', mut', y[G, I, N], best_y[G, I],
    best_x[G, I, V]) plus, when migrate=True, (elite_x[G, I, V],
    worst_idx[G, I]) — the PRE-splice migration ingredients — and last
    best_gen[G, I] as in `ga_epoch_kernel`.  The caller
    owns the ring: shift the elites by one island (`ppermute` across shards
    at the boundary) and `islands.splice_at` the worst slots in XLA, then
    feed the spliced state to the next interval's kernel pass (see
    `ga/backends.IslandRingTopology._streamed_runner`).  migrate=False (the
    `migration="none"` ablation) skips the elite outputs and the caller
    skips the splice.

    Callers should consult `streamed_tile_islands` first; this function
    raises on a tile over the REAL budget (env-derived — a planner-forced
    smaller budget never makes a legitimate tile illegal here).
    """
    check_kernel_lane(cfg)
    assert migrate_every >= 1 and tile_islands >= 1
    i_islands = x.shape[1]
    assert i_islands % tile_islands == 0, \
        f"tile_islands={tile_islands} must divide the island count {i_islands}"
    need = 2 * resident_vmem_bytes(cfg, tile_islands,
                                   ffm_const_vmem_bytes(ffm, cfg))
    real_budget = resident_vmem_budget()
    if need > real_budget:
        raise ValueError(
            f"streamed tile of {tile_islands} island(s) at N={cfg.n} needs "
            f"~{need} B of VMEM (> budget {real_budget} B); use "
            "streamed_tile_islands to size the tile")
    return _epoch_call(x, sel, cross, mut, cfg=cfg, ffm=ffm,
                       migrate_every=migrate_every, intervals=1,
                       ring="emit" if migrate else "none",
                       tile_islands=tile_islands, interpret=interpret,
                       name="ga_streamed_epoch_kernel")
