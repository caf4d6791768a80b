"""Fitness Function Module (FFM) — paper Sec. 3.1, generalized to V variables.

The paper computes  y = γ(α(px) + β(qx))  with three ROMs per individual and
notes the architecture extends "to more variables from some adjustments on
hardware architecture".  This module is that adjustment: a registered
:class:`ProblemDef` (or a user blackbox) is *compiled* into a
:class:`FitnessProgram`, one object that lowers the same problem to every
evaluation mode the engine's executors consume:

  * ``lut``   — faithful: per-variable int32 fixed-point ROMs stacked into
    one [V, 2^c] table (the paper's α/β ROMs are the V=2 rows), one δ add
    tree and an optional γ ROM.  Available for separable problems
    ``f(x) = γ(Σ_i φ(x_i))`` — exactly the family the FFM synthesizes.
  * ``arith`` — TPU-native: the problem's jnp expression evaluated in f32 on
    the VPU (HBM gathers are far more expensive than FMAs on TPU).
  * in-kernel stage — ``FitnessProgram.stage`` is a traceable
    ``uint32[(..., V)] bits -> f32[...]`` function the Pallas ``ga_step``
    kernel calls as its FFM stage, so *any* traceable problem — n-variable
    benchmarks and user blackboxes included — runs fused.  The reference
    executor evaluates the SAME traced function, which is what makes
    reference × fused bit-identity hold for every registered problem.

All modes share the domain mapping: a c-bit unsigned gene u decodes to
v = lo + u * (hi - lo) / (2^c - 1), per variable.  A bitstring genome
(``ProblemDef.genome="bits"``, MAX-3SAT) uses the domain (0, 2^c - 1),
whose step is exactly 1, so its objective sees each word's own value.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Problem registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProblemDef:
    """A registered n-variable optimisation problem.

    ``fn`` is the batch evaluator ``(..., V) f32 -> (...,) f32`` in jnp —
    traceable, so it lowers to XLA *and* into the Pallas kernel.  The
    optional separable form ``f(x) = gamma(Σ_i term(v, i))`` (``term`` in
    numpy, evaluated at ROM-synthesis time) enables the LUT lowering; leave
    it None for non-separable problems (rosenbrock, ackley, blackboxes),
    which then run mode='arith' only.

    ``genome`` says what a chromosome means to its user: ``"real"`` — V
    variables in ``domain``; ``"bits"`` — a V·c-bit string (``fixed_bits``
    pins c, and ``domain`` is (0, 2^c - 1), so ``fn`` reads each word as
    its own value), which `GASpec.decode` returns as its 0/1 assignment.
    """

    name: str
    fn: Callable[[jax.Array], jax.Array]
    domain: Tuple[float, float]          # per-variable decode box
    fixed_vars: Optional[int] = None     # paper and bitstring problems pin V
    default_vars: int = 2
    min_vars: int = 1
    minimize: bool = True
    term: Optional[Callable[[np.ndarray, int], np.ndarray]] = None
    gamma: Optional[Callable[[np.ndarray], np.ndarray]] = None  # None = id
    genome: str = "real"                 # "real" | "bits"
    fixed_bits: Optional[int] = None     # bitstring problems pin c

    @property
    def separable(self) -> bool:
        """Whether the LUT (stacked per-variable ROM) lowering exists."""
        return self.term is not None

    def f(self, vals) -> jax.Array:
        """Convenience single/batch evaluation over a trailing V axis."""
        return self.fn(jnp.asarray(vals, jnp.float32))


PROBLEMS: Dict[str, ProblemDef] = {}


def register_problem(pdef: ProblemDef) -> ProblemDef:
    """Add a problem to the registry (user problems welcome — see
    examples/custom_fitness.py)."""
    PROBLEMS[pdef.name] = pdef
    return pdef


def resolve_problem(problem: str) -> Tuple[ProblemDef, Optional[int]]:
    """Look up ``"name"`` or ``"name:V"`` -> (ProblemDef, requested V or
    None).  The ``:V`` suffix is the CLI/spec shorthand for n_vars."""
    name, sep, vs = problem.partition(":")
    n_vars = None
    if sep:
        try:
            n_vars = int(vs)
        except ValueError:
            raise ValueError(f"bad problem spec {problem!r}: the :V suffix "
                             "must be an integer, e.g. 'rastrigin:8'")
    if name not in PROBLEMS:
        raise ValueError(f"unknown problem {name!r}; "
                         f"choose from {sorted(PROBLEMS)}")
    return PROBLEMS[name], n_vars


def resolve_vars(pdef: ProblemDef, n_vars: Optional[int]) -> int:
    """Validate a requested variable count against a problem's shape rules
    (a pinned V, minimum V) and return the effective V.  THE shared
    rule set — `GASpec` validation and `compile_program` both call this."""
    if pdef.fixed_vars is not None:
        if n_vars is not None and n_vars != pdef.fixed_vars:
            raise ValueError(f"problem {pdef.name!r} is defined at "
                             f"V={pdef.fixed_vars}; got n_vars={n_vars}")
        return pdef.fixed_vars
    v = n_vars if n_vars is not None else pdef.default_vars
    if v < pdef.min_vars:
        raise ValueError(f"problem {pdef.name!r} needs at least "
                         f"{pdef.min_vars} variables; got n_vars={v}")
    return v


def check_bits(pdef: ProblemDef, bits_per_var: int) -> None:
    """Reject a word width a bitstring problem is not defined at (shared by
    `GASpec` validation and `compile_program`)."""
    if pdef.fixed_bits is not None and bits_per_var != pdef.fixed_bits:
        raise ValueError(f"problem {pdef.name!r} is a bitstring of "
                         f"{pdef.fixed_bits}-bit words; got "
                         f"bits_per_var={bits_per_var}")


def check_mode(pdef: ProblemDef, mode: str) -> None:
    """Reject FFM modes the problem cannot lower to (shared by `GASpec`
    validation and `compile_program`)."""
    if mode not in ("lut", "arith"):
        raise ValueError(f"mode must be 'lut' or 'arith', got {mode!r}")
    if mode == "lut" and not pdef.separable:
        raise ValueError(f"problem {pdef.name!r} has no separable form for "
                         "the LUT ROMs (mode='lut'); run mode='arith'")


# --- The paper's three validation functions (Sec. 4), fixed at V=2 ---------

# F1: f(x) = x^3 - 15 x^2 + 500   (one variable; paper Eq. 24, range ±2^12).
# The paper still lays it out as px ‖ qx with α(px) = 0, so V stays 2.
F1 = register_problem(ProblemDef(
    name="F1",
    fn=lambda v: v[..., 1] ** 3 - 15.0 * v[..., 1] ** 2 + 500.0,
    domain=(-4096.0, 4095.0),
    fixed_vars=2,
    term=lambda v, i: (np.zeros_like(v) if i == 0
                       else v ** 3 - 15.0 * v ** 2 + 500.0),
))

# F2: f(x, y) = 8x - 4y + 1020   (paper Eq. 25)
F2 = register_problem(ProblemDef(
    name="F2",
    fn=lambda v: 8.0 * v[..., 0] + (-4.0 * v[..., 1] + 1020.0),
    domain=(-128.0, 127.0),
    fixed_vars=2,
    term=lambda v, i: 8.0 * v if i == 0 else -4.0 * v + 1020.0,
))

# F3: f(x, y) = sqrt(x^2 + y^2)   (paper Eq. 26)
F3 = register_problem(ProblemDef(
    name="F3",
    fn=lambda v: jnp.sqrt(jnp.maximum(
        v[..., 0] * v[..., 0] + v[..., 1] * v[..., 1], 0.0)),
    domain=(-128.0, 127.0),
    fixed_vars=2,
    term=lambda v, i: v.astype(np.float64) ** 2,
    gamma=lambda d: np.sqrt(np.maximum(d, 0.0)),
))


# --- The standard n-variable GA benchmark suite (configurable V) -----------


def vsum(t: jax.Array) -> jax.Array:
    """Σ over the trailing variable axis as a left fold of static slices.

    One fixed add order that XLA and the Pallas kernel both execute — a
    ``jnp.sum`` leaves the order of its float adds to each compiler, and the
    fused and reference executors then round the same population
    differently."""
    acc = t[..., 0]
    for i in range(1, t.shape[-1]):
        acc = acc + t[..., i]
    return acc


register_problem(ProblemDef(
    name="sphere",
    fn=lambda v: vsum(v * v),
    domain=(-5.12, 5.12),
    term=lambda v, i: v.astype(np.float64) ** 2,
))

register_problem(ProblemDef(
    name="rastrigin",
    # 10V + Σ x² - 10 cos(2πx), folded as Σ (x² - 10 cos(2πx) + 10)
    fn=lambda v: vsum(v * v - 10.0 * jnp.cos(2.0 * np.pi * v) + 10.0),
    domain=(-5.12, 5.12),
    term=lambda v, i: (v.astype(np.float64) ** 2
                       - 10.0 * np.cos(2.0 * np.pi * v) + 10.0),
))

register_problem(ProblemDef(
    name="rosenbrock",
    # coupled terms -> not separable -> arith/kernel modes only
    fn=lambda v: vsum(
        100.0 * (v[..., 1:] - v[..., :-1] * v[..., :-1]) ** 2
        + (1.0 - v[..., :-1]) ** 2),
    domain=(-2.048, 2.048),
    min_vars=2,
))

register_problem(ProblemDef(
    name="ackley",
    # two coupled reductions -> not γ(Σφ)-separable -> arith/kernel only
    fn=lambda v: (-20.0 * jnp.exp(
        -0.2 * jnp.sqrt(vsum(v * v) / v.shape[-1]))
        - jnp.exp(vsum(jnp.cos(2.0 * np.pi * v)) / v.shape[-1])
        + 20.0 + np.e),
    domain=(-32.768, 32.768),
))


# --- COCO/BBOB noiseless testbed (Hansen, Finck, Ros & Auger 2009) ---------


@dataclasses.dataclass(frozen=True)
class BbobF24Instance:
    """Instance arrays of f24 at D variables, each rounded once to float32.

    `mt` is M^T for M = Q diag(lambda) R (the fold reads one row of M^T per
    variable), `a` the row 2 * sign(x_opt); the scalars are s, mu1 and
    d * D of RR-6829."""

    mt: np.ndarray          # float32 (D, D)
    a: np.ndarray           # float32 (1, D)
    s: np.float32
    mu1: np.float32
    d_dim: np.float32


BBOB_MU0 = 2.5
BBOB_F_OPT = 0.0


def _orthogonal(rng: np.random.Generator, d: int) -> np.ndarray:
    """Q of the QR of a standard-normal draw, each column's sign set so
    that diag(r) > 0 (a Haar-random orthogonal matrix)."""
    q, r = np.linalg.qr(rng.standard_normal((d, d)))
    return q * np.sign(np.diag(r))


def bbob_f24_instance(d: int) -> BbobF24Instance:
    """Instance 1 of f24 at D=d, from `numpy.random.default_rng(1)`: R, then
    Q, then the signs of x_opt, built in float64.  COCO's own instance
    generator is not used, so x_opt and the rotations differ from COCO's
    instance 1; f_opt is 0."""
    if d < 2:
        raise ValueError(f"bbob_f24 needs at least 2 variables, got {d}")
    rng = np.random.default_rng(1)
    r = _orthogonal(rng, d)
    q = _orthogonal(rng, d)
    sign = np.where(rng.standard_normal(d) < 0.0, -1.0, 1.0)
    lam = 100.0 ** (0.5 * np.arange(d) / (d - 1))
    m = q @ np.diag(lam) @ r
    s = 1.0 - 1.0 / (2.0 * np.sqrt(d + 20.0) - 8.2)
    mu1 = -np.sqrt((BBOB_MU0 ** 2 - 1.0) / s)
    return BbobF24Instance(mt=np.ascontiguousarray(m.T).astype(np.float32),
                           a=(2.0 * sign).astype(np.float32)[None, :],
                           s=np.float32(s), mu1=np.float32(mu1),
                           d_dim=np.float32(1.0 * d))


@functools.lru_cache(maxsize=8)
def bbob_f24(d: int) -> Callable[[jax.Array], jax.Array]:
    """f24, the Lunacek bi-Rastrigin function of RR-6829, at D=d:

        x^ = 2 sign(x_opt) * x,   u = x^ - mu0,   z = M u
        f = min(sum u^2, d D + s sum (x^ - mu1)^2)
            + 10 (D - sum cos(2 pi z)) + 1e4 sum max(0, |x| - 5)^2 + f_opt

    in float32, in the order written: z[..., i] is a left fold over j of
    M[i, j] u[..., j] (one row of M^T per step, so every element rounds in
    the same order on every compiler), each sum is `vsum`, and the final
    sum runs left to right.  The rotation stays on the VPU: a matmul's
    pass split and accumulation order belong to the compiler.  The two
    arrays are traced as one (D, D) and one (1, D) constant.  Cached per
    D, so every program of one D shares one instance and one evaluator."""
    inst = bbob_f24_instance(d)

    def f(x):
        mt, a = jnp.asarray(inst.mt), jnp.asarray(inst.a)
        xh = a * x
        u = xh - BBOB_MU0
        z = u[..., 0:1] * mt[0:1]
        for j in range(1, d):
            z = z + u[..., j:j + 1] * mt[j:j + 1]
        w = xh - inst.mu1
        near = vsum(u * u)
        far = inst.d_dim + inst.s * vsum(w * w)
        ras = 10.0 * (np.float32(d) - vsum(jnp.cos(2.0 * np.pi * z)))
        out = jnp.maximum(jnp.abs(x) - 5.0, 0.0)
        return (jnp.minimum(near, far) + ras + 1e4 * vsum(out * out)
                + BBOB_F_OPT)

    return f


register_problem(ProblemDef(
    name="bbob_f24",
    fn=lambda v: bbob_f24(v.shape[-1])(v),
    domain=(-5.0, 5.0),
    default_vars=40,
    min_vars=2,
))


# --- MAX-3SAT at SATLIB's uf250-1065 shape (a bitstring genome) -----------

MAXSAT_VARS = 250
MAXSAT_CLAUSES = 1065
MAXSAT_WORDS = 25             # V: 25 words ...
MAXSAT_WORD_BITS = 10         # ... of c = 10 bits; variable j is bit j % 10
                              # (from the least significant) of word j // 10


@dataclasses.dataclass(frozen=True)
class MaxSatInstance:
    """A uniform random 3-SAT formula: clause k is the disjunction of
    literals (var[k, l], neg[k, l]), l < 3, over three distinct variables;
    neg 1 marks a negated literal."""

    var: np.ndarray         # int64 (m, 3)
    neg: np.ndarray         # int64 (m, 3)


def maxsat_instance() -> MaxSatInstance:
    """The uf250-1065-shaped instance, drawn by the uniform model of
    Mitchell, Selman & Levesque (AAAI 1992) from
    `numpy.random.default_rng(1)`: each clause's three distinct variables,
    then every clause's negation bits.  Not one of SATLIB's files, and not
    filtered to satisfiable formulas."""
    rng = np.random.default_rng(1)
    var = np.stack([rng.choice(MAXSAT_VARS, size=3, replace=False)
                    for _ in range(MAXSAT_CLAUSES)])
    neg = rng.integers(0, 2, size=(MAXSAT_CLAUSES, 3))
    return MaxSatInstance(var=var, neg=neg)


@functools.lru_cache(maxsize=1)
def maxsat_uf250() -> Callable[[jax.Array], jax.Array]:
    """The number of unsatisfied clauses of the uf250-1065-shaped instance
    as a clause contraction on the MXU: the words' values ``f32[..., 25]``
    (decoded with the domain (0, 1023), whose step is exactly 1.0, so each
    is its word, exact in float32) -> ``f32[...]``, minimised (0: the
    assignment satisfies the formula).

    The population turns lane-major, the kernels' own layout (N along the
    lanes), and its words unpack into ten shift-and-mask bit planes stacked
    down the sublanes: row b * 25 + j of the (250, N) bit matrix holds
    variable 10 j + b.  Column r of the signed incidence
    matrix W (1065, 250) is that variable: W[k, r] is +1 where clause k
    holds it plainly, -1 where negated, 0 elsewhere.  W @ bits + b, with
    b[k] the count of clause k's negated literals, is the number of true
    literals of each clause; a clause with none is unsatisfied.

    Exact at any MXU precision and in any accumulation order: every
    product is 0 or +-1 (exact in bfloat16, where both operands live) and
    each clause's sum has at most three nonzero terms, so every partial
    sum is an integer of magnitude <= 3; the count of unsatisfied clauses
    is an integer <= 1065, exact in float32 whatever order adds it.  So the
    kernel, XLA and a literal-by-literal reference agree bit for bit, and
    padding W with zero columns, or with clauses of b = 1, would change
    nothing.

    Built once per process (the instance, W and b); both ride into the
    fused kernels as hoisted 2-D constants."""
    inst = maxsat_instance()
    c, v = MAXSAT_WORD_BITS, MAXSAT_WORDS
    w = np.zeros((MAXSAT_CLAUSES, MAXSAT_VARS), np.float32)
    cols = (inst.var % c) * v + inst.var // c       # plane-major unpack
    rows = np.broadcast_to(np.arange(MAXSAT_CLAUSES)[:, None], cols.shape)
    w[rows, cols] = np.where(inst.neg == 1, -1.0, 1.0)
    w = w.astype(jnp.bfloat16)
    b = inst.neg.sum(axis=1).astype(np.float32)[:, None]

    def f(vals):
        if vals.ndim == 1:                                 # one genome
            return f(vals[None])[0]
        xt = jnp.swapaxes(vals.astype(jnp.int32), -1, -2)  # (..., V, N)
        planes = [((xt >> k) & 1).astype(jnp.float32) for k in range(c)]
        bits = jnp.concatenate(planes, axis=-2).astype(jnp.bfloat16)
        true_lits = jnp.matmul(jnp.asarray(w), bits,
                               preferred_element_type=jnp.float32)
        true_lits = true_lits + jnp.asarray(b)             # (..., m, N)
        return jnp.sum(jnp.where(true_lits < 0.5, 1.0, 0.0), axis=-2)

    return f


register_problem(ProblemDef(
    name="maxsat_uf250",
    fn=lambda x: maxsat_uf250()(x),
    domain=(0.0, float((1 << MAXSAT_WORD_BITS) - 1)),
    fixed_vars=MAXSAT_WORDS,
    genome="bits",
    fixed_bits=MAXSAT_WORD_BITS,
))

# The builders of the instance arrays that registered objectives close
# over, by problem name.  Each is lru_cached, so it builds once per
# process; the engine's build calls it inside the span
# ``ga.problem.instance``, before anything traces the objective.
INSTANCES: Dict[str, Callable[[], object]] = {"maxsat_uf250": maxsat_uf250}


def decode(u: jax.Array, c: int, domain: tuple) -> jax.Array:
    """Decode a c-bit unsigned gene to its real value (single shared box)."""
    lo, hi = domain
    scale = (hi - lo) / float((1 << c) - 1)
    return lo + u.astype(jnp.int32).astype(jnp.float32) * jnp.float32(scale)


# ---------------------------------------------------------------------------
# LUT (faithful) mode — per-variable ROMs stacked into one table
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LutTables:
    """Fixed-point ROM contents for one separable problem at width c.

    var_t: int32[V, 2^c] — per-variable term ROMs scaled by 2^frac_bits
           (the paper's α/β ROMs are rows 0 and 1 of the V=2 case).
    gamma_t: int32[2^g] or None (None == identity γ, paper's F1/F2 case where
             the third ROM is a pass-through).
    delta_min / delta_shift: the γ ROM is addressed by
             clip((δ - delta_min) >> delta_shift, 0, 2^g - 1).
    """

    c: int
    frac_bits: int
    var_t: np.ndarray
    gamma_t: Optional[np.ndarray]
    delta_min: int
    delta_shift: int
    g: int


def build_tables(pdef: ProblemDef, c: int, n_vars: int,
                 frac_bits: Optional[int] = None, g: int = 14) -> LutTables:
    """Quantize the per-variable terms + γ into ROM tables (FFM synthesis).

    frac_bits may be negative (coarser-than-integer fixed point) — exactly
    what a hardware synthesis would do when the fitness range exceeds the
    ROM word width.  If None, the largest value keeping |Σ terms| within
    int31 is chosen automatically (capped at 8 fractional bits).
    """
    if not pdef.separable:
        raise ValueError(f"problem {pdef.name!r} has no separable form — "
                         "the LUT ROMs cannot be synthesized; run "
                         "mode='arith'")
    u = np.arange(1 << c, dtype=np.float64)
    lo, hi = pdef.domain
    v = lo + u * (hi - lo) / float((1 << c) - 1)
    terms = [np.asarray(pdef.term(v, i), np.float64) for i in range(n_vars)]

    if frac_bits is None:
        peak = sum(np.abs(t).max() for t in terms)
        frac_bits = 8
        while frac_bits > -24 and peak * (2.0 ** frac_bits) >= 2 ** 30:
            frac_bits -= 1

    scale = float(2.0 ** frac_bits)
    fixed = [np.round(t * scale).astype(np.int64) for t in terms]

    # int32 saturation (the ROM word width)
    i32 = lambda t: np.clip(t, -(2 ** 31), 2 ** 31 - 1).astype(np.int32)
    var_t = np.stack([i32(t) for t in fixed])

    if pdef.gamma is None:
        return LutTables(c, frac_bits, var_t, None, 0, 0, 0)

    dmin = int(sum(t.min() for t in fixed))
    dmax = int(sum(t.max() for t in fixed))
    span = max(dmax - dmin, 1)
    shift = max(0, int(np.ceil(np.log2(span / ((1 << g) - 1) + 1e-12))) if span >= (1 << g) else 0)
    # γ table: value at address k represents δ = dmin + (k << shift)
    k = np.arange(1 << g, dtype=np.int64)
    delta = (dmin + (k << shift)).astype(np.float64) / scale
    gamma_t = i32(np.round(pdef.gamma(delta) * scale))
    return LutTables(c, frac_bits, var_t, gamma_t, dmin, shift, g)


def lut_fitness(x: jax.Array, t: LutTables) -> jax.Array:
    """Faithful FFM: V ROM reads, a δ add tree, one more ROM read.

    x: uint32/int32 (..., V) chromosome matrix; int32 fitness out."""
    mask = np.uint32((1 << t.c) - 1)
    idx = (x.astype(jnp.uint32) & mask).astype(jnp.int32)
    tabs = jnp.asarray(t.var_t)
    d = tabs[0][idx[..., 0]]
    for i in range(1, t.var_t.shape[0]):
        d = d + tabs[i][idx[..., i]]
    if t.gamma_t is None:
        return d
    addr = jnp.clip((d - jnp.int32(t.delta_min)) >> t.delta_shift, 0, (1 << t.g) - 1)
    return jnp.asarray(t.gamma_t)[addr]


# ---------------------------------------------------------------------------
# FitnessProgram — one problem compiled for every executor
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FitnessProgram:
    """A problem (or blackbox) lowered to the engine's evaluation modes.

    ``stage`` is THE arith lowering: a traceable bits -> fitness function
    shared verbatim by the XLA executors and the Pallas kernel's FFM stage,
    so reference × fused bit-identity holds by construction for every
    program.  ``lut_stage`` is the faithful ROM pipeline (separable
    problems only).  ``fitness(mode)`` dispatches for the executors.
    """

    name: str
    n_vars: int
    bits_per_var: int
    domains: Tuple[Tuple[float, float], ...]   # per-variable (lo, hi)
    minimize: bool
    fn: Callable[[jax.Array], jax.Array]
    supports_lut: bool
    tables: Optional[LutTables] = None   # synthesized only for mode='lut'

    @property
    def modes(self) -> Tuple[str, ...]:
        return ("lut", "arith") if self.supports_lut else ("arith",)

    def scale(self, mode: str) -> float:
        """Raw-fitness units per real unit (lut mode is fixed-point)."""
        if mode == "lut":
            return 2.0 ** self._tables().frac_bits
        return 1.0

    def _tables(self) -> LutTables:
        if self.tables is None:
            raise ValueError(
                f"program for {self.name!r} was not compiled with "
                "mode='lut'" if self.supports_lut else
                f"problem {self.name!r} has no LUT lowering (not "
                "separable); run mode='arith'")
        return self.tables

    # ---- lowerings ------------------------------------------------------

    def decode(self, x: jax.Array) -> jax.Array:
        """uint32 bits (..., V) -> f32 values (..., V), per-variable box.

        A box shared by every variable (every registered problem) decodes
        with scalar constants, so the traced stage captures no arrays; the
        int32 hop is exact (genes are ≤ 31 bits) and is the only
        uint32 -> f32 path Mosaic lowers."""
        c = self.bits_per_var
        lo = np.asarray([d[0] for d in self.domains], np.float32)
        span = np.asarray([(d[1] - d[0]) / ((1 << c) - 1)
                           for d in self.domains], np.float32)
        if len(set(self.domains)) == 1:
            lo, span = lo[0], span[0]
        u = (x & np.uint32((1 << c) - 1)).astype(jnp.int32)
        return lo + u.astype(jnp.float32) * span

    def stage(self, x: jax.Array) -> jax.Array:
        """The arith/in-kernel FFM stage: uint32 bits (..., V) -> f32 (...,).

        Traceable under XLA jit AND inside a Pallas kernel body — this exact
        function is what `kernels.ga_step` runs in place of the paper's
        hardwired two-variable polynomial pipeline."""
        return jnp.asarray(self.fn(self.decode(x)), jnp.float32)

    def lut_stage(self, x: jax.Array) -> jax.Array:
        """The faithful ROM pipeline: uint32 bits (..., V) -> int32 (...,)."""
        return lut_fitness(x, self._tables())

    def fitness(self, mode: str) -> Callable[[jax.Array], jax.Array]:
        """The executor-facing fitness function for one FFM mode."""
        if mode == "lut":
            self._tables()          # fail loudly before tracing
            return self.lut_stage
        if mode != "arith":
            raise ValueError(f"mode must be 'lut' or 'arith', got {mode!r}")
        return self.stage


def compile_program(problem: Optional[str] = None,
                    fitness: Optional[Callable] = None,
                    bounds=None, *,
                    n_vars: Optional[int] = None,
                    bits_per_var: int,
                    mode: str = "arith",
                    minimize: bool = True) -> FitnessProgram:
    """Lower a registered problem name (``"F3"``, ``"rastrigin:8"``) or a
    blackbox ``(N, V) -> (N,)`` + bounds into a :class:`FitnessProgram`.

    LUT ROMs are synthesized only when mode='lut' (they can be 2^c-entry
    tables); ``supports_lut`` still reports availability either way.
    """
    if (problem is None) == (fitness is None):
        raise ValueError("pass exactly one of problem= or fitness=")
    if mode not in ("lut", "arith"):
        raise ValueError(f"mode must be 'lut' or 'arith', got {mode!r}")

    if problem is not None:
        pdef, v_suffix = resolve_problem(problem)
        if v_suffix is not None and n_vars is not None and v_suffix != n_vars:
            raise ValueError(f"problem {problem!r} pins V={v_suffix} but "
                             f"n_vars={n_vars} was also given")
        v = resolve_vars(pdef, v_suffix if v_suffix is not None else n_vars)
        check_bits(pdef, bits_per_var)
        check_mode(pdef, mode)
        tables = (build_tables(pdef, bits_per_var, v)
                  if mode == "lut" else None)
        return FitnessProgram(name=pdef.name, n_vars=v,
                              bits_per_var=bits_per_var,
                              domains=(pdef.domain,) * v,
                              minimize=minimize, fn=pdef.fn,
                              supports_lut=pdef.separable, tables=tables)

    if bounds is None:
        raise ValueError("blackbox fitness requires bounds=")
    domains = tuple((float(lo), float(hi)) for lo, hi in bounds)
    if n_vars is not None and n_vars != len(domains):
        raise ValueError(f"n_vars={n_vars} does not match "
                         f"len(bounds)={len(domains)}")
    if mode == "lut":
        raise ValueError("blackbox fitness has no LUT lowering; "
                         "run mode='arith'")
    return FitnessProgram(name="blackbox", n_vars=len(domains),
                          bits_per_var=bits_per_var, domains=domains,
                          minimize=minimize, fn=fitness, supports_lut=False)
