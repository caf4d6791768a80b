"""f24 of the COCO/BBOB noiseless testbed, the Lunacek bi-Rastrigin function.

Hansen, Finck, Ros & Auger 2009, "Real-Parameter Black-Box Optimization
Benchmarking 2009: Noiseless Functions Definitions", INRIA RR-6829, f24
(first published by Lunacek, Whitley & Sutton, PPSN 2008):

    x^ = 2 sign(x_opt) x,   x_opt = (mu0 / 2) * (random signs)
    z  = Q Lambda^100 R (x^ - mu0),   Lambda^100_ii = 100^(1/2 (i-1)/(D-1))
    f  = min(sum (x^_i - mu0)^2, d D + s sum (x^_i - mu1)^2)
         + 10 (D - sum cos(2 pi z_i)) + 1e4 sum max(0, |x_i| - 5)^2 + f_opt
    mu0 = 2.5, d = 1, s = 1 - 1 / (2 sqrt(D + 20) - 8.2),
    mu1 = -sqrt((mu0^2 - d) / s)

on [-5, 5]^D; R and Q are random orthogonal matrices.

Departures from RR-6829:

* The instance is not COCO's: COCO's own generator is not available here.
  R is the orthogonal factor of the QR decomposition of a D x D
  standard-normal draw of `numpy.random.default_rng(1)`, its columns'
  signs set so that the triangular factor's diagonal is positive; Q is the
  same from the next draw; the signs of x_opt are those of the next D
  normals.  M = Q Lambda R, s, mu1 and d D are computed in float64 and
  rounded once to float32.
* f_opt = 0.

Evaluated in the operand's precision in the order written: z[..., i] is a
left fold over j of M[i, j] * u[..., j] with u = x^ - mu0, every sum is a
left fold in variable order, and the final sum runs left to right.  Every
constant is cast to the operand's precision first, so a bfloat16 operand
is evaluated in bfloat16 throughout.
"""

import numpy as np
import jax.numpy as jnp

DOMAIN = (-5.0, 5.0)
MU0 = 2.5
F_OPT = 0.0

_INSTANCES = {}


def _haar(rng, d):
    q, r = np.linalg.qr(rng.standard_normal((d, d)))
    return q * np.where(np.diag(r) < 0.0, -1.0, 1.0)[None, :]


def instance(d):
    """Instance arrays at D=d: mt = M^T (D, D) and a = 2 sign(x_opt) (D,)
    in float32, and the scalars s, mu1 and d D, each float32."""
    if d not in _INSTANCES:
        rng = np.random.default_rng(1)
        r = _haar(rng, d)
        q = _haar(rng, d)
        sign = np.sign(rng.standard_normal(d))
        lam = 100.0 ** (0.5 * np.arange(d) / (d - 1))
        m = (q * lam[None, :]) @ r
        s = 1.0 - 1.0 / (2.0 * np.sqrt(d + 20.0) - 8.2)
        _INSTANCES[d] = {
            "mt": m.T.astype(np.float32),
            "a": (2.0 * sign).astype(np.float32),
            "s": np.float32(s),
            "mu1": np.float32(-np.sqrt((MU0 * MU0 - 1.0) / s)),
            "dd": np.float32(d),
        }
    return _INSTANCES[d]


def _fold(t):
    acc = t[..., 0]
    for i in range(1, t.shape[-1]):
        acc = acc + t[..., i]
    return acc


def objective(v):
    d = v.shape[-1]
    inst = instance(d)
    c = lambda a: jnp.asarray(a, v.dtype)
    mt = c(inst["mt"])
    xh = c(inst["a"]) * v
    u = xh - c(MU0)
    z = u[..., 0:1] * mt[0:1]
    for j in range(1, d):
        z = z + u[..., j:j + 1] * mt[j:j + 1]
    w = xh - c(inst["mu1"])
    near = _fold(u * u)
    far = c(inst["dd"]) + c(inst["s"]) * _fold(w * w)
    ras = c(10.0) * (c(d) - _fold(jnp.cos(c(2.0 * np.pi) * z)))
    out = jnp.maximum(jnp.abs(v) - c(5.0), c(0.0))
    return (jnp.minimum(near, far) + ras + c(1e4) * _fold(out * out)
            + c(F_OPT))
