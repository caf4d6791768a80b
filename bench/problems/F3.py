"""F3 of Torquato & Fernandes 2018 (Eq. 26): f(x, y) = sqrt(x^2 + y^2).

Two variables on [-128, 127]; the minimum is at the origin.  Evaluated in
the order written: x*x + y*y, clamped at 0, then the square root.
"""

import jax.numpy as jnp

DOMAIN = (-128.0, 127.0)
N_VARS = 2


def objective(v):
    return jnp.sqrt(jnp.maximum(v[..., 0] * v[..., 0] + v[..., 1] * v[..., 1],
                                0.0))
