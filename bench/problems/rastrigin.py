"""Rastrigin's function, f(x) = sum_i (x_i^2 - 10 cos(2 pi x_i) + 10).

As in the COCO/BBOB testbed (Hansen et al. 2009, f15 without its shift,
rotation and oscillation transforms) on [-5.12, 5.12]^D; the minimum 0 is
at the origin.  Each term is evaluated as x*x - 10*cos(2*pi*x) + 10 in the
operand's precision, and the terms are summed as a left fold in variable
order, which fixes the rounding of the sum.
"""

import numpy as np
import jax.numpy as jnp

DOMAIN = (-5.12, 5.12)


def objective(v):
    t = v * v - 10.0 * jnp.cos(2.0 * np.pi * v) + 10.0
    acc = t[..., 0]
    for i in range(1, t.shape[-1]):
        acc = acc + t[..., i]
    return acc
