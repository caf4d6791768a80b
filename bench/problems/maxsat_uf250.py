"""MAX-3SAT at the shape of SATLIB's uf250-1065 set: the number of clauses
an assignment leaves unsatisfied, minimised.

Hoos & Stuetzle, "SATLIB: An Online Resource for Research on SAT", SAT
2000: uniform random 3-SAT with n = 250 variables and m = 1065 clauses
(m / n = 4.26, where the uniform model is hardest).  A clause is drawn by
the uniform model of Mitchell, Selman & Levesque (AAAI 1992): three
distinct variables chosen uniformly, each negated with probability 1/2.

Departures from SATLIB:

* The formula is not one of SATLIB's 100 files, which are not available
  here.  It is drawn from `numpy.random.default_rng(1)`: for each clause in
  turn `rng.choice(250, size=3, replace=False)`, then all negation bits at
  once, `rng.integers(0, 2, size=(1065, 3))` (1 marks a negated literal).
* It is not filtered to satisfiable formulas, as SATLIB's files were.

The genome is 25 words of 10 bits: variable j is bit j % 10, from the least
significant, of word j // 10.  The reference decodes each word with the
domain (0, 1023), whose step is exactly 1, so a word reaches `objective`
as its own value: exact in float32, and rounded above 256 in bfloat16.

Evaluated literal by literal, with no matrix product: each literal's truth
is its variable's bit XOR its negation bit, a clause is satisfied when any
of its three literals is true, and the unsatisfied clauses are counted.
The count is returned in the operand's precision.
"""

import numpy as np
import jax.numpy as jnp

DOMAIN = (0.0, 1023.0)
N_BOOL = 250
N_CLAUSES = 1065
WORD_BITS = 10

_INSTANCE = {}


def instance():
    """(var, neg): each clause's three variables and negation bits."""
    if not _INSTANCE:
        rng = np.random.default_rng(1)
        var = np.stack([rng.choice(N_BOOL, size=3, replace=False)
                        for _ in range(N_CLAUSES)])
        neg = rng.integers(0, 2, size=(N_CLAUSES, 3))
        _INSTANCE.update(var=var, neg=neg)
    return _INSTANCE["var"], _INSTANCE["neg"]


def objective(v):
    var, neg = instance()
    # variables lead, so each literal's bits are whole rows of the
    # population (the gathers below move rows, not single elements)
    words = jnp.moveaxis(v.astype(jnp.int32), -1, 0)        # (25, ...)
    col = (-1,) + (1,) * (words.ndim - 1)
    j = np.arange(N_BOOL)
    bits = (words[j // WORD_BITS] >> (j % WORD_BITS).reshape(col)) & 1
    satisfied = None                                        # (1065, ...)
    for lit in range(3):
        true = bits[var[:, lit]] ^ neg[:, lit].reshape(col)
        satisfied = true if satisfied is None else satisfied | true
    return jnp.sum(1 - satisfied, axis=0).astype(v.dtype)
