"""Products of a float32 activation with a weight kept in a lower
precision, where one rounding of the activation is too much."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dot_rounded(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` → float32 in one pass: ``x`` rounded to the weight's
    precision, float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def dot_f32(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` → float32 for a float32 ``x`` and a weight in a lower
    precision, in two passes of the weight's precision: ``x`` rounded to
    it, and what that rounding lost (the product is then off by ~2^-17
    of itself and not ~2^-9, at twice the multiply-adds).  An ``x``
    already in ``w``'s precision takes the one pass.

    The rounding is ``lax.reduce_precision`` and not a cast there and
    back: XLA:TPU keeps the excess precision of such a pair inside a
    fusion, which leaves nothing for the second pass (PERF.md, PR 35)."""
    if x.dtype == w.dtype:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    kind = jnp.finfo(w.dtype)
    hi = jax.lax.reduce_precision(x, kind.nexp, kind.nmant)
    return (jnp.dot(hi.astype(w.dtype), w,
                    preferred_element_type=jnp.float32)
            + jnp.dot((x - hi).astype(w.dtype), w,
                      preferred_element_type=jnp.float32))
