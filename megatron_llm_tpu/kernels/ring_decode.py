"""Pallas TPU decode attention over a slot's ring of the last ``W`` keys
and values: a "window" layer's decode step (models/diff_attention.py).

The rings of all the window layers lie stacked, ``ring_k`` and ``ring_v``
[L, slots, kv / r, W, r d]: a value head is shared by ``r`` consecutive
key heads and a row of the key ring holds those ``r`` heads' keys side by
side, position ``t`` of a slot at row ``t % W``.  ``r = 2`` is
differential attention's pair (a stack of runs); ``r = 1`` ordinary
grouped heads, a ring row one key head's (plain attention's "window"
kind: 8 KV heads of 128 with 8 query rows each at Laguna-XS.2's widths).
The kernel
takes the stacked arrays and the layer's index: a layer scan that slices
its layer out for a plain product copies that layer's key ring in every
step (0.67 GB a step at phi-4-mini-flash's size; PERF.md, PR 56).  One
grid step a slot: the slot's two blocks are the next grid step's while
this one is attended (the block pipeline), the ``r`` key heads of a value
head packed into one head ``r d`` wide (``flash_decode.pack_queries``), so
a value head is one score product, one softmax over the ``W`` columns and
the new position's own, and one product with the values.

The order of the rows does not matter, under a rotation as without one:
a softmax is a sum over the keys it counts, whatever order they lie in,
and where the model rotates, a key was rotated at its OWN position before
it went to the ring (by the prompt's install and by a step's write alike)
and the query comes rotated at its own, so a score already carries the
distance between the two and the kernel needs no positions.  Row ``c``
counts where it holds one of the ``W - 1`` positions before the new one,
``c < pos`` but for the row the new position will take; the new
position's own row is attended beside the ring and written after the
layer loop (models/transformer.py:ring_append_rows)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels
from .flash_decode import NEG_INF, pack_heads, pack_queries


def _ring_kernel(scale: float, pos_ref, lyr_ref, q_ref, k_ref, v_ref,
                 kn_ref, vn_ref, o_ref):
    heads, W = k_ref.shape[2], k_ref.shape[3]
    pos = pos_ref[pl.program_id(0)]
    for h in range(heads):
        q = q_ref[0, h]                                    # [rows, r d]
        s = jax.lax.dot_general(
            q, k_ref[0, 0, h], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [rows, W]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((cols < pos) & (cols != pos % W), s, NEG_INF)
        own = jnp.sum(q.astype(jnp.float32)
                      * kn_ref[0, h].astype(jnp.float32),
                      axis=-1, keepdims=True) * scale      # [rows, 1]
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), own)
        p, p_own = jnp.exp(s - m), jnp.exp(own - m)
        v = v_ref[0, 0, h]                                 # [W, r d]
        acc = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = acc + p_own * vn_ref[0, h].astype(jnp.float32)
        o_ref[0, h] = (acc / (jnp.sum(p, axis=-1, keepdims=True) + p_own)
                       ).astype(o_ref.dtype)


def ring_decode(
    q: jax.Array,        # [b, n_heads, d] — ONE new position's queries
    ring_k: jax.Array,   # [L, b, kv / r, W, r d]: r key heads a row
    ring_v: jax.Array,   # [L, b, kv / r, W, r d]
    k_new: jax.Array,    # [b, kv, 1, d]: the new position's own rows,
    v_new: jax.Array,    # [b, kv / r, 1, r d], not in the ring yet
    pos: jax.Array,      # [b] int32: the new position
    layer,               # int32 scalar (traced in a layer scan)
    *,
    softmax_scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """-> [b, n_heads, r d]: the new position of each slot on the ``W - 1``
    positions before it and itself."""
    b, n_heads, d = q.shape
    kv = k_new.shape[1]
    heads, W, dv = ring_v.shape[2:]
    assert ring_k.shape == ring_v.shape and dv == kv // heads * d, (
        ring_k.shape, ring_v.shape, k_new.shape)
    if interpret is None:
        interpret = kernels.default_interpret()
    if not interpret:
        assert W % 128 == 0 and dv % 128 == 0, (W, dv)
    rows = n_heads // heads
    g_pad = max(8, -(-rows // 8) * 8)
    qg = pack_queries(q, kv, heads).reshape(b, heads, rows, dv)
    if g_pad != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - rows), (0, 0)))
    row = lambda r_: pl.BlockSpec(  # noqa: E731
        (1, heads, r_, dv), lambda bi, *s: (bi, 0, 0, 0))
    ring = pl.BlockSpec((1, 1, heads, W, dv),
                        lambda bi, pos, lyr: (lyr[0], bi, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_ring_kernel, float(softmax_scale)),
        name="ring_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[row(g_pad), ring, ring, row(1), row(1)],
            out_specs=row(g_pad),
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, g_pad, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), qg,
      ring_k, ring_v,
      pack_heads(k_new[:, :, 0], heads)[:, :, None], v_new)
    return out[:, :, :rows].reshape(b, n_heads, dv)
