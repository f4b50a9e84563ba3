"""int8 KV-cache quantization for serving (opt-in, composes with the
weight-only int8 of ops/quant.py for a fully int8-resident decode).

Decode streams the whole cache every step, so at long contexts the cache
— not the weights — dominates HBM traffic; int8 rows halve it.  Scheme:
symmetric per-row scales, one fp32
scale per (batch, kv_head, position) row of [head_dim] values — K and V
rows are written once at their position and never rewritten, so the scale
granularity matches the write granularity exactly and requantization
never occurs.

A quantized cache is ``{"q": int8 [..., max_len, d],
"scale": fp32 [..., max_len]}`` — a plain dict subtree, so the scan-xs /
dynamic-update-slice / while-loop-carry plumbing of the decode path works
unchanged on it (pytrees all the way down).  The paged attention
kernel streams the int8 payload directly (dequant fused at the tile
load, kernels/flash_decode.py) and attends the step's new rows as the
pool will hold them (``dequantize_cache`` of ``quantize_rows``).

The reference has no quantized inference cache; its InferenceParams holds
compute-dtype tensors (megatron/model/transformer.py:423-496).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def is_quantized_cache(cache) -> bool:
    return isinstance(cache, dict) and set(cache) == {"q", "scale"}


def init_quantized_cache(shape: tuple) -> dict:
    """Empty cache for ``shape`` = [..., max_len, head_dim]."""
    return {"q": jnp.zeros(shape, jnp.int8),
            "scale": jnp.zeros(shape[:-1], jnp.float32)}


# Scales are amax·(1/127), not amax/127: a verify step's rows must land
# on the very same fp32 scale a sequential step's did — a constant
# multiply is one exactly-rounded op everywhere, while XLA lowers a
# constant *divide* differently across fusion contexts
# (reciprocal-multiply rewrite), which showed up as a 1-ulp scale split
# between two programs.
# numpy, not jnp: this module is lazily imported from inside a jit trace
# (ops/attention.py), where a module-level jnp op would be staged as a
# tracer; IEEE fp32 division is exactly rounded, so the bits match the
# device computation either way.
_RCP127 = float(np.float32(1.0) / np.float32(127.0))


def quantize_rows(rows: jax.Array) -> dict:
    """[..., s, d] new rows → {"q": int8, "scale": fp32 [..., s]}."""
    r32 = rows.astype(jnp.float32)
    scale = jnp.max(jnp.abs(r32), axis=-1) * _RCP127
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(r32 / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def dequantize_cache(cache: dict, dtype=jnp.float32) -> jax.Array:
    return (cache["q"].astype(jnp.float32)
            * cache["scale"][..., None]).astype(dtype)


def rows_as_stored(cache, rows):
    """New-token ``rows`` in the form ``cache`` stores them: the int8
    ``{"q", "scale"}`` dict for a quantized cache, else its dtype."""
    if is_quantized_cache(cache):
        return quantize_rows(rows)
    return rows.astype(cache.dtype)


def cache_update(cache, rows, pos):
    """Write new-token ``rows`` [..., s, d] into ``cache`` at position
    ``pos`` along the -2 (sequence) axis.  Handles both plain arrays and
    quantized dicts — the single write point of the decode path
    (models/transformer.py), so the representations can't drift.

    ``pos`` may be a [batch] vector of per-sample fill levels (ragged
    speculative decoding, generation/speculative.py): the write then
    lands at each sample's own position via a vmap over the batch axis
    (dims are [..., batch, kv, max_len, d], so batch = ndim-4)."""
    if jnp.ndim(pos) == 1:
        b_axis = rows.ndim - 4
        return jax.vmap(cache_update, in_axes=(b_axis, b_axis, 0),
                        out_axes=b_axis)(cache, rows, pos)
    nd = rows.ndim
    start = (0,) * (nd - 2) + (pos, 0)
    if is_quantized_cache(cache):
        qr = quantize_rows(rows)
        return {
            "q": jax.lax.dynamic_update_slice(cache["q"], qr["q"], start),
            "scale": jax.lax.dynamic_update_slice(
                cache["scale"], qr["scale"], start[:-1]),
        }
    return jax.lax.dynamic_update_slice(
        cache, rows.astype(cache.dtype), start)
