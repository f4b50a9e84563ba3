"""Rotary position embeddings with linear position-interpolation scaling.

The reference precomputes complex ``freqs_cis`` and applies them by complex
multiplication (megatron/model/positional_embeddings.py:7-51); the scaling
factor divides positions (``t / scaling_factor``) for Code-Llama style long
context.  Here the same math is expressed in real arithmetic over interleaved
pairs — the layout matches the reference/Meta convention (pairs are adjacent
elements x[..., 0::2], x[..., 1::2]), which is also what the HF checkpoint
permutation in the weight converter assumes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def llama3_scaled_inv_freq(
    inv_freq: jax.Array,
    factor: float,
    low_freq_factor: float,
    high_freq_factor: float,
    original_max_positions: int,
) -> jax.Array:
    """Llama-3.1's piecewise frequency scaling (beyond the reference,
    which only has linear PI): frequencies whose wavelength exceeds the
    original context are slowed by ``factor``, high frequencies are kept,
    and the band between interpolates smoothly by how many times the
    wavelength fits in the original context."""
    import numpy as np

    wavelen = 2.0 * np.pi / inv_freq
    low_wavelen = original_max_positions / low_freq_factor
    high_wavelen = original_max_positions / high_freq_factor
    smooth = (original_max_positions / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    interp = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
    out = jnp.where(wavelen > low_wavelen, inv_freq / factor, interp)
    return jnp.where(wavelen < high_wavelen, inv_freq, out)


def yarn_scaled_inv_freq(
    inv_freq: jax.Array,
    factor: float,
    beta_fast: float,
    beta_slow: float,
    original_max_positions: int,
    head_dim: int,
    theta: float,
    attention_factor: float | None = None,
) -> tuple[jax.Array, float]:
    """YaRN (NTK-by-parts) frequency scaling → (inv_freq, cos/sin scale).

    Dimensions rotating faster than ``beta_fast`` turns over the original
    context keep their frequency (extrapolation); slower than
    ``beta_slow`` are divided by ``factor`` (interpolation); a linear
    ramp blends between.  The attention temperature ``0.1·ln(factor)+1``
    folds into the cos/sin tables, matching HF's attention_scaling.
    (arXiv 2309.00071; extension beyond the reference.)
    """
    import math

    dim = head_dim

    def correction_dim(n_rot):
        return (dim * math.log(original_max_positions
                               / (n_rot * 2 * math.pi))
                ) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
        0.0, 1.0)
    extrap_w = 1.0 - ramp
    scaled = inv_freq / factor * (1.0 - extrap_w) + inv_freq * extrap_w
    if attention_factor is None:
        attention_factor = (0.1 * math.log(factor) + 1.0
                            if factor > 1 else 1.0)
    return scaled, float(attention_factor)


def precompute_rope_freqs(
    head_dim: int,
    max_positions: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
    scaling_type: str = "linear",
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_positions: int | None = None,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    attention_factor: float | None = None,
    dtype=jnp.float32,
) -> tuple[jax.Array, jax.Array]:
    """Return (cos, sin), each [max_positions, head_dim//2].

    ``scaling_type='linear'``: position interpolation ``t / factor``
    (parity: megatron/model/positional_embeddings.py:7-13, the 16k/32k
    Code-Llama mode).  ``scaling_type='llama3'``: Llama-3.1's piecewise
    frequency transform.  ``scaling_type='yarn'``: NTK-by-parts with the
    attention temperature folded into the tables.  Both frequency-space
    modes leave positions unscaled.
    """
    inv_freq = rotary_inv_freq(head_dim, theta)
    table_scale = 1.0
    if scaling_type in ("llama3", "yarn") and scaling_factor != 1.0 \
            and not original_max_positions:
        # ValueError (not assert): must fail early and survive -O
        raise ValueError(
            f"{scaling_type} rope scaling needs original_max_positions "
            "(the pre-extension context length)")
    if scaling_type == "llama3":
        if scaling_factor != 1.0:
            inv_freq = llama3_scaled_inv_freq(
                inv_freq, scaling_factor, low_freq_factor,
                high_freq_factor, original_max_positions)
        t = jnp.arange(max_positions, dtype=jnp.float32)
    elif scaling_type == "yarn":
        if scaling_factor != 1.0:
            inv_freq, table_scale = yarn_scaled_inv_freq(
                inv_freq, scaling_factor, beta_fast, beta_slow,
                original_max_positions, head_dim, theta,
                attention_factor)
        t = jnp.arange(max_positions, dtype=jnp.float32)
    elif scaling_type == "linear":
        t = jnp.arange(max_positions, dtype=jnp.float32) / scaling_factor
    else:
        raise ValueError(f"unknown rope scaling_type {scaling_type!r} "
                         "(want 'linear' | 'llama3' | 'yarn')")
    freqs = jnp.outer(t, inv_freq)  # [pos, dim/2]
    return (table_scale * jnp.cos(freqs)).astype(dtype), \
        (table_scale * jnp.sin(freqs)).astype(dtype)


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    position_ids: jax.Array | None = None,
) -> jax.Array:
    """Rotate ``x`` [..., seq, heads, head_dim] by the precomputed tables.

    Interleaved-pair convention (x0,x1 adjacent), matching the complex-mult
    formulation of megatron/model/positional_embeddings.py:24-51.  Supports
    non-monotonic ``position_ids`` [batch, seq] for packed sequences /
    inference with KV caches (reference ``position_ids`` arg, :33-44).
    """
    seq_axis = x.ndim - 3
    if position_ids is None:
        seq = x.shape[seq_axis]
        cos_t = cos[:seq]
        sin_t = sin[:seq]
        # [seq, dim/2] -> broadcast to [..., seq, 1, dim/2]
        shape = [1] * x.ndim
        shape[seq_axis] = seq
        shape[-1] = cos.shape[-1]
        cos_t = cos_t.reshape(shape)
        sin_t = sin_t.reshape(shape)
    else:
        # position_ids: [batch, seq] → tables [batch, seq, 1, dim/2]
        cos_t = cos[position_ids][..., None, :]
        sin_t = sin[position_ids][..., None, :]

    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    cos_t = cos_t.astype(jnp.float32)
    sin_t = sin_t.astype(jnp.float32)
    x1f = x1.astype(jnp.float32)
    x2f = x2.astype(jnp.float32)
    r1 = x1f * cos_t - x2f * sin_t
    r2 = x2f * cos_t + x1f * sin_t
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_rope_flat(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    position_ids: jax.Array,
    head_dim: int,
) -> jax.Array:
    """``apply_rope`` on ``x`` [batch, seq, heads * head_dim], before it is
    cut into heads: the same rotation in the same float32 arithmetic, the
    pair's partner taken by a shift along the row and no strided slice.

    For the few rows of a decode step.  A projection whose output is
    reshaped to ``[.., heads, head_dim]`` at once is compiled by XLA:TPU as
    one dot with two output dimensions, which wants its weight with the
    contraction dimension minor and re-lays the whole weight in every call
    (PERF.md, PR 30); rotating the row as the matmul leaves it keeps the
    weight where it lies.  A pair never straddles two heads (``head_dim``
    is even), so the shift needs no head boundaries."""
    n = x.shape[-1] // head_dim
    cos_t = jnp.tile(jnp.repeat(cos[position_ids], 2, axis=-1), n)
    sin_t = jnp.tile(jnp.repeat(sin[position_ids], 2, axis=-1), n)
    xf = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                        jnp.roll(xf, 1, axis=-1))
    out = xf * cos_t.astype(jnp.float32) + partner * sin_t.astype(jnp.float32)
    return out.astype(x.dtype)


def rotary_inv_freq(rot_dim: int, theta: float):
    """The unscaled frequencies of ``rot_dim`` rotated dimensions."""
    return 1.0 / (theta ** (
        jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))


def rotation_of(cfg) -> tuple:
    """``(rot_dim, inv_freq, scale)`` of a rotation from the positions
    (``apply_rope_partial``) as ``cfg`` (a ``ModelConfig``, or a layer
    kind's view of one) describes it: the first ``rotary_percent`` of the
    head at ``rope_theta``; under ``rope_scaling_type`` "yarn" with a
    factor, YaRN's frequencies over the ROTATED dimensions and its
    attention factor, which multiplies cos and sin: the rotated part of a
    score carries its square, the rest of the head does not.  ``inv_freq``
    None: the bare frequencies, computed where they are used."""
    rot = int(cfg.head_dim * cfg.rotary_percent)
    if cfg.rope_scaling_type != "yarn" or cfg.rope_scaling_factor == 1.0:
        return rot, None, 1.0
    if not cfg.rope_original_max_positions:
        raise ValueError("yarn rope scaling needs "
                         "rope_original_max_positions")
    inv_freq, scale = yarn_scaled_inv_freq(
        rotary_inv_freq(rot, cfg.rope_theta), cfg.rope_scaling_factor,
        cfg.rope_beta_fast, cfg.rope_beta_slow,
        cfg.rope_original_max_positions, rot, cfg.rope_theta,
        cfg.rope_attention_factor)
    return rot, inv_freq, scale


def apply_rope_partial(x: jax.Array, position_ids: jax.Array, rot_dim: int,
                       theta: float, inv_freq=None,
                       scale: float = 1.0) -> jax.Array:
    """Rotate the first ``rot_dim`` of the last axis of ``x`` [batch, seq,
    heads, head_dim] and leave the rest: the rotate-half convention
    (dimension ``i`` pairs with ``i + rot_dim / 2``), angles computed from
    ``position_ids`` [batch, seq] in float32, no table (a partial rotation
    goes with contexts whose table would be hundreds of thousands of
    rows).  ``inv_freq`` [rot_dim / 2]: scaled frequencies in place of
    ``theta``'s own, and ``scale`` on cos and sin (``rotation_of``)."""
    half = rot_dim // 2
    if inv_freq is None:
        inv_freq = rotary_inv_freq(rot_dim, theta)
    ang = position_ids.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if scale != 1.0:
        cos, sin = scale * cos, scale * sin
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rot_dim]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., rot_dim:]],
        axis=-1).astype(x.dtype)


@partial(jax.jit, static_argnums=(3,))
def apply_rope_single(x, cos, sin, position: int):
    """Single-position variant for incremental decoding."""
    pos = jnp.full(x.shape[:1] + x.shape[1:2], position, dtype=jnp.int32)
    return apply_rope(x, cos, sin, position_ids=pos)
