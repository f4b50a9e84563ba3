"""Pallas TPU decode-attention kernel: one new token against a KV cache.

The XLA lowering of the decode GEMV (`ops/attention.py:decode_attention`)
runs as a kLoop multiply-reduce fusion at a few percent of HBM bandwidth on
v5e (profiled ~0.44 ms/layer at max_len=1024 vs a ~0.04 ms read floor).
This kernel streams the head-major cache blocks through VMEM with the
online-softmax recurrence (same math as kernels/flash_attention.py, q-len =
the GQA group) and reads the dynamic fill level from SMEM, so work beyond
``cache_len`` is masked, not branched.

Layout contract (models/model.py:init_kv_cache): cache [b, kv, max_len, d],
q [b, kv·group, d] for a single new token.

Paged mode (``flash_decode_paged*``): the cache operands are one layer's
view of the serving block pool — ``[n_blocks, kv, block, d]`` — plus a
per-row int32 block table ``[b, T]`` mapping each row's logical block j
to a physical pool block.  The kernel bodies are IDENTICAL (the mask is
over logical columns ``j*block + lane`` exactly as in the dense walk);
only the BlockSpec index maps change: the cache block for grid tick
``ki`` is ``table[bi, min(ki, last_bi)]``, where ``last_bi`` clamps at
row bi's own fill — so HBM traffic is the sum of per-row fills, not
``b * max_len``.  Entries past a row's fill point at the pool's trash
block; their scores are replaced with NEG_INF before the softmax, so
trash contents can never reach the output (exp underflows to exactly
0.0 and 0.0 x finite = 0.0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

NEG_INF = -1e30


def _decode_kernel(scale: float, nk: int, block_k: int,
                   len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                   # [g_pad, d]
    k = k_ref[0, 0]                                   # [block_k, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                          # [g_pad, block_k]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
    # lens is per-sample ([b]); program axis 0 is the batch
    s = jnp.where(cols < len_ref[pl.program_id(0)], s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:] = jnp.broadcast_to(
        alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
        l_scr.shape)
    v = v_ref[0, 0]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[:] = acc_scr[:] * alpha + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)



def _decode_kernel_int8(scale: float, nk: int, block_k: int,
                        len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                        o_ref, m_scr, l_scr, acc_scr):
    """int8-cache variant: K/V blocks arrive as int8 with per-row fp32
    scales; the scales fold into the score columns (K) and the probability
    rows (V) — algebraically exact dequantization, int8 HBM traffic."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                   # [g_pad, d]
    k = k_ref[0, 0].astype(jnp.float32)               # [block_k, d] int8→f32
    ks = ks_ref[0, 0][:, 0]                           # [block_k, 1] → [block_k]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * ks[None, :] * scale                            # [g_pad, block_k]
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
    s = jnp.where(cols < len_ref[pl.program_id(0)], s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[:] = jnp.broadcast_to(
        alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
        l_scr.shape)
    v = v_ref[0, 0].astype(jnp.float32)               # [block_k, d]
    vs = vs_ref[0, 0][:, 0]                           # [block_k]
    pv = jax.lax.dot_general(
        p * vs[None, :], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[:] = acc_scr[:] * alpha + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def _decode_call(kernel_fn, q, caches, cache_len, softmax_scale,
                 block_k, interpret, extra_in_specs):
    """Shared host-side harness for the decode kernels: block sizing,
    GQA-group padding, scalar-prefetch plumbing, grid/specs.  ``caches``
    is the ordered operand list after q; ``extra_in_specs`` its BlockSpecs
    (cache blocks and, for the int8 variant, their per-row scales)."""
    b, n_heads, d = q.shape
    max_len = caches[0].shape[2]
    kv_heads = caches[0].shape[1]
    group = n_heads // kv_heads
    if softmax_scale is None:
        softmax_scale = 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = kernels.default_interpret()
    block_k = min(block_k, max_len)
    while max_len % block_k:
        block_k //= 2
    assert block_k >= 128, (max_len, block_k)
    nk = max_len // block_k

    # [b, kv, g, d] rows, padded up to a multiple of the 8-sublane tile
    g_pad = max(8, -(-group // 8) * 8)
    qg = q.reshape(b, kv_heads, group, d)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))

    # scalar fill → broadcast; [b] per-sample fills pass through (ragged
    # speculative decoding) — the kernel indexes lens by the batch program
    lens = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1,)), (b,))

    grid = (b, kv_heads, nk)
    out = pl.pallas_call(
        functools.partial(kernel_fn, float(softmax_scale), nk, block_k),
        name="flash_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g_pad, d),
                             lambda bi, hi, ki, lens: (bi, hi, 0, 0)),
            ] + extra_in_specs(block_k, d),
            out_specs=pl.BlockSpec((1, 1, g_pad, d),
                                   lambda bi, hi, ki, lens: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g_pad, 128), jnp.float32),
                pltpu.VMEM((g_pad, 128), jnp.float32),
                pltpu.VMEM((g_pad, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, g_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, qg, *caches)
    return out[:, :, :group].reshape(b, n_heads, d)


def _cache_block_spec(block_k, d):
    return pl.BlockSpec((1, 1, block_k, d),
                        lambda bi, hi, ki, lens: (bi, hi, ki, 0))


def _scale_block_spec(block_k):
    # Scales ride as [b, kv, max_len, 1]: a trailing unit dim keeps the
    # block's last two dims (block_k, 1) legal under the TPU (8, 128)
    # tiling rule (last dim equals the array dim; a 3-D [.., block_k]
    # block with a size-1 sublane dim is rejected by the Mosaic lowering).
    return pl.BlockSpec((1, 1, block_k, 1),
                        lambda bi, hi, ki, lens: (bi, hi, ki, 0))


def _paged_body(kernel_fn):
    """Adapter for the paged harness: the block-table scalar operand is
    consumed only by the BlockSpec index maps, so it is dropped before
    the refs reach the shared kernel body."""
    def body(scale, nk, block_k, len_ref, tbl_ref, *refs):
        return kernel_fn(scale, nk, block_k, len_ref, *refs)
    return body


def _paged_cache_spec(block_k, d):
    # tick ki fetches row bi's logical block ki via its table, clamped at
    # the row's own last live block — blocks past the fill (and the whole
    # walk of an empty row, which lands on the trash block) cost no extra
    # bytes beyond one block and are fully masked in the kernel
    def idx(bi, hi, ki, lens, tbl):
        last = jnp.maximum(lens[bi] - 1, 0) // block_k
        return (tbl[bi, jnp.minimum(ki, last)], hi, 0, 0)
    return pl.BlockSpec((1, 1, block_k, d), idx)


def _paged_scale_spec(block_k):
    # same walk as _paged_cache_spec; trailing unit dim as _scale_block_spec
    def idx(bi, hi, ki, lens, tbl):
        last = jnp.maximum(lens[bi] - 1, 0) // block_k
        return (tbl[bi, jnp.minimum(ki, last)], hi, 0, 0)
    return pl.BlockSpec((1, 1, block_k, 1), idx)


def _paged_decode_call(kernel_fn, q, caches, tables, cache_len,
                       softmax_scale, interpret, extra_in_specs):
    """Paged twin of _decode_call: cache operands are pool-layer views
    ``[n_blocks, kv, block_k, d]``, the grid's k axis walks the ``T``
    block-table columns, and both scalars (per-row fills AND the block
    tables) prefetch so the index maps can resolve physical blocks."""
    b, n_heads, d = q.shape
    kv_heads = caches[0].shape[1]
    block_k = caches[0].shape[2]
    group = n_heads // kv_heads
    if softmax_scale is None:
        softmax_scale = 1.0 / float(np.sqrt(d))
    if interpret is None:
        interpret = kernels.default_interpret()
    if not interpret:
        assert block_k % 128 == 0, block_k
    nk = tables.shape[1]

    g_pad = max(8, -(-group // 8) * 8)
    qg = q.reshape(b, kv_heads, group, d)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))

    lens = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1,)), (b,))
    tbl = jnp.asarray(tables, jnp.int32)

    grid = (b, kv_heads, nk)
    out = pl.pallas_call(
        functools.partial(_paged_body(kernel_fn), float(softmax_scale),
                          nk, block_k),
        name="flash_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g_pad, d),
                             lambda bi, hi, ki, *s: (bi, hi, 0, 0)),
            ] + extra_in_specs(block_k, d),
            out_specs=pl.BlockSpec((1, 1, g_pad, d),
                                   lambda bi, hi, ki, *s: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g_pad, 128), jnp.float32),
                pltpu.VMEM((g_pad, 128), jnp.float32),
                pltpu.VMEM((g_pad, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, g_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, tbl, qg, *caches)
    return out[:, :, :group].reshape(b, n_heads, d)


def flash_decode_paged(
    q: jax.Array,        # [b, n_heads, d] — ONE new token's queries
    k_pool: jax.Array,   # [n_blocks, kv_heads, block, d] — one layer's pool
    v_pool: jax.Array,
    tables: jax.Array,   # [b, T] int32 block tables (pad entries = trash)
    cache_len: jax.Array,  # [b] (or scalar) valid rows incl. the new token
    *,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """→ [b, n_heads, d]: decode attention gathered straight from the
    paged block pool — no dense [b, max_len] cache is ever materialized."""
    return _paged_decode_call(
        _decode_kernel, q, [k_pool, v_pool], tables, cache_len,
        softmax_scale, interpret,
        lambda bk, d: [_paged_cache_spec(bk, d), _paged_cache_spec(bk, d)])


def flash_decode_paged_int8(
    q: jax.Array,          # [b, n_heads, d]
    k_q: jax.Array,        # [n_blocks, kv_heads, block, d] int8 pool leaf
    k_scale: jax.Array,    # [n_blocks, kv_heads, block] fp32 row scales
    v_q: jax.Array,
    v_scale: jax.Array,
    tables: jax.Array,     # [b, T] int32
    cache_len: jax.Array,
    *,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode attention over the int8 ``{q, scale}`` pool form."""
    return _paged_decode_call(
        _decode_kernel_int8, q,
        [k_q, k_scale[..., None], v_q, v_scale[..., None]], tables,
        cache_len, softmax_scale, interpret,
        lambda bk, d: [_paged_cache_spec(bk, d), _paged_scale_spec(bk),
                       _paged_cache_spec(bk, d), _paged_scale_spec(bk)])


def flash_decode(
    q: jax.Array,        # [b, n_heads, d] — ONE new token's queries
    k_cache: jax.Array,  # [b, kv_heads, max_len, d]
    v_cache: jax.Array,
    cache_len: jax.Array,  # scalar int32: valid slots = cache_len (incl. new)
    *,
    softmax_scale: float | None = None,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """→ [b, n_heads, d] attention output for the single new token."""
    return _decode_call(
        _decode_kernel, q, [k_cache, v_cache], cache_len, softmax_scale,
        block_k, interpret,
        lambda bk, d: [_cache_block_spec(bk, d), _cache_block_spec(bk, d)])


def flash_decode_int8(
    q: jax.Array,          # [b, n_heads, d] — ONE new token's queries
    k_q: jax.Array,        # [b, kv_heads, max_len, d] int8
    k_scale: jax.Array,    # [b, kv_heads, max_len] fp32
    v_q: jax.Array,
    v_scale: jax.Array,
    cache_len: jax.Array,
    *,
    softmax_scale: float | None = None,
    # 1024 (vs the bf16 kernel's 512): int8 blocks are half the bytes, and
    # the larger tile measured ~7% faster at max_len=1024 on v5e; the
    # harness divides down for shorter caches.
    block_k: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """→ [b, n_heads, d] decode attention over an int8 KV cache
    (ops/kv_quant.py form: per-row fp32 scales folded into the scores /
    probabilities inside the kernel)."""
    return _decode_call(
        _decode_kernel_int8, q,
        [k_q, k_scale[..., None], v_q, v_scale[..., None]], cache_len,
        softmax_scale, block_k, interpret,
        lambda bk, d: [_cache_block_spec(bk, d), _scale_block_spec(bk),
                       _cache_block_spec(bk, d), _scale_block_spec(bk)])
