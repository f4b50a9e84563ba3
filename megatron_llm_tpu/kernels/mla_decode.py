"""Pallas TPU kernel: a decode step's latent attention (MLA, absorbed
form) over the paged pool of latent rows, through the block tables.

One new position a slot.  The pool holds ONE row a position a layer,
``c | k_pe`` (``models/mla.py``), with no head axis, in two leaves: the
latent ``c`` (``[L, n_blocks, 1, block, rank]``) and the rotated key part
``k_pe`` all heads share (``[L, n_blocks, 1, block, rope]``).  Every head
of a slot attends the same rows, its query ``q_lat | q_pe`` as wide as
the row, and its value is the row's latent.  So this is multi-query
attention of all heads on one array, and a latent tile copied out of the
pool once serves both products: the scores take it (and the small
``k_pe`` tile beside it), the weighted sum takes it again.

One grid step a slot, as ``flash_decode._paged_walk_kernel`` walks K/V
blocks: the slot's live blocks are copied out of HBM by table entry,
``n`` an iteration and the next iteration's in flight, and attended as
one online-softmax term of ``n x block`` columns, softmax state in
float32.  The step's own row is not in the pool yet (the caller writes
every layer's rows once, after its layer loop): it is attended from
registers as one more term.

The ``k_pe`` leaf comes transposed, ``[.., rope, block]``: XLA:TPU keeps a
``[..., 128 n, 64]`` array with the 128-multiple as lanes, so the
transpose is a relabelling of the leaf as it lies in HBM (the row-major
block would cost a copy of the whole leaf a call: ``flash_decode``'s
``kt``), and its product with ``q_pe`` needs no transpose in the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

NEG_INF = -1e30

# what the walk holds at once (both halves of the row's double buffer)
# and the columns of a term: flash_decode's, measured there
_WALK_VMEM_BYTES = 2 * 2**20
_WALK_COLUMNS = 512


def _walk_blocks(block: int, width: int, itemsize: int, t: int) -> int:
    """Pool blocks an iteration."""
    half = _WALK_VMEM_BYTES // 2
    return max(1, min(half // (block * width * itemsize),
                      _WALK_COLUMNS // block, t))


def _kernel(scale: float, n: int, block: int,
            len_ref, tbl_ref, lyr_ref, ql_ref, qp_ref, c_ref, pe_ref,
            cn_ref, pn_ref, o_ref, c_buf, pe_buf, sem, m_scr, l_scr,
            acc_scr):
    """``ql_ref`` [1, heads, rank] and ``qp_ref`` [1, heads, rope] the
    slot's queries; ``c_ref`` [L, n_blocks, 1, block, rank] and ``pe_ref``
    [L, n_blocks, 1, rope, block] the whole pool in HBM; ``cn_ref`` [1, 1,
    rank] and ``pn_ref`` [1, 1, rope] the step's own row; ``o_ref`` [1,
    heads, rank]; ``c_buf`` [2, n, block, rank], ``pe_buf`` [2, n, rope,
    block]."""
    bi = pl.program_id(0)
    fill = len_ref[bi]
    live = pl.cdiv(fill, block)
    trips = pl.cdiv(live, n)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    q_lat, q_pe = ql_ref[0], qp_ref[0]

    def copies(c, half, go):
        def one(j, carry):
            blk = tbl_ref[bi, c * n + j]
            go(pltpu.make_async_copy(c_ref.at[lyr_ref[0], blk, 0],
                                     c_buf.at[half, j], sem.at[half, 0]))
            go(pltpu.make_async_copy(pe_ref.at[lyr_ref[0], blk, 0],
                                     pe_buf.at[half, j], sem.at[half, 1]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n, live - c * n), one, 0)

    @pl.when(trips > 0)
    def _first():
        copies(0, 0, lambda cp: cp.start())

    def step(c, carry):
        half = jax.lax.rem(c, 2)

        @pl.when(c + 1 < trips)
        def _next():
            copies(c + 1, 1 - half, lambda cp: cp.start())

        copies(c, half, lambda cp: cp.wait())

        # the last iteration attends its dead blocks' buffers too,
        # masked: a probability of exactly 0 times the latents they hold,
        # which must be finite (VMEM noise, an earlier slot's rows)
        def zero(j, carry):
            c_buf[half, j] = jnp.zeros(c_buf.shape[2:], c_buf.dtype)
            return carry

        jax.lax.fori_loop(live - c * n, n, zero, 0)
        tiles = [c_buf[half, j] for j in range(n)]     # [block, rank]
        s = jnp.concatenate([
            jax.lax.dot_general(q_lat, t, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(q_pe, pe_buf[half, j],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            for j, t in enumerate(tiles)], axis=1) * scale
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + c * (n * block)
        s = jnp.where(cols < fill, s, NEG_INF)         # [heads, n * block]
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        pv = None
        for j, t in enumerate(tiles):
            term = jax.lax.dot_general(
                p[:, j * block:(j + 1) * block].astype(t.dtype), t,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pv = term if pv is None else pv + term
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        return carry

    jax.lax.fori_loop(0, trips, step, 0)

    # the step's own row, from registers, in float32 on the VPU
    c_new = cn_ref[0].astype(jnp.float32)              # [1, rank]
    pe_new = pn_ref[0].astype(jnp.float32)             # [1, rope]
    s = (jnp.sum(q_lat.astype(jnp.float32) * c_new, axis=-1, keepdims=True)
         + jnp.sum(q_pe.astype(jnp.float32) * pe_new, axis=-1,
                   keepdims=True)) * scale             # [heads, 1]
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, s)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l_scr[:, :1] + p
    o_ref[0] = ((acc_scr[:] * alpha + p * c_new) / l).astype(o_ref.dtype)


def mla_decode(
    q_lat: jax.Array,      # [b, heads, rank]: q_nope through W_uk
    q_pe: jax.Array,       # [b, heads, rope]: the rotated query part
    c_pool: jax.Array,     # [L, n_blocks, 1, block, rank]: the latents
    pe_pool: jax.Array,    # [L, n_blocks, 1, block, rope]: the key parts
    tables: jax.Array,     # [b, T] int32 block tables (pad entries: trash)
    fills: jax.Array,      # [b] int32: rows each slot holds IN THE POOL
    c_new: jax.Array,      # [b, 1, rank]: the step's own row
    pe_new: jax.Array,     # [b, 1, rope]
    layer,                 # int32 scalar (traced in a layer scan)
    *,
    softmax_scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """→ ``o_lat`` [b, heads, rank] float32: each head's softmax over the
    slot's ``fills`` pooled rows and its own new row of ``(q_lat . c +
    q_pe . k_pe) x softmax_scale``, times the rows' latents."""
    b, heads, rank = q_lat.shape
    rope, block = q_pe.shape[-1], c_pool.shape[3]
    assert c_pool.shape[2:] == (1, block, rank), c_pool.shape
    assert pe_pool.shape[2:] == (1, block, rope), pe_pool.shape
    if interpret is None:
        interpret = kernels.default_interpret()
    if not interpret:
        assert block % 128 == 0 and rank % 128 == 0, (block, rank)
    n = _walk_blocks(block, rank + rope, c_pool.dtype.itemsize,
                     tables.shape[1])
    lens = jnp.asarray(fills, jnp.int32).reshape(b)
    tbl = jnp.asarray(tables, jnp.int32)
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    row = lambda rows, width: pl.BlockSpec(  # noqa: E731
        (1, rows, width), lambda bi, *s: (bi, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, float(softmax_scale), n, block),
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[row(heads, rank), row(heads, rope),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY),
                      row(1, rank), row(1, rope)],
            out_specs=row(heads, rank),
            scratch_shapes=[
                pltpu.VMEM((2, n, block, rank), c_pool.dtype),
                pltpu.VMEM((2, n, rope, block), pe_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((heads, 128), jnp.float32),
                pltpu.VMEM((heads, 128), jnp.float32),
                pltpu.VMEM((heads, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(lens, tbl, lyr, q_lat, q_pe, c_pool, jnp.swapaxes(pe_pool, -1, -2),
      c_new, pe_new)
