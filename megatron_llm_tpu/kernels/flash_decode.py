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
view of the serving block pool — ``[n_blocks, kv, block, d]``, or the
whole ``[L, ...]`` pool with a layer index — plus a per-row int32 block
table ``[b, T]`` mapping each row's logical block j to a physical pool
block.  The pool stays in HBM and the kernel walks a row's live blocks
itself (``_paged_walk_kernel``): one grid step a slot, and in it a loop
whose trip count comes from the row's fill, each iteration copying the
next few pool blocks — every KV head of a block in one copy, as they lie
side by side in the pool — into a double-buffered VMEM scratch while
the blocks before them are attended as one online-softmax term
(``_attend_blocks``: the mask is over logical columns).  So HBM traffic
and the walk's length are the sum of per-row fills, not ``b * max_len``:
a block past the fill is never copied, an empty row copies nothing.
The copies are one stream over the whole call (PR 57): the grid runs in
order, and a grid step's last iteration starts the first blocks of the
next grid step that has a live row, so that step finds them in flight
and no slot's first copy is waited out bare (``walk_counts`` says how
many steps of a call that holds for).
How many blocks an iteration takes follows from the shapes
(``_walk_shape``).  Entries past a row's fill point at the pool's trash
block and are never read; the rest of a partly filled block (and of a
partly live iteration) has its scores replaced with NEG_INF before the
softmax, so what lies there can never reach the output (exp underflows
to exactly 0.0 and 0.0 x finite = 0.0).

Measured alone on a v5e (PR 39; PERF.md §6): 44 slots of 4-16k rows at
16 heads over 2 KV heads of width 256, table width 130 — 1.34 ms a call,
85 % of the rows' HBM time (the grid this replaced, one tick a table
column and KV head, 4.35 ms); 16 slots of 50-700 rows at Falcon's 71
heads over one KV head of width 64, table width 16 — 28 us (67).

Who calls what: ``ops/attention.py:decode_attention`` the dense kernels
(head width 128·n, ``generation/`` and the engine's gather route);
``ops/attention.py:paged_decode_attention`` the paged ones with
``new_rows`` — the serving engine's composed decode step on a TPU
(``models/model.py:forward_cached_paged``), head width 64·n, where the
new token's row is folded in as one more softmax term because the pool
is written once, after the layer loop.
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


def _attend_blocks(scale, col0, n_valid, q, k, v, ks, vs,
                   m_scr, l_scr, acc_scr, rows, *, kt: bool, vt=None):
    """One online-softmax term over consecutive cache blocks: ``k``/``v``
    are lists of [block_k, d] rows, together the logical columns
    ``col0..`` (``kt``: [d, block_k], the block as the pool holds it at
    head width 64); columns at or past ``n_valid`` are masked by score
    replacement.  ``ks``/``vs`` are the int8 form's per-row fp32 scales,
    a [1, block_k] a block (``None`` for a float cache): they fold into
    the score columns (K) and the probability rows (V) — algebraically
    exact dequantization, int8 HBM traffic.  The softmax state is rows
    ``rows`` of the three scratches (one query group's, of the several a
    grid step holds).  ``vt``: the same for the value blocks where they
    are of another width than the key's (None: as ``kt``)."""
    k_dims = (((1,), (0 if kt else 1,)), ((), ()))
    v_dims = (((1,), (1 if (kt if vt is None else vt) else 0,)), ((), ()))
    if ks is None:
        s = [jax.lax.dot_general(
            q, kj, k_dims, preferred_element_type=jnp.float32,
        ) * scale for kj in k]                         # [g_pad, block_k]
    else:
        s = [jax.lax.dot_general(
            q.astype(jnp.float32), kj.astype(jnp.float32), k_dims,
            preferred_element_type=jnp.float32,
        ) * ksj * scale for kj, ksj in zip(k, ks)]
    block_k = s[0].shape[1]
    s = jnp.concatenate(s, axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + col0
    s = jnp.where(cols < n_valid, s, NEG_INF)

    m_prev = m_scr[rows, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[rows, :] = jnp.broadcast_to(
        alpha * l_scr[rows, :1] + jnp.sum(p, axis=-1, keepdims=True),
        (len(q), l_scr.shape[1]))
    pv = None
    for j, vj in enumerate(v):
        pj = p[:, j * block_k:(j + 1) * block_k]
        if vs is None:
            term = jax.lax.dot_general(
                pj.astype(vj.dtype), vj, v_dims,
                preferred_element_type=jnp.float32,
            )
        else:
            term = jax.lax.dot_general(
                pj * vs[j], vj.astype(jnp.float32), v_dims,
                preferred_element_type=jnp.float32,
            )
        pv = term if pv is None else pv + term
    acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv
    m_scr[rows, :] = jnp.broadcast_to(m_new, (len(q), m_scr.shape[1]))


def _attend_new_row(scale, q, k_row, v_row, m_scr, l_scr, acc_scr, rows):
    """The new token's own K/V row ([1, d] each) as one more
    online-softmax term, in float32 on the VPU: the row is not in the
    pool yet when its layer's attention runs (the paged route writes all
    layers' rows once, after the layer loop)."""
    s = jnp.sum(q.astype(jnp.float32) * k_row.astype(jnp.float32),
                axis=-1, keepdims=True) * scale        # [g_pad, 1]
    m_prev = m_scr[rows, :1]
    m_new = jnp.maximum(m_prev, s)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[rows, :] = jnp.broadcast_to(alpha * l_scr[rows, :1] + p,
                                      (len(q), l_scr.shape[1]))
    acc_scr[rows, :] = acc_scr[rows, :] * alpha + p * v_row.astype(jnp.float32)
    m_scr[rows, :] = jnp.broadcast_to(m_new, (len(q), m_scr.shape[1]))


# What the paged walk may hold and attend at once.  VMEM: the K and the V
# copies, both halves of their double buffer (an int8 pool's scales ride
# beside them, 1/16 of the bytes).  Columns: one online-softmax term; its
# float32 score tile [g_pad, columns] should stay in the vector registers
# (36 of 64 at Falcon's 72 query rows).  Measured on a v5e (PR 39), a call
# at the two geometries of the module docstring: 2.73 ms / 40 us at 128
# columns a term, 1.73 / 32 at 256, 1.34 / 28 at 512, 1.34 ms at 1024, 30
# us at 2048; the same blocks attended one after another, a term each,
# 2.6-2.8 ms / 41-43 us at every count (the chain of mask, maxima, exp and
# scratch rewrites a term is latency: what PR 25 met as "several blocks a
# tick bought nothing").  The VMEM was 2 MiB until PR 57: no plain walk of
# a cell is bound by it (the columns bind first: Falcon (1, 4), 8 KV heads
# of 64 (8, 4), 2 of 128 or of 256 (2, 4), the same at 2.5 MiB), and the
# packed walk's ten heads of 128 at two blocks an iteration need 2.5 (what
# that buys: ``_walk_shape``).
_WALK_VMEM_BYTES = 5 * 2**19
_WALK_COLUMNS = 512


def _walk_shape(kv_heads, block_k, d, itemsize, t, packed: bool = False):
    """(KV heads a copy, pool blocks an iteration) of the paged walk, from
    what the trace sees: a pool block's bytes against the VMEM the walk
    may hold, its rows against the columns of a term, the table's width.
    ``packed`` (the key heads that share a value head packed into one,
    ``_pack_shared``): half the columns a term, and as many heads a copy
    as the same VMEM holds.

    Measured on a v5e with the copies one stream over the call (PR 57;
    the kernel's own time in a profile, parent -> change at equal shape),
    (heads, blocks) a call.  Packed, 64 slots of 1-6 k rows at 40 heads
    on 20 key heads of 64 and 10 value heads of 128: (5, 2) 1.843 ->
    1.732 ms, (5, 4) 1.986 -> 1.774, **(10, 2) 1.633 -> 1.536** (90 % of
    the rows' HBM time), (10, 4) 1.827 -> 1.619, (10, 1) 1.909 -> 1.845,
    (5, 3) 2.232 -> 2.053, (5, 1) 2.548 -> 2.476, (2, 2) 2.961 -> 2.804:
    a pair is one chain of mask, maxima and exp, so short terms keep the
    copies ahead, and every head in one copy halves the grid steps, each
    of which still costs 0.6-0.75 us that no copy covers (the same rows
    in half and a quarter as many slots: 1.517 and 1.505 ms; the parent
    paid 1.5-2.3 us a grid step).  (PR 56: a walk a key
    head, unpacked, 2.33 ms at its best.)  Plain, as the rule gives them:
    64 slots of 64-3000 rows at 32 heads on 8 KV heads of 64, (8, 4)
    0.691 -> 0.602 ms ((8, 2) 0.625 -> 0.572, (4, 4) 0.759 -> 0.678, and
    (8, 8), twice the columns and past the VMEM, 0.538 -> 0.396: PERF.md
    section 7 q); 16 slots of 50-700 rows at Falcon's 71 heads on one of
    64, (1, 4) 26.9 -> 21.9 us ((1, 2) 31.3 -> 26.8, (1, 8) 26.1 -> 20.9);
    44 slots of 4-16 k rows at 16 heads on 2 KV heads of 256, (2, 4) 1.311
    -> 1.257 ms ((2, 2) 1.692 -> 1.654, (2, 8) 1.316 -> 1.241)."""
    half = _WALK_VMEM_BYTES // 4
    head_bytes = block_k * d * itemsize
    if packed:
        n = max(1, min(_WALK_COLUMNS // 2 // block_k, t))
        fits = [g for g in range(1, kv_heads + 1)
                if kv_heads % g == 0 and g * head_bytes * n <= half]
        return (max(fits) if fits else 1), n
    kvg = max(g for g in range(1, kv_heads + 1)
              if kv_heads % g == 0 and (g == 1 or g * head_bytes <= half))
    n = min(half // (kvg * head_bytes), _WALK_COLUMNS // block_k, t)
    return kvg, max(1, n)


def pool_walk(k, v, t):
    """``(KV heads, heads a copy, blocks an iteration)`` of the walk over
    a pool whose key leaf is ``k`` ``[.., kv, block, d]`` and value leaf
    ``v`` (arrays or their shapes), under tables ``t`` wide: the grid
    ``_paged_decode_call`` runs, ``(slots, KV heads / heads a copy)``."""
    heads = v.shape[-3]
    packed = {"packed": True} if k.shape[-3] != heads else {}
    return (heads,) + _walk_shape(heads, k.shape[-2], v.shape[-1],
                                  jnp.dtype(k.dtype).itemsize, t, **packed)


def walk_counts(fills, kv_heads, kvg):
    """For one call of the paged walk over rows of ``fills`` cached
    positions at ``kvg`` of ``kv_heads`` KV heads a copy: ``(grid steps
    with a live row, those of them that found their first iteration's
    copies already started)``.  A row with any fill has a first
    iteration whatever the blocks an iteration, and every live step but
    the call's first is looked ahead to by the live step before it, so
    the second count is the first less one."""
    steps = int(np.count_nonzero(np.asarray(fills) > 0)) * (kv_heads // kvg)
    return steps, max(0, steps - 1)


def _paged_walk_kernel(scale: float, n: int, block_k: int, int8: bool,
                       kt: bool, has_new: bool,
                       len_ref, tbl_ref, lyr_ref, q_ref, *refs, vt=None):
    """One grid step a slot (and group of ``kvg`` KV heads): the walk over
    the row's live blocks is a loop in here, its trip count from the fill.
    ``refs``: the pool leaves in HBM — (k, v), or (k, k_scale, v,
    v_scale) for the int8 pool — the new token's (k_row, v_row) when
    ``has_new``, the output, then the scratch: one double-buffered VMEM
    copy ``[2, n, kvg, ...]`` a leaf, their DMA semaphores ``[2, leaves]``,
    the stream's phase ``[2]`` in SMEM and the three softmax scratches
    ``[kvg * g_pad, ...]``.

    Iteration ``c`` waits for the row's logical blocks ``c*n .. c*n+n-1``
    — those under the fill: a block past it is never copied, an empty row
    starts no copy and runs no iteration — and attends them as ONE
    online-softmax term of ``n * block_k`` columns a KV head.  A copy is
    a whole pool block, every KV head of the group at once, as it lies in
    HBM.  ``vt`` says whether the value blocks come transposed, as ``kt``
    does for the key's (None: as ``kt``).

    The copies are ONE stream over the whole call: the grid runs in order
    (slot by slot, a slot's head groups one after another) and the two
    buffer halves alternate across grid steps as they do inside one.  At
    the start of iteration ``c`` the copies of what comes next are
    started into the other half: the row's iteration ``c + 1`` or, at its
    last iteration, iteration 0 of the next grid step that has a live row
    (the slot's next head group, else the first group of the next slot
    whose fill is not 0: fills and tables of every slot are in SMEM).
    ``ph_ref`` carries to that step which half its first iteration lies
    in and that its copies were started; it only waits for them.  The
    call's first live step finds nothing started and starts its own; its
    last live step starts nothing, so every copy started is waited for
    inside the call.  An empty row passes the phase on untouched: the
    step before it has already looked past it."""
    n_cache = 4 if int8 else 2
    hbm, rest = refs[:n_cache], refs[n_cache:]
    new_refs, rest = rest[:2 * has_new], rest[2 * has_new:]
    o_ref, bufs = rest[0], rest[1:1 + n_cache]
    sem, ph_ref, m_scr, l_scr, acc_scr = rest[1 + n_cache:]
    bi, gi = pl.program_id(0), pl.program_id(1)
    kvg, g_pad = q_ref.shape[1:3]
    slots, groups = len_ref.shape[0], hbm[0].shape[2] // kvg
    fill = len_ref[bi]
    live = pl.cdiv(fill, block_k)
    trips = pl.cdiv(live, n)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def copies(s, g, c, half, count, go):
        """``go`` (start or wait) the copies of the ``count`` live blocks
        of iteration ``c`` of grid step ``(s, g)`` into buffer half
        ``half``."""
        def one(j, carry):
            blk = tbl_ref[s, c * n + j]
            for i, (src, dst) in enumerate(zip(hbm, bufs)):
                src = src.at[lyr_ref[0], blk]
                if src.shape[0] != kvg:
                    src = src.at[pl.ds(g * kvg, kvg)]
                go(pltpu.make_async_copy(src, dst.at[half, j],
                                         sem.at[half, i]))
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    # the phase the step before left (the call's first step: none)
    first = (bi == 0) & (gi == 0)
    half0 = jnp.where(first, 0, ph_ref[0])
    started = jnp.where(first, 0, ph_ref[1])
    # the next grid step with a live row: (s_next, g_next), if ``more``
    same = gi + 1 < groups
    s_next = jax.lax.while_loop(
        lambda s: (s < slots) & (len_ref[jnp.minimum(s, slots - 1)] == 0),
        lambda s: s + 1, jnp.where(same, bi, bi + 1))
    g_next = jnp.where(same, gi + 1, 0)
    more = s_next < slots
    s_next = jnp.minimum(s_next, slots - 1)
    # the live blocks of that step's first iteration (none: nothing starts)
    first_next = jnp.where(
        more, jnp.minimum(n, pl.cdiv(len_ref[s_next], block_k)), 0)

    copies(bi, gi, 0, half0,
           jnp.where(started == 1, 0, jnp.minimum(n, live)),
           lambda cp: cp.start())

    def step(c, carry):
        half = (half0 + c) & 1
        # what flies under this iteration's term
        last = c + 1 == trips
        copies(jnp.where(last, s_next, bi), jnp.where(last, g_next, gi),
               jnp.where(last, 0, c + 1), 1 - half,
               jnp.where(last, first_next, jnp.minimum(n, live - (c + 1) * n)),
               lambda cp: cp.start())

        copies(bi, gi, c, half, jnp.minimum(n, live - c * n),
               lambda cp: cp.wait())
        # a row's last iteration attends the buffers of its dead blocks
        # too, masked: a probability of exactly 0 multiplies what they
        # hold (V and its scales), which must be finite, and what they
        # hold is VMEM noise or an earlier row's blocks.  They lie in
        # this iteration's half; the copies in flight fill the other
        def zero(j, carry):
            for buf in bufs[n_cache // 2:]:
                buf[half, j] = jnp.zeros(buf.shape[2:], buf.dtype)
            return carry

        jax.lax.fori_loop(live - c * n, n, zero, 0)
        for h in range(kvg):
            # [K, V] blocks of KV head h, then the int8 pool's
            # [K scale, V scale] rows, else None
            blocks_h = [[buf[half, j, h] for j in range(n)]
                        for buf in bufs[::n_cache // 2]]
            scales = [[buf[half, j, pl.ds(h, 1), :] for j in range(n)]
                      for buf in bufs[1::2]] if int8 else [None, None]
            _attend_blocks(scale, c * n * block_k, fill, q_ref[0, h],
                           *blocks_h, *scales, m_scr, l_scr, acc_scr,
                           pl.ds(h * g_pad, g_pad), kt=kt, vt=vt)
        return carry

    jax.lax.fori_loop(0, trips, step, 0)
    # to the next grid step: where its first iteration lies, and whether
    # it is in flight
    ph_ref[0] = (half0 + trips) & 1
    ph_ref[1] = jnp.where(trips > 0, more.astype(jnp.int32), started)

    for h in range(kvg):
        rows = pl.ds(h * g_pad, g_pad)
        if has_new:
            kn_ref, vn_ref = new_refs
            _attend_new_row(scale, q_ref[0, h], kn_ref[0, h], vn_ref[0, h],
                            m_scr, l_scr, acc_scr, rows)
        l = l_scr[rows, :1]
        o_ref[0, h] = (acc_scr[rows, :] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def _pack_shared(q, leaves, new_rows):
    """A value head shared by ``r`` consecutive key heads (differential
    attention: a pair's two value heads side by side), as the plain walk
    takes it: the ``r`` key heads become ONE head ``r`` times as wide.
    Its queries are the ``r`` groups' rows, each with its own key head's
    ``d`` columns and zeros in the others', so a row's score is its own
    key head's; the key leaf, transposed as it lies at a width under 128,
    is that head's ``[r * d, block_k]`` by a reshape; the value leaf and
    the output are the shared head's.  So the ``r`` key heads' scores are
    one product, their softmax one chain and their values read once, where
    a walk a key head pays each ``r`` times for ``group`` rows of a tile.
    ``q`` [b, n_heads, d], ``leaves`` (k [L, n, kv, block, d], v [L, n,
    kv / r, block, r d]), ``new_rows`` (k [b, kv, 1, d], v [b, kv / r, 1,
    r d]) or None -> the same three at ``kv / r`` heads of ``r d``, the
    key leaf ``[.., r d, block]``."""
    k, v = leaves
    kv, heads = k.shape[2], v.shape[2]
    assert v.shape[-1] == kv // heads * k.shape[-1], (k.shape, v.shape)
    k = jnp.swapaxes(k, -1, -2)
    k = k.reshape(k.shape[:2] + (heads, v.shape[-1]) + k.shape[-1:])
    if new_rows:
        new_rows = (pack_heads(new_rows[0][:, :, 0], heads)[:, :, None],
                    new_rows[1])
    return pack_queries(q, kv, heads), [k, v], new_rows


def pack_heads(x, heads):
    """``x`` [b, kv, d] -> [b, heads, kv / heads * d]: consecutive heads
    side by side."""
    return x.reshape(x.shape[0], heads, -1)


def pack_queries(q, kv, heads):
    """``q`` [b, n_heads, d], consecutive query heads on one of ``kv``
    key heads, for ``heads`` packed heads of ``r = kv / heads`` key heads
    each -> [b, n_heads, r d]: row ``(j, i)`` of a packed head holds query
    ``i`` of its ``j``-th key head in columns ``j d .. (j + 1) d`` and
    zeros elsewhere, so against the ``r`` key heads side by side its
    score is its own key head's."""
    b, n_heads, d = q.shape
    r = kv // heads
    assert kv == r * heads, (kv, heads)
    qg = q.reshape(b, heads, r, n_heads // kv, d)
    qg = jnp.einsum("bhjid,jk->bhjikd", qg, jnp.eye(r, dtype=q.dtype))
    return qg.reshape(b, n_heads, r * d)


def _paged_decode_call(q, leaves, tables, cache_len, *, layer=None,
                       new_rows=None, softmax_scale=None, interpret=None):
    """Paged twin of _decode_call.  ``leaves`` is (k, v) or the int8
    pool's (k_q, k_scale, v_q, v_scale): one layer's pool view
    ``[n_blocks, kv, block_k(, d)]``, or with ``layer`` (an int32 scalar,
    traced in a layer scan) the whole pool ``[L, n_blocks, ...]`` of
    which the kernel addresses layer ``layer`` — a scan body that slices
    its layer out first makes XLA copy that slice for the custom call.
    The leaves stay in HBM; fills, tables and the layer prefetch to SMEM,
    where the kernel's walk reads the physical block of each copy.  The
    grid is one step a slot and group of KV heads; how many heads a copy
    takes and how many blocks an iteration follow from the shapes
    (``pool_walk``, ``_walk_shape``), the same walk for every pool form.
    Both axes are ``"arbitrary"``: the steps run one after another, a
    slot's head groups innermost, because each starts the next one's
    first copies and hands it the buffer's phase in an SMEM scratch (on
    a chip that split a ``"parallel"`` axis between cores a copy would be
    started on one and waited for on the other; the v5e has one).  Who
    waits for what: a step for the copies of its own iterations, which
    it or the step before it started — never for another step's.

    At a head width under 128 the blocks are handed over transposed,
    ``[d, block_k]``: XLA:TPU keeps a ``[..., 128·n, 64]`` array with
    the 128-multiple as the minor (lane) dimension, so the transpose is
    a relabelling of the pool as it lies in HBM, where the row-major
    block Mosaic would otherwise ask for costs a copy of the whole pool
    in every call."""
    int8 = len(leaves) == 4
    if softmax_scale is None:
        softmax_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if layer is None:
        leaves, layer = [a[None] for a in leaves], 0
    block_k = leaves[0].shape[3]
    kv_heads, kvg, n = pool_walk(leaves[0], leaves[len(leaves) // 2],
                                 tables.shape[1])
    packed = leaves[0].shape[2] != kv_heads
    vt = {}
    if packed:
        assert not int8, "a shared value head is served from a float pool"
        q, leaves, new_rows = _pack_shared(q, leaves, new_rows)
        kt, vt = True, {"vt": leaves[1].shape[-1] % 128 != 0}
        if vt["vt"]:
            leaves[1] = jnp.swapaxes(leaves[1], -1, -2)
    b, n_heads, d = q.shape
    if not packed:
        kt = d % 128 != 0
        if kt:
            leaves = [jnp.swapaxes(a, -1, -2) if a.ndim == 5 else a
                      for a in leaves]
    group = n_heads // kv_heads
    if interpret is None:
        interpret = kernels.default_interpret()
    if not interpret:
        assert block_k % 128 == 0 and d % 64 == 0, (block_k, d)

    g_pad = max(8, -(-group // 8) * 8)
    qg = q.reshape(b, kv_heads, group, d)
    if g_pad != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, g_pad - group), (0, 0)))

    lens = jnp.broadcast_to(
        jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1,)), (b,))
    tbl = jnp.asarray(tables, jnp.int32)
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    row_spec = lambda rows: pl.BlockSpec(  # noqa: E731
        (1, kvg, rows, d), lambda bi, gi, *s: (bi, gi, 0, 0))
    new_rows = list(new_rows or ())
    out = pl.pallas_call(
        functools.partial(_paged_walk_kernel, float(softmax_scale), n,
                          block_k, int8, kt, bool(new_rows), **vt),
        name="flash_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, kv_heads // kvg),
            in_specs=([row_spec(g_pad)]
                      + [pl.BlockSpec(memory_space=pl.ANY)] * len(leaves)
                      + [row_spec(1)] * len(new_rows)),
            out_specs=row_spec(g_pad),
            scratch_shapes=(
                [pltpu.VMEM((2, n, kvg) + a.shape[3:], a.dtype)
                 for a in leaves]
                + [pltpu.SemaphoreType.DMA((2, len(leaves))),
                   pltpu.SMEM((2,), jnp.int32),
                   pltpu.VMEM((kvg * g_pad, 128), jnp.float32),
                   pltpu.VMEM((kvg * g_pad, 128), jnp.float32),
                   pltpu.VMEM((kvg * g_pad, d), jnp.float32)]),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv_heads, g_pad, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, tbl, lyr, qg, *leaves, *new_rows)
    return out[:, :, :group].reshape(b, n_heads, d)


def flash_decode_paged(
    q: jax.Array,        # [b, n_heads, d] — ONE new token's queries
    k_pool: jax.Array,   # [n_blocks, kv_heads, block, d] — one layer's
    v_pool: jax.Array,   # pool; with ``layer``, the whole [L, ...] pool
    tables: jax.Array,   # [b, T] int32 block tables (pad entries = trash)
    cache_len: jax.Array,  # [b] (or scalar) valid rows IN THE POOL
    *,
    new_rows: tuple | None = None,  # (k, v) [b, kv_heads, 1, d]: the new
    #                      token's own rows, attended but not in the pool
    layer=None,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """→ [b, n_heads, d]: decode attention gathered straight from the
    paged block pool — no dense [b, max_len] cache is ever materialized.
    Without ``new_rows`` the new token's row is expected in the pool and
    counted in ``cache_len``."""
    return _paged_decode_call(
        q, [k_pool, v_pool], tables, cache_len, layer=layer,
        new_rows=new_rows, softmax_scale=softmax_scale, interpret=interpret)


def flash_decode_paged_int8(
    q: jax.Array,          # [b, n_heads, d]
    k_q: jax.Array,        # [n_blocks, kv_heads, block, d] int8 pool leaf
    k_scale: jax.Array,    # [n_blocks, kv_heads, block] fp32 row scales
    v_q: jax.Array,
    v_scale: jax.Array,
    tables: jax.Array,     # [b, T] int32
    cache_len: jax.Array,
    *,
    new_rows: tuple | None = None,  # float rows, as the pool will hold
    #                        them (kv_quant.dequantize_cache of the
    #                        quantized rows)
    layer=None,
    softmax_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode attention over the int8 ``{q, scale}`` pool form."""
    return _paged_decode_call(
        q, [k_q, k_scale, v_q, v_scale], tables, cache_len, layer=layer,
        new_rows=new_rows, softmax_scale=softmax_scale, interpret=interpret)


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
