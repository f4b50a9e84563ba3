"""Pallas kernel for one position through a Mamba-2 layer's state-space
recurrence (a decode step, ``models/mamba2.py``)::

    S <- a S + (dt x) (x) B;   y = S C

The states of all the Mamba-2 layers lie stacked in one array, ``[layers,
slots, heads, head width, state width]`` float32, as the engine carries
and donates it.  The kernel takes that whole array, aliased to its
output, and a layer index as a prefetched scalar: the block index map
picks the layer, so nothing is sliced out or written back around the
call.  A grid step loads a tile of one slot's heads from HBM, computes
the new tile and ``y`` from it in VMEM and stores the tile: one read and
one write of the layer's states, every other layer untouched.

``B`` and ``C`` are shared by the heads of a group and lie along the
lanes as the state width does.  ``a`` is a scalar a head, read from SMEM.
``dt x`` varies down a tile's sublanes (the head width): it comes in
with the head width as rows, ``[slots, head width, heads]``, a slot's
block resident over its grid steps, and a head's column is spread over
the lanes.  ``y``, a sum over the lanes with the head width left on the
sublanes, goes out the same way and each head's column is put into its
lane.  Both are a few KB a slot beside 4 MB of state; the caller's
transposes are XLA's, and so is the padding of their heads to whole
128-lane registers where a layer has fewer (Mosaic rolls no narrower
array along its lanes).

A grid step takes ``heads_per_step`` heads, whatever the groups: blocks
of at most 16 heads that share a group (a group of 16 is one block, a
group of 64 four), as many of them as make 32 heads; a block's ``B`` and
``C`` are its first head's group's.

Everything is float32.  The update is the vector unit's; the sum over
the state width is one product a group on the matrix unit at
``Precision.HIGHEST`` (as a lane reduction on the vector unit it does
not hide under the copies: 2.0 against 1.7 ms a layer of 128 slots,
PERF.md).  A row with ``dt = 0`` has ``a = 1`` and adds zeros: its state
comes back bit for bit.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

# heads a grid step, in blocks that share a group: two blocks' 32 heads
# are 1 MB of state at the published widths, 4 MB in flight; with one the
# products no longer hide under the copies (1.93 against 1.70 ms a layer
# of 128 slots, PERF.md)
_BLOCK_HEADS = 16
_STEP_HEADS = 32
_LANES = 128
_PREC = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))


def _tiling(H: int, G: int):
    """→ (heads a block, blocks a grid step): the largest divisor of a
    group's heads up to ``_BLOCK_HEADS``, and as many blocks as divide
    the layer's and make no more than ``_STEP_HEADS`` heads."""
    per = H // G
    block = max(i for i in range(1, _BLOCK_HEADS + 1) if per % i == 0)
    blocks = max(i for i in range(1, _STEP_HEADS // block + 1)
                 if (H // block) % i == 0)
    return block, blocks


def heads_per_step(H: int, G: int) -> int:
    """The heads of one slot a grid step advances, for ``H`` heads in
    ``G`` groups."""
    block, blocks = _tiling(H, G)
    return block * blocks


def _kernel(blocks, per, group, at_ref, a_ref, x_ref, B_ref, C_ref, s_ref,
            y_ref, out_ref):
    """``blocks`` blocks of ``per`` heads of one slot, each block inside
    one group of ``group`` heads.  ``a_ref`` [1, heads] in SMEM, ``x_ref
    y_ref`` [head width, heads (whole registers of lanes)], ``B_ref
    C_ref`` [all groups, state width], ``s_ref out_ref`` [blocks x per,
    head width, state width].  Traced in every program that holds a
    decode step: scalar arithmetic is ``lax`` on constants, the blocks
    are a loop (PERF.md, PR 42)."""
    del at_ref
    P, H = x_ref.shape
    N = s_ref.shape[-1]
    i32 = np.int32
    first = jax.lax.mul(pl.program_id(1), i32(blocks))
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (per, P, H), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (per, P, H), 2)

    def group_block(g, y):
        row = jax.lax.add(first, g)
        h0 = jax.lax.mul(row, i32(per))
        if group != per:              # a block is a part of its group
            row = jax.lax.div(h0, i32(group))
        B, C = B_ref[pl.ds(row, 1), :], C_ref[pl.ds(row, 1), :]
        # this group's heads to lanes 0, 1, ...: a head's column of dt x,
        # spread over the lanes, meets B along them
        x = pltpu.roll(x_ref[...], jax.lax.rem(
            jax.lax.sub(i32(H), h0), i32(H)), 1)
        at = pl.ds(jax.lax.mul(g, i32(per)), per)
        s_g, out_g = s_ref.at[at], out_ref.at[at]
        for j in range(per):
            a = a_ref[0, jax.lax.add(h0, i32(j))]
            out_g[j] = s_g[j] * a + x[:, j:j + 1] * B
        # y = S C for the group's heads at once, on the matrix unit: the
        # new tiles as rows against C in every column, so that a head's y
        # lies along the lanes already and its own lane is picked
        ys = jax.lax.dot_general(
            out_g[...].reshape(per * P, N), jnp.broadcast_to(C, (H, N)),
            _NT, precision=_PREC, preferred_element_type=jnp.float32)
        ys = jnp.sum(jnp.where(jax.lax.add(head, h0) == lanes,
                               ys.reshape(per, P, H), 0.0), axis=0)
        mine = (lane >= h0) & (lane < jax.lax.add(h0, i32(per)))
        return jnp.where(mine, ys, y)

    # (the block of y stays in VMEM over a slot's grid steps; what the
    # first of them finds there is replaced lane by lane)
    y_ref[...] = jax.lax.fori_loop(0, blocks, group_block, y_ref[...],
                                   unroll=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(x, B, C, dt, A, ssm, at, *, interpret: bool):
    b, H, P = x.shape
    G, N = B.shape[1:]
    per, blocks = _tiling(H, G)            # heads a block, blocks a step
    lanes = -(-H // _LANES) * _LANES
    at = jnp.reshape(at, (1,)).astype(jnp.int32)
    a = jnp.exp(dt * A)[:, None]
    dtx = jnp.swapaxes(dt[..., None] * x, 1, 2)
    if lanes != H:
        dtx = jnp.pad(dtx, ((0, 0), (0, 0), (0, lanes - H)))
    slot = lambda bi, gi, at: (bi, 0, 0)  # noqa: E731
    rows = pl.BlockSpec((None, P, lanes), slot)
    shared = pl.BlockSpec((None, G, N), slot)
    state = pl.BlockSpec((None, None, blocks * per, P, N),
                         lambda bi, gi, at_: (at_[0], bi, gi, 0, 0))
    y, ssm = pl.pallas_call(
        functools.partial(_kernel, blocks, per, H // G),
        name="mamba_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, H // (blocks * per)),
            in_specs=[pl.BlockSpec((None, 1, H), slot,
                                   memory_space=pltpu.SMEM),
                      rows, shared, shared, state],
            out_specs=[rows, state]),
        out_shape=[jax.ShapeDtypeStruct((b, P, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, jnp.float32)],
        # (operands count the prefetched scalar)
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(at, a, dtx, B, C, ssm)
    return jnp.swapaxes(y[..., :H] if lanes != H else y, 1, 2), ssm


def mamba_step(x, B, C, dt, A, ssm, at, interpret: Optional[bool] = None):
    """``x`` [b, H, P], ``B C`` [b, G, N], ``dt`` [b, H], ``A`` [H]
    (negative), ``ssm`` [layers, b, H, P, N], float32, ``at`` an int32
    scalar (may be traced) → ``(y [b, H, P], ssm with layer ``at``
    advanced: in place when ``ssm`` is donated)``.  The layers of a stack
    call it with the same shapes: it is traced once a program."""
    if interpret is None:
        interpret = kernels.default_interpret()
    assert ssm.shape[1:] == x.shape + B.shape[2:] and B.shape == C.shape \
        and x.shape[1] % B.shape[1] == 0, (x.shape, B.shape, ssm.shape)
    return _call(x, B, C, dt, A, ssm, at, interpret=interpret)
