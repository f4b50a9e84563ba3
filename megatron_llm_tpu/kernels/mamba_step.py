"""Pallas kernel for one position through a Mamba-2 layer, everything
between its two projections (a decode step, ``models/mamba2.py``)::

    x | B | C <- SiLU(conv(tail, x | B | C) + bias);   tail <- moved on
    dt <- softplus(dt + dt_bias);   a = exp(-exp(A_log) dt)
    S <- a S + (dt x) (x) B;   y = S C + D x
    y <- RMSNorm over each group's channels of (y SiLU(z)), scaled

**Where the operands lie.**  The in-projection's output ``[slots, z | x |
B | C | dt]`` comes as ``dot_rounded`` left it and ``y`` goes out as
``[slots, inner width]``, the row the out-projection reads: nothing is
sliced, transposed or padded around the call.  Both travel in blocks of
eight slots (whole ``(8, 128)`` tiles, fetched and stored once for the
eight) and a slot's row is picked in VMEM.  The layer's small parameters
are resident.  Two arrays are aliased to outputs, the states of all the
Mamba-2 layers ``[layers, slots, heads, head width, state width]`` and
their convolution tails, as the engine carries and donates them; the
layer is a prefetched scalar that the block index maps read, so the
kernel advances layer ``at`` where it lies and no other layer is touched.
The tails come in the two forms ``mamba2.init_state`` makes: flat
``[layers, slots, 3 x channels]``, eight slots' rows a block as above; or
``[layers, slots, 3, channels]``, which XLA:TPU keeps with the three rows
outermost of a layer (``slots x channels`` in whole tiles): the kernel
takes that as ``[layers, 3, slots, channels]``, a relabelling, where the
blocks as written cost a copy of the whole array at both ends of a call.
A slot that ``live`` does not mark has ``dt = 0``, so ``a = 1`` and
zeros are added, and keeps its tail: both come back bit for bit.

**A grid step** is ``heads_per_step`` heads of one slot, whatever the
groups: blocks of at most 16 heads that share a group (a group of 16 is
one block, a group of 64 four), as many of them as make 32 heads, 1 MB
of state in and 1 MB out.  A slot's first grid step convolves ``B | C``
and keeps them in VMEM for the others.  For each block a grid step
convolves the block's channels of ``x`` against the slot's tail and
stores the tail moved on, advances the tiles on the vector unit (``a S +
x (x) (dt B)``: the step size goes to the one register of ``B``, not to
``x``'s eight), and takes ``y = S C`` for the block as ONE product on the
matrix unit at ``Precision.HIGHEST`` (as a lane reduction on the vector
unit it does not hide under the copies: 2.0 against 1.7 ms a layer of 128
slots, PERF.md).  The slot's ``x`` and ``S C`` stay in VMEM rows over its
grid steps; the last of them adds the skip (``D`` a channel, spread over
its head's lanes once a call by a product with zeros and ones), gates,
norms each group (a group may span blocks and grid steps: one group of
64 heads is both grid steps of a slot) and writes the slot's row.

The tails are read through one block and written through another over
ONE array, so no element is read after it was written: each channel's
three rows are loaded, then stored, once a call (making ``B | C`` again
in a slot's second grid step read what the first had stored, on the chip
and with the flat form only; PERF.md, PR 50).

**The transposition.**  The projection's row has ``x`` along the lanes,
a head's width after a head's, and a state's tile wants a head's width
down the sublanes against ``B`` along the lanes.  A register of the row
holds ``128 // head width`` heads, a *chunk*: the block's row is
reshaped to ``[chunks, 128]`` and transposed whole (an aligned 2-D
transpose, which Mosaic compiles; a ``pltpu.roll`` of fewer than 128
lanes it refuses, PR 49), and head ``j``'s column is a static slice of
that.  Nothing is transposed back: ``C`` (as eight equal rows) against
the block's new tiles as columns, an NT product, gives ``S C`` along the
lanes in the order the row has.

With that the kernel's time is its copies': every stage switched off in
turn read the same 0.436 ms a layer of 64 slots at one group of 64 heads
and 1.72 at 128 slots of eight groups of 16 (my chip run, PR 50).

Everything is float32 and the exact ``softplus``, ``exp`` and ``rsqrt``.
The body is traced in every program that holds a decode step, once (the
call is jitted and the layers of a stack share its shapes): scalar
arithmetic is ``lax`` on constants and indices are static wherever the
grid's are not needed (PERF.md, PR 42).
"""

from __future__ import annotations

import functools
import math
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
# slots a block of the rows that lie slot by slot (the projection's
# output, a flat tail, y): whole (8, 128) tiles, fetched once for eight
# slots and a slot's row picked in VMEM
_SLOT_ROWS = 8
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


def _kernel(G, flat, eps, at_ref, live_ref, zx_ref, w_ref, bias_ref, dtb_ref,
            alog_ref, D_ref, scale_ref, tin_ref, s_ref, y_ref, tout_ref,
            out_ref, bc_ref, x_ref, yr_ref, Dx_ref):
    """One grid step: ``blocks`` blocks of ``per`` heads of one slot, each
    block inside one group.  ``live_ref`` [slots] in SMEM; ``zx_ref
    y_ref`` the rows of a block of slots, [slots a block, z | x | B | C |
    dt] and [.., inner width]; ``tin_ref tout_ref`` the tails of the same
    slots, flat rows or [taps - 1, slots a block, channels]; the
    parameters whole; ``s_ref out_ref`` [blocks x per, head width, state
    width]; the scratch rows: ``bc_ref`` the slot's ``B | C``, ``x_ref
    yr_ref`` [1, inner width] its convolved ``x`` and its ``S C``, kept
    over its grid steps, ``Dx_ref`` the skip a channel, kept over the
    call.  No element of the tail is read after it was written (in and
    out are one array).  Traced in every program that holds a decode
    step: scalar arithmetic is ``lax`` on constants, indices are static
    wherever the grid's are not needed (PERF.md, PR 42)."""
    del at_ref
    S, P, N = s_ref.shape
    taps, ch = w_ref.shape
    H = dtb_ref.shape[1]
    per, blocks = _tiling(H, G)
    di, group, steps, W = H * P, H // G, H // S, per * P
    # the projection's row holds 128 // P heads in a register's lanes: a
    # chunk, which turns into a column of registers whole
    hc = math.gcd(per, max(_LANES // P, 1))
    cw, chunks = hc * P, per // hc
    i32, f32 = np.int32, jnp.float32
    bi, gi = pl.program_id(0), pl.program_id(1)
    row = pl.ds(jax.lax.rem(bi, i32(zx_ref.shape[0])), 1)
    live = live_ref[bi] != 0
    head0 = jax.lax.mul(gi, i32(S))                # the step's first head

    def once(cond, of_many=True):
        """``pl.when(cond)``; where a slot has one grid step, the body as
        it stands."""
        return pl.when(cond) if of_many else (lambda f: f())

    def lanes_at(start, width):
        if width % _LANES == 0 and not isinstance(start, int):
            start = pl.multiple_of(start, _LANES)
        return pl.ds(start, width)

    def tail_at(k, at):
        """Row ``k`` of the slot's tail, oldest first."""
        if flat:
            return row, lanes_at(at.start + k * ch, at.size)
        return k, row, at

    def conv(start, new):
        """The convolution's channels ``start ..`` at the new position
        ``new``, bias and SiLU; and their tail moved on, where the slot
        lives."""
        at = lanes_at(start, new.shape[1])
        rows = [tin_ref[tail_at(k, at)] for k in range(taps - 1)] + [new]
        acc = rows[0] * w_ref[0:1, at].astype(f32)
        for k in range(1, taps):
            acc = acc + rows[k] * w_ref[k:k + 1, at].astype(f32)
        for k in range(taps - 1):
            tout_ref[tail_at(k, at)] = jnp.where(live, rows[k + 1], rows[k])
        return jax.nn.silu(acc + bias_ref[:, at].astype(f32))

    def pick(v, width, starts):
        """``width`` lanes of the row ``v`` from this grid step's of
        ``starts``."""
        out = v[:, starts[0]:starts[0] + width]
        for t in range(1, steps):
            if starts[t] != starts[0]:
                out = jnp.where(gi == t,
                                v[:, starts[t]:starts[t] + width], out)
        return out

    @once(jax.lax.eq(jax.lax.add(bi, gi), i32(0)))
    def _():
        """The skip a channel, ``D`` of a channel's head: each head's
        spread over its lanes by a product with zeros and ones, exact at
        ``HIGHEST``."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1)
        first = jax.lax.broadcasted_iota(jnp.int32, (H, W), 0) * P
        D = jnp.broadcast_to(D_ref[...], (8, H))
        for c in range(0, di, W):
            ones = ((lane >= first - c) & (lane < first + (P - c))
                    ).astype(f32)
            Dx_ref[:, c:c + W] = jax.lax.dot_general(
                D, ones, (((1,), (0,)), ((), ())), precision=_PREC,
                preferred_element_type=f32)[:1]

    bcdt = zx_ref[row, 2 * di:]                    # B | C | dt, as projected

    @once(jax.lax.eq(gi, i32(0)), steps > 1)
    def _():
        bc_ref[...] = conv(di, bcdt[:, :2 * G * N])

    # (no clamp of the step: the published config has no time_step_limit)
    dt = jax.nn.softplus(bcdt[:, 2 * G * N:] + dtb_ref[...]) \
        * live.astype(f32)
    mine = [t * S for t in range(steps)]           # the step's heads
    a = pick(jnp.exp(dt * -jnp.exp(alog_ref[...])), S, mine)
    dt = pick(dt, S, mine)
    for g in range(blocks):
        h0 = jax.lax.add(head0, i32(g * per))
        at = lanes_at(jax.lax.mul(h0, i32(P)), W)
        x = conv(at.start, zx_ref[row, lanes_at(at.start + i32(di), W)])
        x_ref[:, at] = x
        B, C = (pick(bc_ref, N, [(first + (t * S + g * per) // group) * N
                             for t in range(steps)]) for first in (0, G))
        # a chunk's heads down the sublanes, a chunk a lane: a head's
        # column, spread over the lanes, meets B along them
        xt = x.reshape(chunks, cw).T
        for j in range(per):
            h = g * per + j
            col = xt[(j % hc) * P:(j % hc + 1) * P, j // hc:j // hc + 1]
            out_ref[h] = s_ref[h] * a[:, h:h + 1] \
                + col * (B * dt[:, h:h + 1])
        # y = S C for the block's heads at once, on the matrix unit: C
        # against the new tiles as columns, so that y comes out along the
        # lanes, a head's width after a head's, as the row it leaves in
        yr_ref[:, at] = jax.lax.dot_general(
            jnp.broadcast_to(C, (8, N)),
            out_ref[g * per:(g + 1) * per].reshape(W, N), _NT,
            precision=_PREC, preferred_element_type=f32)[:1]

    @once(jax.lax.eq(gi, i32(steps - 1)), steps > 1)
    def _():
        """With all of the slot's y: the skip, the gate, RMSNorm over
        each group's channels, its scale: the row the out-projection
        reads."""
        y = (yr_ref[...] + Dx_ref[...] * x_ref[...]) \
            * jax.nn.silu(zx_ref[row, :di])
        for c in range(0, di, di // G):
            part = y[:, c:c + di // G]
            ms = jnp.sum(part * part, axis=1, keepdims=True) / f32(di // G)
            y_ref[row, c:c + di // G] = part * jax.lax.rsqrt(ms + f32(eps)) \
                * scale_ref[:, c:c + di // G].astype(f32)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _call(zxbcdt, conv, small, live, ssm, tail, at, *, eps: float,
          interpret: bool):
    b = zxbcdt.shape[0]
    H, P, N = ssm.shape[2:]
    taps, ch = conv.shape
    di = H * P
    G = (ch - di) // (2 * N)
    per, blocks = _tiling(H, G)            # heads a block, blocks a step
    rb = min(b, _SLOT_ROWS)
    eight = lambda bi: jax.lax.div(bi, np.int32(rb))  # noqa: E731
    slots = lambda bi, gi, at, live: (eight(bi), 0)  # noqa: E731
    whole = lambda bi, gi, at, live: (0, 0)  # noqa: E731
    row = lambda a: pl.BlockSpec((1, a.shape[0]), whole)  # noqa: E731
    flat = tail.ndim == 3
    if flat:
        tails = pl.BlockSpec((None, rb, tail.shape[2]),
                             lambda bi, gi, at, live: (at[0], eight(bi), 0))
    else:
        # XLA:TPU keeps [layers, slots, 3, channels] with the three rows
        # outermost of a layer (slots x channels in whole tiles): handed
        # over in that order the swap is a relabelling, and as it is
        # written a copy of all of it at both ends of every call
        tail = jnp.swapaxes(tail, 1, 2)
        tails = pl.BlockSpec(
            (None, taps - 1, rb, ch),
            lambda bi, gi, at, live: (at[0], 0, eight(bi), 0))
    state = pl.BlockSpec((None, None, blocks * per, P, N),
                         lambda bi, gi, at, live: (at[0], bi, gi, 0, 0))
    rows_y = pl.BlockSpec((rb, di), slots)
    y, tail, ssm = pl.pallas_call(
        functools.partial(_kernel, G, flat, eps),
        name="mamba_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, H // (blocks * per)),
            in_specs=[pl.BlockSpec((rb, zxbcdt.shape[1]), slots),
                      pl.BlockSpec((taps, ch), whole)]
            + [row(a) for a in small] + [tails, state],
            out_specs=[rows_y, tails, state],
            scratch_shapes=[pltpu.VMEM((1, 2 * G * N), jnp.float32)]
            + [pltpu.VMEM((1, di), jnp.float32)] * 3),
        out_shape=[jax.ShapeDtypeStruct((b, di), jnp.float32),
                   jax.ShapeDtypeStruct(tail.shape, jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, jnp.float32)],
        # (operands count the prefetched scalars)
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(at, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      zxbcdt, conv, *(a[None] for a in small), tail, ssm)
    return y, ssm, tail if flat else jnp.swapaxes(tail, 1, 2)


def mamba_step(zxbcdt, conv, conv_bias, dt_bias, A_log, D, scale, live, ssm,
               tail, at, *, eps: float, interpret: Optional[bool] = None):
    """``zxbcdt`` [b, z | x | B | C | dt] float32, the in-projection's
    output at one position; ``conv`` [taps, channels], ``conv_bias``
    [channels], ``dt_bias A_log D`` [heads], ``scale`` [inner width], the
    layer's; ``live`` [b] bool; ``ssm`` [layers, b, H, P, N] and ``tail``
    [layers, b, (taps - 1) x channels] or [layers, b, taps - 1, channels]
    float32, the stacked states and convolution tails; ``at`` an int32
    scalar (may be traced) → ``(y [b, H x P] float32, what the
    out-projection reads; ssm and tail with layer ``at`` advanced where
    ``live``: in place when they are donated)``.  The layers of a stack
    call it with the same shapes: it is traced once a program."""
    if interpret is None:
        interpret = kernels.default_interpret()
    b, (H, P, N), (taps, ch) = zxbcdt.shape[0], ssm.shape[2:], conv.shape
    assert ssm.shape[1] == tail.shape[1] == b and zxbcdt.shape[1] \
        == 2 * H * P + (ch - H * P) + H and tail[0, 0].size \
        == (taps - 1) * ch and (ch - H * P) % (2 * N) == 0, (
            zxbcdt.shape, conv.shape, ssm.shape, tail.shape)
    return _call(zxbcdt, conv, (conv_bias, dt_bias, A_log, D, scale), live,
                 ssm, tail, at, eps=eps, interpret=interpret)
