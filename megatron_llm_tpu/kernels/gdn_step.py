"""Pallas kernel for one position through a Gated DeltaNet layer,
everything between its two projections (a decode step,
``models/gated_deltanet.py``)::

    q | k | v <- SiLU(conv(tail, q | k | v));   tail <- moved on
    q <- l2norm(q) dk^-0.5;   k <- l2norm(k)
    beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)
    S <- exp(g) S;   d = beta (v - k^T S);   S <- S + k d^T;   o = q^T S
    o <- RMSNorm over a head's width of o, scaled, times SiLU(z)

**Where the operands lie.**  The in-projection's outputs ``[slots, q | k
| v | z]`` and ``[slots, b | a]`` come as ``dot_f32`` left them and ``o``
goes out as ``[slots, value heads x value width]``, the row the
out-projection reads: nothing is sliced, repeated or padded around the
call (two value heads read one key head's columns of the row).  The rows
travel in blocks of eight slots (whole ``(8, 128)`` tiles, fetched and
stored once for the eight) and a slot's row is picked in VMEM.  Two
arrays are aliased to outputs, the states of all the DeltaNet layers
``[layers, slots, value heads, key width, value width]`` and their
convolution tails ``[layers, slots, taps - 1, channels]``, as the engine
carries and donates them; the layer is a prefetched scalar that the block
index maps read, so the kernel advances layer ``at`` where it lies and no
other layer is touched.  XLA:TPU keeps the tails with the three rows
outermost of a layer (``slots x channels`` in whole tiles): the kernel
takes them as ``[layers, taps - 1, slots, channels]``, a relabelling (as
they are written the blocks cost a copy of the whole array at both ends
of a call; ``kernels/mamba_step.py``, PERF.md PR 50).  A slot that
``live`` does not mark has ``beta = 0`` and ``g = 0``, so its state is
multiplied by one and zeros are added, and keeps its tail: both come back
bit for bit.

**A grid step** is ``heads_per_step`` value heads of one slot with the
key heads that serve them: it convolves its own channels of q, k and v
against the slot's tail and stores the tail moved on, norms q and k a
head, and then takes a head's ``[key width, value width]`` tile once, on
the vector unit: both contractions run over the key width, the tile's
sublane axis, so ``k^T S`` and ``q^T S`` are a multiply of whole registers
by k or q spread along the lanes and one reduction down the sublanes, the
decay a multiply and the rank-one update a multiply-add.  (On the matrix
unit at ``Precision.HIGHEST`` a tile would be a stationary operand used
for eight rows, six passes a product.)  q and k are wanted down the
sublanes: a step's heads of the projection's row, ``[heads, key width]``,
are transposed whole and a head is a column.  Every element of the tail
and of the state is read once and written once, in the same grid step
(in and out are one array: nothing is read after it was written).

Everything is float32 and the exact ``softplus``, ``exp`` and ``rsqrt``:
no product here is rounded to bfloat16 (the rule takes differences of
near-equal quantities, PERF.md PR 35).  The body is traced in every
program that holds a decode step, once (the call is jitted and the layers
of a stack share its shapes): scalar arithmetic is ``lax`` on constants,
indices are static wherever the grid's are not needed, and there is no
``pl.when`` (PERF.md, PR 42).
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
from .gdn_scan import conv_taps, gated_rmsnorm, l2norm

# value heads a grid step: 16 tiles of 128 x 128 are 1 MB of state in and
# 1 MB out, 4 MB in flight (a layer of 44 slots 0.335 ms on the chip, 1.07
# of XLA's read-and-write pass over the same state; with 32 heads 0.330,
# with 8 0.365: PERF.md, PR 51)
_STEP_HEADS = 16
_LANES = 128
# slots a block of the rows that lie slot by slot (the projection's
# outputs, the tails, o): whole (8, 128) tiles, fetched once for eight
# slots and a slot's row picked in VMEM
_SLOT_ROWS = 8


def heads_per_step(nv: int, nk: int) -> int:
    """The value heads of one slot a grid step advances: whole key heads'
    value heads, as many as divide the layer's and make no more than
    ``_STEP_HEADS``."""
    r = nv // nk
    return r * max(i for i in range(1, max(_STEP_HEADS // r, 1) + 1)
                   if nk % i == 0)


def _kernel(nk, eps, at_ref, live_ref, x_ref, ba_ref, w_ref, alog_ref,
            dtb_ref, scale_ref, tin_ref, s_ref, o_ref, tout_ref, out_ref):
    """One grid step: ``hs`` value heads of one slot.  ``live_ref``
    [slots] in SMEM; ``x_ref ba_ref o_ref`` the rows of a block of slots,
    [slots a block, q | k | v | z], [.., b | a] and [.., value heads x
    value width]; ``tin_ref tout_ref`` the tails of the same slots, [taps
    - 1, slots a block, channels]; ``w_ref`` [taps, channels], ``alog_ref
    dtb_ref`` [1, value heads], ``scale_ref`` [1, value width], whole;
    ``s_ref out_ref`` [hs, key width, value width]."""
    del at_ref
    hs, dk, dv = s_ref.shape
    taps = w_ref.shape[0]
    nv = alog_ref.shape[1]
    r = nv // nk
    kh, steps = hs // r, nv // hs          # key heads a step, steps a slot
    kd, vd = nk * dk, nv * dv
    i32, f32 = np.int32, jnp.float32
    bi, gi = pl.program_id(0), pl.program_id(1)
    row = pl.ds(jax.lax.rem(bi, i32(x_ref.shape[0])), 1)
    live = live_ref[bi] != 0

    def lanes_at(first, per, width):
        """``width`` lanes from ``first``, ``per`` further a grid step of
        the slot."""
        if steps == 1:
            return pl.ds(first, width)
        start = jax.lax.add(jax.lax.mul(gi, i32(per)), i32(first))
        if per % _LANES == 0 and first % _LANES == 0:
            start = pl.multiple_of(start, _LANES)
        return pl.ds(start, width)

    def conv(at):
        """The convolution's channels ``at`` at the new position and
        their SiLU; and their tail moved on, where the slot lives."""
        rows = [tin_ref[k, row, at] for k in range(taps - 1)] \
            + [x_ref[row, at]]
        for k in range(taps - 1):
            tout_ref[k, row, at] = jnp.where(live, rows[k + 1], rows[k])
        return jax.nn.silu(conv_taps(
            rows, (w_ref[k:k + 1, at].astype(f32) for k in range(taps))))

    def mine(v):
        """This grid step's ``hs`` lanes of the row ``v`` [1, value
        heads]."""
        out = v[:, :hs]
        for t in range(1, steps):
            out = jnp.where(gi == t, v[:, t * hs:(t + 1) * hs], out)
        return out

    # the step's key heads of q and k, a head a row, normed, then a head a
    # column: down the sublanes, as a state's tile has the key width
    q = l2norm(conv(lanes_at(0, kh * dk, kh * dk)).reshape(kh, dk)) \
        * f32(dk ** -0.5)
    k = l2norm(conv(lanes_at(kd, kh * dk, kh * dk)).reshape(kh, dk))
    qt, kt = q.T, k.T
    v = conv(lanes_at(2 * kd, hs * dv, hs * dv))
    z = x_ref[row, lanes_at(2 * kd + vd, hs * dv, hs * dv)]
    alive = live.astype(f32)
    beta = mine(jax.nn.sigmoid(ba_ref[row, :nv]) * alive)
    decay = mine(jnp.exp(-jnp.exp(alog_ref[...]) * jax.nn.softplus(
        ba_ref[row, nv:] + dtb_ref[...]) * alive))
    o = []
    for j in range(hs):
        if j % r == 0:
            # a key head's k and q along the lanes of whole tiles, once
            # for the value heads it serves
            kb, qb = (jnp.broadcast_to(t[:, j // r:j // r + 1], (dk, dv))
                      for t in (kt, qt))
        at = slice(j * dv, (j + 1) * dv)
        S = s_ref[j] * decay[:, j:j + 1]
        d = beta[:, j:j + 1] * (
            v[:, at] - jnp.sum(S * kb, axis=0, keepdims=True))
        S = S + kb * d
        out_ref[j] = S
        o.append(gated_rmsnorm(jnp.sum(S * qb, axis=0, keepdims=True),
                               z[:, at], scale_ref[...], eps))
    o_ref[row, lanes_at(0, hs * dv, hs * dv)] = jnp.concatenate(o, axis=1)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _call(qkvz, ba, conv, small, live, S, tail, at, *, eps: float,
          interpret: bool):
    b = qkvz.shape[0]
    nv, dk, dv = S.shape[2:]
    taps, ch = conv.shape
    nk = (ch - nv * dv) // (2 * dk)
    hs = heads_per_step(nv, nk)
    rb = min(b, _SLOT_ROWS)
    eight = lambda bi: jax.lax.div(bi, np.int32(rb))  # noqa: E731
    slots = lambda bi, gi, at, live: (eight(bi), 0)  # noqa: E731
    whole = lambda bi, gi, at, live: (0, 0)  # noqa: E731
    rows = lambda a: pl.BlockSpec((rb, a.shape[1]), slots)  # noqa: E731
    # XLA:TPU keeps [layers, slots, 3, channels] with the three rows
    # outermost of a layer (slots x channels in whole tiles): handed over
    # in that order the swap is a relabelling, and as it is written a copy
    # of all of it at both ends of every call
    tail = jnp.swapaxes(tail, 1, 2)
    tails = pl.BlockSpec((None, taps - 1, rb, ch),
                         lambda bi, gi, at, live: (at[0], 0, eight(bi), 0))
    state = pl.BlockSpec((None, None, hs, dk, dv),
                         lambda bi, gi, at, live: (at[0], bi, gi, 0, 0))
    o_rows = jax.ShapeDtypeStruct((b, nv * dv), jnp.float32)
    o, tail, S = pl.pallas_call(
        functools.partial(_kernel, nk, eps),
        name="gdn_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nv // hs),
            in_specs=[rows(qkvz), rows(ba), pl.BlockSpec((taps, ch), whole)]
            + [pl.BlockSpec((1, a.shape[0]), whole) for a in small]
            + [tails, state],
            out_specs=[rows(o_rows), tails, state]),
        out_shape=[o_rows, jax.ShapeDtypeStruct(tail.shape, jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, jnp.float32)],
        # (operands count the prefetched scalars)
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(at, (1,)).astype(jnp.int32), live.astype(jnp.int32),
      qkvz, ba, conv, *(a[None] for a in small), tail, S)
    return o, S, jnp.swapaxes(tail, 1, 2)


def gdn_step(qkvz, ba, conv, A_log, dt_bias, scale, live, S, tail, at, *,
             eps: float, interpret: Optional[bool] = None):
    """``qkvz`` [b, q | k | v | z] and ``ba`` [b, b | a] float32, the
    in-projections' outputs at one position; ``conv`` [taps, q | k | v],
    ``A_log dt_bias`` [value heads], ``scale`` [value width], the
    layer's; ``live`` [b] bool; ``S`` [layers, b, value heads, dk, dv]
    and ``tail`` [layers, b, taps - 1, q | k | v] float32, the stacked
    states and convolution tails; ``at`` an int32 scalar (may be traced)
    → ``(o [b, value heads x dv] float32, normalised and gated: what the
    out-projection reads; S and tail with layer ``at`` advanced where
    ``live``: in place when they are donated)``.  Value head ``h`` is
    served by key head ``h // (value heads / key heads)``.  The layers
    of a stack call it with the same shapes: it is traced once a
    program."""
    if interpret is None:
        interpret = kernels.default_interpret()
    b, (nv, dk, dv), (taps, ch) = qkvz.shape[0], S.shape[2:], conv.shape
    assert S.shape[1] == tail.shape[1] == b and ba.shape == (b, 2 * nv) \
        and tail.shape[2:] == (taps - 1, ch) and (ch - nv * dv) % (2 * dk) \
        == 0 and qkvz.shape[1] == ch + nv * dv, (
            qkvz.shape, ba.shape, conv.shape, S.shape, tail.shape)
    return _call(qkvz, ba, conv, (A_log, dt_bias, scale), live, S, tail, at,
                 eps=float(eps), interpret=interpret)
