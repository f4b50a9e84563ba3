"""Pallas kernel for a grouped MLP: the rows each expert was given, through
that expert's two (or three) matrices, in one pass over the experts.

The caller sorts its ``(token, choice)`` pairs by expert and hands over the
sorted pairs, the group sizes and the tokens' rows where they lie.  The
grid visits the experts that have a row, in order, one a step, so an
expert's matrices are a block that changes only when the expert does: each
is read from HBM once a call and an expert without a row is never read.
Inside a step a loop, whose trip count comes from the group's size, takes
the group's rows a tile at a time.  The tiles follow the groups (a tile
starts where its group starts and belongs to one expert), and for a tile
the kernel

* copies the tile's rows from ``x`` in HBM into VMEM itself, a copy a
  row, by the token number of its sorted pair (``[pairs, h]`` is never
  written), the next tile's copies in flight while this tile is
  multiplied;
* takes both products of the gated form on the one tile, float32
  accumulators, applies the activation to the accumulators and rounds
  ``hidden`` once, for the down product (it never leaves VMEM);
* copies each finished row to the place of its pair in the output, which
  is in (token, choice) order: no inverse permutation after.

A tile's copies are started and waited for in whole units (16 rows of a
128-row tile, 4 of a 16-row one): past its live rows a partly filled
tile fetches its last live row again and writes that row's result once
more to the place it has, the same bytes, so the copies a call makes are
twice the pairs that are in a group and a few more, nothing a group does
not own is ever written, and neither the copies nor their waits need a
branch (a loop with a trip count each: a ``pl.when`` costs a start 20 ms
of tracing on the sealed machine, and this body is traced once a
program).  Pairs in no group (the sorted order's tail: choices of experts
that are not here) are never visited: their output rows stay as they were
allocated, and the caller replaces them (``jnp.where``), it does not
multiply them by zero.

A row copy moves whole (8, 128) tiles of 32-bit words (Mosaic slices a
tiled array no finer), so ``x`` and the output are handed over as
``[rows x h / 128, 128]`` float32: a row is ``h / 128`` consecutive
sublane rows (whole tiles where ``h`` is a multiple of 1024; a strided
read wants the 128 lanes), 8 KiB at the published width, and the kernel
reads a tile's rows back with a stride.

Measured alone on a v5e (PR 42; PERF.md section 6), Qwen3-Next's 256 held
experts of 2048 x 512, ten choices a token, half of them held: a
10 240-token prompt (51 393 held rows) 7.0 ms a call, of it 3.6 ms the
products at 128 rows a tile and the rest starting some 107 000 row
copies (~27 ns each whatever their size, and not hidden by the
products: the scalar unit starts them between two tiles' products) and
waiting for them by units; a 44-slot decode step (216 held rows over
~150 experts) 1.27 ms, 90 % of those experts' weight reads at 819 GB/s.
The three ``lax.ragged_dot`` calls this replaced took 8.8 and 1.44 ms
without the row gathers around them.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

# rows a tile.  A prompt's groups hold some hundreds of rows and the
# matrix unit wants 128 or more at a time; a decode step's hold one or
# two, and there a tile is what the copies and the unit's passes cost
# beside the expert's weight read (PERF.md, PR 42)
_TILE_ROWS = 128
_TILE_ROWS_FEW = 16
# pairs a call under which the small tile is taken: a mean of under one
# row an expert
_FEW_PAIRS_AN_EXPERT = 4
# an expert's three matrices twice (12 MiB at the published widths) and
# two tiles of rows in and out: over the 16 MiB a kernel has by default
_VMEM_LIMIT = 64 * 1024 * 1024
# row copies started an iteration of their loop.  The more, the less the
# scalar unit spends between copies (a 10 240-token prompt's call lost
# 0.5 ms from 1 to 4, 0.15 more to 8, nothing after), and the more
# operators a trace of the kernel holds, which a start pays for once a
# program (nine of them, ~1 ms an operator on the sealed machine)
_COPIES = 4


def tile_rows(pairs: int, experts: int) -> int:
    """The row tile for a call of ``pairs`` sorted pairs over ``experts``
    groups: chosen from the shapes alone."""
    if pairs < _FEW_PAIRS_AN_EXPERT * experts:
        return _TILE_ROWS_FEW
    return _TILE_ROWS


def _kernel(tm, k, act, order_ref, offs_ref, visit_ref, nvis_ref, x_hbm,
            *refs):
    """One grid step: the expert ``visit_ref[i]``'s rows, a tile at a time.
    ``order_ref`` [pairs] the sorted pairs, ``offs_ref`` [E + 1]
    where each group starts among them, ``visit_ref`` [E] the experts that
    have a row (the last of them repeated to the end), ``nvis_ref`` [1]
    how many.  ``cnt_ref``: tiles done over all steps, then the rows in
    flight out of each half of ``obuf``.

    The scalar arithmetic is plain ``lax`` on ``np.int32`` constants: an
    operator or a ``jnp`` function on a traced scalar is a jitted call.
    And the copies and their waits are loops with a trip count, no
    ``pl.when``: a ``cond`` is ~20 ms of a trace on the sealed machine,
    and a wait for ``live`` rows as copies of 2^j rows under eight of
    them, at three sites, was 0.4 s a program of every start."""
    *gate_ref, wu_ref, wd_ref, out_hbm, xbuf, obuf, gsem, ssem, cnt_ref = refs
    lax, c = jax.lax, np.int32
    i = pl.program_id(0)
    nvis = nvis_ref[0]
    steps = visit_ref.shape[0]
    L, lane = xbuf.shape[1] // tm, xbuf.shape[2]
    cbits = (k - 1).bit_length()
    # row copies are started and waited for in units of this many
    unit = max(_COPIES, tm // 8)

    def piece(ref, at):             # one row, from sublane row ``at``
        return ref.at[pl.ds(pl.multiple_of(at, L), L)]

    def units(live):
        return lax.div(lax.add(live, c(unit - 1)), c(unit))

    def copies(go, slot, row0, live):
        """Start the row copies of the tile at ``row0`` that has ``live``
        rows: into ``xbuf[slot]`` (``go`` "gather") or out of
        ``obuf[slot]``, ``_COPIES`` an iteration of the loop and whole
        units of them: past its live rows a tile copies its last live
        row again (fetched into the next row; written out once more to
        the place it has, the same bytes)."""
        buf = (xbuf if go == "gather" else obuf).at[slot]
        last = lax.sub(live, c(1))

        def bunch(b, carry):
            first = lax.mul(b, c(_COPIES))
            for u in range(_COPIES):
                r = lax.add(first, c(u))
                held = lax.min(r, last)
                pair = order_ref[lax.add(row0, held)]
                tok = lax.shift_right_logical(pair, c(cbits))
                if go == "gather":
                    pltpu.make_async_copy(piece(x_hbm, lax.mul(tok, c(L))),
                                          piece(buf, lax.mul(r, c(L))),
                                          gsem.at[slot]).start()
                else:
                    dst = lax.add(lax.mul(tok, c(k)), lax.bitwise_and(
                        pair, c((1 << cbits) - 1)))
                    pltpu.make_async_copy(piece(buf, lax.mul(held, c(L))),
                                          piece(out_hbm, lax.mul(dst, c(L))),
                                          ssem.at[slot]).start()
            return carry
        lax.fori_loop(c(0), lax.mul(units(live), c(unit // _COPIES)),
                      bunch, 0)

    def wait(go, slot, live):
        """A DMA semaphore counts bytes: a unit of row copies is waited
        for as one copy of ``unit`` rows (never started: only its size
        is read)."""
        buf, sem = (xbuf, gsem) if go == "gather" else (obuf, ssem)
        part = buf.at[slot, pl.ds(0, unit * L)]

        def one(_, carry):
            pltpu.make_async_copy(part, part, sem.at[slot]).wait()
            return carry
        lax.fori_loop(c(0), units(live), one, 0)

    def group(j):       # where the j-th visited group starts and ends
        e = visit_ref[lax.min(j, c(steps - 1))]
        return offs_ref[e], offs_ref[lax.add(e, c(1))]

    @pl.when(lax.lt(i, nvis))
    def _visit():
        # the scratch counters start at zero with the call's first step
        for j in range(3):
            cnt_ref[j] = lax.select(lax.eq(i, c(0)), c(0), cnt_ref[j])
        start, end = group(i)
        tiles = lax.div(lax.add(lax.sub(end, start), c(tm - 1)), c(tm))
        # the tile after this group's last is the next group's first
        after, after_end = group(lax.add(i, c(1)))
        has_after = lax.lt(lax.add(i, c(1)), nvis)

        def tile(j, carry):
            cnt = cnt_ref[0]
            slot = lax.rem(cnt, c(2))
            other = lax.sub(c(1), slot)
            row0 = lax.add(start, lax.mul(j, c(tm)))
            live = lax.min(c(tm), lax.sub(end, row0))
            more = lax.lt(lax.add(j, c(1)), tiles)
            nxt = lax.select(more, lax.add(row0, c(tm)), after)
            nxt_live = lax.select(
                lax.bitwise_or(more, has_after),
                lax.min(c(tm), lax.sub(lax.select(more, end, after_end),
                                       nxt)), c(0))

            def fetch(q, carry):    # q = 0: this tile; q = 1: the next
                this = lax.eq(q, c(0))
                copies("gather", lax.select(this, slot, other),
                       lax.select(this, row0, nxt),
                       lax.select(this, live, nxt_live))
                return carry

            # the next tile's rows come in while this one is multiplied;
            # a call's first tile has to fetch its own
            lax.fori_loop(lax.select(lax.eq(cnt, c(0)), c(0), c(1)), c(2),
                          fetch, 0)
            wait("gather", slot, live)
            x = jnp.concatenate(
                [xbuf[slot, pl.ds(p, tm, stride=L), :] for p in range(L)],
                axis=-1).astype(wu_ref.dtype)
            up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            for wg_ref in gate_ref:     # the gated form: [gate | up]
                gate = jnp.dot(x, wg_ref[...],
                               preferred_element_type=jnp.float32)
                up = jnp.concatenate([gate, up], axis=-1)
            y = jnp.dot(act(up).astype(wd_ref.dtype), wd_ref[...],
                        preferred_element_type=jnp.float32)
            # this half's last tile (two tiles ago) has left ``obuf``
            in_flight = lax.add(slot, c(1))
            wait("scatter", slot, cnt_ref[in_flight])
            for p in range(L):
                obuf[slot, pl.ds(p, tm, stride=L), :] = y[
                    :, p * lane:(p + 1) * lane]
            copies("scatter", slot, row0, live)
            cnt_ref[0] = lax.add(cnt, c(1))
            cnt_ref[in_flight] = live
            return carry

        lax.fori_loop(c(0), tiles, tile, 0)

    @pl.when(lax.bitwise_and(lax.eq(i, c(steps - 1)), lax.gt(nvis, c(0))))
    def _drain():
        def half(slot, carry):
            wait("scatter", slot, cnt_ref[lax.add(slot, c(1))])
            return carry
        lax.fori_loop(c(0), c(2), half, 0)


def grouped_mlp(x: jax.Array, order: jax.Array, sizes: jax.Array,
                w_gate: Optional[jax.Array], w_up: jax.Array,
                w_down: jax.Array, act: Callable, *, choices: int,
                interpret: Optional[bool] = None, layer=None) -> jax.Array:
    """``x`` [tokens, h]; ``order`` [pairs] int32, the (token, choice)
    pairs sorted by group, a pair as ``token << bits | choice`` with
    ``bits = (choices - 1).bit_length()``, the first ``sizes.sum()`` of
    them the pairs that are in a group, group by group; ``sizes`` [E]
    int32; ``w_gate w_up`` [E, h, f] (``w_gate`` None where ``act`` is no
    gated form: ``act`` takes ``[gate | up]`` along the last axis as
    ``ops/activations.py`` has it), ``w_down`` [E, f, h] → ``[tokens,
    choices, h / 128, 128]`` float32 (a row as the 128-lane pieces it is
    copied in: reshape what is made of the rows, not the rows): the row of
    a pair in group ``e`` is ``act(x[token] @ w_gate[e] | @ w_up[e]) @
    w_down[e]``, ``x`` rounded to the weights' dtype, float32
    accumulation, ``hidden`` rounded once.  **The rows of pairs in no
    group are not written**: whatever the buffer held.

    On a TPU a row is copied as whole (8, 128) tiles of 32-bit words: ``h``
    a multiple of 1024 (interpreted, any ``h``; one piece where ``h`` is
    no multiple of 128).

    With ``layer`` (an int32 scalar, traced in a layer scan) the matrices
    are those of a whole stack, ``[layers, E, ...]``, of which the kernel
    addresses layer ``layer`` through its index maps: a scan body that
    slices its layer's matrices out first makes XLA copy them, every
    expert of the layer, for the custom call (1.2 GB a layer at 128
    experts of 2048 x 768)."""
    if interpret is None:
        interpret = kernels.default_interpret()
    n = order.shape[0]
    g = x.shape[0]
    E, h, f = w_up.shape[-3:]
    tm = tile_rows(n, E)
    lane = 128 if h % 128 == 0 else h
    pieces = h // lane
    assert interpret or pieces % 8 == 0, h
    if x.dtype.itemsize != 4:
        x = x.astype(jnp.float32)

    sizes = sizes.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes)])
    nvis = jnp.sum(sizes > 0).astype(jnp.int32)
    visit = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    visit = jnp.where(jnp.arange(E) < nvis, visit,
                      visit[jnp.maximum(nvis - 1, 0)])

    weight = lambda a, b: pl.BlockSpec(    # noqa: E731
        (None, a, b), lambda i, order, offs, visit, nvis: (visit[i], 0, 0))
    body = functools.partial(_kernel, tm, choices, act)
    prefetch = (order.astype(jnp.int32), offs, visit, nvis.reshape(1))
    if layer is not None:
        # a fifth prefetched scalar, read by the index maps alone
        weight = lambda a, b: pl.BlockSpec(    # noqa: E731
            (None, None, a, b),
            lambda i, order, offs, visit, nvis, layer: (
                layer[0], visit[i], 0, 0))
        body = lambda *refs: _kernel(     # noqa: E731
            tm, choices, act, *refs[:4], *refs[5:])
        prefetch += (jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    weights = ([] if w_gate is None else [w_gate]) + [w_up, w_down]
    out = pl.pallas_call(
        body,
        # not "..._mlp": the benchmark's scope table files an operation
        # under the last scope name its path holds, as a substring
        name="grouped_experts",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E,),
            in_specs=([anywhere] + [weight(h, f)] * (len(weights) - 1)
                      + [weight(f, h)]),
            out_specs=anywhere,
            scratch_shapes=[
                pltpu.VMEM((2, tm * pieces, lane), x.dtype),
                pltpu.VMEM((2, tm * pieces, lane), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((3,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g * choices * pieces, lane),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*prefetch, x.reshape(g * pieces, lane), *weights)
    return out.reshape(g, choices, pieces, lane)
