"""Pallas kernel for the chunked gated delta rule (a prompt through a
Gated DeltaNet layer, ``models/gated_deltanet.py``).  Forward only.

The rule, a position at a time, a value head::

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t (x) d_t
    o_t = S^T q_t

``CHUNK`` positions at a time (the WY form of "Gated Delta Networks",
arXiv 2412.06464, section 3) the ``d_t`` of a chunk solve ``(I + L) D =
beta V - (beta K e^G) S0``, ``L`` strictly lower triangular with ``L_ij =
beta_i (k_i . k_j) e^{G_i - G_j}`` and ``G`` the running sum of ``g``
inside the chunk.  ``L`` is nilpotent, so ``(I + L)^-1 = prod_m (I +
(-L)^(2^m))``: squarings and products of ``CHUNK``-square matrices, no
row-by-row substitution.

The grid walks (batch, key head) in parallel and a prompt's chunks in
order.  A step holds the state of the value heads its key head serves in
VMEM (the output block of ``S``, resident from the first chunk to the
last and written to HBM once), reads q, k, v as rows of ``[b, s, heads x
width]`` where the projection left them, and writes o the same way: HBM
sees q, k, v, g, beta once going in, o once coming out, ``S`` at both
ends.  Everything the rule computes inside a chunk stays in VMEM.

Everything is float32 and every product is taken at
``Precision.HIGHEST``: the rule takes differences of near-equal
quantities (``v - S^T k``) and hands a rounding of its input on three
times as large (PERF.md, PR 35).

The value heads of one key head are worked on side by side.  Their
``CHUNK``-square matrices lie along the lanes, ``[CHUNK, heads x CHUNK]``,
and a product of each with its own right-hand side is one product with
the block diagonal of them all: with two value heads a key head (the
published model) that is one full 128-wide pass of the matrix unit for
both in place of a quarter-filled one each.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels

CHUNK = 64
# chunks a grid step: 512 rows of q, k, v and o in flight, double
# buffered, are 3 MB of VMEM at the published widths, and a step's fixed
# cost is spread over 16 chunk-heads
_STEP_CHUNKS = 8
# m^2, m^4, ... m^(CHUNK / 2): L^CHUNK = 0
_SQUARINGS = CHUNK.bit_length() - 2
_PREC = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_PREC,
                               preferred_element_type=jnp.float32)


def _kernel(r, dk, dv, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
            o_ref, s_ref):
    """One key head's ``r`` value heads over ``g_ref.shape[0]`` chunks.
    ``q_ref k_ref`` [rows, dk], ``v_ref o_ref`` [rows, r dv], ``g_ref
    beta_ref`` [chunks, 1, r CHUNK] (a chunk's values, head by head, along
    the lanes), ``s0_ref s_ref`` [r, dk, dv]."""
    c = CHUNK

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[...] = s0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (c, r * c), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, r * c), 1)
    head, col = jax.lax.div(lane, c), jax.lax.rem(lane, c)
    eye, lower, strict = row == col, row >= col, row > col
    heads = range(r)

    def of_head(x, h):      # the lanes of head h, zeros elsewhere
        return x if r == 1 else jnp.where(head == h, x, 0.0)

    def block_diag(x):      # [c, r c] -> [r c, r c]
        return jnp.concatenate([of_head(x, h) for h in heads], axis=0)

    def columns(x, mask):   # [1, r c] -> r x [c, 1]: sums over mask's lanes
        return [jnp.sum(jnp.where(mask & (head == h), x, 0.0), axis=1,
                        keepdims=True) for h in heads]

    def along_lanes(cols):  # r x [c, 1] -> [c, r c], head h's in its lanes
        out = jnp.broadcast_to(cols[0], (c, r * c))
        for h in heads[1:]:
            out = jnp.where(head == h, cols[h], out)
        return out

    def chunk(i, carry):
        rows = pl.ds(pl.multiple_of(i * c, c), c)
        q, k = q_ref[rows, :], k_ref[rows, :]
        # the running sum of g as columns, and from the same numbers as a
        # row (its diagonal), so that the decay of a position to itself
        # is exactly 1
        G = columns(g_ref[i], lower)
        beta = columns(beta_ref[i], eye)
        G_cols = along_lanes(G)
        G_row = jnp.sum(jnp.where(eye, G_cols, 0.0), axis=0, keepdims=True)
        decay = jnp.exp(jnp.where(lower, G_cols - G_row, -jnp.inf))
        # K K^T and Q K^T, one below the other, once for all the heads
        kq = _dot(jnp.concatenate([k, q], axis=0),
                  jnp.concatenate([k] * r, axis=0), _NT)    # [2 c, r c]
        kk, qk = kq[:c], kq[c:] * decay
        # (I + L)^-1 = (I + m)(I + m^2)(I + m^4)..., m = -L
        m = jnp.where(strict, -(kk * along_lanes(beta)) * decay, 0.0)
        t = jnp.where(eye, 1.0, m)
        m = _dot(m, block_diag(m))
        for _ in range(_SQUARINGS - 1):
            both = _dot(jnp.concatenate([t, m], axis=0), block_diag(m))
            t, m = t + both[:c], both[c:]
        t = t + _dot(t, block_diag(m))
        e_G = [jnp.exp(G[h]) for h in heads]
        vb = jnp.concatenate(
            [v_ref[rows, pl.ds(h * dv, dv)] * beta[h] for h in heads], axis=0)
        kb = jnp.concatenate(
            [k * (beta[h] * e_G[h]) for h in heads], axis=0)
        # the heads' rows one below the other: t_h (u_h | w_h) for every h
        # is one product with the block diagonal of t
        uw = _dot(block_diag(t), jnp.concatenate([vb, kb], axis=1))
        u, w = uw[:, :dv], uw[:, dv:]
        d, q_S = [], []
        for h in heads:
            at = slice(h * c, (h + 1) * c)
            both = _dot(jnp.concatenate([w[at], q * e_G[h]], axis=0),
                        s_ref[h])
            d.append(u[at] - both[:c])
            q_S.append(both[c:])
        o = _dot(block_diag(qk), jnp.concatenate(d, axis=0))
        for h in heads:
            at = slice(h * c, (h + 1) * c)
            o_ref[rows, pl.ds(h * dv, dv)] = q_S[h] + o[at]
            g_end = G[h][c - 1:, :]
            s_ref[h] = s_ref[h] * jnp.exp(g_end) + _dot(
                k * jnp.exp(g_end - G[h]), d[h], _TN)
        return carry

    # unrolled: what a chunk computes before it meets the state (most of
    # it) does not wait for the chunk before
    jax.lax.fori_loop(0, g_ref.shape[0], chunk, 0, unroll=True)


def gdn_scan(q, k, v, g, beta, S, interpret: Optional[bool] = None):
    """``q k`` [b, s, key heads x dk], ``v`` [b, s, value heads x dv], ``g
    beta`` [b, s, value heads], ``S`` [b, value heads, dk, dv], float32,
    ``s`` a multiple of ``CHUNK``, value head ``h`` served by key head
    ``h // (value heads / key heads)`` → ``(o [b, s, value heads x dv],
    S)``.  A position with ``beta = g = 0`` changes nothing."""
    if interpret is None:
        interpret = kernels.default_interpret()
    b, s, _ = q.shape
    _, nv, dk, dv = S.shape
    nk, n, c = q.shape[2] // dk, s // CHUNK, CHUNK
    r = nv // nk
    assert nv == r * nk and s == n * c and v.shape[2] == nv * dv, (
        q.shape, v.shape, S.shape)
    per = max(i for i in range(1, _STEP_CHUNKS + 1) if n % i == 0)

    def lanes(x):       # [b, s, nv] -> [b, nk, n, 1, r c]
        x = x.reshape(b, n, c, nk, r).transpose(0, 3, 1, 4, 2)
        return x.reshape(b, nk, n, 1, r * c)

    def rows(width):
        return pl.BlockSpec((None, per * c, width),
                            lambda bi, hi, ti: (bi, ti, hi))

    small = pl.BlockSpec((None, None, per, 1, r * c),
                         lambda bi, hi, ti: (bi, hi, ti, 0, 0))
    state = pl.BlockSpec((None, r, dk, dv), lambda bi, hi, ti: (bi, hi, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, r, dk, dv),
        name="gdn_scan",
        grid=(b, nk, n // per),
        in_specs=[rows(dk), rows(dk), rows(r * dv), small, small, state],
        out_specs=[rows(r * dv), state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, lanes(g), lanes(beta), S)
