"""Pallas kernel for a prompt through a Gated DeltaNet mixer between its
two projections (``models/gated_deltanet.py``): the short convolution,
the L2 norms, the chunked gated delta rule, the output norm and the
gate.  Forward only.

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
last and written to HBM once) and reads its rows of q, k, v and z out of
the input projection's one output ``[b, s, q | k | v | z]`` where it
lies: four block specs over the same array, the column block picked in
the index map (where a width does not put every offset on a whole block,
small test shapes, the same kernel is handed XLA's slices).  On those
tiles it takes, in order: the causal depthwise convolution and its SiLU
on the raw q, k and v columns (a key head's columns need nothing of
another's; the ``taps - 1`` rows before a grid step's first wait in a
VMEM scratch from the step before, started from the state's tail), the
L2 norms of q and k, the rule, and a head's RMSNorm, scale and
``SiLU(z)`` gate on ``o`` before it is written.  HBM sees q, k, v, z, g
and beta once going in, the gated ``o`` once coming out, ``S`` at both
ends; everything else stays in VMEM.

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

The body is traced once for every shape a process meets (the call is
jitted: a stack's layers share the trace) and that is set-up time:
scalar arithmetic stays ``lax`` on constants, the chunks are one loop,
and the first grid step's ``pl.when`` is the only one.
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
L2_EPS = 1e-6
# chunks a grid step: 512 rows of q, k, v, z and o in flight, double
# buffered, are 4 MB of VMEM at the published widths, and a step's fixed
# cost is spread over 16 chunk-heads
_STEP_CHUNKS = 8
# the rows a step keeps of the one before for the convolution: a whole
# sublane tile, of which the last ``taps - 1`` are read
_KEPT = 8
# m^2, m^4, ... m^(CHUNK / 2): L^CHUNK = 0
_SQUARINGS = CHUNK.bit_length() - 2
_PREC = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_PREC,
                               preferred_element_type=jnp.float32)


# The mixer's pointwise stages, written once: the kernel applies them to
# its tiles, ``models/gated_deltanet.py`` to the one position of a
# decode step.

def conv_taps(windows, weights):
    """A causal depthwise convolution from its input as each tap sees it
    (``windows[j]``: shifted so that a row holds the position ``taps - 1
    - j`` before it) and the taps' ``weights``, both in the taps' order."""
    return sum(x * w for x, w in zip(windows, weights))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gated_rmsnorm(o, z, scale, eps):
    """RMSNorm over a head's width first, the gate ``SiLU(z)`` after."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * scale.astype(jnp.float32) * jax.nn.silu(z.reshape(o.shape))


def _kernel(r, dk, dv, eps, q_ref, k_ref, v_ref, z_ref, tq_ref, tk_ref,
            tv_ref, wq_ref, wk_ref, wv_ref, g_ref, beta_ref, scale_ref,
            s0_ref, o_ref, s_ref, pq_ref, pk_ref, pv_ref):
    """One key head's ``r`` value heads over ``g_ref.shape[0]`` chunks.
    ``q_ref k_ref`` [rows, dk] and ``v_ref z_ref o_ref`` [rows, r dv]:
    the projection's raw columns; ``t*_ref`` [_KEPT, width]: the state's
    tail of q's, k's and v's columns in its last rows; ``w*_ref`` [taps,
    width]; ``g_ref beta_ref`` [chunks, 1, r CHUNK] (a chunk's values,
    head by head, along the lanes); ``scale_ref`` [1, dv]; ``s0_ref
    s_ref`` [r, dk, dv]; ``p*_ref`` [_KEPT, width] scratch: the raw rows
    before this step's first."""
    c = CHUNK
    raw = ((q_ref, wq_ref, pq_ref), (k_ref, wk_ref, pk_ref),
           (v_ref, wv_ref, pv_ref))

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_ref[...] = s0_ref[...]
        for (_, _, kept), tail in zip(raw, (tq_ref, tk_ref, tv_ref)):
            kept[...] = tail[...]

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

    def convolved(x, before, w_ref):
        """``x`` [c, width] raw rows, ``before`` the ``_KEPT`` raw rows
        ahead of them: a tap's window is the two rolled down the
        sublanes by its reach."""
        taps = w_ref.shape[0]
        both = jnp.concatenate([before, x], axis=0)
        return jax.nn.silu(conv_taps(
            (pltpu.roll(both, taps - 1 - j, 0)[_KEPT:] if j < taps - 1
             else x for j in range(taps)),
            (w_ref[j:j + 1, :] for j in range(taps))))

    def chunk(i, before):
        rows = pl.ds(pl.multiple_of(i * c, c), c)
        fresh = [x_ref[rows, :] for x_ref, _, _ in raw]
        q, k, v = (convolved(x, kept, w_ref)
                   for x, kept, (_, w_ref, _) in zip(fresh, before, raw))
        q, k = l2norm(q) * dk ** -0.5, l2norm(k)
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
            [v[:, h * dv:(h + 1) * dv] * beta[h] for h in heads], axis=0)
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
            at, width = slice(h * c, (h + 1) * c), pl.ds(h * dv, dv)
            o_ref[rows, width] = gated_rmsnorm(
                q_S[h] + o[at], z_ref[rows, width], scale_ref[...], eps)
            g_end = G[h][c - 1:, :]
            s_ref[h] = s_ref[h] * jnp.exp(g_end) + _dot(
                k * jnp.exp(g_end - G[h]), d[h], _TN)
        return [x[c - _KEPT:] for x in fresh]

    # unrolled: what a chunk computes before it meets the state (most of
    # it) does not wait for the chunk before
    last = jax.lax.fori_loop(0, g_ref.shape[0], chunk,
                             [kept[...] for _, _, kept in raw], unroll=True)
    for (_, _, kept), x in zip(raw, last):
        kept[...] = x


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _call(qkvz, g, beta, S, tail, conv_w, scale, *, eps, interpret):
    b, s, _ = qkvz.shape
    _, nv, dk, dv = S.shape
    nk = (qkvz.shape[2] - 2 * nv * dv) // (2 * dk)
    taps, n, c, r = conv_w.shape[0], s // CHUNK, CHUNK, nv // nk
    assert (nv == r * nk and s == n * c and taps - 1 <= _KEPT
            and tail.shape == (b, taps - 1, 2 * nk * dk + nv * dv)), (
        qkvz.shape, S.shape, tail.shape, conv_w.shape)
    per = max(i for i in range(1, _STEP_CHUNKS + 1) if n % i == 0)
    tail = jnp.pad(tail, [(0, 0), (_KEPT - (taps - 1), 0), (0, 0)])
    # q | k | v | z, as (first column, a key head's width)
    parts = ((0, dk), (nk * dk, dk), (2 * nk * dk, r * dv),
             (2 * nk * dk + nv * dv, r * dv))
    in_place = all(at % width == 0 for at, width in parts)

    def part_of(x, part, block, index):
        """``x``'s columns of ``part`` as an operand and the spec of a
        key head's block of them: ``x`` itself where the parts start on
        whole blocks, else XLA's slice of it."""
        at, width = parts[part]
        if not in_place:
            x, at = x[..., at:at + nk * width], 0
        return x, pl.BlockSpec(
            block + (width,),
            lambda bi, hi, ti: index(bi, ti) + (at // width + hi,))

    def lanes(x):       # [b, s, nv] -> [b, nk, n, 1, r c]
        x = x.reshape(b, n, c, nk, r).transpose(0, 3, 1, 4, 2)
        return x.reshape(b, nk, n, 1, r * c)

    small = pl.BlockSpec((None, None, per, 1, r * c),
                         lambda bi, hi, ti: (bi, hi, ti, 0, 0))
    state = pl.BlockSpec((None, r, dk, dv), lambda bi, hi, ti: (bi, hi, 0, 0))
    out = pl.BlockSpec((None, per * c, r * dv),
                       lambda bi, hi, ti: (bi, ti, hi))
    operands, specs = zip(
        *(part_of(qkvz, i, (None, per * c), lambda bi, ti: (bi, ti))
          for i in range(4)),
        *(part_of(tail, i, (None, _KEPT), lambda bi, ti: (bi, 0))
          for i in range(3)),
        *(part_of(conv_w, i, (taps,), lambda bi, ti: (0,))
          for i in range(3)),
        (lanes(g), small), (lanes(beta), small),
        (scale.reshape(1, dv), pl.BlockSpec((1, dv), lambda *_: (0, 0))),
        (S, state))
    return pl.pallas_call(
        functools.partial(_kernel, r, dk, dv, eps),
        name="gdn_scan",
        grid=(b, nk, n // per),
        in_specs=list(specs),
        out_specs=[out, state],
        out_shape=[jax.ShapeDtypeStruct((b, s, nv * dv), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_KEPT, width), jnp.float32)
                        for _, width in parts[:3]],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


def gdn_scan(qkvz, g, beta, S, tail, conv_w, scale, eps: float,
             interpret: Optional[bool] = None):
    """``qkvz`` [b, s, q | k | v | z] (key heads x dk twice, value heads
    x dv twice: the input projection's output, raw), ``g beta`` [b, s,
    value heads], ``S`` [b, value heads, dk, dv], ``tail`` [b, taps - 1,
    q | k | v] (the raw rows before the first), ``conv_w`` [taps, q | k |
    v], ``scale`` [dv], float32, ``s`` a multiple of ``CHUNK``, value
    head ``h`` served by key head ``h // (value heads / key heads)`` →
    ``(o [b, s, value heads x dv], normalised and gated, S)``.  A
    position with ``beta = g = 0`` changes ``S`` by nothing."""
    if interpret is None:
        interpret = kernels.default_interpret()
    return _call(qkvz, g, beta, S, tail, conv_w, scale, eps=float(eps),
                 interpret=interpret)
