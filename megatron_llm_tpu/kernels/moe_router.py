"""Pallas kernel for a router's choice: the ``k`` largest of a token's
scores, in order, and how many counted tokens chose each expert
(``models/moe.py:moe_dropless_block``).

``jax.lax.top_k`` over a few hundred scores is a sort on this backend,
the un-biased scores under the choice a gather and the engine's load
counter a scatter-add of rows x k single elements.  Here the scores are
read once, into VMEM, and the choice is **k rounds of max-and-mask** on
the tile where it lies: a round takes every token's largest score, the
lowest index that holds it (so ties go to the lower index, as
``lax.top_k`` orders them), marks that entry and masks it out for the
next round.  The rounds' indices and values in order are ``lax.top_k``'s,
element for element; where the choice is made on ``score + bias`` the
value is the un-biased score under the round's mark (a sum of one score
and zeros: exact); after the rounds the masked entries ARE the choice, so
the load is ``counted`` summed over the tokens of that mask (whole
numbers in float32: exact in any order).

**Where the operands lie.**  The tile is ``[experts, tokens]``, experts
down the sublanes and tokens along the lanes: a round's two reductions
run down the sublanes, elementwise over whole registers with one
reduction inside the last, and nothing crosses the lanes; the rounds'
results are whole rows ``[1, tokens]``, stored at the round's row of the
``[k, tokens]`` outputs.  The caller's ``[tokens, experts]`` scores are
transposed on the way in and the ``[k, tokens]`` results on the way out,
by XLA.  The load goes out as ``[experts, lanes]``, a token's lane its
place in a tile, accumulated over the grid in the output's one block and
summed over the lanes outside.  A last tile that reaches past the tokens
holds whatever the copy left there: a token is a lane, so nothing of it
reaches another token, its rows of the outputs are never stored and its
lanes are left out of the load by their index.

The body is one round in a ``fori_loop`` (traced once, whatever k) and no
``pl.when``: a kernel's trace is set-up time (PERF.md, PR 42).
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

# tokens a grid step: 512 experts of 11 264 tokens at top 10 take 0.179 /
# 0.171 / 0.140 / 0.132 ms at 128 / 256 / 512 / 1024, of 1280 tokens at
# top 22 0.058 / 0.044 / 0.043 / 0.061 (PERF.md, PR 53)
_TILE = 512
_LANES = 128


def _kernel(k, tokens, biased, *refs):
    """One grid step: a tile of tokens.  ``score_ref`` [experts, tile];
    ``bias_ref`` [experts, 1] where ``biased``; ``counted_ref`` [1, tile];
    ``idx_ref w_ref`` [k, tile]; ``load_ref`` [experts, lanes], the same
    block every step; ``s_ref`` [experts, tile], the scores the rounds
    mask."""
    if biased:
        score_ref, bias_ref, counted_ref, idx_ref, w_ref, load_ref, s_ref = \
            refs
    else:
        score_ref, counted_ref, idx_ref, w_ref, load_ref, s_ref = refs
    R, tile = score_ref.shape
    f32 = jnp.float32
    # (an expert's index as a float: whole numbers, exact far past any
    # router's width, and the reductions stay float32's)
    expert = jax.lax.broadcasted_iota(jnp.int32, (R, tile), 0).astype(f32)
    s_ref[...] = score_ref[...] + bias_ref[...] if biased else score_ref[...]

    def one_round(j, carry):
        s = s_ref[...]
        top = jnp.max(s, axis=0, keepdims=True)
        at = jnp.min(jnp.where(s == top, expert, f32(R)), axis=0,
                     keepdims=True)
        hit = expert == at
        s_ref[...] = jnp.where(hit, -jnp.inf, s)
        idx_ref[pl.ds(j, 1), :] = at.astype(jnp.int32)
        w_ref[pl.ds(j, 1), :] = jnp.sum(
            jnp.where(hit, score_ref[...], 0.0), axis=0,
            keepdims=True) if biased else top
        return carry

    jax.lax.fori_loop(0, k, one_round, 0)
    step = pl.program_id(0)
    token = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) \
        + step * np.int32(tile)
    mine = jnp.where((s_ref[...] == -jnp.inf) & (token < tokens),
                     counted_ref[...], 0.0)
    lanes = load_ref.shape[1]
    folded = mine[:, :lanes]
    for t in range(1, tile // lanes):
        folded = folded + mine[:, t * lanes:(t + 1) * lanes]
    load_ref[...] = jnp.where(step == 0, 0.0, load_ref[...]) + folded


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _call(score, bias, counted, *, k: int, interpret: bool):
    tokens, R = score.shape
    # whole lane tiles, or all of a call of fewer tokens than one
    tile = tokens if tokens <= _LANES else min(
        _TILE, tokens // _LANES * _LANES)
    lanes = min(tile, _LANES)
    at = lambda i: (0, i)  # noqa: E731
    whole = lambda i: (0, 0)  # noqa: E731
    operands = [score.T, counted[None]]
    in_specs = [pl.BlockSpec((R, tile), at), pl.BlockSpec((1, tile), at)]
    # tpulint: allow[tracer-leak] an array or None, no traced value
    if bias is not None:
        operands.insert(1, bias[:, None])
        in_specs.insert(1, pl.BlockSpec((R, 1), whole))
    idx, weight, load = pl.pallas_call(
        functools.partial(_kernel, k, tokens, bias is not None),
        name="moe_router",
        grid=(pl.cdiv(tokens, tile),),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((k, tile), at), pl.BlockSpec((k, tile), at),
                   pl.BlockSpec((R, lanes), whole)],
        out_shape=[jax.ShapeDtypeStruct((k, tokens), jnp.int32),
                   jax.ShapeDtypeStruct((k, tokens), jnp.float32),
                   jax.ShapeDtypeStruct((R, lanes), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((R, tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)
    return idx.T, weight.T, load.sum(axis=1)


def router_top_k(score, bias, counted, k: int, *,
                 interpret: Optional[bool] = None):
    """``score`` [tokens, experts] float32, finite; ``bias`` [experts]
    float32 or None; ``counted`` [tokens] float32 → ``(idx [tokens, k]
    int32, value [tokens, k] float32, load [experts] float32)``: the
    experts of the ``k`` largest of ``score + bias`` (of ``score`` where
    there is no bias) in falling order, ties to the lower index, as
    ``jax.lax.top_k`` gives them; ``score`` at those experts; and
    ``counted`` summed over the tokens that chose an expert."""
    if interpret is None:
        interpret = kernels.default_interpret()
    assert score.ndim == 2 and score.dtype == jnp.float32 \
        and counted.shape == score.shape[:1] and 0 < k <= score.shape[1], (
            score.shape, score.dtype, counted.shape, k)
    if bias is not None:
        bias = bias.astype(jnp.float32)
    return _call(score, bias, counted, k=k, interpret=interpret)
