"""Mamba-1: the S6 selective state-space mixer of a stack of runs.

A layer keeps, for each of its ``inner`` channels, a state of ``N``
columns (float32) and advances it a position at a time (Gu, Dao:
"Mamba", arXiv 2312.00752)::

    [xs | z] = u W_in;   xs <- SiLU(causal depthwise conv(xs) + bias)
    [dt_r | B_t | C_t] = xs W_x;   dt_t = softplus(dt_r W_dt + b_dt)
    h <- exp(dt_t A) * h + dt_t * xs_t * B_t;   A = -exp(A_log)
    y_t = h . C_t + D * xs_t;   out = (y * SiLU(z)) W_out

Where Mamba-2 (``models/mamba2.py``) has one decay a head, here every
channel AND state column has its own (``A`` is ``[inner, N]``), the step
``dt`` goes through a bottleneck of ``dt_rank`` columns, and there is no
norm before the output projection.  ``y`` before the gate, skip
included, is the layer's *memory*: the last such layer of a stack hands
it to the gated memory units behind it (``models/transformer.py``).

Two forms of the one recurrence, plain ``jax.numpy``: ``s6_scan`` for a
prompt, a position at a time over the whole bucket with the state as the
loop's carry, and ``s6_step`` for the one new position of a decode step
over every slot's state.  The mixer is float32 from end to end: every
product takes its float32 operand in two passes of the weights'
precision (the operand rounded to it, and what that rounding lost).  For
the two small products of the step (``W_x``, ``W_dt``:
``ops/precision.py:dot_f32``) because what ``dt`` loses to a rounding the
decay ``exp(dt A)`` compounds over every later position; for the two
large ones (``W_in``, ``W_out``: ``dot_two_pass``, both passes one
product, so a decode step reads the weight once) because they were
measured to need it: at the published widths, on the chip, the nine
mixers' one-pass projections made a third of the variance of the whole
model's log-prob gap to the float32 reference (rms 0.0279, of which
0.0197 theirs; the last mixer's output is also the memory that seven
later layers gate with), as much as all 32 MLPs (``PERF.md``, PR 56).  A position
whose ``valid`` is false (the padded tail of a prefill bucket, a decode
step's free slot) has ``dt = 0``: the decay is 1 and nothing is added,
so it changes neither the state nor the convolution's tail.

The parameters lie as the published checkpoint has them: ``w_in = [xs |
z]``, ``w_x = [dt | B | C]``, ``A_log`` and ``D`` float32.  The state is
kept ``[b, N, inner]``: the channels along the lanes, a state column a
sublane, so neither a tile nor a vector register is padded.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..ops.precision import dot_f32

Params = dict

# the step's initialisation (Mamba's): dt log-uniform in [DT_MIN, DT_MAX],
# not under DT_FLOOR
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4

# under these names the serving state tree keeps ``Mamba1State``'s two
# arrays, stacked over the Mamba-1 layers (models/model.py:init_rec_state)
STATE_NAMES = ("ssm1", "ssm1_conv")

# positions a trip of the prompt form's loop
SCAN_UNROLL = 8


class Mamba1State(NamedTuple):
    """What a Mamba-1 layer keeps of a sequence: ``S`` [b, N, inner] and
    ``conv``, the convolution's last ``taps - 1`` inputs, oldest first,
    flat [b, (taps - 1) x inner] (three rows would be padded to a tile of
    eight once stacked); both float32."""

    S: jax.Array
    conv: jax.Array


def init_mamba1_params(key: jax.Array, cfg: ModelConfig) -> Params:
    h, dtype, std = cfg.hidden_size, cfg.dtype, cfg.init_method_std
    di, N, R = cfg.mamba1_inner, cfg.mamba1_state_size, cfg.mamba1_dt_rank
    taps = cfg.mamba1_conv_kernel
    ks = jax.random.split(key, 7)

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    # A = -(1..N) a channel; b_dt the inverse softplus of the step, so that
    # a channel's memory spans from about ten positions to about a
    # thousand; W_dt uniform in +-dt_rank^-1/2; D (the skip) 1
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        ks[4], (di,), jnp.float32, math.log(DT_MIN), math.log(DT_MAX))),
        DT_FLOOR)
    bound = 1.0 / math.sqrt(taps)      # a depthwise Conv1d's default
    return {
        "w_in": normal(ks[0], (h, 2 * di)),
        "conv": jax.random.uniform(ks[1], (taps, di), jnp.float32,
                                   -bound, bound).astype(dtype),
        "conv_bias": jax.random.uniform(ks[2], (di,), jnp.float32,
                                        -bound, bound).astype(dtype),
        "w_x": normal(ks[3], (di, R + 2 * N)),
        "w_dt": jax.random.uniform(ks[5], (R, di), jnp.float32,
                                   -R ** -0.5, R ** -0.5).astype(dtype),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (di, N)),
        "D": jnp.ones((di,), jnp.float32),
        "w_out": normal(ks[6], (di, h)),
    }


def init_state(cfg: ModelConfig, batch: int) -> Mamba1State:
    """A sequence's start."""
    di, N = cfg.mamba1_inner, cfg.mamba1_state_size
    return Mamba1State(
        jnp.zeros((batch, N, di), jnp.float32),
        jnp.zeros((batch, (cfg.mamba1_conv_kernel - 1) * di), jnp.float32))


def dot_two_pass(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` -> float32 for a float32 ``x`` [..., k] and a weight in a
    lower precision, as ``dot_f32`` (``x`` rounded to the weight's
    precision, and what that rounding lost) but as ONE product over the
    two parts side by side: the weight is read once, which is what a
    decode step is bound by."""
    if x.dtype == w.dtype:
        return jnp.dot(x, w, preferred_element_type=jnp.float32)
    kind = jnp.finfo(w.dtype)
    hi = jax.lax.reduce_precision(x, kind.nexp, kind.nmant)
    both = jnp.stack([hi, x - hi]).astype(w.dtype)
    return jnp.sum(jnp.dot(both, w, preferred_element_type=jnp.float32),
                   axis=0)


def _selection(p: Params, xs, valid):
    """``xs`` [..., inner] float32 → ``(dt [..., inner], B, C [..., N])``;
    ``dt`` is 0 where ``valid`` [...] is false."""
    N = p["A_log"].shape[1]
    R = p["w_dt"].shape[0]
    sel = dot_f32(xs, p["w_x"])
    dt = jax.nn.softplus(dot_f32(sel[..., :R], p["w_dt"]) + p["dt_bias"])
    return (dt * valid[..., None], sel[..., R:R + N], sel[..., R + N:])


@jax.named_scope("mamba1_scan")
def s6_scan(xs, dt, B, C, A, D, S):
    """The recurrence over a prompt, a position at a time.  ``xs dt`` [b,
    s, inner], ``B C`` [b, s, N], ``A`` [N, inner] (negative), ``D``
    [inner], ``S`` [b, N, inner], float32 → ``(y [b, s, inner], S)``."""
    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t[:, None, :] * A) * S \
            + (dt_t * x_t)[:, None, :] * B_t
        return S, jnp.sum(S * C_t, axis=1)

    lead = lambda a: jnp.moveaxis(a, 1, 0)        # noqa: E731
    S, y = jax.lax.scan(
        step, S, (lead(xs), lead(dt), lead(B)[..., None],
                  lead(C)[..., None]),
        unroll=min(SCAN_UNROLL, xs.shape[1]))
    return lead(y) + D * xs, S


@jax.named_scope("mamba1_step")
def s6_step(p: Params, zx, live, state: Mamba1State):
    """One position between the two projections.  ``zx`` [b, xs | z]
    float32, ``live`` [b] bool → ``(y before the gate [b, inner], z, the
    state advanced where ``live``)``."""
    b, di = zx.shape[0], zx.shape[1] // 2
    taps = p["conv"].shape[0]
    x_new, z = zx[:, :di], zx[:, di:]
    window = jnp.concatenate([state.conv.reshape(b, taps - 1, di),
                              x_new[:, None]], axis=1)
    xs = jax.nn.silu(jnp.sum(window * p["conv"].astype(jnp.float32), axis=1)
                     + p["conv_bias"].astype(jnp.float32))
    tail = jnp.where(live[:, None], window[:, 1:].reshape(b, -1), state.conv)
    dt, B, C = _selection(p, xs, live)
    A = -jnp.exp(p["A_log"]).T
    S = jnp.exp(dt[:, None, :] * A) * state.S \
        + (dt * xs)[:, None, :] * B[:, :, None]
    y = jnp.sum(S * C[:, :, None], axis=1) + p["D"] * xs
    return y, z, Mamba1State(S, tail)


def _prompt(p: Params, zx, state: Mamba1State, valid):
    """A prompt between the two projections: the convolution, the
    selection, the recurrence and the skip."""
    b, s, _ = zx.shape
    di = zx.shape[2] // 2
    taps = p["conv"].shape[0]
    mixed, z = zx[..., :di], zx[..., di:]
    with jax.named_scope("mamba1_conv"):
        full = jnp.concatenate([state.conv.reshape(b, taps - 1, di), mixed],
                               axis=1)
        w = p["conv"].astype(jnp.float32)
        xs = jax.nn.silu(sum(full[:, j:j + s] * w[j] for j in range(taps))
                         + p["conv_bias"].astype(jnp.float32))
        lengths = jnp.sum(valid, axis=1, dtype=jnp.int32)
        tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
            f, n, taps - 1, axis=0))(full, lengths).reshape(b, -1)
    dt, B, C = _selection(p, xs, valid)
    y, S = s6_scan(xs, dt, B, C, -jnp.exp(p["A_log"]).T, p["D"], state.S)
    return y, z, Mamba1State(S, tail)


def memory_of(y, z):
    """What a layer hands to the gated memory units behind it: its
    recurrence's output BEFORE the gate ``SiLU(z)``, skip included."""
    del z
    return y


@jax.named_scope("mamba1")
def mamba1_block(cfg: ModelConfig, p: Params, x: jax.Array,
                 state: Optional[Mamba1State] = None,
                 valid: Optional[jax.Array] = None):
    """The mixer over ``x`` [b, s, h] continuing ``state`` (None: the
    start of a sequence) → ``(out [b, s, h], the state after each row's
    valid positions, the memory [b, s, inner]: y before the gate)``.
    ``valid`` [b, s] bool marks the positions that are there, a prefix of
    each row (None: all)."""
    b, s, _ = x.shape
    if state is None:
        state = init_state(cfg, b)
    if valid is None:
        valid = jnp.ones((b, s), bool)
    with jax.named_scope("mamba1_proj"):
        zx = dot_two_pass(x, p["w_in"])
    if s == 1:
        y, z, state = s6_step(p, zx[:, 0], valid[:, 0], state)
        y, z = y[:, None], z[:, None]
    else:
        y, z, state = _prompt(p, zx, state, valid)
    with jax.named_scope("mamba1_proj"):
        out = dot_two_pass(y * jax.nn.silu(z), p["w_out"]).astype(x.dtype)
    return out, state, memory_of(y, z)
