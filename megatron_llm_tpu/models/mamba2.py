"""Mamba-2: the selective state-space mixer of a hybrid stack.

A layer keeps, for each of its heads, a matrix state ``S`` (head width x
state width, float32) in place of keys and values, and advances it a
position at a time (Dao, Gu: "Transformers are SSMs", arXiv 2405.21060)::

    S <- a_t S + dt_t x_t (x) B_t;   y_t = S C_t + D x_t
    dt_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) dt_t)

with ``x | B | C`` first passed through a short causal depthwise
convolution (with a bias) and SiLU, ``B`` and ``C`` shared by the heads of
a group, ``a`` and ``dt`` scalars a head.  The output is gated by
``SiLU(z)``, RMS-normalised over each group's channels and projected back.

Two forms of the same recurrence: ``ssd_step`` for the one new position
of a decode step (the kernel ``kernels/mamba_step.py``, which is the whole
mixer between its two projections: a step reads and writes every live
slot's state and tail once, where they lie in the stacked states of the
serving tree, and is bound by that traffic, not by arithmetic), and
``ssd_chunked`` for a prompt, which rearranges ``chunk``
positions at a time into matrix products (the paper's state-space
duality, section 6): inside a chunk ``(C B^T . L)(dt x)`` with ``L`` the
lower-triangular products of ``a``, the chunk's own end state from
``B^T (dt x)`` decayed to the chunk's end, ``C S_prev`` decayed for what
the earlier chunks left, and the state handed from chunk to chunk: plain
``jax.numpy``.  The state, the decays and everything after
the input projection are float32.  A position whose ``valid`` is false
(the padded tail of a prefill bucket, a decode step's free slot) has
``dt = 0``: ``a = 1`` and nothing is added, so it changes neither ``S``
nor the convolution's tail, whatever it holds.

The fused input projection is laid out ``[z | x | B | C | dt]`` (inner
width, inner width, groups x state width twice, heads), as the published
checkpoint has it.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..kernels.mamba_step import mamba_step
from ..ops.precision import dot_rounded

Params = dict

# the step's initialisation (Mamba-2's): dt log-uniform in [DT_MIN,
# DT_MAX], not under DT_FLOOR
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


# under these names the serving state tree keeps ``MambaState``'s two
# arrays, stacked over the Mamba-2 layers (models/model.py:init_rec_state)
STATE_NAMES = ("ssm", "ssm_conv")


class MambaState(NamedTuple):
    """What a Mamba-2 layer keeps of a sequence: ``S`` [b, heads, head
    width, state width], and ``conv``: the convolution's last ``taps - 1``
    inputs, oldest first, [b, taps - 1, channels] or, where the stack's
    scan has more than one period, flat [b, (taps - 1) x channels]
    (``init_state``); both float32.  With ``at`` (an int32
    scalar, may be traced) ``S`` and ``conv`` are the stacked states and
    tails of all the layers, [layers, b, ...], and this layer's are
    ``S[at]`` and ``conv[at]``: how a decode step hands them through,
    since its kernel advances the layer where it lies."""

    S: jax.Array
    conv: jax.Array
    at: Optional[jax.Array] = None


def dims(cfg: ModelConfig):
    """(heads, head width, groups, state width, inner width, conv
    channels)."""
    return (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.mamba_state_size, cfg.mamba_inner, cfg.mamba_conv_channels)


def init_mamba_params(key: jax.Array, cfg: ModelConfig) -> Params:
    h, dtype, std = cfg.hidden_size, cfg.dtype, cfg.init_method_std
    H, _P, _G, _N, di, ch = dims(cfg)
    taps = cfg.mamba_conv_kernel
    out_std = (std / (2.0 * cfg.num_layers) ** 0.5 if cfg.use_scaled_init
               else std)
    ks = jax.random.split(key, 6)

    def normal(k, shape, s):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    # A uniform in [1, 16]; dt_bias the inverse softplus of the step, so
    # that a head's memory spans from about one position to about a
    # thousand; D (the skip) 1
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        ks[4], (H,), jnp.float32, math.log(DT_MIN), math.log(DT_MAX))),
        DT_FLOOR)
    bound = 1.0 / math.sqrt(taps)      # a depthwise Conv1d's default
    return {
        "w_in": normal(ks[0], (h, di + ch + H), std),
        "conv": jax.random.uniform(ks[1], (taps, ch), jnp.float32,
                                   -bound, bound).astype(dtype),
        "conv_bias": jax.random.uniform(ks[2], (ch,), jnp.float32,
                                        -bound, bound).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[3], (H,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": {"scale": jnp.ones((di,), dtype)},
        "w_out": normal(ks[5], (di, h), out_std),
    }


def init_state(cfg: ModelConfig, batch: int) -> MambaState:
    """A sequence's start.  The tail's three rows are padded to a tile of
    four once the layers' tails are stacked.  Riding the carry of a
    ``while`` (a scan of more than one period) XLA:TPU re-lays that whole
    padded array between every two layers, 7 GB a decode step of a
    40-layer stack, so there the tail is kept flat, whole tiles of (slots,
    lanes); a one-period stack is unrolled, keeps the rows apart at no
    cost, and kept flat would be copied whole at both ends of every step
    (PERF.md, PR 49: both measured)."""
    H, P, _G, N, _di, ch = dims(cfg)
    rows = cfg.mamba_conv_kernel - 1
    periods = max(times for _period, times in cfg.stack_runs)
    return MambaState(
        jnp.zeros((batch, H, P, N), jnp.float32),
        jnp.zeros((batch, rows * ch) if periods > 1 else (batch, rows, ch),
                  jnp.float32))


@jax.named_scope("mamba_step")
def ssd_step(p: Params, zxbcdt, live, state: MambaState, eps: float):
    """One position, everything between the two projections.  ``zxbcdt``
    [b, z | x | B | C | dt] float32, ``live`` [b] bool, ``state`` one
    layer's or (``state.at``) the stacked states and tails, of which layer
    ``at`` is advanced where ``live`` and the others are left as they lie
    → ``(y [b, inner width] float32, the state in the form it came)``.
    One kernel (``kernels/mamba_step.py``): the state and the tail are
    read once and written once; a state that is not stacked goes through
    it as a stack of one."""
    S, conv, at = state
    if at is None:
        S, conv = S[None], conv[None]
    y, S, conv = mamba_step(
        zxbcdt, p["conv"], p["conv_bias"], p["dt_bias"], p["A_log"], p["D"],
        p["norm"]["scale"], live, S, conv,
        jnp.int32(0) if at is None else at, eps=eps)
    if at is None:
        S, conv = S[0], conv[0]
    return y, MambaState(S, conv, at)


@jax.named_scope("mamba_scan")
def ssd_chunked(x, B, C, dt, A, S, chunk: int):
    """``chunk`` positions at a time.  ``x`` [b, s, H, P], ``B C`` [b, s,
    G, N], ``dt`` [b, s, H], ``A`` [H] (negative), ``S`` [b, H, P, N],
    float32, ``s`` a multiple of ``chunk`` → ``(y [b, s, H, P], S)``."""
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    Hg, nc, Q = H // G, s // chunk, chunk
    x = (x * dt[..., None]).reshape(b, nc, Q, G, Hg, P)
    B = B.reshape(b, nc, Q, G, N)
    C = C.reshape(b, nc, Q, G, N)
    # cum[i]: log of the decay from the chunk's start through position i
    cum = jnp.cumsum((dt * A).reshape(b, nc, Q, G, Hg), axis=2)
    # inside a chunk: (C B^T . L) (dt x), L[i, j] = a_{j+1} ... a_i, j <= i
    diff = cum[:, :, :, None] - cum[:, :, None]           # [b,c,i,j,g,h]
    causal = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None, None]
    L = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    CB = jnp.einsum("bcign,bcjgn->bcijg", C, B)
    y = jnp.einsum("bcijgh,bcjghp->bcighp", CB[..., None] * L, x)
    # a chunk's own end state: B^T (dt x), each position decayed to the end
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    local = jnp.einsum("bcjghp,bcjgn->bcghpn", x * to_end[..., None], B)
    whole = jnp.exp(cum[:, :, -1])                        # [b, c, g, h]

    def carry_on(S, c):
        decay, add = c
        return S * decay[..., None, None] + add, S

    S, before = jax.lax.scan(
        carry_on, S.reshape(b, G, Hg, P, N),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(local, 1, 0)))
    # what the earlier chunks left, read by C and decayed to position i
    y = y + jnp.einsum("bcign,cbghpn->bcighp", C, before) \
        * jnp.exp(cum)[..., None]
    return y.reshape(b, s, H, P), S.reshape(b, H, P, N)


@jax.named_scope("mamba_conv")
def _conv(p: Params, mixed, tail, lengths):
    """Causal depthwise convolution of ``mixed`` [b, s, ch] continuing
    ``tail`` ([b, taps - 1, ch], or flat), its bias, then SiLU → ``(out
    [b, s, ch], the tail after each row's ``lengths`` positions, in the
    form it came in)``."""
    taps, (b, s, ch) = p["conv"].shape[0], mixed.shape
    full = jnp.concatenate([tail.reshape(b, taps - 1, ch), mixed],
                           axis=1)                         # float32
    w = p["conv"].astype(jnp.float32)
    out = sum(full[:, j:j + s] * w[j] for j in range(taps))
    out = out + p["conv_bias"].astype(jnp.float32)
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, taps - 1, axis=0))(full, lengths)
    return jax.nn.silu(out), new_tail.reshape(tail.shape)


@jax.named_scope("mamba")
def mamba_block(cfg: ModelConfig, p: Params, x: jax.Array,
                state: Optional[MambaState] = None,
                valid: Optional[jax.Array] = None):
    """The mixer over ``x`` [b, s, h] continuing ``state`` (None: the
    start of a sequence) → ``(out [b, s, h], the state after each row's
    valid positions)``.  ``valid`` [b, s] bool marks the positions that
    are there, a prefix of each row (None: all)."""
    b, s, _ = x.shape
    if state is None:
        state = init_state(cfg, b)
    if valid is None:
        valid = jnp.ones((b, s), bool)
    with jax.named_scope("mamba_proj"):
        zxbcdt = dot_rounded(x, p["w_in"])
    if s == 1:
        y, state = ssd_step(p, zxbcdt[:, 0], valid[:, 0], state,
                            cfg.norm_eps)
        y = y[:, None]
    else:
        assert state.at is None, "a prompt takes one layer's state"
        y, state = _prompt(cfg, p, zxbcdt, state, valid)
    with jax.named_scope("mamba_proj"):
        out = dot_rounded(y, p["w_out"]).astype(x.dtype)
    return out, state


def _prompt(cfg: ModelConfig, p: Params, zxbcdt, state: MambaState, valid):
    """A prompt between the two projections: the convolution, the chunked
    form, the skip, the gate and the norm, plain ``jax.numpy``."""
    b, s, _ = zxbcdt.shape
    H, P, G, N, di, ch = dims(cfg)
    z, mixed, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + ch],
                    zxbcdt[..., di + ch:])
    mixed, conv = _conv(p, mixed, state.conv,
                        jnp.sum(valid, axis=1, dtype=jnp.int32))
    xs = mixed[..., :di].reshape(b, s, H, P)
    B = mixed[..., di:di + G * N].reshape(b, s, G, N)
    C = mixed[..., di + G * N:].reshape(b, s, G, N)
    # (no clamp of the step: the published config has no time_step_limit)
    dt = jax.nn.softplus(dt + p["dt_bias"]) * valid[..., None]
    A = -jnp.exp(p["A_log"])
    pad = -s % cfg.mamba_chunk_size    # padded positions: dt = 0

    def padded(a):
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))

    y, S = ssd_chunked(*map(padded, (xs, B, C, dt)), A, state.S,
                       cfg.mamba_chunk_size)
    y = y[:, :s] + p["D"][:, None] * xs
    # the gate first, RMSNorm over each group's channels after
    y = (y.reshape(b, s, di) * jax.nn.silu(z)).reshape(b, s, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = y.reshape(b, s, di) * p["norm"]["scale"].astype(jnp.float32)
    return y, MambaState(S, conv)
