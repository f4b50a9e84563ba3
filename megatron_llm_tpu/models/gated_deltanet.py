"""Gated DeltaNet: the linear-attention mixer of a hybrid stack.

A layer keeps, for each of its value heads, a matrix state ``S`` (key
width x value width, float32) in place of keys and values, and updates it
by the gated delta rule (Yang, Kautz, Hatamizadeh: "Gated Delta Networks",
arXiv 2412.06464), a position at a time::

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t (x) d_t
    o_t = S^T q_t

with ``q | k | v`` first passed through a short causal depthwise
convolution and SiLU, q and k L2-normalised, ``beta = sigmoid(b)`` and
``g = -exp(A_log) softplus(a + dt_bias)`` a value head.  The output is
RMS-normalised a head, gated by ``SiLU(z)`` and projected back.

Two forms of the same recurrence: ``delta_rule_step`` for the one new
position of a decode step, and the chunked form for a prompt, which
rearranges ``CHUNK`` positions at a time into matrix products (the WY
form of the paper's section 3).  For every ``s > 1`` everything between
the two projections is one Pallas kernel (``kernels/gdn_scan.py``): it
reads q, k, v and z out of the input projection's output where they
lie, a key head's column blocks at a time, and takes the convolution,
the L2 norms, the rule (``S`` in VMEM from a prompt's first chunk to its
last), the output norm and the gate on those tiles; HBM sees the
projection's output once going in and the gated ``o`` once coming out.
What is left to XLA of a prompt is the convolution's new tail, a few
rows.  The one position of a decode step is one kernel too
(``kernels/gdn_step.py``): it takes the same stages (their definitions
are the prompt kernel's: ``conv_taps``, ``l2norm``, ``gated_rmsnorm``)
and the rule on a head's tile of the stacked states where it lies, one
read and one write of the state; ``one_position`` and
``delta_rule_step`` are its plain ``jax.numpy`` form, which the tests
hold it to.  The
state, the kernels' operands and everything that meets them are float32,
and every product of the rule is taken at ``Precision.HIGHEST``: the rule
takes differences of near-equal quantities (``v - S^T k``), so one bf16
rounding comes out of it three times as large and the next router's
near-ties turn that into other experts (PERF.md, PR 35).  The kernel is
forward only, as the einsum form it replaced was only ever run: training
through the rule is not there yet (tests/kernels/test_gdn_scan.py keeps
the einsum form and the ``jax.numpy`` stages as an oracle beside the
recurrence).  A position whose
``valid`` is false (the padded tail of a prefill bucket) has ``beta = 0``
and ``g = 0``: it changes neither ``S`` nor the convolution's tail,
whatever it holds.

The fused input projection is laid out flat, ``[q | k | v | z]`` (key
heads x key width twice, value heads x value width twice) and ``[b | a]``;
the published checkpoint groups the same columns by key head, which is a
fixed permutation of the columns of ``w_qkvz`` and ``w_ba``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import ModelConfig
from ..kernels.gdn_scan import (
    CHUNK,
    conv_taps,
    gated_rmsnorm,
    gdn_scan,
    l2norm,
)
from ..kernels.gdn_step import gdn_step
from ..ops.precision import dot_f32

Params = dict

# the state and everything that meets it is float32, and its products are
# taken at full float32 precision: XLA:TPU's default for float32 operands
# is one bfloat16 pass
_PREC = jax.lax.Precision.HIGHEST


class GDNState(NamedTuple):
    """What a linear layer keeps of a sequence: ``S`` [b, value heads,
    key width, value width], and ``conv`` [b, taps - 1, channels]: the
    convolution's last inputs; both float32 (a tail rounded to bfloat16
    would round the next positions' q, k and v before the rule).  With
    ``at`` (an int32 scalar, may be traced) ``S`` and ``conv`` are the
    stacked states and tails of all the layers, [layers, b, ...], and
    this layer's are ``S[at]`` and ``conv[at]``: how a decode step hands
    them through, since its kernel advances the layer where it lies."""

    S: jax.Array
    conv: jax.Array
    at: Optional[jax.Array] = None


# the names ``models/model.py:init_rec_state`` keeps a layer's two arrays
# under, stacked over the DeltaNet layers
STATE_NAMES = ("S", "conv")


def dims(cfg: ModelConfig):
    """(key heads, value heads, key width, value width, conv channels)."""
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return nk, nv, dk, dv, 2 * nk * dk + nv * dv


def init_gdn_params(key: jax.Array, cfg: ModelConfig) -> Params:
    h, dtype, std = cfg.hidden_size, cfg.dtype, cfg.init_method_std
    nk, nv, dk, dv, ch = dims(cfg)
    taps = cfg.linear_conv_kernel
    out_std = (std / (2.0 * cfg.num_layers) ** 0.5 if cfg.use_scaled_init
               else std)
    ks = jax.random.split(key, 6)

    def normal(k, shape, s):
        return (s * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    # the decay's initialisation is the Mamba-2 / Gated DeltaNet
    # convention: A uniform in [1, 16], the step dt log-uniform in
    # [0.001, 0.1] and dt_bias its inverse softplus, so that a head's
    # memory spans from about one position to about a thousand
    dt = jnp.exp(jax.random.uniform(ks[4], (nv,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    bound = 1.0 / math.sqrt(taps)      # a depthwise Conv1d's default
    return {
        "w_qkvz": normal(ks[0], (h, 2 * nk * dk + 2 * nv * dv), std),
        "w_ba": normal(ks[1], (h, 2 * nv), std),
        "conv": jax.random.uniform(ks[2], (taps, ch), jnp.float32,
                                   -bound, bound).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[3], (nv,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm": {"scale": jnp.ones((dv,), dtype)},
        "w_out": normal(ks[5], (nv * dv, h), out_std),
    }


def init_state(cfg: ModelConfig, batch: int) -> GDNState:
    _nk, nv, dk, dv, ch = dims(cfg)
    return GDNState(jnp.zeros((batch, nv, dk, dv), jnp.float32),
                    jnp.zeros((batch, cfg.linear_conv_kernel - 1, ch),
                              jnp.float32))


def delta_rule_step(q, k, v, g, beta, S):
    """One position, the plain form.  ``q k`` [b, h, dk], ``v`` [b, h,
    dv], ``g beta`` [b, h], ``S`` [b, h, dk, dv], all float32 → ``(o [b,
    h, dv], S)``."""
    S = S * jnp.exp(g)[..., None, None]
    d = beta[..., None] * (v - jnp.einsum("bhk,bhkv->bhv", k, S,
                                          precision=_PREC))
    S = S + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, S, precision=_PREC), S


@jax.named_scope("gdn_conv")
def _conv(p: Params, mixed, tail, lengths):
    """Causal depthwise convolution of ``mixed`` [b, s, ch] continuing
    ``tail`` [b, taps - 1, ch], then SiLU → ``(out [b, s, ch], the tail
    after each row's ``lengths`` positions)``."""
    taps, s = p["conv"].shape[0], mixed.shape[1]
    full = jnp.concatenate([tail, mixed], axis=1)          # float32
    w = p["conv"].astype(jnp.float32)
    out = conv_taps((full[:, j:j + s] for j in range(taps)),
                    (w[j] for j in range(taps)))
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, taps - 1, axis=0))(full, lengths)
    return jax.nn.silu(out), new_tail


@jax.named_scope("gdn_conv")
def _tail_after(tail, mixed, lengths):
    """The tail ``_conv`` hands on, without its concatenation: of ``tail``
    [b, taps - 1, ch] followed by the first ``ch`` columns of ``mixed``
    [b, s, ch or wider], the ``taps - 1`` rows that end at each row's
    ``lengths``."""
    keep, ch = tail.shape[1:]
    n = min(keep, mixed.shape[1])

    def one(tail, mixed, length):
        last = jax.lax.dynamic_slice(
            mixed, (jnp.maximum(length - n, 0), 0), (n, ch))
        return jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([tail, last]), jnp.minimum(length, keep), keep)

    return jax.vmap(one)(tail, mixed, lengths)


def _gates(p: Params, ba, valid, nv):
    """``(beta, g)`` [b, s, value heads] of the positions that are there,
    zeros of the others."""
    live = valid[..., None].astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :nv]) * live
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        ba[..., nv:] + p["dt_bias"]) * live
    return beta, g


@jax.named_scope("gdn_step")
def _one_position(cfg: ModelConfig, p: Params, qkvz, ba,
                  state: GDNState, valid):
    """A decode step between the two projections: one kernel
    (``kernels/gdn_step.py``).  ``state`` one layer's or (``state.at``)
    the stacked states and tails, of which layer ``at`` is advanced where
    ``valid`` and the others are left as they lie; it comes back in the
    form it came.  The state and the tail are read once and written once;
    a state that is not stacked goes through as a stack of one."""
    S, conv, at = state
    if at is None:
        S, conv = S[None], conv[None]
    o, S, conv = gdn_step(
        qkvz[:, 0], ba[:, 0], p["conv"], p["A_log"], p["dt_bias"],
        p["norm"]["scale"], valid[:, 0], S, conv,
        jnp.int32(0) if at is None else at, eps=cfg.norm_eps)
    if at is None:
        S, conv = S[0], conv[0]
    return o[:, None], GDNState(S, conv, at)


def one_position(cfg: ModelConfig, p: Params, qkvz, ba, state, valid):
    """``_one_position`` as ``jax.numpy``, a stage a line: what the
    kernel is held to (tests/kernels/test_gdn_step.py)."""
    b = qkvz.shape[0]
    nk, nv, dk, dv, _ch = dims(cfg)
    kd, vd = nk * dk, nv * dv
    mixed, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    mixed, conv = _conv(p, mixed, state.conv,
                        jnp.sum(valid, axis=1, dtype=jnp.int32))
    q = l2norm(mixed[..., :kd].reshape(b, 1, nk, dk)) * dk ** -0.5
    k = l2norm(mixed[..., kd:2 * kd].reshape(b, 1, nk, dk))
    v = mixed[..., 2 * kd:]
    beta, g = _gates(p, ba, valid, nv)
    # each key head serves value heads / key heads value heads
    o, S = delta_rule_step(jnp.repeat(q[:, 0], nv // nk, axis=1),
                           jnp.repeat(k[:, 0], nv // nk, axis=1),
                           v.reshape(b, nv, dv), g[:, 0], beta[:, 0],
                           state.S)
    o = gated_rmsnorm(o.reshape(b, 1, nv, dv), z, p["norm"]["scale"],
                      cfg.norm_eps)
    return o.reshape(b, 1, vd), GDNState(S, conv)


def _prompt(cfg: ModelConfig, p: Params, qkvz, ba, state, valid):
    """A prompt between the two projections: the kernel, and the
    convolution's new tail."""
    assert state.at is None, "a prompt takes one layer's state"
    s = qkvz.shape[1]
    beta, g = _gates(p, ba, valid, cfg.linear_num_value_heads)
    pad = -s % CHUNK       # padded positions: beta = g = 0, no-ops

    def padded(a):
        return jnp.pad(a, [(0, 0), (0, pad), (0, 0)]) if pad else a

    with jax.named_scope("gdn_scan"):
        o, S = gdn_scan(*map(padded, (qkvz, g, beta)), state.S, state.conv,
                        p["conv"].astype(jnp.float32),
                        p["norm"]["scale"].astype(jnp.float32), cfg.norm_eps)
    conv = _tail_after(state.conv, qkvz,
                       jnp.sum(valid, axis=1, dtype=jnp.int32))
    return (o[:, :s] if pad else o), GDNState(S, conv)


@jax.named_scope("gdn")
def gdn_block(cfg: ModelConfig, p: Params, x: jax.Array,
              state: Optional[GDNState] = None,
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
    with jax.named_scope("gdn_proj"):
        # the float32 stream in two bf16 passes, results left in float32
        # for the convolution, the norms and the rule: the rule takes
        # differences of near-equal quantities (``v - S^T k``), so one
        # bf16 rounding of the mixer's input comes out of it three times
        # as large (0.33 % of its output at the published widths, and
        # 0.17 % more from rounding ``o`` before ``w_out``), and the next
        # router's near-ties turn that into other experts (PERF.md, PR 35)
        qkvz = dot_f32(x, p["w_qkvz"])
        ba = dot_f32(x, p["w_ba"])
    between = _one_position if s == 1 else _prompt
    o, state = between(cfg, p, qkvz, ba, state, valid)
    with jax.named_scope("gdn_proj"):
        out = dot_f32(o, p["w_out"]).astype(x.dtype)
    return out, state
