"""A Mamba-2 layer's decode step between its two projections as one
kernel (``kernels/mamba_step.py``, in interpret mode here) against the
plain composition written a line at a time: the convolution against the
tail, the step size, the recurrence, the skip, the gate and the group
norm."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.mamba_step import heads_per_step, mamba_step

P, N, G = 8, 16, 2
TAPS, EPS = 4, 1e-5


def plain(p, zxbcdt, live, S, tail, groups):
    """One layer, one position, in float64, a slot and a head at a time:
    ``(y [b, inner width], S, tail)`` with the tail in the form it came."""
    f64 = lambda a: np.asarray(jnp.asarray(a, jnp.float32), np.float64)  # noqa: E731
    zx, S, live = f64(zxbcdt), f64(S).copy(), np.asarray(live)
    w, bias, scale = f64(p["conv"]), f64(p["conv_bias"]), f64(p["scale"])
    b, H, hp, n = S.shape
    di, ch = H * hp, w.shape[1]
    z, mixed, dt = zx[:, :di], zx[:, di:di + ch], zx[:, di + ch:]
    full = np.concatenate(
        [f64(tail).reshape(b, TAPS - 1, ch), mixed[:, None]], axis=1)
    out = (full * w).sum(axis=1) + bias
    out = out / (1.0 + np.exp(-out))                       # SiLU
    new_tail = np.where(live[:, None, None], full[:, 1:], full[:, :-1])
    x = out[:, :di].reshape(b, H, hp)
    B = out[:, di:di + groups * n].reshape(b, groups, n)
    C = out[:, di + groups * n:].reshape(b, groups, n)
    dt = np.logaddexp(0.0, dt + f64(p["dt_bias"])) * live[:, None]
    A, D = -np.exp(f64(p["A_log"])), f64(p["D"])
    y = np.zeros((b, H, hp))
    for i in range(b):
        for h in range(H):
            g = h // (H // groups)
            S[i, h] = (np.exp(dt[i, h] * A[h]) * S[i, h]
                       + np.outer(dt[i, h] * x[i, h], B[i, g]))
            y[i, h] = S[i, h] @ C[i, g] + D[h] * x[i, h]
    # the gate first, RMSNorm over each group's channels after
    y = (y.reshape(b, di) * z / (1.0 + np.exp(-z))).reshape(b, groups, -1)
    y = y / np.sqrt((y * y).mean(axis=-1, keepdims=True) + EPS)
    return (y.reshape(b, di) * scale, S,
            new_tail.reshape(np.shape(tail)).astype(np.float32))


def layer(key, H, hp, n, groups, slots, layers, flat, dtype=jnp.float32):
    """A layer's small parameters, one position's projection, and the
    stacked states and tails; the last slot is dead."""
    di = H * hp
    ch = di + 2 * groups * n
    ks = jax.random.split(key, 10)
    bound = 1.0 / TAPS ** 0.5
    p = {
        "conv": jax.random.uniform(ks[0], (TAPS, ch), jnp.float32,
                                   -bound, bound).astype(dtype),
        "conv_bias": jax.random.uniform(ks[1], (ch,), jnp.float32,
                                        -bound, bound).astype(dtype),
        "dt_bias": jax.random.normal(ks[2], (H,)),
        "A_log": jax.random.uniform(ks[3], (H,), minval=0.0, maxval=2.5),
        "D": jax.random.normal(ks[4], (H,)),
        "scale": (1.0 + 0.1 * jax.random.normal(ks[5], (di,))).astype(dtype),
    }
    zx = jax.random.normal(ks[6], (slots, di + ch + H))
    ssm = jax.random.normal(ks[7], (layers, slots, H, hp, n))
    tail = jax.random.normal(
        ks[8], (layers, slots, (TAPS - 1) * ch) if flat
        else (layers, slots, TAPS - 1, ch))
    live = jnp.ones((slots,), bool).at[slots - 1].set(False)
    return p, zx, live, ssm, tail


def run(p, zx, live, ssm, tail, at):
    # the layer as a traced scalar, as the scan over periods hands it
    # over: the kernel's own jitted call takes it so, and the cases that
    # differ in the layer alone run one executable
    return mamba_step(
        zx, p["conv"], p["conv_bias"], p["dt_bias"], p["A_log"], p["D"],
        p["scale"], live, ssm, tail, jnp.int32(at), eps=EPS)


def check(p, zx, live, ssm, tail, at, groups):
    """The kernel's three results against the plain composition's; the
    other layers and the dead slot as they were, bit for bit."""
    y, new, new_tail = run(p, zx, live, ssm, tail, at)
    want_y, want_S, want_tail = plain(p, zx, live, ssm[at], tail[at], groups)
    assert y.shape == want_y.shape and y.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(new[at], want_S, atol=2e-6, rtol=2e-5)
    np.testing.assert_array_equal(new_tail[at], want_tail)
    for other in set(range(ssm.shape[0])) - {at}:
        np.testing.assert_array_equal(new[other], ssm[other])
        np.testing.assert_array_equal(new_tail[other], tail[other])
    dead = ssm.shape[1] - 1
    np.testing.assert_array_equal(new[at, dead], ssm[at, dead])
    np.testing.assert_array_equal(new_tail[at, dead], tail[at, dead])
    if dead:
        assert float(jnp.abs(new[at, 0] - ssm[at, 0]).max()) > 1e-3
        assert float(jnp.abs(new_tail[at, 0] - tail[at, 0]).max()) > 1e-3


# (the layer innermost: the three cases of one set of shapes run one after
# another, on one worker, through one executable)
STACKED = [(per, slots, where, flat) for per in (1, 2, 16)
           for slots in (1, 3, 8) for flat in (False, True)
           for where in ("first", "middle", "last")]


@pytest.mark.parametrize(
    "per,slots,where,flat", STACKED,
    ids=["%d-%d-%s-%s" % (p, s, w, "flat" if f else "rows")
         for p, s, w, f in STACKED])
def test_one_layer_of_the_stacked_states_is_advanced_where_it_lies(
        per, slots, where, flat):
    layers, H = 3, per * G
    at = {"first": 0, "middle": 1, "last": 2}[where]
    check(*layer(jax.random.key(per * 100 + slots * 10 + at), H, P, N, G,
                 slots, layers, flat), at, G)


@pytest.mark.parametrize("H,groups,tile", [
    (64, 1, 32),          # granite-4.0-h-micro: one group of 64 heads
    (8, 1, 8),            # one group, fewer heads than a block
    (16, 2, 16),          # two groups of 8: both in one grid step, as before
    (128, 8, 32),         # Nemotron-3-Super: two groups of 16 a grid step
    (48, 2, 24),          # groups of 24: blocks of 12, two a grid step
])
@pytest.mark.parametrize("flat", [False, True], ids=["rows", "flat"])
def test_a_grid_step_takes_blocks_of_a_group_whatever_the_groups(
        H, groups, tile, flat):
    """A group wider than a block is cut into blocks that read the
    group's one ``B`` and ``C``, and a group's norm spans its blocks and,
    where a slot has several, its grid steps."""
    assert heads_per_step(H, groups) == tile
    check(*layer(jax.random.key(H + groups), H, P, N, groups, 3, 2, flat),
          1, groups)


@pytest.mark.parametrize("H,groups,slots,layers,flat", [
    (64, 1, 3, 3, True),      # granite-4.0-h-micro: a flat tail in a stack
    (128, 8, 2, 1, False),    # Nemotron-3-Super: a tail of three rows
    (64, 1, 12, 2, True),     # slots that are no whole blocks of eight
    (32, 2, 11, 2, False),
], ids=["granite", "nemotron", "flat-12-slots", "rows-11-slots"])
def test_the_mixer_at_the_published_head_and_state_widths(
        H, groups, slots, layers, flat):
    """Heads of 64 against a state of 128, the parameters in bfloat16 as
    the cells hold them: two heads a register of the projection's row."""
    check(*layer(jax.random.key(H), H, 64, 128, groups, slots, layers, flat,
                 jnp.bfloat16), layers - 1, groups)


def test_every_slot_dead_changes_nothing():
    p, zx, _, ssm, tail = layer(jax.random.key(7), 16, P, N, G, 4, 2, True)
    _, new, new_tail = run(p, zx, jnp.zeros((4,), bool), ssm, tail, 0)
    np.testing.assert_array_equal(new, ssm)
    np.testing.assert_array_equal(new_tail, tail)
