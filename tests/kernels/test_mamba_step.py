"""The state-space decode step's kernel (``kernels/mamba_step.py``, in
interpret mode here) against the recurrence written a line at a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.mamba_step import mamba_step

P, N, G = 8, 16, 2


def recurrence(x, B, C, dt, A, S):
    """One layer's ``S <- a S + (dt x) (x) B;  y = S C`` in float64, a slot
    and a head at a time."""
    x, B, C, dt, A, S = (np.asarray(a, np.float64)
                         for a in (x, B, C, dt, A, S))
    b, H, _ = x.shape
    S, y = S.copy(), np.zeros(x.shape)
    for i in range(b):
        for h in range(H):
            g = h // (H // B.shape[1])
            S[i, h] = (np.exp(dt[i, h] * A[h]) * S[i, h]
                       + np.outer(dt[i, h] * x[i, h], B[i, g]))
            y[i, h] = S[i, h] @ C[i, g]
    return y, S


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("slots", [1, 3, 8])
@pytest.mark.parametrize("per", [1, 2, 16])
def test_one_layer_of_the_stacked_states_is_advanced_where_it_lies(
        per, slots, where):
    layers, H = 3, per * G
    at = {"first": 0, "middle": 1, "last": 2}[where]
    ks = jax.random.split(jax.random.key(per * 100 + slots * 10 + at), 6)
    x = jax.random.normal(ks[0], (slots, H, P))
    B = jax.random.normal(ks[1], (slots, G, N))
    C = jax.random.normal(ks[2], (slots, G, N))
    # the last row is a dead slot: dt = 0
    dt = jax.nn.softplus(jax.random.normal(ks[3], (slots, H))
                         ).at[slots - 1].set(0.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5))
    ssm = jax.random.normal(ks[5], (layers, slots, H, P, N))
    # the layer as a traced scalar, as the scan over periods hands it over
    y, new = jax.jit(mamba_step)(x, B, C, dt, A, ssm, jnp.int32(at))
    want_y, want_S = recurrence(x, B, C, dt, A, ssm[at])
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-6)
    np.testing.assert_allclose(new[at], want_S, atol=2e-6, rtol=1e-6)
    for other in set(range(layers)) - {at}:
        np.testing.assert_array_equal(new[other], ssm[other])
    np.testing.assert_array_equal(new[at, slots - 1], ssm[at, slots - 1])
    if slots > 1:
        assert float(jnp.abs(new[at, 0] - ssm[at, 0]).max()) > 1e-3


@pytest.mark.parametrize("H,groups,tile", [
    (64, 1, 32),          # granite-4.0-h-micro: one group of 64 heads
    (8, 1, 8),            # one group, fewer heads than a block
    (16, 2, 16),          # two groups of 8: both in one grid step, as before
    (128, 8, 32),         # Nemotron-3-Super: two groups of 16 a grid step
    (48, 2, 24),          # groups of 24: blocks of 12, two a grid step
])
def test_a_grid_step_takes_blocks_of_a_group_whatever_the_groups(
        H, groups, tile):
    """A group wider than a block is cut into blocks that read the
    group's one ``B`` and ``C``; fewer heads than a register has lanes
    are padded to it around the kernel."""
    from megatron_llm_tpu.kernels.mamba_step import heads_per_step

    assert heads_per_step(H, groups) == tile
    layers, slots, at = 2, 3, 1
    ks = jax.random.split(jax.random.key(H + groups), 6)
    x = jax.random.normal(ks[0], (slots, H, P))
    B = jax.random.normal(ks[1], (slots, groups, N))
    C = jax.random.normal(ks[2], (slots, groups, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (slots, H))
                         ).at[slots - 1].set(0.0)
    A = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5))
    ssm = jax.random.normal(ks[5], (layers, slots, H, P, N))
    y, new = jax.jit(mamba_step)(x, B, C, dt, A, ssm, jnp.int32(at))
    want_y, want_S = recurrence(x, B, C, dt, A, ssm[at])
    assert y.shape == x.shape
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=1e-6)
    np.testing.assert_allclose(new[at], want_S, atol=2e-6, rtol=1e-6)
    np.testing.assert_array_equal(new[0], ssm[0])
    np.testing.assert_array_equal(new[at, slots - 1], ssm[at, slots - 1])
