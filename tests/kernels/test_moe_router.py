"""kernels/moe_router.py in interpret mode against ``jax.lax.top_k``: the
rounds' indices, the scores under them and the load, bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels.moe_router import router_top_k


def by_sort(score, bias, counted, k):
    if bias is None:
        value, idx = jax.lax.top_k(score, k)
    else:
        _, idx = jax.lax.top_k(score + bias, k)
        value = jnp.take_along_axis(score, idx, axis=-1)
    load = jnp.zeros((score.shape[1],), jnp.float32).at[
        idx.reshape(-1)].add(jnp.repeat(counted, k))
    return idx, value, load


@pytest.mark.parametrize("tokens", [1, 200, 1100])
@pytest.mark.parametrize("experts,k,biased", [(512, 10, False),
                                               (128, 6, True)])
def test_the_rounds_are_top_k(experts, k, biased, tokens):
    """Whole tiles and ragged ones (200 tokens are a tile of 128 and a
    last one of 72 live lanes, 1100 two of 512 and 76), scores on a grid
    of 64 values so that every token's row is full of ties, the k-th and
    the (k+1)-th among them, and counted weights that are not all one."""
    ks = jax.random.split(jax.random.key(tokens + experts), 3)
    score = jnp.floor(jax.random.uniform(ks[0], (tokens, experts)) * 64) / 64
    bias = jnp.floor(jax.random.uniform(ks[1], (experts,)) * 8) / 8 \
        if biased else None
    counted = jnp.floor(jax.random.uniform(ks[2], (tokens,)) * 3)
    want = by_sort(score, bias, counted, k)
    got = router_top_k(score, bias, counted, k, interpret=True)
    ranked = jnp.sort(score + (0.0 if bias is None else bias), axis=-1)
    assert tokens == 1 or bool((ranked[:, -k] == ranked[:, -k - 1]).any())
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
