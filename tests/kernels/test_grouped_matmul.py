"""The grouped MLP kernel (``kernels/grouped_matmul.py``), through the
interpreter at small widths, against two oracles: the three
``lax.ragged_dot`` calls over gathered rows that the kernel replaced in
``models/moe.py`` (PR 42), kept here, and a plain loop over the experts in
float64."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.kernels import grouped_matmul as gm
from megatron_llm_tpu.ops.activations import gelu, swiglu

H, F = 256, 128     # two 128-lane pieces a row: the row copies' layout


def weights(E, dtype, seed=0, glu=True):
    ks = jax.random.split(jax.random.key(seed), 3)
    make = lambda key, shape: (0.1 * jax.random.normal(  # noqa: E731
        key, shape, jnp.float32)).astype(dtype)
    return (make(ks[0], (E, H, F)) if glu else None,
            make(ks[1], (E, H, F)), make(ks[2], (E, F, H)))


def sorted_pairs(local, E):
    """``local`` [g, k] (``E``: in no group) → ``(order, sizes, rows)``:
    the first two as the kernel takes them, the order among equal
    experts shuffled, which nobody may depend on; ``rows`` the sorted
    pairs' numbers ``token * k + choice``."""
    flat = np.asarray(local).reshape(-1)
    k = local.shape[1]
    shuffled = np.random.default_rng(7).permutation(flat.size)
    rows = shuffled[np.argsort(flat[shuffled], kind="stable")]
    order = (rows // k) << (k - 1).bit_length() | rows % k
    return (jnp.asarray(order, jnp.int32),
            jnp.asarray(np.bincount(flat, minlength=E + 1)[:E], jnp.int32),
            rows)


def run(x, order, sizes, w, act, k):
    """The kernel → ``[g * k, h]`` float32 in (token, choice) order."""
    out = gm.grouped_mlp(x, order, sizes, *w, act, choices=k)
    assert out.shape[:2] == (x.shape[0], k) and out.dtype == jnp.float32
    return np.asarray(out).reshape(x.shape[0] * k, -1)


def ragged(x, rows, sizes, w, act, k):
    """The former formulation over the sorted pair numbers ``rows`` →
    results in *sorted* order; past the last group undefined."""
    w_gate, w_up, w_down = w
    rows = x.astype(w_up.dtype)[rows // k]
    up = jax.lax.ragged_dot(rows, w_up, sizes,
                            preferred_element_type=jnp.float32)
    if w_gate is not None:
        gate = jax.lax.ragged_dot(rows, w_gate, sizes,
                                  preferred_element_type=jnp.float32)
        up = jnp.concatenate([gate, up], axis=-1)
    return jax.lax.ragged_dot(act(up).astype(w_up.dtype), w_down, sizes,
                              preferred_element_type=jnp.float32)


def loop(x, local, w, act, E):
    """→ ``[g * k, h]`` float64 in (token, choice) order, NaN where the
    choice is in no group."""
    w_gate, w_up, w_down = (None if a is None
                            else np.asarray(a.astype(jnp.float32), np.float64)
                            for a in w)
    x = np.asarray(x.astype(w[1].dtype).astype(jnp.float32), np.float64)
    g, k = local.shape
    out = np.full((g * k, x.shape[1]), np.nan)
    for e in range(E):
        for t, c in zip(*np.nonzero(np.asarray(local) == e)):
            up = x[t] @ w_up[e]
            if w_gate is not None:
                up = np.concatenate([x[t] @ w_gate[e], up])
            hidden = np.asarray(act(jnp.asarray(up, jnp.float32)), np.float64)
            out[t * k + c] = hidden @ w_down[e]
    return out


def routing(case, g, k, E, rng):
    """``local`` [g, k] for a named layout of the groups."""
    if case == "empty_groups":      # every other expert has no row
        return 2 * rng.integers(0, E // 2, (g, k))
    if case == "not_held_half":
        local = rng.integers(0, 2 * E, (g, k))
        return np.where(local < E, local, E)
    if case == "all_held":
        return rng.integers(0, E, (g, k))
    if case == "none_held":
        return np.full((g, k), E)
    if case == "three_tiles":       # expert 1: 2 tiles and a part
        local = np.full((g, k), E)
        local.reshape(-1)[:2 * gm._TILE_ROWS + 37] = 1
        local.reshape(-1)[-5:] = 3
        return local
    if case == "ends_on_edge":      # expert 0 fills one tile exactly,
        local = np.full((g, k), E)  # expert 2 two, the last group ends
        flat = local.reshape(-1)    # the sorted order
        flat[:gm._TILE_ROWS] = 0
        flat[gm._TILE_ROWS:] = 2
        assert (flat == 2).sum() == 2 * gm._TILE_ROWS
        return local
    if case == "repeated_token":    # a token's choices name one expert
        local = rng.integers(0, E, (g, 1)).repeat(k, axis=1)
        local[::3, -1] = E
        return local
    raise ValueError(case)


CASES = [("empty_groups", 40, 4, 8), ("not_held_half", 40, 4, 6),
         ("all_held", 40, 4, 5), ("none_held", 16, 4, 4),
         ("three_tiles", 96, 4, 4), ("ends_on_edge", 96, 4, 3),
         ("repeated_token", 40, 4, 6)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case,g,k,E", CASES, ids=[c[0] for c in CASES])
def test_against_ragged_dot_and_a_loop(case, g, k, E, dtype):
    rng = np.random.default_rng(1)
    local = routing(case, g, k, E, rng)
    x = jnp.asarray(rng.standard_normal((g, H)), jnp.float32).astype(dtype)
    w = weights(E, dtype)
    order, sizes, rows = sorted_pairs(local, E)
    out = run(x, order, sizes, w, swiglu, k)
    assert out.shape == (g * k, H)
    held = np.asarray(local).reshape(-1) < E
    n_held = int(held.sum())
    assert n_held == int(sizes.sum())
    # a row in no group is never written (the interpreter allocates NaN)
    assert np.isnan(out[~held]).all()
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    want = loop(x, local, w, swiglu, E)
    np.testing.assert_allclose(out[held], want[held], rtol=tol, atol=tol)
    if n_held:
        former = np.asarray(ragged(x, rows, sizes, w, swiglu, k))
        np.testing.assert_allclose(out[rows[:n_held]], former[:n_held],
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("fill", [np.nan, 3e38, -3e38],
                         ids=["nan", "huge", "minus_huge"])
def test_what_no_group_owns_leaks_nothing(fill):
    """Tokens none of whose choices is in a group hold ``fill``: their
    pairs sort last, past the last group, and no held row may change by
    it."""
    g, k, E = 48, 4, 6
    rng = np.random.default_rng(2)
    local = routing("not_held_half", g, k, E, rng)
    local[g // 2:] = E
    x = rng.standard_normal((g, H)).astype(np.float32)
    w = weights(E, jnp.float32)
    order, sizes, _ = sorted_pairs(local, E)
    clean = run(jnp.asarray(x), order, sizes, w, swiglu, k)
    x[g // 2:] = fill
    dirty = run(jnp.asarray(x), order, sizes, w, swiglu, k)
    held = local.reshape(-1) < E
    assert np.isfinite(dirty[held]).all()
    np.testing.assert_array_equal(dirty[held], clean[held])
    assert np.isnan(dirty[~held]).all()


def test_the_decode_shape():
    """44 tokens of 10 choices over 256 groups, half of them held: most
    groups hold one row or none, and the small tile is taken."""
    g, k, E = 44, 10, 256
    assert gm.tile_rows(g * k, E) == gm._TILE_ROWS_FEW
    assert gm.tile_rows(2048 * k, E) == gm._TILE_ROWS
    rng = np.random.default_rng(3)
    local = np.stack([rng.permutation(2 * E)[:k] for _ in range(g)])
    local = np.where(local < E, local, E)
    x = jnp.asarray(rng.standard_normal((g, H)), jnp.float32)
    w = weights(E, jnp.bfloat16)
    order, sizes, _ = sorted_pairs(local, E)
    assert int((sizes == 0).sum()) > E // 4 and int(sizes.max()) >= 2
    out = run(x, order, sizes, w, swiglu, k)
    held = local.reshape(-1) < E
    want = loop(x, local, w, swiglu, E)
    np.testing.assert_allclose(out[held], want[held], rtol=2e-2, atol=2e-2)
    assert np.isnan(out[~held]).all()


def test_a_form_without_a_gate():
    g, k, E = 24, 2, 4
    rng = np.random.default_rng(4)
    local = routing("not_held_half", g, k, E, rng)
    x = jnp.asarray(rng.standard_normal((g, H)), jnp.float32)
    w = weights(E, jnp.float32, glu=False)
    order, sizes, _ = sorted_pairs(local, E)
    out = run(x, order, sizes, w, gelu, k)
    held = local.reshape(-1) < E
    np.testing.assert_allclose(out[held], loop(x, local, w, gelu, E)[held],
                               rtol=2e-5, atol=2e-5)


def test_a_trace_of_the_body_is_short():
    """A start traces the kernel once a program, and on the sealed machine
    a ``cond`` is ~20 ms of that and an operator ~1 ms (PERF.md, PR 42:
    the body as first written, with its waits under ``pl.when``, cost the
    long-document cell 30 s of set-up): the copies and waits are loops,
    the scalar arithmetic plain ``lax``."""
    g, k, E = 2048, 10, 16
    w = weights(E, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda x, order, sizes: gm.grouped_mlp(
            x, order, sizes, *w, swiglu, choices=k))(
        jax.ShapeDtypeStruct((g, H), jnp.float32),
        jax.ShapeDtypeStruct((g * k,), jnp.int32),
        jax.ShapeDtypeStruct((E,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    names = []

    def walk(j):
        for e in j.eqns:
            names.append(e.primitive.name)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(call.params["jaxpr"])
    assert names.count("cond") == 2, names.count("cond")
    # jitted calls: the activation's own (silu), nothing on a scalar
    assert names.count("jit") + names.count("pjit") <= 2
    assert len(names) < 260, len(names)
