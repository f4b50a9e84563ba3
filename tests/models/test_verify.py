"""A verify window against the sequential decode steps it stands for.

``forward_cached_paged_verify`` scores a slot's ``[pending, draft...]``
window (or the nodes of a candidate tree) in one call.  Speculation is
exact, not approximate, because every window position is BITWISE the
single-token step ``forward_cached_paged`` (gather route) would have
taken after the rows before it had landed — for a plain tree of weights
and for each quantised form an engine may hold, over an int8 pool too.
Small Llama-like stack (RMSNorm, SwiGLU, grouped KV heads), float32, CPU.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import llama2_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.models.model import (
    cache_gather_blocks,
    cache_move_rows,
    forward_cached_paged,
    forward_cached_paged_verify,
)
from megatron_llm_tpu.ops import quant

MAX_LEN, BLOCK, SLOTS, WINDOW = 128, 32, 3, 4

# weights, int4 group size, pool
_OPERANDS = {
    "fp32": (None, None, "none"),
    "int8": ("int8", None, "int8"),
    "int4-g128": ("int4", 128, "none"),
    "mixed": ("mixed", 64, "none"),
}


def _shuffled_tables(b, t, rng):
    """Per-slot tables over shuffled physical ids 1..b*t (0 is trash)."""
    return (rng.permutation(b * t) + 1).reshape(b, t).astype(np.int32)


def _pool_from_cache(cache, bk, tables):
    """Re-lay a dense cache (leaves [L, b, kv, max_len(, d)]) as a block
    pool (leaves [L, 1 + b*T, kv, bk(, d)]) at the physical ids named by
    ``tables``; the trash block and nothing else holds large garbage."""
    b, t = tables.shape

    def to_pool(leaf):
        arr = np.asarray(leaf)
        layers, _, kv = arr.shape[:3]
        garbage = 127 if np.issubdtype(arr.dtype, np.integer) else 1e4
        pool = np.full((layers, 1 + b * t, kv, bk) + arr.shape[4:], garbage,
                       arr.dtype)
        for bi in range(b):
            for j in range(t):
                pool[:, tables[bi, j]] = arr[:, bi, :, j * bk:(j + 1) * bk]
        return jnp.asarray(pool)

    return jax.tree.map(to_pool, cache)


@functools.lru_cache(maxsize=None)
def _setup(operands):
    """Params under ``operands`` and shuffled paged pools holding a
    100-token prefill of every slot, with the jitted step and verify."""
    policy, group, kvq = _OPERANDS[operands]
    cfg = llama2_config(
        "7b", hidden_size=128, num_layers=2, num_attention_heads=4,
        num_kv_heads=2, ffn_hidden_size=256, vocab_size=128,
        seq_length=MAX_LEN, max_position_embeddings=MAX_LEN,
        params_dtype="float32", attention_impl="dot", kv_cache_quant=kvq)
    params = model_lib.init_params(jax.random.key(0), cfg)
    if policy:
        pol = quant.resolve_policy(policy)
        if group:
            pol = dataclasses.replace(pol, group_size=group)
        params = quant.quantize_params(params, pol)
    k_cache, v_cache = model_lib.init_kv_cache(cfg, SLOTS, MAX_LEN)
    toks = jax.random.randint(jax.random.key(1), (SLOTS, 100), 0,
                              cfg.vocab_size)
    _, k_cache, v_cache = model_lib.forward_cached(
        cfg, params, toks, k_cache, v_cache, jnp.int32(0))
    tables = _shuffled_tables(SLOTS, MAX_LEN // BLOCK,
                              np.random.default_rng(7))
    pools = (_pool_from_cache(k_cache, BLOCK, tables),
             _pool_from_cache(v_cache, BLOCK, tables))
    jt = jnp.asarray(tables)
    step = jax.jit(lambda tok, k, v, fills: forward_cached_paged(
        cfg, params, tok, k, v, jt, fills))
    verify = jax.jit(
        lambda window, k, v, fills, bids, offs, tree=None:
        forward_cached_paged_verify(cfg, params, window, k, v, jt, fills,
                                    bids, offs, tree=tree))
    window = jax.random.randint(jax.random.key(5), (SLOTS, WINDOW), 0,
                                cfg.vocab_size)
    return tables, pools, step, verify, window


def _landing(tables, fills, nodes):
    """Block and offset of position ``fill + n`` for every slot and every
    ``n`` of ``nodes``, slot-major."""
    bids = [tables[s, (fills[s] + n) // BLOCK]
            for s in range(SLOTS) for n in nodes]
    offs = [(fills[s] + n) % BLOCK for s in range(SLOTS) for n in nodes]
    return jnp.asarray(bids, jnp.int32), jnp.asarray(offs, jnp.int32)


def _same(got, want):
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(
        np.asarray(g), np.asarray(w)), got, want)


def _chain_topology():
    """The degenerate tree that IS the linear window: node j at depth j,
    ancestor closure = identity prefix."""
    depths = np.tile(np.arange(WINDOW), (SLOTS, 1)).astype(np.int32)
    anc = np.tile(np.arange(WINDOW), (SLOTS, WINDOW, 1)).astype(np.int32)
    return jnp.asarray(depths), jnp.asarray(anc)


def _branched_topology():
    """A tree of four a slot: 0(root) -> 1 -> 3 and 0 -> 2 — a main chain
    plus a depth-1 hedge, the shape the engine's tree planner emits."""
    depths = np.tile(np.asarray([0, 1, 1, 2], np.int32), (SLOTS, 1))
    anc = np.zeros((SLOTS, WINDOW, WINDOW), np.int32)
    anc[:, 3, 1] = 1  # node 3's depth-1 ancestor is node 1; depth-0 = 0
    return jnp.asarray(depths), jnp.asarray(anc)


@pytest.mark.parametrize("operands", list(_OPERANDS))
def test_composed_verify_matches_sequential_forwards(operands):
    """A linear window against WINDOW sequential single-token
    ``forward_cached_paged`` calls: logits at every window position and
    both pools after the append bitwise equal, windows straddling a
    block's edge (fills 30 and 62) and one near the table's end."""
    tables, pools, step, verify, window = _setup(operands)
    fills = np.asarray([30, 62, 97], np.int32)
    ks, vs = pools
    want = []
    for j in range(WINDOW):
        logits, ks, vs = step(window[:, j:j + 1], ks, vs,
                              jnp.asarray(fills + j))
        want.append(np.asarray(logits[:, 0]))
    got, kp, vp = verify(window, *pools, jnp.asarray(fills),
                         *_landing(tables, fills, range(WINDOW)))
    for j in range(WINDOW):
        np.testing.assert_array_equal(np.asarray(got[:, j]), want[j])
    _same((kp, vp), (ks, vs))


@pytest.mark.parametrize("operands", ["fp32", "int8"])
def test_chain_tree_equals_linear(operands):
    """An explicit chain topology through the tree walk is bitwise the
    linear window with no topology at all: logits and pools."""
    tables, pools, _, verify, window = _setup(operands)
    fills = np.asarray([30, 62, 1], np.int32)
    args = (window, *pools, jnp.asarray(fills),
            *_landing(tables, fills, range(WINDOW)))
    _same(verify(*args, tree=_chain_topology()), verify(*args))


@pytest.mark.parametrize("operands", ["fp32", "int8"])
def test_branched_tree_composed_matches_sequential_and_compacts(operands):
    """Under a tree topology every node's logits bitwise equal the
    sequential decode of its root path, and after ``cache_move_rows``
    compacts the accepted path's node-indexed rows to depth positions,
    the pool matches the sequential pools row for row.  The tree window
    straddles a block edge (fill 62) and a slot sits near the table's
    end (fill 97)."""
    tables, pools, step, verify, window = _setup(operands)
    fills = np.asarray([30, 62, 97], np.int32)
    jt = jnp.asarray(tables)
    # node-indexed landing spots (node j at position fill + j): what the
    # engine passes in tree mode before the accept walk re-packs rows
    got, kp, vp = verify(window, *pools, jnp.asarray(fills),
                         *_landing(tables, fills, range(WINDOW)),
                         tree=_branched_topology())
    accepted = [0, 1, 3]
    for path in (accepted, [0, 2]):
        ks, vs = pools
        for t, node in enumerate(path):
            logits, ks, vs = step(window[:, node:node + 1], ks, vs,
                                  jnp.asarray(fills + t))
            np.testing.assert_array_equal(np.asarray(got[:, node]),
                                          np.asarray(logits[:, 0]))
        if path is accepted:
            want = (cache_gather_blocks(ks, jt), cache_gather_blocks(vs, jt))
    # accept the [0, 1, 3] path: move its node rows (positions fill+0/1/3)
    # to depth positions (fill+0/1/2) and compare against the pools the
    # sequential decode of that path produced, over each slot's live rows
    src = _landing(tables, fills, accepted)
    dst = _landing(tables, fills, range(len(accepted)))
    moved = (cache_gather_blocks(cache_move_rows(kp, *src, *dst), jt),
             cache_gather_blocks(cache_move_rows(vp, *src, *dst), jt))

    def cmp(g, w):
        g, w = np.asarray(g), np.asarray(w)
        for s in range(SLOTS):
            n = fills[s] + len(accepted)
            np.testing.assert_array_equal(g[:, s, :, :n], w[:, s, :, :n])
    jax.tree.map(cmp, moved, want)
