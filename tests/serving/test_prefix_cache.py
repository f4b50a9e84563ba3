"""Automatic prefix-cache tests (CPU, tiny model).

Two layers:

- **unit** — the radix trie over pool block ids: offers adopt a retiring
  slot's blocks by ref bump (zero K/V copies), matches hand the ids back
  as a pinned lease, LRU eviction respects the block budget and returns
  pool refs, ref-count pinning protects a live request's blocks under
  pressure, and a released lease becomes evictable; plus
  ``models/model.py:cache_slot_copy`` row surgery directly.
- **engine** — the load-bearing invariant: a prefix-HIT admission must
  commit bitwise the same tokens as the one-shot ``generate_tokens``
  trajectory (the same bar every fast-path PR met), whole-prompt and
  chunked, fp32 and fully-int8, with the hit actually counted and the
  pure-hit path performing ZERO copy-on-write copies.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import (
    EngineConfig,
    PrefixCache,
    ServingEngine,
    ServingMetrics,
)
from tests.serving.one_shot import reference as _reference


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(num_layers=2, vocab_size=64,
                      make_vocab_size_divisible_by=8)
    params = model_lib.init_params(jax.random.key(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def tiny_int8(tiny):
    from megatron_llm_tpu.ops.quant import quantize_params

    cfg, params = tiny
    cfg_q = dataclasses.replace(cfg, kv_cache_quant="int8")
    return cfg_q, quantize_params(params)


def _rand_like(tree, seed):
    """Random-content cache of the same structure/dtypes: int8 leaves get
    random bytes, float leaves uniform values — recognizable rows so row
    surgery mistakes show up as value mismatches."""
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, a in enumerate(leaves):
        k = jax.random.fold_in(jax.random.key(seed), i)
        if a.dtype == jnp.int8:
            out.append(jax.random.randint(k, a.shape, -127, 128,
                                          jnp.int32).astype(jnp.int8))
        else:
            out.append(jax.random.uniform(k, a.shape,
                                          jnp.float32).astype(a.dtype))
    return jax.tree.unflatten(treedef, out)


def _rows(cache, slot, start, stop):
    """Host copy of sequence rows [start, stop) of batch row ``slot``
    for every leaf (seq axis 3)."""
    return [np.asarray(a[:, slot:slot + 1, :, start:stop])
            for a in jax.tree.leaves(cache)]


# ---------------------------------------------------------------------------
# cache_slot_copy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_cache_slot_copy_moves_exact_rows(tiny, quant):
    cfg, _ = tiny
    if quant == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_quant="int8")
    src, _ = model_lib.init_kv_cache(cfg, 2, 16)
    src = _rand_like(src, seed=1)
    dst, _ = model_lib.init_kv_cache(cfg, 3, 32)
    out = model_lib.cache_slot_copy(dst, src, dst_slot=2, dst_pos=8,
                                    src_slot=1, src_pos=4, length=8)
    for got, want in zip(_rows(out, 2, 8, 16), _rows(src, 1, 4, 12)):
        np.testing.assert_array_equal(got, want)
    # rows outside the window stay zero-initialized
    for leaf in jax.tree.leaves(out):
        assert not np.asarray(leaf[:, 2:3, :, :8]).any()
        assert not np.asarray(leaf[:, :2]).any()


# ---------------------------------------------------------------------------
# Trie units (pool block ids, no engine)
# ---------------------------------------------------------------------------

from megatron_llm_tpu.serving.block_pool import BlockPool  # noqa: E402


def _mk_cache(cfg, *, block=4, budget=8, max_seq=32, n_blocks=32,
              metrics=None):
    pool = BlockPool(cfg, n_blocks, block)
    return pool, PrefixCache(cfg, pool=pool, max_blocks=budget,
                             max_seq_len=max_seq, metrics=metrics)


def _slot_table(pool, n):
    """Emulate an admitted slot: allocate ``n`` blocks (the slot holds
    one pool ref each, as SlotAllocator.insert would)."""
    assert pool.reserve(n)
    return [pool.alloc_reserved() for _ in range(n)]


def _retire(pool, table):
    """Emulate slot release after an offer: the slot's own refs drop;
    only refs the trie (or another sharer) took keep blocks alive."""
    for bid in table:
        pool.decref(bid)


@pytest.mark.parametrize("quant", ["fp32", "int8"])
def test_offer_match_is_zero_copy_ref_bump(tiny, quant):
    """offer() adopts a retiring slot's blocks by pool incref — no K/V
    bytes move (fp32 and int8 pools alike) — and a later match hands the
    SAME pool block ids back as a pinned lease."""
    cfg, _ = tiny
    if quant == "int8":
        cfg = dataclasses.replace(cfg, kv_cache_quant="int8")
    m = ServingMetrics()
    pool, cache = _mk_cache(cfg, metrics=m)
    tokens = list(range(1, 11))  # 10 tokens -> 2 full blocks of 4
    table = _slot_table(pool, 3)  # ceil(10/4): 2 full + boundary block
    assert cache.offer(tokens, table) == 2
    assert cache.blocks == 2
    assert all(pool.ref(b) == 2 for b in table[:2])  # slot + trie
    _retire(pool, table)
    assert all(pool.ref(b) == 1 for b in table[:2])  # trie keeps them
    assert pool.used_blocks == 2                     # boundary block freed

    lease = cache.match_and_acquire(tokens)
    assert lease is not None and lease.tokens == 8
    assert lease.bids == table[:2]   # the very same pool blocks
    assert pool.cow_copies == 0      # adoption + match moved zero bytes
    cache.release(lease)
    snap = m.snapshot()
    assert snap["prefix_hits"] == 1
    assert snap["prefix_hit_tokens"]["mean"] == 8.0


def test_match_is_strictly_shorter_than_prompt(tiny):
    """A fully-cached prompt must still leave >= 1 token for the suffix
    prefill: an exactly-2-block prompt matches only 1 block."""
    cfg, _ = tiny
    pool, cache = _mk_cache(cfg)
    tokens = list(range(1, 9))  # exactly 2 blocks
    table = _slot_table(pool, 2)
    cache.offer(tokens, table)
    _retire(pool, table)
    lease = cache.match_and_acquire(tokens)
    assert lease is not None and lease.tokens == 4
    cache.release(lease)
    # shorter than one block: no usable prefix at all
    assert cache.match_and_acquire(tokens[:4]) is None


def test_match_miss_diverging_block(tiny):
    cfg, _ = tiny
    m = ServingMetrics()
    pool, cache = _mk_cache(cfg, metrics=m)
    table = _slot_table(pool, 2)
    cache.offer([1, 2, 3, 4, 5, 6, 7, 8], table)
    _retire(pool, table)
    assert cache.match_and_acquire([9, 9, 9, 9, 5, 6]) is None
    # divergence in the SECOND block still matches the first
    lease = cache.match_and_acquire([1, 2, 3, 4, 9, 9, 9, 9, 1])
    assert lease is not None and lease.tokens == 4
    cache.release(lease)
    assert m.snapshot()["prefix_misses"] == 1


def test_lru_eviction_under_budget_pressure(tiny):
    """Budget 2: offering a third distinct prefix evicts the least
    recently USED block (A was touched after B's insert, so B goes) —
    and eviction returns the block's pool ref to the free list."""
    cfg, _ = tiny
    m = ServingMetrics()
    pool, cache = _mk_cache(cfg, budget=2, metrics=m)
    A, B, C = [10] * 5, [20 + i for i in range(5)], [30] * 5
    for toks in (A, B):
        t = _slot_table(pool, 2)
        cache.offer(toks, t)
        _retire(pool, t)
    cache.release(cache.match_and_acquire(A))  # LRU-touch A
    t = _slot_table(pool, 2)
    cache.offer(C, t)
    _retire(pool, t)
    assert cache.blocks == 2
    assert pool.used_blocks == 2               # B's block is FREE again
    assert cache.match_and_acquire(B) is None          # evicted
    lease = cache.match_and_acquire(A)                 # survived
    assert lease is not None
    cache.release(lease)
    assert cache.match_and_acquire(C) is not None      # newest
    assert m.snapshot()["prefix_evicted_blocks"] == 1


def test_ref_pinning_blocks_eviction_until_release(tiny):
    """A block pinned by a live lease must survive any budget pressure;
    once released it becomes the eviction victim."""
    cfg, _ = tiny
    pool, cache = _mk_cache(cfg, budget=1)
    A, B = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
    t = _slot_table(pool, 2)
    cache.offer(A, t)
    _retire(pool, t)
    lease = cache.match_and_acquire(A)   # pin A (a live request)
    assert lease is not None
    t = _slot_table(pool, 2)
    cache.offer(B, t)                    # over budget; A is pinned
    _retire(pool, t)
    assert cache.match_and_acquire(B) is None   # B was the only victim
    held = cache.match_and_acquire(A)
    assert held is not None                     # A survived the pressure
    cache.release(held)
    cache.release(lease)                 # unpin: A is now fair game
    t = _slot_table(pool, 2)
    cache.offer(B, t)
    _retire(pool, t)
    assert cache.match_and_acquire(A) is None   # evicted post-release
    got = cache.match_and_acquire(B)
    assert got is not None
    cache.release(got)
    assert cache.blocks == 1
    assert pool.used_blocks == 1         # every evicted ref came back


def test_eviction_never_orphans_a_chain_middle(tiny):
    """Evicting a middle block would break its descendants' match path:
    with the deep chain's tail pinned, budget pressure may only evict
    OTHER unpinned leaves, never the chain's interior."""
    cfg, _ = tiny
    pool, cache = _mk_cache(cfg, budget=3)
    chain = list(range(1, 13))           # 3 blocks: parent->child->leaf
    t = _slot_table(pool, 3)
    cache.offer(chain, t)                # exactly fills budget 3
    _retire(pool, t)
    lease = cache.match_and_acquire(chain + [99])  # pin all 3
    assert lease is not None and lease.tokens == 12
    t = _slot_table(pool, 2)
    cache.offer([50] * 6, t)             # unpinned single block: evicted
    _retire(pool, t)
    assert cache.match_and_acquire([50] * 6) is None
    # the pinned chain is intact end to end
    again = cache.match_and_acquire(chain + [99])
    assert again is not None and again.tokens == 12
    cache.release(again)
    cache.release(lease)


def test_forced_eviction_under_pool_pressure(tiny):
    """evict_blocks(): the engine squeezes the trie when the POOL (not
    the trie budget) is scarce — unpinned blocks go even though the trie
    is within budget, pinned ones never do."""
    cfg, _ = tiny
    pool, cache = _mk_cache(cfg, budget=8)
    A, B = [1, 2, 3, 4, 5], [6, 7, 8, 9, 10]
    for toks in (A, B):
        t = _slot_table(pool, 2)
        cache.offer(toks, t)
        _retire(pool, t)
    lease = cache.match_and_acquire(A)   # pin A
    freed = cache.evict_blocks(2)
    assert freed == 1                    # only B was evictable
    assert cache.match_and_acquire(B) is None
    again = cache.match_and_acquire(A + [0])
    assert again is not None             # pinned A survived the squeeze
    cache.release(again)
    cache.release(lease)


# ---------------------------------------------------------------------------
# Engine integration: bitwise one-shot equivalence on the hit path
# ---------------------------------------------------------------------------


def _engine(cfg, params, **overrides):
    kw = dict(max_batch_size=2, max_seq_len=64, max_queue_size=8,
              prefill_bucket=4, prefix_cache_blocks=32)
    kw.update(overrides)
    return ServingEngine(cfg, params, EngineConfig(**kw))


def _run_seq(engine, specs):
    """Run requests one at a time (each retires — and donates its prefix —
    before the next admission) and return their token lists."""
    try:
        return [engine.submit(p, max_new_tokens=n,
                              use_eos_stop=False).result(timeout=600).tokens
                for p, n in specs]
    finally:
        engine.shutdown()


@pytest.mark.parametrize("fixture", ["tiny", "tiny_int8"])
def test_prefix_hit_bitwise_equals_cold(fixture, request):
    """The acceptance bar: a request admitted via a prefix HIT (cached
    blocks spliced + suffix-only prefill) must produce exactly the
    one-shot greedy trajectory — fp32 and fully-int8 caches."""
    cfg, params = request.getfixturevalue(fixture)
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab_size, 11).tolist()
    fork = prompt[:8] + rng.integers(1, cfg.vocab_size, 5).tolist()
    engine = _engine(cfg, params).start()
    got = _run_seq(engine, [(prompt, 8),   # cold: populates the cache
                            (prompt, 8),   # full-prefix hit (8 of 11)
                            (fork, 8)])    # shared-prefix hit, new tail
    assert got[0] == _reference(cfg, params, prompt, 8)
    assert got[1] == got[0]                # bitwise: hit == cold
    assert got[2] == _reference(cfg, params, fork, 8)
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 2 and snap["prefix_misses"] == 1
    # both hits matched the 8-token (2-block) shared prefix
    assert snap["prefix_hit_tokens"]["mean"] == 8.0
    assert snap["prefix_blocks"] > 0


def test_pure_hit_admission_performs_zero_copies(tiny):
    """The zero-copy acceptance bar: shared-prefix admissions are ref
    bumps into the slot table — ``cow_copies_total`` stays 0 across a
    whole hit-heavy sequence (decode appends land in fresh, unshared
    boundary blocks), while the pool gauges show real occupancy."""
    cfg, params = tiny
    rng = np.random.default_rng(16)
    prompt = rng.integers(1, cfg.vocab_size, 13).tolist()
    engine = _engine(cfg, params).start()
    got = _run_seq(engine, [(prompt, 6)] * 3)
    ref = _reference(cfg, params, prompt, 6)
    assert got == [ref] * 3
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 2
    assert snap["cow_copies_total"] == 0
    assert snap["blocks_used"] > 0          # trie still holds the prefix
    assert 0.0 < snap["kv_cache_util"] <= 1.0


def test_prefix_hit_bitwise_chunked(tiny):
    """Chunked admission: a hit pre-advances the chunk cursor past the
    cached blocks, so only suffix chunks run — same bitwise bar, and the
    prefill_chunks counter proves the skip actually happened."""
    cfg, params = tiny
    rng = np.random.default_rng(12)
    prompt = rng.integers(1, cfg.vocab_size, 11).tolist()
    engine = _engine(cfg, params, prefill_chunk=4).start()
    got = _run_seq(engine, [(prompt, 8), (prompt, 8)])
    ref = _reference(cfg, params, prompt, 8)
    assert got[0] == ref and got[1] == ref
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 1
    # cold: ceil(11/4)=3 chunks; hit: (12 padded - 8 cached)/4 = 1 chunk
    assert snap["prefill_chunks"] == 4


def test_prefix_cache_disabled(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, cfg.vocab_size, 11).tolist()
    engine = _engine(cfg, params, prefix_cache_blocks=0).start()
    got = _run_seq(engine, [(prompt, 6), (prompt, 6)])
    assert engine.prefix_cache is None
    ref = _reference(cfg, params, prompt, 6)
    assert got == [ref, ref]
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 0 and snap["prefix_misses"] == 0


def test_logprob_requests_bypass_the_cache(tiny):
    """Prompt logprobs need every prompt logit in one pass: those
    requests must take the cold whole-prompt prefill (and not count as
    cache lookups), while still returning correct logprobs."""
    cfg, params = tiny
    rng = np.random.default_rng(14)
    prompt = rng.integers(1, cfg.vocab_size, 9).tolist()
    engine = _engine(cfg, params).start()
    try:
        a = engine.submit(prompt, max_new_tokens=4, use_eos_stop=False,
                          return_logprobs=True).result(timeout=600)
        b = engine.submit(prompt, max_new_tokens=4, use_eos_stop=False,
                          return_logprobs=True).result(timeout=600)
    finally:
        engine.shutdown()
    assert a.tokens == b.tokens
    np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=0, atol=0)
    snap = engine.metrics.snapshot()
    assert snap["prefix_hits"] == 0 and snap["prefix_misses"] == 0


def test_pinned_blocks_survive_a_concurrent_eviction_storm(tiny):
    """Ref-count pinning at engine level: while request A decodes (its
    lease live), a wave of distinct-prefix requests overflows a tiny
    budget — A's own retirement offer and every hit must stay coherent,
    and a repeat of A's prompt afterwards still matches bitwise."""
    cfg, params = tiny
    rng = np.random.default_rng(15)
    shared = rng.integers(1, cfg.vocab_size, 9).tolist()
    engine = _engine(cfg, params, prefix_cache_blocks=2,
                     max_batch_size=2).start()
    try:
        first = engine.submit(shared, max_new_tokens=12,
                              use_eos_stop=False)
        storm = [engine.submit(
            rng.integers(1, cfg.vocab_size, 9).tolist(),
            max_new_tokens=2, use_eos_stop=False) for _ in range(6)]
        for h in storm:
            h.result(timeout=600)
        a = first.result(timeout=600)
        b = engine.submit(shared, max_new_tokens=12,
                          use_eos_stop=False).result(timeout=600)
    finally:
        engine.shutdown()
    ref = _reference(cfg, params, shared, 12)
    assert a.tokens == ref and b.tokens == ref
    snap = engine.metrics.snapshot()
    assert snap["prefix_evicted_blocks"] > 0
    # the soft budget recovers once leases drain
    assert engine.prefix_cache.blocks <= 2 + 2  # slack: last offers
