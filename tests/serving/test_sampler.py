"""The decode step's sampler against the three-sort form it replaced.

``_three_sort_sampler`` is a frozen copy of ``engine._sample_slots`` as it
was before the sampling arithmetic moved under a ``cond`` and onto one
sort: the oracle.  For the same seeds the sampler must return the same
tokens and bitwise the same log-probs, whatever the mix of rows, and the
decode step's program must hold its sorts inside the ``cond``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import tiny_config
from megatron_llm_tpu.generation.sampling import NEG_INF
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.serving import engine as engine_lib


def _three_sort_sampler(logits, seeds, counters, greedy, temps, top_ks,
                        top_ps, vocab: int):
    S, V = logits.shape
    pad = jnp.arange(V) >= vocab
    logits = jnp.where(pad[None, :], NEG_INF, logits)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    ranks = jnp.argsort(jnp.argsort(-scaled, axis=-1), axis=-1)
    kmask = (top_ks[:, None] > 0) & (ranks >= top_ks[:, None])
    scaled = jnp.where(kmask, NEG_INF, scaled)
    p_eff = jnp.where(top_ps > 0.0, top_ps, 1.0)[:, None]
    sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    remove_sorted = (cum - sorted_probs) > p_eff
    kept = jnp.where(remove_sorted, jnp.inf, sorted_logits)
    threshold = jnp.min(kept, axis=-1, keepdims=True)
    scaled = jnp.where(scaled < threshold, NEG_INF, scaled)

    keys = jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.key(s), c))(seeds,
                                                               counters)
    sampled = jax.vmap(
        lambda row, key: jax.random.categorical(key, row))(scaled, keys)
    tok = jnp.where(greedy, greedy_tok, sampled.astype(jnp.int32))
    lp = jax.nn.log_softmax(logits, axis=-1)
    tok_lp = jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]
    return tok, tok_lp


V = 1024


def _logits(rng, rows, *, tied=False):
    x = rng.normal(0.0, 3.0, (rows, V)).astype(np.float32)
    if tied:
        # what a bf16 head hands the sampler: few distinct values, so
        # the k-th largest is shared by several indices
        x = np.asarray(jnp.asarray(np.round(x * 2) / 2, jnp.bfloat16)
                       .astype(jnp.float32))
    return x


def _case(rows=8, vocab=V, greedy=False, temps=1.0, top_ks=0, top_ps=0.0,
          tied=False):
    """One batch of knobs; a scalar stands for every row."""
    full = lambda v, dtype: np.broadcast_to(  # noqa: E731
        np.asarray(v, dtype), (rows,)).copy()
    return dict(rows=rows, vocab=vocab, tied=tied,
                greedy=full(greedy, bool), temps=full(temps, np.float32),
                top_ks=full(top_ks, np.int32),
                top_ps=full(top_ps, np.float32))


_ROWS8_K = [1, 2, 5, 40, 300, V - 1, V, V + 7]
_ROWS8_P = [0.05, 0.3, 0.5, 0.8, 0.9, 0.95, 0.999, 1.0]
_ROWS8_T = [0.0, 0.05, 0.5, 0.7, 0.8, 1.0, 1.3, 4.0]
_ALTERNATE = [True, False] * 4

CASES = {
    "all_greedy": _case(greedy=True, temps=_ROWS8_T, top_ks=_ROWS8_K,
                        top_ps=_ROWS8_P),
    "temperature_only": _case(temps=_ROWS8_T),
    "top_k_only": _case(top_ks=_ROWS8_K),
    "top_p_only": _case(top_ps=_ROWS8_P),
    "top_k_and_top_p": _case(temps=0.8, top_ks=_ROWS8_K,
                             top_ps=_ROWS8_P[::-1]),
    "greedy_and_sampling_mixed": _case(greedy=_ALTERNATE, temps=0.7,
                                       top_ks=50, top_ps=0.9),
    "one_sampling_row": _case(greedy=[True] * 5 + [False] + [True] * 2,
                              temps=0.8, top_ks=_ROWS8_K, top_ps=0.9),
    "padded_vocabulary": _case(vocab=V - 24, temps=_ROWS8_T,
                               top_ks=_ROWS8_K, top_ps=_ROWS8_P),
    # the pad rows read NEG_INF / t, below NEG_INF itself
    "cold_top_k_padded": _case(vocab=V - 24, temps=0.25,
                               top_ks=[1, 2, 5, 40, 300, 999, 1000, 0]),
    # k reaches past the real vocabulary into pad rows below NEG_INF: the
    # masked tail is then not in descending order, and weighs nothing
    "cold_top_k_past_the_vocabulary": _case(
        vocab=V - 24, temps=0.25, top_ps=[0, 0, 0.9, 0.9, 0.5, 1.0, 0.99, 0.3],
        top_ks=[1001, 1005, 1010, 1020, 1023, 1024, 1030, 0]),
    "warm_top_k_padded": _case(vocab=V - 24, temps=4.0, top_ks=_ROWS8_K,
                               top_ps=_ROWS8_P),
    "ties_at_the_kth_value": _case(tied=True, temps=_ROWS8_T,
                                   top_ks=[1, 2, 3, 5, 8, 13, 40, 300]),
    "ties_top_k_and_top_p": _case(tied=True, vocab=V - 24,
                                  greedy=_ALTERNATE[::-1], temps=0.5,
                                  top_ks=[3, 3, 8, 8, 40, 40, 300, 300],
                                  top_ps=0.9),
    "one_slot_first_token": _case(rows=1, temps=0.7, top_ks=50,
                                  top_ps=0.9),
    "one_slot_greedy": _case(rows=1, greedy=True),
}


@pytest.mark.parametrize("name", list(CASES))
def test_sampler_gives_the_three_sort_samplers_tokens_and_logprobs(name):
    case = CASES[name]
    rows, vocab = case["rows"], case["vocab"]
    new = jax.jit(engine_lib._sample_slots, static_argnames="vocab")
    old = jax.jit(_three_sort_sampler, static_argnames="vocab")
    sampled_somewhere = False
    for trial in range(6):
        rng = np.random.default_rng(1000 * trial + len(name))
        args = (jnp.asarray(_logits(rng, rows, tied=case["tied"])),
                jnp.asarray(rng.integers(0, 2 ** 32, rows, np.uint32)),
                jnp.asarray(rng.integers(0, 500, rows, np.int32)),
                jnp.asarray(case["greedy"]), jnp.asarray(case["temps"]),
                jnp.asarray(case["top_ks"]), jnp.asarray(case["top_ps"]))
        tok, lp = new(*args, vocab=vocab)
        want_tok, want_lp = old(*args, vocab=vocab)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(want_tok))
        np.testing.assert_array_equal(np.asarray(lp).view(np.uint32),
                                      np.asarray(want_lp).view(np.uint32))
        assert np.asarray(tok).max() < vocab
        greedy_tok = np.asarray(jnp.argmax(args[0][:, :vocab], axis=-1))
        sampled_somewhere |= bool((np.asarray(tok) != greedy_tok).any())
    # the case exercises what it says: a sampling row strays from the
    # argmax somewhere in its trials, an all-greedy batch never does
    assert sampled_somewhere == (not case["greedy"].all())


def _eqns(jaxpr, inside_cond=False):
    """``(primitive name, inside a cond's branch)`` of every equation of
    ``jaxpr`` and of the programs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_cond
        inner = inside_cond or eqn.primitive.name == "cond"
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, inner)


def test_decode_step_sorts_only_inside_its_cond_and_at_most_twice():
    cfg = tiny_config(num_layers=2, vocab_size=61,
                      make_vocab_size_divisible_by=8)
    slots, t, bk = 4, 4, 8
    params = jax.eval_shape(
        lambda k: model_lib.init_params(k, cfg), jax.random.key(0))
    pools = jax.eval_shape(
        lambda: model_lib.init_kv_pool(cfg, 1 + slots * t, bk))
    vec = lambda dtype, *s: jax.ShapeDtypeStruct(  # noqa: E731
        s or (slots,), dtype)
    i32, f32 = jnp.int32, jnp.float32
    jaxpr = jax.make_jaxpr(functools.partial(
        engine_lib._decode_impl, cfg))(
            params, *pools, vec(i32, slots, t), vec(i32), vec(i32),
            vec(jnp.uint32), vec(i32), vec(jnp.bool_), vec(f32), vec(i32),
            vec(f32))
    eqns = list(_eqns(jaxpr.jaxpr))
    assert sum(name == "cond" for name, _ in eqns) == 1
    sorts = [inside for name, inside in eqns if name == "sort"]
    assert sorts and all(sorts) and len(sorts) <= 2
    # the draw's randomness is the branch's too
    assert all(inside for name, inside in eqns
               if name in ("random_bits", "threefry2x32"))
