"""The serving decode step and its weights (CPU, tiny model).

A decode step reads every weight once, as the parameter tree stores it
(docs/inference.md).  What the chip's compiler makes of that is audited
on its HLO (tests/obs/test_hlo_audit.py, tests/kernels/test_tpu_compile.py,
tests_tpu/); here are the parts that hold on any backend: the step's logits
are ``model.forward``'s on both routes, tied head or untied, at a hidden
size that is no multiple of 128 as Falcon-7B's 71 x 64 is not, and on a
Llama-like stack under every weight precision and over an int8 pool, the
slots at one fill or each at its own; and the program the step is traced
into transposes no weight.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import PositionEmbeddingType, tiny_config
from megatron_llm_tpu.models import model as model_lib
from megatron_llm_tpu.ops import attention as attn_ops
from megatron_llm_tpu.ops import quant

BLOCK = 128     # the pool block the paged route asks for

# hidden 9 x 64 = 576 = 4.5 x 128: one LayerNorm over parallel attention +
# MLP with one KV head (Falcon-7B's block), and two LayerNorms with grouped
# KV heads (Falcon-40B's)
_BLOCKS = {
    "mqa_parallel": dict(num_kv_heads=1),
    "gqa_two_norms": dict(num_kv_heads=3, parallel_layernorm=True),
}


def _falcon_like(block: str, tied: bool):
    cfg = tiny_config(
        hidden_size=576, num_attention_heads=9, ffn_hidden_size=320,
        vocab_size=96, num_layers=2, max_position_embeddings=BLOCK,
        norm_type="layernorm", activation="gelu_exact", parallel_attn=True,
        position_embedding_type=PositionEmbeddingType.ROTARY,
        tie_embed_logits=tied, **_BLOCKS[block])
    return cfg, model_lib.init_params(jax.random.key(5), cfg)


# weights (a policy's name, an int4 group size) and the pool, on the
# Llama-like stack: what an engine with quantised weights or an int8 pool
# hands the step
_PRECISIONS = {
    "int8_weights": ("int8", None, "none"),
    "int8_pool": (None, None, "int8"),
    "int8_both": ("int8", None, "int8"),
    "int4_g64": ("int4", 64, "none"),
    "int4_g128": ("int4", 128, "none"),
    "mixed": ("mixed", 128, "none"),
}
# a pool's rows are rounded to a 127th of their largest value
# (tests/ops/test_kv_quant.py); weights are the same numbers on both sides
_POOL_TOL = {"none": 2e-4, "int8": 3e-2}


def _llama_like(precision=None):
    """RMSNorm, SwiGLU, rotary, grouped KV heads, untied head: hidden
    256 = 4 x 64, two groups of 128 rows into every projection."""
    policy, group, kvq = _PRECISIONS.get(precision, (None, None, "none"))
    cfg = tiny_config(
        hidden_size=256, num_attention_heads=4, num_kv_heads=2,
        ffn_hidden_size=384, vocab_size=96, num_layers=2,
        max_position_embeddings=BLOCK, norm_type="rmsnorm",
        activation="swiglu", tie_embed_logits=False,
        position_embedding_type=PositionEmbeddingType.ROTARY,
        kv_cache_quant=kvq)
    params = model_lib.init_params(jax.random.key(5), cfg)
    if policy:
        pol = quant.resolve_policy(policy)
        if group:
            pol = dataclasses.replace(pol, group_size=group)
        params = quant.quantize_params(params, pol)
    return cfg, params


def _decode(cfg, params, tokens, late=None):
    """``tokens`` [b, n] one position a step through
    ``forward_cached_paged`` from an empty pool, a block a slot →
    logits [b, n, vocab].  ``late`` [b]: slot s takes its first position
    ``late[s]`` steps after the first step, so that every step sees each
    slot at its own fill (a slot that waits feeds position 0 again)."""
    b, n = tokens.shape
    late = np.zeros((b,), np.int32) if late is None else np.asarray(late)
    pools = model_lib.init_kv_pool(cfg, b + 1, BLOCK)
    tables = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    step = jax.jit(lambda p, t, k, v, f: model_lib.forward_cached_paged(
        cfg, p, t, k, v, tables, f))
    out = []
    for i in range(n + int(late.max())):
        fills = np.clip(i - late, 0, n - 1).astype(np.int32)
        logits, *pools = step(
            params, jnp.take_along_axis(tokens, fills[:, None], axis=1),
            *pools, jnp.asarray(fills))
        out.append(logits[:, 0])
    steps = jnp.stack(out)                       # [steps, b, vocab]
    at = late[:, None] + np.arange(n)[None, :]   # the step of [s, position]
    return steps[at, np.arange(b)[:, None]]


# every stack the step is held to: Falcon's two blocks, tied head and
# untied; the Llama-like block under each precision; and that block with
# each slot at its own fill in every step (the engine's slot batch: a fill
# vector, not a scalar), over a plain and an int8 pool
_STACKS = {
    **{f"{'tied' if tied else 'untied'}-{block}":
       (lambda block=block, tied=tied: (*_falcon_like(block, tied), None))
       for tied in (True, False) for block in _BLOCKS},
    **{f"llama-{precision}":
       (lambda precision=precision: (*_llama_like(precision), None))
       for precision in _PRECISIONS},
    "llama-own_fills": lambda: (*_llama_like(), [2, 0, 4]),
    "llama-own_fills-int8_pool": lambda: (*_llama_like("int8_pool"),
                                          [2, 0, 4]),
}


@functools.lru_cache(maxsize=None)
def _built(stack):
    """A stack's configuration, parameters and late slots, its tokens and
    ``model.forward``'s logits of them: once for both routes."""
    cfg, params, late = _STACKS[stack]()
    tokens = jax.random.randint(jax.random.key(6), (3, 5), 1, cfg.vocab_size)
    return cfg, params, late, tokens, model_lib.forward(cfg, params, tokens)


@pytest.mark.parametrize("route", ["gather", "paged"])
@pytest.mark.parametrize("stack", list(_STACKS))
def test_decode_step_logits_are_forwards(monkeypatch, stack, route):
    """Both routes' layer scan (``_scan_layers_cached``: the gather
    route's dense view, the paged route's kernel in interpret mode
    behind a backend reported as a TPU) give ``model.forward``'s logits
    at every position: over the same quantised parameters under each
    precision policy, and within the int8 pool's rounding of them where
    the pool is quantised."""
    cfg, params, late, tokens, want = _built(stack)
    if route == "paged":
        monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    pool = model_lib.init_kv_pool(cfg, 2, BLOCK)[0]
    assert model_lib.paged_decode_eligible(cfg, pool) == (route == "paged")
    got = _decode(cfg, params, tokens, late)
    assert got.dtype == want.dtype == jnp.float32
    tol = _POOL_TOL[cfg.kv_cache_quant]
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("route", ["gather", "paged"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_decode_step_transposes_no_weight(monkeypatch, tied, route):
    """Backend-independent: the jaxpr of a decode step holds no
    ``transpose`` whose operand has the shape of a weight or of one
    layer's slice of a stacked weight — a transposed weight in the
    program is a second pass over it wherever the compiler does not
    fold it away (the tied head was ``x @ word.T``)."""
    cfg, params = _falcon_like("mqa_parallel", tied)
    if route == "paged":
        monkeypatch.setattr(attn_ops, "_backend", lambda: "tpu")
    b = 3
    pools = model_lib.init_kv_pool(cfg, b + 1, BLOCK)
    tables = jnp.arange(1, b + 1, dtype=jnp.int32)[:, None]
    jaxpr = jax.make_jaxpr(
        lambda p, t, k, v, f: model_lib.forward_cached_paged(
            cfg, p, t, k, v, tables, f))(
        params, jnp.ones((b, 1), jnp.int32), *pools,
        jnp.zeros((b,), jnp.int32))
    weights = set()
    for leaf in jax.tree.leaves(params):
        if leaf.ndim >= 2:
            weights.update({leaf.shape, leaf.shape[1:]})
    assert params["embedding"]["word"].shape in weights
    eqns = list(_equations(jaxpr.jaxpr))
    assert any(e.primitive.name == "dot_general" for e in eqns)
    transposed = [e.invars[0].aval.shape for e in eqns
                  if e.primitive.name == "transpose"
                  and e.invars[0].aval.shape in weights]
    assert not transposed, transposed


def test_few_token_lookup_reads_rows_not_a_gather():
    """``embedding_lookup`` of a decode step's few tokens is one
    ``dynamic_slice`` a token (a gather makes XLA:TPU re-lay a table it
    stores vocabulary-minor, in every call); a prefill's many tokens stay
    a gather; both give ``word[tokens]``, an index out of range clamped."""
    from megatron_llm_tpu.ops.quant import embedding_lookup

    word = jax.random.normal(jax.random.key(0), (96, 40))
    few = jnp.array([[0], [95], [7], [200]], jnp.int32)
    many = jax.random.randint(jax.random.key(1), (2, 48), 0, 96)
    for tokens, prim in ((few, "dynamic_slice"), (many, "gather")):
        np.testing.assert_array_equal(embedding_lookup(word, tokens),
                                      word[tokens])
        names = {e.primitive.name for e in _equations(jax.make_jaxpr(
            lambda w, t: embedding_lookup(w, t))(word, tokens).jaxpr)}
        assert prim in names
        assert not names & ({"dynamic_slice", "gather"} - {prim})
