"""Falcon's decoder in straightforward ``jax.numpy`` and float32.

Written from the published description (Falcon-7B / Falcon-40B model
cards and the ``FalconForCausalLM`` architecture their ``config.json``
names), independent of ``megatron_llm_tpu/models``: no kernels, no cache,
no batching, one sequence at a time, ``default_matmul_precision
("highest")``.

A block, with ``ln`` a LayerNorm with scale and bias::

    a = ln_attn(x)                        # Falcon-7B: one ln for both
    m = ln_mlp(x)  (Falcon-40B)  or  a    #   branches (parallel_attn)
    q, k, v = a @ Wq, a @ Wk, a @ Wv      # 1 KV head (7B) or 8 (40B)
    q, k = rotary(q), rotary(k)
    attn = softmax(q k^T / sqrt(64) + causal) v @ Wo
    x = x + attn + gelu_exact(m @ W_up) @ W_down      # no biases

then a final LayerNorm and the tied head ``logits = x @ E^T``.

Departures, each forced by reading the parameters the program made:

* Parameter names and the stacked ``[layers, ...]`` leading axis are the
  program's checkpoint layout (``wq wk wv wo w_up w_down``, ``scale``
  ``bias``); the published checkpoint fuses ``Wq Wk Wv`` into one matrix.
* Rotary pairs adjacent columns ``(2i, 2i+1)`` of a head, as the
  Megatron checkpoint layout stores them; the published ``rotate_half``
  pairs ``(i, i + 32)``.  The two are the same function under a fixed
  permutation of each head's columns of ``Wq`` and ``Wk``, and random
  weights have no preferred order.
* Layers are upcast to float32 one at a time, so 32 bfloat16 layers
  never need a float32 copy; the head is applied in row blocks of the
  embedding for the same reason.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_ROW_BLOCKS = 8


def meta_of(model_cfg) -> tuple:
    """The sizes the reference needs, as a hashable tuple of pairs."""
    return (("heads", model_cfg.num_attention_heads),
            ("kv_heads", model_cfg.kv_heads),
            ("head_dim", model_cfg.head_dim),
            ("eps", float(model_cfg.norm_eps)),
            ("theta", float(model_cfg.rope_theta)),
            ("vocab", model_cfg.vocab_size),
            ("layers", model_cfg.num_layers))


def _layernorm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rotary(x, theta):
    """``x`` [T, heads, d]: rotate each adjacent pair by position * freq."""
    t, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]      # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("meta",))
def _block(layers, i, x, *, meta):
    m = dict(meta)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False).astype(F32), layers)
    t = x.shape[0]
    nq, nkv, d = m["heads"], m["kv_heads"], m["head_dim"]
    a = _layernorm(x, p["input_norm"], m["eps"])
    mlp_in = _layernorm(x, p["mlp_norm"], m["eps"]) if "mlp_norm" in p else a
    q = _rotary((a @ p["attn"]["wq"]).reshape(t, nq, d), m["theta"])
    k = _rotary((a @ p["attn"]["wk"]).reshape(t, nkv, d), m["theta"])
    v = (a @ p["attn"]["wv"]).reshape(t, nkv, d)
    group = nq // nkv                      # query heads that share a KV head
    q = q.reshape(t, nkv, group, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nq * d)
    attn = ctx @ p["attn"]["wo"]
    mlp = jax.nn.gelu(mlp_in @ p["mlp"]["w_up"], approximate=False) \
        @ p["mlp"]["w_down"]
    return x + attn + mlp


@functools.partial(jax.jit, static_argnames=("meta",))
def _embed(word, tokens, *, meta):
    return word[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("meta",))
def _head(final_norm, word, x, targets, *, meta):
    """log p(targets[t] | tokens[..t]) for every position of ``x``."""
    m = dict(meta)
    x = _layernorm(x, jax.tree.map(lambda a: a.astype(F32), final_norm),
                   m["eps"])
    vocab = m["vocab"]
    step = -(-vocab // HEAD_ROW_BLOCKS)
    lse, picked = [], []
    for lo in range(0, vocab, step):
        hi = min(lo + step, vocab)
        logits = x @ word[lo:hi].astype(F32).T              # [T, rows]
        lse.append(jax.nn.logsumexp(logits, axis=-1))
        inside = (targets >= lo) & (targets < hi)
        idx = jnp.clip(targets - lo, 0, hi - lo - 1)
        picked.append(jnp.where(
            inside, jnp.take_along_axis(logits, idx[:, None], 1)[:, 0], 0.0))
    return sum(picked) - jax.nn.logsumexp(jnp.stack(lse), axis=0)


def token_logprobs(params, tokens, meta: tuple):
    """→ float32 ``[len(tokens) - 1]``: the log-probability of each token
    of one sequence given the tokens before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embedding"]["word"], tokens[:-1], meta=meta)
        for i in range(dict(meta)["layers"]):
            x = _block(params["layers"], jnp.int32(i), x, meta=meta)
        return _head(params["final_norm"], params["embedding"]["word"], x,
                     tokens[1:], meta=meta)


def loss(params, sequences, meta: tuple) -> float:
    """Mean next-token cross-entropy over ``sequences`` (each
    ``seq_length + 1`` tokens), every position weighted alike."""
    total, count = 0.0, 0
    for seq in sequences:
        lp = token_logprobs(params, seq, meta)
        total += float(-jnp.sum(lp))
        count += int(lp.shape[0])
    return total / count
