"""``model_type: deepseek_v3`` without a query down-projection (``q_lora_rank``
null; kanana-2-30b-a3b) in straightforward ``jax.numpy`` and float32: the
EXPANDED form of latent attention only.

Written from the published ``config.json``, the equations of ISSUE 52 and
the ``transformers`` ``deepseek_v3`` forward pass, independent of
``megatron_llm_tpu/models``: nothing of the program is imported, only the
parameter tree it made is read.  No kernels, no cache, no latent row kept,
no absorbed form, no batching: one sequence at a time, the experts in a
loop, ``default_matmul_precision("highest")``.

With ``RMS(x) = w * x / sqrt(mean(x^2) + eps)``, every layer is
``h <- h + attn(RMS(h))`` and then ``h <- h + ffn(RMS(h))``:

* ``attn`` (MLA): ``q = x Wq`` cut a head into ``q_nope`` (``nope``) and
  ``q_pe`` (``rope``); ``x Wkva`` cut into ``c_raw`` (``rank``) and
  ``k_pe`` (``rope``, ONE head shared by all); ``c = RMS(c_raw)``; ``q_pe``
  and ``k_pe`` rotated by position; ``c Wkvb`` cut a head into ``k_nope``
  (``nope``) and ``v`` (``v``); scores ``(q_nope . k_nope + q_pe . k_pe)
  / sqrt(nope + rope)``, causal softmax, ``o = P v``, ``out = concat(o)
  Wo``.
* The rotation is the published one (``rope_interleave`` true): a vector's
  adjacent pairs are first moved apart (columns 0, 2, 4 ... then 1, 3, 5
  ...) and the halves rotated against each other, ``x cos + rotate_half(x)
  sin`` at angles ``position / theta^(2i / rope)``; no scaling.
* ``ffn`` of the first ``first_dense`` layers: ``(SiLU(x Wg) * (x Wu))
  Wd``.  Of the others: ``s = sigmoid(x Wr)``; the ``top_k`` largest of
  ``s + b`` over all the router's outputs (no group limit); weights
  ``s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling``; ``y = sum_e w_e
  E_e(x) + S(x)``, every ``E_e`` and the shared ``S`` such a gated MLP
  (``S`` without a gate of its own).

Departures, each forced by reading the parameters the program made:

* The tree is the program's checkpoint layout: ``params["lead_layers"]``
  holds the leading dense layers stacked, ``params["layers"][0]`` the
  expert layers stacked; a layer holds ``input_norm``, ``attn`` (``wq
  wkv_a kv_norm wkv_b wo``, each the published matrix transposed: ``x @
  w``), ``post_attn_norm`` and ``mlp`` (``w_gate w_up w_down``, or
  ``router router_bias w_gate w_up w_down shared``).
* The tree may hold only ``held`` consecutive experts from
  ``expert_offset`` on (a chip's share); the kanana cell holds all 128.
* Layers and experts are upcast to float32 one at a time, attention runs
  in blocks of ``QUERY_BLOCK`` queries and the head in column blocks, so
  that the reference fits beside the engine on the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCKS = 8
QUERY_BLOCK = 512


def meta_of(model_cfg) -> tuple:
    """The sizes the reference needs, as a hashable tuple of pairs."""
    c = model_cfg
    return (("heads", c.num_attention_heads), ("rank", c.kv_lora_rank),
            ("nope", c.qk_nope_head_dim), ("rope", c.qk_rope_head_dim),
            ("v", c.v_head_dim), ("theta", float(c.rope_theta)),
            ("eps", float(c.norm_eps)), ("vocab", c.vocab_size),
            ("layers", c.num_layers),
            ("first_dense", c.moe_first_dense_layers),
            ("top_k", c.moe_top_k), ("held", c.num_experts),
            ("expert_offset", c.moe_expert_offset),
            ("routed_scaling", float(c.moe_routed_scaling)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope_interleave(x, theta):
    """``x`` [T, ..., rope] rotated by its position (axis 0), the
    published way: pairs moved apart first, then rotate-half."""
    t, d = x.shape[0], x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d,))
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def attention(p, x, m):
    """Latent attention, expanded, over ``x`` [T, hidden]; ``p`` float32."""
    t, H = x.shape[0], m["heads"]
    r, dn, dr, dv = m["rank"], m["nope"], m["rope"], m["v"]
    q = (x @ p["wq"]).reshape(t, H, dn + dr)
    kva = x @ p["wkv_a"]
    c = _rms(kva[:, :r], p["kv_norm"]["scale"], m["eps"])
    q_pe = rope_interleave(q[..., dn:], m["theta"])
    k_pe = rope_interleave(kva[:, r:], m["theta"])          # [T, rope]
    kv = (c @ p["wkv_b"]).reshape(t, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        scores = (jnp.einsum("thd,shd->hts", q[lo:hi, :, :dn], k_nope)
                  + jnp.einsum("thd,sd->hts", q_pe[lo:hi], k_pe)
                  ) / math.sqrt(dn + dr)
        causal = jnp.arange(lo, hi)[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hts,shd->thd", probs, v))
    return jnp.concatenate(out).reshape(t, H * dv) @ p["wo"]


def _gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(p, x, m):
    """The routed experts that ``p`` holds plus the shared one, over ``x``
    [T, hidden].  ``p`` as the program stores it (the experts are upcast
    one at a time)."""
    score = jax.nn.sigmoid(x @ p["router"].astype(F32))
    _, chosen = jax.lax.top_k(score + p["router_bias"].astype(F32),
                              m["top_k"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20) \
        * m["routed_scaling"]

    def one(e, acc):
        w_e = jnp.sum(jnp.where(chosen == e + m["expert_offset"], weight,
                                0.0), axis=-1)
        pick = lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False).astype(F32)
        return acc + w_e[:, None] * _gated_mlp(
            x, pick(p["w_gate"]), pick(p["w_up"]), pick(p["w_down"]))

    out = jnp.zeros_like(x)
    if m["held"]:
        out = jax.lax.fori_loop(0, m["held"], one, out)
    s = _f32(p["shared"])
    return out + _gated_mlp(x, s["w_gate"], s["w_up"], s["w_down"])


@functools.partial(jax.jit, static_argnames=("dense", "meta"))
def _layer(stacked, i, x, *, dense, meta):
    """Layer ``i`` of the layers ``stacked`` holds."""
    m = dict(meta)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stacked)
    h = x + attention(_f32(p["attn"]), _rms(
        x, p["input_norm"]["scale"].astype(F32), m["eps"]), m)
    a = _rms(h, p["post_attn_norm"]["scale"].astype(F32), m["eps"])
    if dense:
        return h + _gated_mlp(a, *(p["mlp"][k].astype(F32)
                                   for k in ("w_gate", "w_up", "w_down")))
    return h + moe(p["mlp"], a, m)


@functools.partial(jax.jit, static_argnames=("meta",))
def _embed(word, tokens, *, meta):
    return word[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("meta",))
def _head(final_norm, lm_head, x, targets, *, meta):
    """log p(targets[t] | tokens[..t]) for every position of ``x``."""
    m = dict(meta)
    x = _rms(x, final_norm["scale"].astype(F32), m["eps"])
    vocab = m["vocab"]
    step = -(-vocab // HEAD_BLOCKS)
    lse, picked = [], []
    for lo in range(0, vocab, step):
        hi = min(lo + step, vocab)
        logits = x @ lm_head[:, lo:hi].astype(F32)           # [T, columns]
        lse.append(jax.nn.logsumexp(logits, axis=-1))
        inside = (targets >= lo) & (targets < hi)
        idx = jnp.clip(targets - lo, 0, hi - lo - 1)
        picked.append(jnp.where(
            inside, jnp.take_along_axis(logits, idx[:, None], 1)[:, 0], 0.0))
    return sum(picked) - jax.nn.logsumexp(jnp.stack(lse), axis=0)


def hidden_states(params, tokens, meta: tuple):
    """→ float32 [len(tokens), hidden]: the stack's output before the
    final norm."""
    m = dict(meta)
    x = _embed(params["embedding"]["word"], tokens, meta=meta)
    for layer in range(m["layers"]):
        dense = layer < m["first_dense"]
        x = _layer(params["lead_layers"] if dense else params["layers"][0],
                   jnp.int32(layer - (0 if dense else m["first_dense"])), x,
                   dense=dense, meta=meta)
    return x


def token_logprobs(params, tokens, meta: tuple):
    """→ float32 ``[len(tokens) - 1]``: the log-probability of each token
    of one sequence given the tokens before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:-1], meta)
        return _head(params["final_norm"], params["lm_head"], x,
                     tokens[1:], meta=meta)
