"""Qwen3-Next's decoder in straightforward ``jax.numpy`` and float32.

Written from the published ``config.json`` (``Qwen3NextForCausalLM``)
and the equations of ISSUE 35, independent of ``megatron_llm_tpu/models``:
nothing of the program is imported, only the parameter tree it made is
read.  No kernels, no cache, no batching, no chunks: one sequence at a
time, the delta rule as the position-by-position recurrence it is, the
experts in a loop, ``default_matmul_precision("highest")``.

With ``RMS0(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``, layer ``l`` is
*full* where ``(l + 1) % 4 == 0`` and *linear* elsewhere, and every
layer is ``h = x + Mixer(RMS0(x)); y = h + MoE(RMS0(h))``.

* full: ``[q | gate] = x Wq`` (per head: 256 of query, then 256 of
  gate), ``k = x Wk``, ``v = x Wv``; ``q``, ``k`` through ``RMS0`` over
  their 256; rotate-half rotary (theta 1e7) over the first 64 of the
  256; causal ``softmax(q k^T / 16) v``, 2 KV heads serving 8 query heads
  each; ``out = (attn * sigmoid(gate)) Wo``.
* linear (Gated DeltaNet): ``[q | k | v | z] = x Wqkvz``, ``[b | a] =
  x Wba``; ``[q | k | v] <- SiLU(causal depthwise conv, 4 taps)``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q
  and k L2-normalised (``x / sqrt(sum x^2 + 1e-6)``), ``q / sqrt(128)``;
  16 key heads serve 32 value heads, two each.  A value head's state
  ``S`` (128 x 128, zero at the start): ``S <- e^g S; d = beta (v - S^T
  k); S <- S + k (x) d; o = S^T q``.  Then ``o <- w * o / sqrt(mean(o^2)
  + eps) * SiLU(z)`` a head and ``out = o Wout``.
* MoE: ``p = softmax(x Wr)`` over the router's 512 outputs, the 10
  largest, divided by their sum; ``sum_e w_e E_e(x) + sigmoid(x ws)
  E_shared(x)``, ``E(x) = (SiLU(x Wg) * x Wu) Wd``.

Departures, each forced by reading the parameters the program made:

* The tree is the program's checkpoint layout: ``params["layers"]`` is a
  list with one entry a position of the 4-layer period, each stacked
  over the periods; ``wq wk wv wo``, ``w_qkvz w_ba conv A_log dt_bias``,
  ``router w_gate w_up w_down shared``.
* ``w_qkvz`` and ``w_ba`` are laid out flat, ``[q | k | v | z]`` and
  ``[b | a]``; the published checkpoint groups the same columns by key
  head, a fixed permutation of columns that random weights do not see.
* **The held share.**  The tree may hold only ``held`` consecutive
  experts of the router's ``router_experts``, starting at
  ``expert_offset`` (one chip of an expert-parallel pair).  The router
  keeps all its outputs and its 10 choices; the sum runs over the chosen
  experts that are held, and what the absent ones would add is left out,
  here as in the program.  With all of them held this is the whole model.
* The multi-token-prediction module of the model card is not here: the
  ``config.json`` has no key for it and the published forward pass does
  not run it.
* Layers and experts are upcast to float32 one at a time, and the head
  is applied in column blocks, so that the reference fits beside the
  engine on the chip.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCKS = 8
L2_EPS = 1e-6


def meta_of(model_cfg) -> tuple:
    """The sizes the reference needs, as a hashable tuple of pairs."""
    c = model_cfg
    return (("heads", c.num_attention_heads), ("kv_heads", c.kv_heads),
            ("head_dim", c.head_dim), ("eps", float(c.norm_eps)),
            ("theta", float(c.rope_theta)),
            ("rot", int(c.head_dim * c.rotary_percent)),
            ("vocab", c.vocab_size), ("layers", c.num_layers),
            ("pattern", tuple(c.layer_pattern)),
            ("key_heads", c.linear_num_key_heads),
            ("value_heads", c.linear_num_value_heads),
            ("key_dim", c.linear_key_head_dim),
            ("value_dim", c.linear_value_head_dim),
            ("top_k", c.moe_top_k), ("held", c.num_experts),
            ("expert_offset", c.moe_expert_offset))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms0(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _rotary(x, rot, theta):
    """``x`` [T, heads, d]: rotate-half over the first ``rot`` of ``d``."""
    t, half = x.shape[0], rot // 2
    freq = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def attention(p, x, m):
    """Gated softmax attention over ``x`` [T, hidden], ``p`` float32."""
    t, nq, nkv, d = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    qg = (x @ p["wq"]).reshape(t, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(t, nq * d)
    k = (x @ p["wk"]).reshape(t, nkv, d)
    v = (x @ p["wv"]).reshape(t, nkv, d)
    q = _rotary(_rms0(q, p["q_norm"]["scale"], m["eps"]), m["rot"],
                m["theta"])
    k = _rotary(_rms0(k, p["k_norm"]["scale"], m["eps"]), m["rot"],
                m["theta"])
    q = q.reshape(t, nkv, nq // nkv, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nq * d)
    return (ctx * jax.nn.sigmoid(gate)) @ p["wo"]


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The recurrence, a position at a time.  ``q k`` [T, H, dk], ``v``
    [T, H, dv], ``g beta`` [T, H] → ``o`` [T, H, dv]."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def gated_deltanet(p, x, m):
    """The Gated DeltaNet mixer over ``x`` [T, hidden], ``p`` float32."""
    t = x.shape[0]
    nk, nv, dk, dv = (m["key_heads"], m["value_heads"], m["key_dim"],
                      m["value_dim"])
    kd, vd = nk * dk, nv * dv
    qkvz, ba = x @ p["w_qkvz"], x @ p["w_ba"]
    mixed, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
    taps = p["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1]), F32),
                              mixed])
    mixed = jax.nn.silu(sum(padded[j:j + t] * p["conv"][j]
                            for j in range(taps)))
    q = _l2norm(mixed[:, :kd].reshape(t, nk, dk)) / math.sqrt(dk)
    k = _l2norm(mixed[:, kd:2 * kd].reshape(t, nk, dk))
    v = mixed[:, 2 * kd:].reshape(t, nv, dv)
    q, k = (jnp.repeat(a, nv // nk, axis=1) for a in (q, k))
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, nv:] + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + m["eps"])
    o = p["norm"]["scale"] * o * jax.nn.silu(z.reshape(t, nv, dv))
    return o.reshape(t, vd) @ p["w_out"]


def _expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(p, x, m):
    """The routed experts that ``p`` holds plus the shared one, over ``x``
    [T, hidden].  ``p`` as the program stores it (the experts are upcast
    one at a time)."""
    probs = jax.nn.softmax(x @ p["router"].astype(F32), axis=-1)
    weight, chosen = jax.lax.top_k(probs, m["top_k"])
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one(e, acc):
        w_e = jnp.sum(jnp.where(chosen == e + m["expert_offset"], weight,
                                0.0), axis=-1)
        pick = lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False).astype(F32)
        return acc + w_e[:, None] * _expert(
            x, pick(p["w_gate"]), pick(p["w_up"]), pick(p["w_down"]))

    out = jnp.zeros_like(x)
    if m["held"]:                    # (none held: the shared expert alone)
        out = jax.lax.fori_loop(0, m["held"], one, out)
    s = _f32(p["shared"])
    return out + jax.nn.sigmoid(x @ s["gate"]) * _expert(
        x, s["w_gate"], s["w_up"], s["w_down"])


@functools.partial(jax.jit, static_argnames=("kind", "meta"))
def _layer(stacked, i, x, *, kind, meta):
    """Layer ``i`` of the stack ``stacked`` holds (one position of the
    period, stacked over the periods)."""
    m = dict(meta)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stacked)
    a = _rms0(x, p["input_norm"]["scale"].astype(F32), m["eps"])
    if kind == "full":
        h = x + attention(_f32(p["attn"]), a, m)
    else:
        h = x + gated_deltanet(_f32(p["gdn"]), a, m)
    return h + moe(p["mlp"], _rms0(
        h, p["post_attn_norm"]["scale"].astype(F32), m["eps"]), m)


@functools.partial(jax.jit, static_argnames=("meta",))
def _embed(word, tokens, *, meta):
    return word[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("meta",))
def _head(final_norm, lm_head, x, targets, *, meta):
    """log p(targets[t] | tokens[..t]) for every position of ``x``."""
    m = dict(meta)
    x = _rms0(x, final_norm["scale"].astype(F32), m["eps"])
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
    period = m["pattern"]
    for layer in range(m["layers"]):
        j = layer % len(period)
        x = _layer(params["layers"][j], jnp.int32(layer // len(period)), x,
                   kind=period[j], meta=meta)
    return x


def token_logprobs(params, tokens, meta: tuple):
    """→ float32 ``[len(tokens) - 1]``: the log-probability of each token
    of one sequence given the tokens before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:-1], meta)
        return _head(params["final_norm"], params["lm_head"], x,
                     tokens[1:], meta=meta)
