"""Nemotron-H's decoder (``model_type: nemotron_h``; Nemotron-3-Super) in
straightforward ``jax.numpy`` and float32.

Written from the published ``config.json`` and the equations of ISSUE 44,
independent of ``megatron_llm_tpu/models``: nothing of the program is
imported, only the parameter tree it made is read.  No kernels, no cache,
no batching, no chunks: one sequence at a time, the state-space
recurrence a position at a time, the experts in a loop,
``default_matmul_precision("highest")``.

With ``RMS(x) = w * x / sqrt(mean(x^2) + eps)``, every layer is one part
under one norm, ``h <- h + f(RMS(h))``, ``f`` by the layer's kind:

* ``attention``: ``q = x Wq`` (32 heads x 128), ``k = x Wk``, ``v = x Wv``
  (2 KV heads x 128, each serving 16 query heads), **no rotation of q or
  k** (the family uses no position embedding), causal ``softmax(q k^T /
  sqrt(128)) v``, ``out = attn Wo``.
* ``mamba`` (Mamba-2): ``[z | xBC | dt] = x W_in``; ``xBC <- SiLU(causal
  depthwise conv, 4 taps, + bias)``, split into ``x_t`` [heads, 64] and
  ``B_t``, ``C_t`` [groups, 128]; head ``h`` reads group ``h // (heads /
  groups)``.  ``dt_t = softplus(dt_t + dt_bias)`` (no clamp), ``a_t =
  exp(-exp(A_log) dt_t)`` a head.  A head's state ``S`` (64 x 128, zero
  at the start): ``S <- a_t S + dt_t x_t (x) B_t; y_t = S C_t + D x_t``.
  Then ``y <- w * RMS_group(y * SiLU(z))``, the mean of squares taken
  over each group's channels, and ``out = y W_out``.
* ``mlp`` (LatentMoE): ``s = sigmoid(x Wr)`` over the router's 512
  outputs; the 22 largest of ``s + b`` are chosen; their weights are
  ``s[chosen] / (sum s[chosen] + 1e-20) * 5``.  ``u = x W_down_latent``
  (4096 -> 1024); expert ``e``: ``relu(u W1_e)^2 W2_e``; ``r = sum_e w_e
  expert_e(u)``; ``out = r W_up_latent + relu(x V1)^2 V2`` (the shared
  expert, on the full stream, no gate).

Departures, each forced by reading the parameters the program made:

* The tree is the program's checkpoint layout: ``params["layers"]`` is a
  list with one entry a position of the period, each stacked over the
  periods; a block holds ``input_norm`` and one of ``attn`` (``wq wk wv
  wo``), ``mamba`` (``w_in conv conv_bias A_log dt_bias D norm w_out``),
  ``mlp`` (``router router_bias latent_down latent_up w_up w_down
  shared``).  A ``full`` block (attention and then the feed-forward part,
  each under a norm of its own: the rehearsal's two-layer stand-in for a
  period, no kind of the published model) is both parts in turn.
* **The held share.**  The tree may hold only ``held`` consecutive
  experts of the router's ``router_experts``, starting at
  ``expert_offset`` (one chip of an expert-parallel four).  The router
  keeps all its outputs, its bias and its 22 choices; the sum ``r`` runs
  over the chosen experts that are held, goes through the shared
  up-projection as it is, and what the absent ones would add is left
  out, here as in the program.  With all of them held this is the whole
  layer.
* The multi-token-prediction module (``num_nextn_predict_layers`` 1) is
  not here: the served forward pass does not run it.
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

# the parts of a block, by its kind
PARTS = {"attention": ("attn",), "mamba": ("mamba",), "mlp": ("mlp",),
         "full": ("attn", "mlp")}
NORM_OF = {0: "input_norm", 1: "post_attn_norm"}


def meta_of(model_cfg) -> tuple:
    """The sizes the reference needs, as a hashable tuple of pairs."""
    c = model_cfg
    return (("heads", c.num_attention_heads), ("kv_heads", c.kv_heads),
            ("head_dim", c.head_dim), ("eps", float(c.norm_eps)),
            ("vocab", c.vocab_size), ("layers", c.num_layers),
            ("pattern", tuple(c.layer_pattern)),
            ("mamba_heads", c.mamba_num_heads),
            ("mamba_head_dim", c.mamba_head_dim),
            ("groups", c.mamba_n_groups), ("state", c.mamba_state_size),
            ("top_k", c.moe_top_k), ("held", c.num_experts),
            ("expert_offset", c.moe_expert_offset),
            ("routed_scaling", float(c.moe_routed_scaling)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def attention(p, x, m):
    """Causal softmax attention over ``x`` [T, hidden], no rotation; ``p``
    float32."""
    t, nq, nkv, d = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    q = (x @ p["wq"]).reshape(t, nkv, nq // nkv, d)
    k = (x @ p["wk"]).reshape(t, nkv, d)
    v = (x @ p["wv"]).reshape(t, nkv, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(t, nq * d)
    return ctx @ p["wo"]


def state_space(x, B, C, dt, A, D):
    """The recurrence, a position at a time.  ``x`` [T, H, P], ``B C``
    [T, H, N] (a head's group's), ``dt`` [T, H], ``A D`` [H] → ``y``
    [T, H, P]."""
    def step(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t

    S0 = jnp.zeros((x.shape[1], x.shape[2], B.shape[2]), F32)
    return jax.lax.scan(step, S0, (x, B, C, dt))[1]


def mamba2(p, x, m):
    """The Mamba-2 mixer over ``x`` [T, hidden], ``p`` float32."""
    t = x.shape[0]
    H, P, G, N = (m["mamba_heads"], m["mamba_head_dim"], m["groups"],
                  m["state"])
    di = H * P
    zxbcdt = x @ p["w_in"]
    z, mixed, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * G * N],
                    zxbcdt[:, di + di + 2 * G * N:])
    taps = p["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, mixed.shape[1]), F32),
                              mixed])
    mixed = jax.nn.silu(sum(padded[j:j + t] * p["conv"][j]
                            for j in range(taps)) + p["conv_bias"])
    xs = mixed[:, :di].reshape(t, H, P)
    B, C = (jnp.repeat(a.reshape(t, G, N), H // G, axis=1)
            for a in (mixed[:, di:di + G * N], mixed[:, di + G * N:]))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = state_space(xs, B, C, dt, -jnp.exp(p["A_log"]), p["D"])
    y = (y.reshape(t, di) * jax.nn.silu(z)).reshape(t, G, di // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + m["eps"])
    return (p["norm"]["scale"] * y.reshape(t, di)) @ p["w_out"]


def _expert(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def moe(p, x, m):
    """The routed experts that ``p`` holds, in their latent, plus the
    shared one, over ``x`` [T, hidden].  ``p`` as the program stores it
    (the experts are upcast one at a time)."""
    score = jax.nn.sigmoid(x @ p["router"].astype(F32))
    _, chosen = jax.lax.top_k(score + p["router_bias"].astype(F32),
                              m["top_k"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20) \
        * m["routed_scaling"]
    u = x @ p["latent_down"].astype(F32)

    def one(e, acc):
        w_e = jnp.sum(jnp.where(chosen == e + m["expert_offset"], weight,
                                0.0), axis=-1)
        pick = lambda a: jax.lax.dynamic_index_in_dim(
            a, e, keepdims=False).astype(F32)
        return acc + w_e[:, None] * _expert(u, pick(p["w_up"]),
                                            pick(p["w_down"]))

    r = jnp.zeros_like(u)
    if m["held"]:                    # (none held: the shared expert alone)
        r = jax.lax.fori_loop(0, m["held"], one, r)
    s = _f32(p["shared"])
    return r @ p["latent_up"].astype(F32) + _expert(x, s["w_up"],
                                                    s["w_down"])


@functools.partial(jax.jit, static_argnames=("kind", "meta"))
def _layer(stacked, i, x, *, kind, meta):
    """Layer ``i`` of the stack ``stacked`` holds (one position of the
    period, stacked over the periods)."""
    m = dict(meta)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stacked)
    for n, part in enumerate(PARTS[kind]):
        a = _rms(x, p[NORM_OF[n]]["scale"].astype(F32), m["eps"])
        if part == "attn":
            x = x + attention(_f32(p["attn"]), a, m)
        elif part == "mamba":
            x = x + mamba2(_f32(p["mamba"]), a, m)
        else:
            x = x + moe(p["mlp"], a, m)
    return x


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
