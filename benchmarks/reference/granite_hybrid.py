"""Granite 4.0-H's decoder (``model_type: granitemoehybrid``;
granite-4.0-h-micro) in straightforward ``jax.numpy`` and float32.

Written from the published ``config.json`` and the equations of ISSUE 49,
independent of ``megatron_llm_tpu/models`` and of the other references:
nothing of the program is imported, only the parameter tree it made is
read.  No kernels, no cache, no batching, no chunks: one sequence at a
time, the state-space recurrence a position at a time,
``default_matmul_precision("highest")``.

With ``RMS(x) = w * x / sqrt(mean(x^2) + eps)`` and the config's four
scalars ``e`` (``embedding_multiplier``), ``r`` (``residual_multiplier``),
``a`` (``attention_multiplier``) and ``l`` (``logits_scaling``)::

    x_0 = e * E[token]
    x <- x + r * Mixer(RMS_1(x));   x <- x + r * W_o(SiLU(g) * u),
                                    [g | u] = RMS_2(x) W_i
    logits = RMS_f(x) E^T / l

``Mixer``, by the layer's entry in ``layer_types``:

* ``attention``: ``q = u Wq`` (32 heads x 64), ``k = u Wk``, ``v = u Wv``
  (8 KV heads x 64, each serving 4 query heads), **no rotation of q or
  k** (``position_embedding_type: nope``), causal ``softmax(a q k^T) v``
  with ``a`` 1/64 where ``1/sqrt(64)`` would be usual, ``out = attn Wo``.
* ``mamba`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC <- SiLU(causal
  depthwise conv, 4 taps, + bias)``, split into ``x_t`` [heads, 64] and
  ``B_t``, ``C_t`` [groups, 128]; head ``h`` reads group ``h // (heads /
  groups)`` (one group: all 64 heads share it).  ``dt_t = softplus(dt_t +
  dt_bias)`` (no clamp), ``a_t = exp(-exp(A_log) dt_t)`` a head.  A
  head's state ``S`` (64 x 128, zero at the start): ``S <- a_t S + dt_t
  x_t (x) B_t; y_t = S C_t + D x_t``.  Then ``y <- w * RMS_group(y *
  SiLU(z))``, the mean of squares taken over each group's channels (one
  group: all 4096), and ``out = y W_out``.

Departures, each forced by reading the parameters the program made:

* The tree is the program's checkpoint layout: ``params["layers"]`` is a
  list with one entry a position of the ten-layer period, each stacked
  over the periods; a block holds ``input_norm``, a mixer (``attn``: ``wq
  wk wv wo``, or ``mamba``: ``w_in conv conv_bias A_log dt_bias D norm
  w_out``), ``post_attn_norm`` and ``mlp`` (``w_gate w_up w_down``: the
  published ``W_i`` as its two halves).  The head is the embedding table,
  ``[vocab, hidden]``, read as it lies.
* The program's names for the two kinds of layer are ``ssm`` (``mamba``
  in ``layer_types``) and ``full`` (``attention``).
* Layers are upcast to float32 one at a time, and the head is applied in
  column blocks, so that the reference fits beside the engine on the
  chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_BLOCKS = 8

# a block's mixer, by its kind
MIXER = {"ssm": "mamba", "full": "attn"}


def meta_of(model_cfg) -> tuple:
    """The sizes and scalars the reference needs, as a hashable tuple of
    pairs."""
    c = model_cfg
    return (("heads", c.num_attention_heads), ("kv_heads", c.kv_heads),
            ("head_dim", c.head_dim), ("eps", float(c.norm_eps)),
            ("vocab", c.vocab_size), ("layers", c.num_layers),
            ("pattern", tuple(c.layer_pattern)),
            ("mamba_heads", c.mamba_num_heads),
            ("mamba_head_dim", c.mamba_head_dim),
            ("groups", c.mamba_n_groups), ("state", c.mamba_state_size),
            ("embedding_multiplier", float(c.embedding_multiplier)),
            ("residual_multiplier", float(c.residual_multiplier)),
            ("attention_multiplier", float(
                c.head_dim ** -0.5 if c.attention_multiplier is None
                else c.attention_multiplier)),
            ("logits_scaling", float(c.logits_scaling)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def attention(p, x, m):
    """Causal softmax attention over ``x`` [T, hidden], no rotation, the
    scores times ``attention_multiplier``; ``p`` float32."""
    t, nq, nkv, d = x.shape[0], m["heads"], m["kv_heads"], m["head_dim"]
    q = (x @ p["wq"]).reshape(t, nkv, nq // nkv, d)
    k = (x @ p["wk"]).reshape(t, nkv, d)
    v = (x @ p["wv"]).reshape(t, nkv, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * m["attention_multiplier"]
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


def gated_mlp(p, x):
    """``W_o(SiLU(g) * u)``, ``[g | u] = x W_i``; ``p`` float32."""
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("kind", "meta"))
def _layer(stacked, i, x, *, kind, meta):
    """Layer ``i`` of the stack ``stacked`` holds (one position of the
    period, stacked over the periods)."""
    m = dict(meta)
    p = _f32(jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stacked))
    r = m["residual_multiplier"]
    u = _rms(x, p["input_norm"]["scale"], m["eps"])
    mixer = MIXER[kind]
    x = x + r * (attention(p[mixer], u, m) if mixer == "attn"
                 else mamba2(p[mixer], u, m))
    u = _rms(x, p["post_attn_norm"]["scale"], m["eps"])
    return x + r * gated_mlp(p["mlp"], u)


@functools.partial(jax.jit, static_argnames=("meta",))
def _embed(word, tokens, *, meta):
    return dict(meta)["embedding_multiplier"] * word[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("meta",))
def _head(final_norm, word, x, targets, *, meta):
    """log p(targets[t] | tokens[..t]) for every position of ``x``: the
    tied head, its logits divided by ``logits_scaling``, a block of the
    vocabulary's rows at a time."""
    m = dict(meta)
    x = _rms(x, final_norm["scale"].astype(F32), m["eps"])
    vocab = m["vocab"]
    step = -(-vocab // HEAD_BLOCKS)
    lse, picked = [], []
    for lo in range(0, vocab, step):
        hi = min(lo + step, vocab)
        logits = x @ word[lo:hi].astype(F32).T / m["logits_scaling"]
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


def logits_of(params, tokens, meta: tuple):
    """→ float32 [len(tokens), vocab]: every position's logits, whole (a
    test's: the cell's comparison is ``token_logprobs``)."""
    m = dict(meta)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, jnp.asarray(tokens, jnp.int32), meta)
        x = _rms(x, params["final_norm"]["scale"].astype(F32), m["eps"])
        word = params["embedding"]["word"][:m["vocab"]].astype(F32)
        return x @ word.T / m["logits_scaling"]


def token_logprobs(params, tokens, meta: tuple):
    """→ float32 ``[len(tokens) - 1]``: the log-probability of each token
    of one sequence given the tokens before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:-1], meta)
        return _head(params["final_norm"], params["embedding"]["word"], x,
                     tokens[1:], meta=meta)


def loss(params, sequences, meta: tuple) -> float:
    """Mean next-token cross-entropy over ``sequences``, every position
    weighted alike."""
    total, count = 0.0, 0
    for seq in sequences:
        lp = token_logprobs(params, seq, meta)
        total += float(-jnp.sum(lp))
        count += int(lp.shape[0])
    return total / count
