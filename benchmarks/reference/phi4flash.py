"""Phi-4-mini-flash-reasoning's decoder (``model_type: phi4flash``; the
"SambaY" decoder-hybrid-decoder of arXiv 2507.06607 with differential
attention, arXiv 2410.05258) in straightforward ``jax.numpy`` and float32.

Written from the equations of ISSUE 56, independent of
``megatron_llm_tpu/models`` and of the other references: nothing of the
program is imported, only the parameter tree it made is read.  No kernels,
no cache, no ring, no batching, no cut of rows: one sequence at a time,
EVERY row through all the layers, the state-space recurrence a position at
a time, the four attentions written out with an explicit mask,
``default_matmul_precision("highest")``.

``LN(x)`` is LayerNorm with weight and bias.  Every layer ``i`` is two
parts::

    x <- x + Mixer_i(LN_1(x));   x <- x + W_down(SiLU(g) * up),
                                 [g | up] = LN_2(x) W_gate_up
    x_0 = E[token];   logits = LN_f(x) E^T          (tied, no bias)

No rotation and no position table anywhere.  ``Mixer_i``, by the layer's
kind:

* ``ssm1`` (Mamba-1): ``[xs | z] = u W_in``; ``xs <- SiLU(causal depthwise
  conv, 4 taps, + bias)``; ``[dt_r | B_t | C_t] = xs W_x``; ``dt_t =
  softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)`` [inner, N]; a
  channel's state ``h`` [N], zero at the start: ``h <- exp(dt_t A) * h +
  dt_t * xs_t * B_t``; ``y_t = h . C_t + D * xs_t``; out ``(y * SiLU(z))
  W_out``.  ``y`` (before the gate, skip included) of the LAST such layer
  is the memory ``m`` of the gated memory units.
* ``window`` / ``full``: ``q = u W_q + b_q`` (heads x d), ``k``, ``v``
  (kv_heads x d).  Adjacent heads pair: query pair ``p`` holds heads ``2p,
  2p + 1``; key/value pair ``g`` heads ``2g, 2g + 1``; pair ``p`` reads
  ``g = p // 2``.  ``V_g = [v_{2g} | v_{2g+1}]``.  Causal, and in a
  ``window`` layer a query sees itself and the ``window - 1`` positions
  before it.  ``A_{p,j} = softmax(q_{2p+j} k_{2g+j}^T / sqrt(d)) V_g``;
  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(i)``, ``lam_init(i) =
  0.8 - 0.6 exp(-0.3 i)``; ``o_p = (1 - lam_init(i)) * RMS(A_{p,0} - lam
  A_{p,1})`` with a learned weight over the ``2 d`` columns; ``out = [o_0
  | o_1 | ...] W_o + b_o``.
* ``gmu``: ``out = (m_t * SiLU(u W_1)) W_2``, ``m_t`` the memory at the
  same position.
* ``cross``: ``q = u W_q + b_q`` only; keys and values are the ``full``
  layer's, for every position up to the current one; the rest as above
  with the layer's own ``lam`` vectors, ``lam_init(i)``, norm weight and
  ``W_o``.

Departures, each forced by reading the parameters the program made:

* The tree is the program's checkpoint layout: ``params["layers"]`` is a
  list of runs, each a list with one entry a position of the run's
  period, each stacked over the run's periods; a block holds
  ``input_norm``, a mixer (``mamba1``: ``w_in conv conv_bias w_x w_dt
  dt_bias A_log D w_out``; ``attn``: ``wq bq [wk bk wv bv] wo bo lam
  pair_norm``; ``gmu``: ``w_in w_out``), ``post_attn_norm`` and ``mlp``
  (``w_gate w_up w_down``: the published ``W_gate_up`` as its two halves,
  as the published ``W_qkv`` is ``wq | wk | wv``).  The head is the
  embedding table, ``[vocab, hidden]``, read as it lies.
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


def meta_of(model_cfg) -> tuple:
    """The sizes the reference needs, as a hashable tuple of pairs."""
    c = model_cfg
    return (("heads", c.num_attention_heads), ("kv_heads", c.kv_heads),
            ("head_dim", c.head_dim), ("eps", float(c.norm_eps)),
            ("vocab", c.vocab_size), ("window", c.sliding_window),
            ("runs", tuple((tuple(p), int(n)) for p, n in c.layer_runs)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def mamba1(p, u):
    """-> (the mixer's output [T, hidden], its memory y [T, inner])."""
    t = u.shape[0]
    di, n = p["A_log"].shape
    r = p["w_dt"].shape[0]
    xz = u @ p["w_in"]
    xs, z = xz[:, :di], xz[:, di:]
    taps = p["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, di), F32), xs])
    xs = jax.nn.silu(sum(padded[j:j + t] * p["conv"][j]
                         for j in range(taps)) + p["conv_bias"])
    sel = xs @ p["w_x"]
    dt = jax.nn.softplus(sel[:, :r] @ p["w_dt"] + p["dt_bias"])
    B, C = sel[:, r:r + n], sel[:, r + n:]
    A = -jnp.exp(p["A_log"])

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h \
            + (dt_t * x_t)[:, None] * B_t[None, :]
        return h, h @ C_t

    y = jax.lax.scan(step, jnp.zeros((di, n), F32), (xs, dt, B, C))[1]
    y = y + p["D"] * xs
    return (y * jax.nn.silu(z)) @ p["w_out"], y


def _keys_values(p, u, m):
    t = u.shape[0]
    k = (u @ p["wk"] + p["bk"]).reshape(t, m["kv_heads"], m["head_dim"])
    v = (u @ p["wv"] + p["bv"]).reshape(t, m["kv_heads"], m["head_dim"])
    return k, v


def diff_attention(p, u, k, v, layer, m, window=0):
    """Differential attention of the queries of ``u`` [T, hidden] on the
    keys and values ``k v`` [T, kv_heads, d] of the same positions."""
    t, d = u.shape[0], m["head_dim"]
    q = (u @ p["wq"] + p["bq"]).reshape(t, m["heads"], d)
    pos = jnp.arange(t)
    keep = pos[None, :] <= pos[:, None]
    if window:
        keep = keep & (pos[None, :] >= pos[:, None] - (window - 1))
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lq1, lk1, lq2, lk2 = p["lam"]
    lam = jnp.exp(lq1 @ lk1) - jnp.exp(lq2 @ lk2) + lam0
    out = []
    for pair in range(m["heads"] // 2):
        g = pair // 2
        V = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        A = []
        for j in range(2):
            scores = q[:, 2 * pair + j] @ k[:, 2 * g + j].T / jnp.sqrt(
                jnp.asarray(d, F32))
            probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
            A.append(probs @ V)
        x = A[0] - lam * A[1]
        x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + m["eps"])
        out.append((1.0 - lam0) * x * p["pair_norm"]["scale"])
    return jnp.concatenate(out, axis=-1) @ p["wo"] + p["bo"]


def gated_mlp(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnames=("kind", "meta"))
def _layer(stacked, i, layer, x, memory, k, v, *, kind, meta):
    """Layer number ``layer`` of the stack, the ``i``-th of what
    ``stacked`` holds -> ``(x, memory, k, v)``: what it makes of them."""
    m = dict(meta)
    p = _f32(jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stacked))
    u = _ln(x, p["input_norm"], m["eps"])
    layer = layer.astype(F32)
    if kind == "ssm1":
        out, memory = mamba1(p["mamba1"], u)
    elif kind == "gmu":
        out = (memory * jax.nn.silu(u @ p["gmu"]["w_in"])) @ p["gmu"]["w_out"]
    elif kind == "cross":
        out = diff_attention(p["attn"], u, k, v, layer, m)
    else:
        own_k, own_v = _keys_values(p["attn"], u, m)
        out = diff_attention(p["attn"], u, own_k, own_v, layer, m,
                             m["window"] if kind == "window" else 0)
        if kind == "full":
            k, v = own_k, own_v
    x = x + out
    x = x + gated_mlp(p["mlp"], _ln(x, p["post_attn_norm"], m["eps"]))
    return x, memory, k, v


def hidden_states(params, tokens, meta: tuple):
    """-> float32 [len(tokens), hidden]: the stack's output before the
    final norm."""
    m = dict(meta)
    x = params["embedding"]["word"][tokens].astype(F32)
    t = x.shape[0]
    memory = jnp.zeros((t, 1), F32)
    k = v = jnp.zeros((t, m["kv_heads"], m["head_dim"]), F32)
    layer = 0
    for (period, times), trees in zip(m["runs"], params["layers"]):
        for i in range(times):
            for kind, stacked in zip(period, trees):
                if kind == "ssm1":      # (its memory is of another width)
                    memory = jnp.zeros((t, 1), F32)
                x, memory, k, v = _layer(
                    stacked, jnp.int32(i), jnp.int32(layer), x, memory, k, v,
                    kind=kind, meta=meta)
                layer += 1
    return x


@functools.partial(jax.jit, static_argnames=("meta",))
def _head(final_norm, word, x, targets, *, meta):
    """log p(targets[t] | tokens[..t]) for every position of ``x``: the
    tied head, a block of the vocabulary's rows at a time."""
    m = dict(meta)
    x = _ln(x, _f32(final_norm), m["eps"])
    vocab = m["vocab"]
    step = -(-vocab // HEAD_BLOCKS)
    lse, picked = [], []
    for lo in range(0, vocab, step):
        hi = min(lo + step, vocab)
        logits = x @ word[lo:hi].astype(F32).T
        lse.append(jax.nn.logsumexp(logits, axis=-1))
        inside = (targets >= lo) & (targets < hi)
        idx = jnp.clip(targets - lo, 0, hi - lo - 1)
        picked.append(jnp.where(
            inside, jnp.take_along_axis(logits, idx[:, None], 1)[:, 0], 0.0))
    return sum(picked) - jax.nn.logsumexp(jnp.stack(lse), axis=0)


def logits_of(params, tokens, meta: tuple):
    """-> float32 [len(tokens), vocab]: every position's logits, whole (a
    test's: the cell's comparison is ``token_logprobs``)."""
    m = dict(meta)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, jnp.asarray(tokens, jnp.int32), meta)
        x = _ln(x, _f32(params["final_norm"]), m["eps"])
        return x @ params["embedding"]["word"][:m["vocab"]].astype(F32).T


def token_logprobs(params, tokens, meta: tuple):
    """-> float32 ``[len(tokens) - 1]``: the log-probability of each token
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
