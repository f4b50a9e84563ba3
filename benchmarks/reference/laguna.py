"""``model_type: laguna`` (poolside/Laguna-XS.2) in straightforward
``jax.numpy`` and float32.

Written from the published ``config.json`` and the equations of ISSUE 58,
independent of ``megatron_llm_tpu/models``: nothing of the program is
imported, only the parameter tree it made is read.  No kernels, no cache,
no ring, no pool, no batching: one sequence at a time, every key a query
may see under an explicit mask, the experts in a loop,
``default_matmul_precision("highest")``.

With ``RMS(x) = w * x / sqrt(mean(x^2) + eps)``, every layer is
``h <- h + attn(RMS(h))`` and then ``h <- h + ffn(RMS(h))``; the logits are
``RMS(h) W_head`` (untied).  No bias anywhere.  Layer ``i`` is of the kind
``kinds[i]``, ``"full"`` or ``"window"``:

* ``attn``, with ``u`` the normed input and ``H`` the KIND's head count
  (48 full, 64 window; 8 key/value heads of 128 both): ``q = u Wq`` cut
  into ``H`` heads, ``k = u Wk``, ``v = u Wv`` into 8; query head ``j``
  reads key/value head ``j // (H / 8)``.  ``q`` and ``k`` are rotated
  (below); scores ``q k^T / sqrt(128)`` under the causal mask, and in a
  window layer under the window's too (a query at ``t`` sees keys ``t -
  window + 1 .. t``); ``A_j = softmax(.) v``.  The gate: ``g = sigmoid(u
  Wg)``, ``Wg`` hidden -> ``H``, one scalar a head; ``out = [g_j A_j]_j
  Wo``.
* The rotation is rotate-half over the first ``R`` dimensions of a head
  (dimension ``m`` pairs with ``m + R / 2``), the rest pass through: ``x
  cos + rotate_half(x) sin`` at angles ``position * inv_freq``.  A window
  layer: ``R = 128``, ``inv_freq_m = theta_w^(-2m / 128)``, nothing else.  A
  full layer: ``R = 64``, ``inv_freq`` YaRN's over those 64 dimensions
  (``yarn_inv_freq``: the extrapolated frequency where a dimension turns
  more than ``beta_fast`` times over the original length, the
  interpolated one, divided by ``factor``, where fewer than ``beta_slow``,
  a linear ramp between), and cos and sin both multiplied by
  ``attention_factor``: the rotated half of a score carries its square,
  the unrotated half does not.
* ``ffn`` of the first ``first_dense`` layers: ``(SiLU(u Wg) * (u Wu))
  Wd``.  Of the others: ``s = sigmoid(u Wr)``; the ``top_k`` largest of ``s
  + b`` over all the router's outputs; weights ``s[chosen] / (sum
  s[chosen] + 1e-20) * routed_scaling``; ``y = sum_e w_e E_e(u) + S(u)``,
  every ``E_e`` and the shared ``S`` such a gated MLP, ``S`` with weight 1.

Departures, each forced by reading the parameters the program made:

* The tree is the program's checkpoint layout: ``params["lead_layers"]``
  holds the leading dense layers stacked, ``params["layers"][j]`` the
  layers at position ``j`` of the period stacked over the periods; a layer
  holds ``input_norm``, ``attn`` (``wq wk wv wo wg``, each the published
  matrix transposed: ``x @ w``), ``post_attn_norm`` and ``mlp`` (``w_gate
  w_up w_down``, or ``router router_bias w_gate w_up w_down shared``).
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
QUERY_BLOCK = 256


def meta_of(model_cfg) -> tuple:
    """The sizes the reference needs, as a hashable tuple of pairs."""
    c = model_cfg
    d = c.head_dim
    w_theta, w_share, w_factor = c.window_rope or (
        c.rope_theta, c.rotary_percent, c.rope_scaling_factor)
    assert w_factor == 1.0, "a window layer's rotation is not scaled"
    lead = c.moe_first_dense_layers
    kinds = (c.lead_layer_kind or c.layer_pattern[0],) * lead \
        + tuple(c.layer_pattern) * ((c.num_layers - lead)
                                    // len(c.layer_pattern))
    return (("kinds", kinds), ("period", len(c.layer_pattern)),
            ("heads", c.num_attention_heads),
            ("window_heads", c.window_attention_heads
             or c.num_attention_heads),
            ("kv_heads", c.kv_heads), ("head_dim", d),
            ("window", c.sliding_window),
            ("theta", float(c.rope_theta)),
            ("rot", int(d * c.rotary_percent)),
            ("factor", float(c.rope_scaling_factor)),
            ("original", c.rope_original_max_positions),
            ("beta_fast", float(c.rope_beta_fast)),
            ("beta_slow", float(c.rope_beta_slow)),
            ("attention_factor", c.rope_attention_factor),
            ("window_theta", float(w_theta)),
            ("window_rot", int(d * w_share)),
            ("eps", float(c.norm_eps)), ("vocab", c.vocab_size),
            ("first_dense", lead), ("top_k", c.moe_top_k),
            ("held", c.num_experts),
            ("expert_offset", c.moe_expert_offset),
            ("routed_scaling", float(c.moe_routed_scaling)))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _rms(x, w, eps):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_inv_freq(rot: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's frequencies over ``rot`` rotated dimensions (arXiv
    2309.00071, as ``transformers`` computes them)."""
    def turns_at(n):          # the dimension that turns n times
        return rot * math.log(original / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), rot - 1)
    if low == high:
        high += 0.001
    extrapolated = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotate(x, inv_freq, scale: float):
    """``x`` [T, heads, d] by its position (axis 0): the first ``2 x
    len(inv_freq)`` dimensions rotate-half, the rest as they are."""
    t, rot = x.shape[0], 2 * inv_freq.shape[0]
    ang = jnp.arange(t, dtype=F32)[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    xr = x[..., :rot]
    xr = xr * (scale * jnp.cos(ang)) + _rotate_half(xr) * (
        scale * jnp.sin(ang))
    return jnp.concatenate([xr, x[..., rot:]], axis=-1)


def attention(p, u, m, kind: str):
    """A layer's attention over ``u`` [T, hidden]; ``p`` float32."""
    t, d, G = u.shape[0], m["head_dim"], m["kv_heads"]
    window = kind == "window"
    H = m["window_heads"] if window else m["heads"]
    q = (u @ p["wq"]).reshape(t, H, d)
    k = (u @ p["wk"]).reshape(t, G, d)
    v = (u @ p["wv"]).reshape(t, G, d)
    if window:
        rot = m["window_rot"]
        inv_freq = 1.0 / m["window_theta"] ** (
            jnp.arange(0, rot, 2, dtype=F32) / rot)
        scale = 1.0
    else:
        rot = m["rot"]
        if m["factor"] != 1.0:
            inv_freq = yarn_inv_freq(rot, m["theta"], m["factor"],
                                     m["original"], m["beta_fast"],
                                     m["beta_slow"])
            scale = (m["attention_factor"] if m["attention_factor"]
                     is not None else 0.1 * math.log(m["factor"]) + 1.0)
        else:
            inv_freq = 1.0 / m["theta"] ** (
                jnp.arange(0, rot, 2, dtype=F32) / rot)
            scale = 1.0
    q, k = rotate(q, inv_freq, scale), rotate(k, inv_freq, scale)
    # query head j reads key/value head j // (H / G)
    q = q.reshape(t, G, H // G, d)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, t)
        scores = jnp.einsum("tgid,sgd->gits", q[lo:hi], k) / math.sqrt(d)
        at, keys = jnp.arange(lo, hi)[:, None], jnp.arange(t)[None, :]
        seen = keys <= at
        if window:
            seen = seen & (keys > at - m["window"])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("gits,sgd->tgid", probs, v))
    a = jnp.concatenate(out).reshape(t, H, d)
    gate = jax.nn.sigmoid(u @ p["wg"])                      # [T, H]
    return (gate[..., None] * a).reshape(t, H * d) @ p["wo"]


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
        pick = lambda a: jax.lax.dynamic_index_in_dim(  # noqa: E731
            a, e, keepdims=False).astype(F32)
        return acc + w_e[:, None] * _gated_mlp(
            x, pick(p["w_gate"]), pick(p["w_up"]), pick(p["w_down"]))

    out = jnp.zeros_like(x)
    if m["held"]:
        out = jax.lax.fori_loop(0, m["held"], one, out)
    s = _f32(p["shared"])
    return out + _gated_mlp(x, s["w_gate"], s["w_up"], s["w_down"])


@functools.partial(jax.jit, static_argnames=("dense", "kind", "meta"))
def _layer(stacked, i, x, *, dense, kind, meta):
    """Layer ``i`` of the layers ``stacked`` holds."""
    m = dict(meta)
    p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), stacked)
    h = x + attention(_f32(p["attn"]), _rms(
        x, p["input_norm"]["scale"].astype(F32), m["eps"]), m, kind)
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
    """-> float32 [len(tokens), hidden]: the stack's output before the
    final norm."""
    m = dict(meta)
    x = _embed(params["embedding"]["word"], tokens, meta=meta)
    for layer, kind in enumerate(m["kinds"]):
        dense = layer < m["first_dense"]
        if dense:
            stacked, i = params["lead_layers"], layer
        else:
            at = layer - m["first_dense"]
            stacked, i = params["layers"][at % m["period"]], \
                at // m["period"]
        x = _layer(stacked, jnp.int32(i), x, dense=dense, kind=kind,
                   meta=meta)
    return x


def logits_of(params, tokens, meta: tuple):
    """-> float32 [len(tokens), vocab]: every position's logits (a test's
    small vocabulary)."""
    m = dict(meta)
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _rms(hidden_states(params, tokens, meta),
                 params["final_norm"]["scale"].astype(F32), m["eps"])
        return (x @ params["lm_head"].astype(F32))[:, :m["vocab"]]


def token_logprobs(params, tokens, meta: tuple):
    """-> float32 ``[len(tokens) - 1]``: the log-probability of each token
    of one sequence given the tokens before it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = hidden_states(params, tokens[:-1], meta)
        return _head(params["final_norm"], params["lm_head"], x,
                     tokens[1:], meta=meta)


def loss(params, tokens, meta: tuple):
    """-> the mean negative log-likelihood of one sequence's tokens."""
    return -jnp.mean(token_logprobs(params, tokens, meta))
