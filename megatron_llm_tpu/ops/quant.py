"""Weight-only quantization for serving: int8, group-wise int4, and a
per-tensor precision policy.

The reference's low-precision story is optional TransformerEngine FP8 on
H100 (megatron/model/transformer.py:932-951, off by default).  The TPU
equivalent worth having first is *weight-only residency for decode*:
bs=1..8 generation streams every weight once a token, so halving (int8)
or quartering (int4) weight bytes cuts the bytes a decode step must read,
and the MXU reads int8 natively.  Training
stays bf16/fp32 — this is a serving transform, applied after load.

Three leaf schemes, all plain dict subtrees so pytree machinery
(sharding specs, checkpointing) needs no custom node class:

- **int8 per-output-channel** (the original scheme): ``w ≈ q * scale``
  with ``q ∈ int8[-127, 127]``, ``scale = max|w_col| / 127`` per output
  column — ``{"q": int8 [in, out], "scale": fp32 [out]}``.
- **int4 group-wise** (AWQ/GPTQ-style): the input axis splits into
  groups of ``group_size`` rows, each with its own per-column scale —
  ``{"q": int4-packed int8 [in/2, out], "scale": fp32 [n_groups, out]}``
  with ``q ∈ [-7, 7]`` two-nibbles-per-byte along the input axis.  The
  two forms are distinguished structurally: an int8 scale *drops* the
  input axis (``scale.ndim == q.ndim - 1``) while an int4 scale keeps it
  as the group axis (``scale.ndim == q.ndim``).
- **int8 per-row embedding**: ``{"q": int8 [v, h], "scale": fp32 [v]}``
  consumed by :func:`embedding_lookup`, which dequantizes only the
  gathered rows — the table stays int8-resident in HBM.

:class:`PrecisionPolicy` names which class (attention projections / MLP
projections / embedding table) gets which scheme; ``quantize_params`` /
``quantize_specs`` honor it end-to-end.  Norm scales, biases, and the
lm_head always stay unquantized (fp logits matter for sampling quality).

``mm(x, w)`` is the single matmul dispatch point used by the transformer
blocks: plain arrays go straight to ``@``; quantized subtrees dequantize
into the matmul (XLA fuses the convert+scale into the dot read, keeping
the HBM traffic at the quantized width).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

QUANT_KEYS = ("q", "scale")

DEFAULT_GROUP_SIZE = 128


def is_quantized(w) -> bool:
    return isinstance(w, dict) and set(w) == set(QUANT_KEYS)


def is_quantized_int4(w) -> bool:
    """int4 group-wise leaves keep the input axis on the scale (as the
    group axis); int8 per-channel scales drop it."""
    return is_quantized(w) and w["scale"].ndim == w["q"].ndim


def weight_bits(w) -> int:
    """0 (plain array), 8, or 4 — the HBM-resident width of ``w``."""
    if not is_quantized(w):
        return 0
    return 4 if is_quantized_int4(w) else 8


def int4_group_size(qw: dict) -> int:
    """Rows per scale group of an int4 leaf (q is packed two-per-byte)."""
    return 2 * qw["q"].shape[-2] // qw["scale"].shape[-2]


def quantize_weight(w: jax.Array) -> dict:
    """[in, out] (or layer-stacked [L, in, out]) weight →
    {"q": int8, "scale": fp32 [out] / [L, out]} — symmetric,
    per-output-channel (reduction over the input axis, -2)."""
    w32 = jnp.asarray(w, jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w32 / scale[..., None, :]),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def pack_int4(q: jax.Array) -> jax.Array:
    """int8 values in [-8, 7], [..., in, out] → packed [..., in/2, out]:
    even input row in the low nibble, odd row in the high nibble of each
    byte."""
    *lead, rows, cols = q.shape
    pairs = q.reshape(*lead, rows // 2, 2, cols).astype(jnp.int32)
    word = ((pairs[..., 1, :] & 0xF) << 4) | (pairs[..., 0, :] & 0xF)
    return jax.lax.bitcast_convert_type(
        word.astype(jnp.uint8), jnp.int8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`: [..., in/2, out] → int8 [..., in, out].

    Sign extension via int32 shifts (``(p << 28) >> 28``) rather than
    nibble-table lookups."""
    p32 = packed.astype(jnp.int32)
    low = (p32 << 28) >> 28
    high = (p32 << 24) >> 28
    *lead, r2, cols = packed.shape
    return jnp.stack([low, high], axis=-2).reshape(
        *lead, 2 * r2, cols).astype(jnp.int8)


def quantize_weight_int4(w: jax.Array,
                         group_size: int = DEFAULT_GROUP_SIZE) -> dict:
    """[in, out] (or layer-stacked [L, in, out]) weight → int4 group-wise
    ``{"q": packed int8 [..., in/2, out], "scale": fp32 [..., n_groups,
    out]}`` — symmetric, one scale per ``group_size`` input rows per
    output column (``scale = max|w_group_col| / 7``)."""
    w32 = jnp.asarray(w, jnp.float32)
    *lead, rows, cols = w32.shape
    if rows % group_size or rows % 2:
        raise ValueError(
            f"int4 group quantization needs group_size ({group_size}) to "
            f"divide the (even) input dim, got {rows}")
    grp = w32.reshape(*lead, rows // group_size, group_size, cols)
    scale = jnp.max(jnp.abs(grp), axis=-2) / 7.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(grp / scale[..., None, :]), -7, 7)
    q = q.reshape(*lead, rows, cols).astype(jnp.int8)
    return {"q": pack_int4(q), "scale": scale}


def dequantize_weight(qw: dict, dtype=jnp.float32) -> jax.Array:
    if is_quantized_int4(qw):
        q = unpack_int4(qw["q"]).astype(jnp.float32)
        scale = qw["scale"]
        *lead, rows, cols = q.shape
        ng = scale.shape[-2]
        deq = q.reshape(*lead, ng, rows // ng, cols) * scale[..., None, :]
        return deq.reshape(*lead, rows, cols).astype(dtype)
    return (qw["q"].astype(jnp.float32)
            * qw["scale"][..., None, :]).astype(dtype)


def mm(x: jax.Array, w) -> jax.Array:
    """``x @ w`` for plain or quantized ``w``.

    int8 path: dequantize in the compute dtype of ``x`` — the scale
    multiply is applied to the *output* (columns), which is algebraically
    identical to scaling the weight but keeps the inner dot int8→x.dtype
    with a [out]-vector epilogue XLA fuses for free.

    int4 path: group scales vary along the contraction axis, so they
    cannot ride as an output epilogue — the weight dequantizes into the
    dot instead (XLA fuses unpack+scale into the dot read; HBM traffic
    stays at the packed half-byte width).
    """
    if is_quantized(w):
        if is_quantized_int4(w):
            return x @ dequantize_weight(w, x.dtype)
        y = x @ w["q"].astype(x.dtype)
        return y * w["scale"].astype(x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# int8 TRAINING matmuls (reference: the optional TransformerEngine FP8 path,
# megatron/model/transformer.py:932-951 — mixed-precision GEMMs behind a
# flag, fp32 master weights unchanged).  On TPU the native low-precision
# MXU format is int8 (v5e: 2x the bf16 peak), so the analogue is W8A8:
# dynamically quantize both operands per call, run the dot int8xint8->int32
# on the MXU, apply the rank-1 scale epilogue.  The backward evaluates the
# dense matmul formulas (dx = g @ w.T, dw = x.T @ g) on the *dequantized
# int8* operands — the same tensors the forward consumed, matching
# TransformerEngine's fp8 wgrad/dgrad semantics (see _int8_mm_bwd) — and
# the fp32 master-weight update (training/optimizer.py) is untouched.
# No speed is claimed for it (no cell of the benchmark runs it: PERF.md
# section 7): the operands are quantized anew a call and the backward is
# unquantized; int8 dgrad/wgrad + static scaling are the path to a win.
# ---------------------------------------------------------------------------


def _int8_rowwise(x: jax.Array):
    """Symmetric per-row (last-dim) quantization: [..., k] →
    (int8 [..., k], fp32 scale [..., 1])."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _int8_operands(x: jax.Array, w: jax.Array):
    qx, sx = _int8_rowwise(x)                       # [..., k], [..., 1]
    qw = quantize_weight(w)                         # {"q" [k, n], "scale" [n]}
    return qx, sx, qw


def _int8_dot(qx, sx, qw, out_dtype):
    y = jax.lax.dot_general(
        qx, qw["q"], (((qx.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)
    return (y * sx * qw["scale"]).astype(out_dtype)


@jax.custom_vjp
def int8_training_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` with both operands dynamically int8-quantized (per-token
    rows × per-output-channel columns); the backward evaluates the dense
    matmul formulas on the *dequantized* int8 operands."""
    qx, sx, qw = _int8_operands(x, w)
    return _int8_dot(qx, sx, qw, x.dtype)


def _int8_mm_fwd(x, w):
    qx, sx, qw = _int8_operands(x, w)
    # Residuals are the int8 operands, not (x, w): a custom_vjp is a remat
    # barrier (checkpoint policies can't drop its residuals), and full
    # activations saved at every projection OOM'd a 374M/seq-1k/mb-12
    # config by 1.9 GB on v5e.  int8 residuals halve the bytes AND match
    # TransformerEngine semantics — TE's wgrad/dgrad GEMMs also consume
    # the fp8 tensors, not the originals.  (The zero-size arrays carry the
    # primal dtypes — residual leaves must be JAX values.)
    carriers = (jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))
    return _int8_dot(qx, sx, qw, x.dtype), (qx, sx, qw, carriers)


def _int8_mm_bwd(res, g):
    qx, sx, qw, (x_c, w_c) = res
    wd = (qw["q"].astype(g.dtype) * qw["scale"].astype(g.dtype))
    dx = jnp.einsum("...n,kn->...k", g, wd).astype(x_c.dtype)
    xd = qx.astype(jnp.float32) * sx
    # fp32 wgrad accumulation — the same invariant the bf16 path keeps
    # (training/step.py casts per use-site so cotangents sum in fp32)
    dw = jnp.einsum("...k,...n->kn", xd,
                    g.astype(jnp.float32)).astype(w_c.dtype)
    return dx, dw


int8_training_matmul.defvjp(_int8_mm_fwd, _int8_mm_bwd)


# Weight leaves worth quantizing: the big projection matmuls, split by
# tensor class so a PrecisionPolicy can treat attention and MLP
# differently.  Norm scales, biases, router (precision-sensitive) and the
# lm_head stay as-is — the lm_head's fp logits matter for sampling
# quality.  The embedding table has its own per-row int8 scheme
# (quantize_embedding) because it is consumed by a gather, not mm().
_ATTN_LEAF_NAMES = frozenset({"wq", "wk", "wv", "wo"})
_MLP_LEAF_NAMES = frozenset({"w_gate", "w_up", "w_down"})
_QUANT_LEAF_NAMES = _ATTN_LEAF_NAMES | _MLP_LEAF_NAMES


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Per-tensor-class precision for the serving quantize transform.

    ``attn`` / ``mlp`` ∈ {"none", "int8", "int4"} pick the projection
    scheme per class; ``embedding`` ∈ {"none", "int8"} opts the word
    table into the per-row int8 gather scheme (untied tables only — a
    tied table doubles as the unembed matrix, whose fp logits we keep);
    ``group_size`` is the int4 group width.  Norm scales, biases, the
    lm_head, and every int4/int8 *scale* tensor stay at the model dtype
    (bf16/fp32) — the policy never touches them.
    """

    attn: str = "int8"
    mlp: str = "int8"
    embedding: str = "none"
    group_size: int = DEFAULT_GROUP_SIZE


# Named presets, also the CLI --weight_quant vocabulary.  "int8" is the
# pre-policy behavior (all seven projections int8, embedding untouched);
# "int4" is the full bytes-floor point; "mixed" keeps the
# quality-sensitive attention projections at int8 and takes the int4 win
# on the MLP, which carries ~2/3 of the projection bytes.
POLICIES = {
    "int8": PrecisionPolicy(),
    "int4": PrecisionPolicy(attn="int4", mlp="int4", embedding="int8"),
    "mixed": PrecisionPolicy(attn="int8", mlp="int4", embedding="int8"),
}


def resolve_policy(policy) -> PrecisionPolicy:
    """None (legacy int8), a preset name, or a PrecisionPolicy."""
    if policy is None:
        return POLICIES["int8"]
    if isinstance(policy, str):
        return POLICIES[policy]
    return policy


def quantize_embedding(word: jax.Array) -> dict:
    """[v, h] embedding table → per-row int8
    ``{"q": int8 [v, h], "scale": fp32 [v]}`` (one symmetric scale per
    vocab row, matching the gather granularity — a row is read whole or
    not at all, so no finer scale ever pays)."""
    w32 = jnp.asarray(word, jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-1) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(w32 / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


# the most tokens ``embedding_lookup`` reads row by row: a decode step's
# slots.  A row costs ~2 us (16 of them 0.033 ms a step where the copy
# the gather drew cost 1.9; PERF.md, PR 30) and each is an operation of
# its own: a prefill's hundreds stay one gather
_SLICED_LOOKUP_TOKENS = 64


def embedding_lookup(word, tokens: jax.Array, dtype=None) -> jax.Array:
    """``word[tokens]`` for a plain or int8-quantized embedding table.

    Quantized path: gather the int8 rows and their scales, dequantize
    only those — per step this touches ``b × h`` int8 bytes instead of
    keeping a ``v × h`` fp table resident.

    A few tokens of a plain table are read by one ``dynamic_slice`` each,
    not by a gather.  XLA:TPU keeps a ``[v, h]`` table whose ``v`` divides
    by 128 and whose ``h`` does not (Falcon-7B: 65024 × 4544) with the
    vocabulary as the minor dimension — the layout the tied head reads —
    and its gather wants rows: it copied the whole table first, 591 MB
    read and written in every decode step (PERF.md, PR 30).  A slice
    reads its row where it lies, whichever way the table lies; the rows
    and the clamping of an index out of range are the gather's.  Not
    under a mesh: there the table's rows are split over tp and GSPMD
    partitions the gather (each shard its own rows, then an all-reduce of
    ``b × h``), where a slice makes it all-gather the table."""
    if is_quantized(word):
        rows = word["q"][tokens].astype(jnp.float32)
        x = rows * word["scale"][tokens][..., None]
        return x.astype(dtype) if dtype is not None else x
    from .attention import _mesh_active

    if tokens.size <= _SLICED_LOOKUP_TOKENS and not _mesh_active():
        flat = tokens.reshape(-1)
        rows = [jax.lax.dynamic_slice_in_dim(word, flat[i], 1, axis=0)
                for i in range(flat.size)]
        return jnp.concatenate(rows).reshape(*tokens.shape, word.shape[1])
    return word[tokens]


def quantize_params(params: dict, policy=None) -> dict:
    """Serving transform: quantize the layer projection weights (and
    optionally the embedding table) of a *flat-layout* native param tree
    per ``policy`` (None → the legacy "int8" preset; see
    :class:`PrecisionPolicy`).  Matching is by leaf name; dense 2D or
    layer-stacked 3D weights only — convert pipeline checkpoints with
    ``parallel.pipeline.from_pipeline_params`` first, exactly as serving
    already requires.  An int4 class whose input dim the group size does
    not divide falls back to int8 for that leaf (tiny test configs);
    ``mm`` and ``weight_bits`` read the actual leaves, never the policy,
    so the fallback is visible, not silent corruption."""
    pol = resolve_policy(policy)
    prec_of = {**{k: pol.attn for k in _ATTN_LEAF_NAMES},
               **{k: pol.mlp for k in _MLP_LEAF_NAMES}}

    def q_leaf(v, prec):
        if prec == "int4" and v.shape[-2] % pol.group_size == 0 \
                and v.shape[-2] % 2 == 0:
            return quantize_weight_int4(v, pol.group_size)
        return quantize_weight(v)

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for k, v in tree.items():
            # ndim guard: dense [in, out] or layer-stacked [L, in, out]
            # only.  MoE expert stacks ([L, E, h, f]) flow through einsums
            # in models/moe.py, not mm() — leave them unquantized.
            if (k in _QUANT_LEAF_NAMES and not isinstance(v, dict)
                    and v.ndim in (2, 3)
                    and prec_of[k] != "none"):
                out[k] = q_leaf(v, prec_of[k])
            else:
                out[k] = walk(v)
        return out

    out = walk(params)
    if (pol.embedding == "int8" and "lm_head" in params
            and isinstance(params.get("embedding", {}).get("word"),
                           jax.Array)):
        out["embedding"] = dict(out["embedding"])
        out["embedding"]["word"] = quantize_embedding(
            params["embedding"]["word"])
    return out


def quantize_specs(specs: dict, params: dict | None = None) -> dict:
    """Mirror of :func:`quantize_params` for a PartitionSpec tree.

    With ``params`` (a quantized tree), the spec tree mirrors exactly
    which leaves are quantized and in which form — required for mixed
    policies.  Scale specs co-shard with their ``q`` leaves (the
    kv_pool_specs pattern): an int8 scale [out] takes the weight's
    output axis; an int4 scale [n_groups, out] takes the weight's
    output-axis sharding but replicates the group axis — the group
    count (rows / group_size) need not divide a mesh axis that the
    packed rows do divide (e.g. one group total under a row-sharded
    w_down), and scales are 1/group_size of the weight bytes, so
    replication costs ~nothing while co-sharding the big axis still
    splits them tp-ways on column-parallel weights.  MQA-replicated
    leaves stay replicated.  The embedding's per-row scale [v] takes
    the vocab axis, so the table's tp split divides the scale bytes
    too.

    Without ``params`` (legacy), every projection leaf is assumed int8.
    """
    from jax.sharding import PartitionSpec as P

    def scale_spec(k, v, t, leaf):
        if k == "word":
            return P(t[0]) if t else P()
        if leaf is not None and is_quantized_int4(leaf):
            # [L, n_groups, out]: weight spec minus the input/group axis
            return (P(*t[:-2], None, t[-1]) if len(t) >= 2 else P())
        return P(*t[:-2], t[-1]) if len(t) >= 2 else P()

    def walk(tree, ptree):
        if isinstance(tree, P):
            return tree
        out = {}
        for k, v in tree.items():
            pv = ptree.get(k) if isinstance(ptree, dict) else None
            t = tuple(v) if isinstance(v, P) else ()
            if params is not None:
                if is_quantized(pv):
                    out[k] = {"q": v, "scale": scale_spec(k, v, t, pv)}
                else:
                    out[k] = walk(v, pv)
                continue
            # rank-4 specs are MoE expert stacks [L, E, h, f], which
            # quantize_params skips (they flow through einsums) — the
            # spec must stay a plain leaf to mirror the param tree.
            if (k in _QUANT_LEAF_NAMES and isinstance(v, P)
                    and len(t) != 4):
                # scale drops the input (-2) axis of the weight spec:
                # P(a, b, c) [L, in, out] → scale [L, out] spec P(a, c)
                out[k] = {"q": v, "scale": P(*t[:-2], t[-1]) if len(t) >= 2
                          else P()}
            else:
                out[k] = walk(v, pv)
        return out

    return walk(specs, params)


def precision_route(params: dict) -> str:
    """Label for the decode precision route a param tree selects:
    "fp32" (no quantized projections — full model dtype), "int8",
    "int4", or "mixed".  Used by the serving engine to tag its
    paged/fallback step counters per precision."""
    bits = set()

    def walk(tree):
        if not isinstance(tree, dict) or is_quantized(tree):
            return
        for k, v in tree.items():
            if k in _QUANT_LEAF_NAMES and (not isinstance(v, dict)
                                           or is_quantized(v)):
                bits.add(weight_bits(v))
            else:
                walk(v)

    walk(params.get("layers", params) if isinstance(params, dict)
         else params)
    if not bits or bits == {0}:
        return "fp32"
    if bits == {8}:
        return "int8"
    if bits == {4}:
        return "int4"
    return "mixed"
