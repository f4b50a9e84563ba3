"""Vocab-parallel cross entropy.

The reference computes a numerically-stable CE over vocab-sharded logits
with three all-reduces (max, predicted-logit, sum-exp) and a custom backward
(megatron/core/tensor_parallel/cross_entropy.py:14-130).  On TPU there are
two equivalent expressions, both provided here:

- ``cross_entropy``: plain stable jnp log-softmax CE.  Under GSPMD with the
  logits sharded P(dp, None, tp) on the vocab axis, XLA lowers the max /
  take / logsumexp reductions into exactly the psum trio the reference hand
  codes — this is the default path.
- ``vocab_parallel_cross_entropy_shardmap``: explicit shard_map version with
  the psums written out, for use inside manually-partitioned regions (the
  pipeline loop) and as an executable spec of the math.

Both support label smoothing (reference :83-116) and return per-token losses
so callers apply their own loss masks (finetune.py:196-213).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@jax.named_scope("cross_entropy")
def cross_entropy(
    logits: jax.Array,  # [..., vocab] (may be padded)
    targets: jax.Array,  # [...] int
    label_smoothing: float = 0.0,
    vocab_size: int | None = None,
) -> jax.Array:
    """Stable per-token CE.  ``vocab_size`` masks padded vocab columns."""
    logits = logits.astype(jnp.float32)
    width = logits.shape[-1]
    valid = None
    if vocab_size is not None and vocab_size < width:
        valid = jnp.arange(width) < vocab_size
        logits = jnp.where(valid, logits, -1e30)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = logits - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0]
    target_logit = jnp.take_along_axis(
        logits, targets[..., None], axis=-1
    )[..., 0]
    loss = lse - target_logit
    if label_smoothing > 0.0:
        # Reference smoothing (cross_entropy.py:71-86):
        #   s = ls * K / (K - 1);  loss = (1-s)*nll - s*mean(log_probs)
        # computed over the K *real* vocab columns only (padded columns are
        # excluded — they carry the -1e30 sentinel).
        n = vocab_size if vocab_size is not None else width
        smoothing = label_smoothing * n / (n - 1)
        logits_for_sum = logits if valid is None else jnp.where(valid, logits, 0.0)
        sum_log_probs = jnp.sum(logits_for_sum, axis=-1) - n * lse
        loss = (1.0 - smoothing) * loss - smoothing * (sum_log_probs / n)
    return loss


def _ce_shard(logits_shard, targets, axis_name, label_smoothing, vocab_size):
    """Per-shard body: the psum trio of the reference custom autograd
    (cross_entropy.py:14-95) expressed with differentiable collectives."""
    tp = jax.lax.psum(1, axis_name)
    shard_v = logits_shard.shape[-1]
    rank = jax.lax.axis_index(axis_name)
    vocab_start = rank * shard_v
    full_v = shard_v * tp

    logits_shard = logits_shard.astype(jnp.float32)
    # Mask padded vocab columns (global column index >= vocab_size) so both
    # CE implementations agree on padded vocabs.
    valid = None
    if vocab_size is not None:
        valid = (vocab_start + jnp.arange(shard_v)) < vocab_size
        logits_shard = jnp.where(valid, logits_shard, -1e30)

    # all-reduce #1: global max
    local_max = jnp.max(logits_shard, axis=-1)
    global_max = jax.lax.pmax(jax.lax.stop_gradient(local_max), axis_name)
    shifted = logits_shard - global_max[..., None]

    # all-reduce #2: predicted (target) logit — mask targets outside shard
    local_t = targets - vocab_start
    in_shard = (local_t >= 0) & (local_t < shard_v)
    local_t = jnp.clip(local_t, 0, shard_v - 1)
    tl = jnp.take_along_axis(shifted, local_t[..., None], axis=-1)[..., 0]
    target_logit = jax.lax.psum(jnp.where(in_shard, tl, 0.0), axis_name)

    # all-reduce #3: sum of exp
    sum_exp = jax.lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), axis_name)
    loss = jnp.log(sum_exp) - target_logit
    if label_smoothing > 0.0:
        # Same formula as ``cross_entropy`` (reference cross_entropy.py:71-86),
        # over real vocab columns only; shifted is relative to global_max so
        # the lse used here must be too.
        n = vocab_size if vocab_size is not None else full_v
        smoothing = label_smoothing * n / (n - 1)
        lse = jnp.log(sum_exp)
        shifted_for_sum = shifted if valid is None else jnp.where(valid, shifted, 0.0)
        sum_log_probs = (
            jax.lax.psum(jnp.sum(shifted_for_sum, axis=-1), axis_name) - n * lse
        )
        loss = (1.0 - smoothing) * loss - smoothing * (sum_log_probs / n)
    return loss


def vocab_parallel_cross_entropy_shardmap(
    logits: jax.Array,  # [b, s, vocab] sharded on vocab over 'tp'
    targets: jax.Array,  # [b, s]
    mesh,
    axis_name: str = "tp",
    label_smoothing: float = 0.0,
    vocab_size: int | None = None,
) -> jax.Array:
    from jax import shard_map

    fn = shard_map(
        partial(_ce_shard, axis_name=axis_name,
                label_smoothing=label_smoothing, vocab_size=vocab_size),
        mesh=mesh,
        in_specs=(P(None, None, axis_name), P(None, None)),
        out_specs=P(None, None),
    )
    return fn(logits, targets)


def vocab_parallel_max_indices(logits: jax.Array) -> jax.Array:
    """Greedy argmax over (possibly sharded) vocab logits
    (reference: cross_entropy.py:146-175).  Under GSPMD a plain argmax
    lowers to the shard-local argmax + cross-shard reduce."""
    return jnp.argmax(logits, axis=-1)


@jax.named_scope("cross_entropy")
def masked_mean_loss(per_token_loss: jax.Array, loss_mask: jax.Array):
    """Loss-mask weighted mean (reference: finetune.py:196-213)."""
    loss_mask = loss_mask.astype(per_token_loss.dtype)
    total = jnp.sum(per_token_loss * loss_mask)
    denom = jnp.maximum(jnp.sum(loss_mask), 1.0)
    return total / denom


# ---------------------------------------------------------------------------
# Fused LM head: blockwise linear + cross entropy that never materializes
# the fp32 logits.  The plain path writes/reads a [b, s, vocab] fp32 tensor
# several times (the dominant HBM cost of small-hidden models); here the
# head matmul is streamed over vocab blocks with an online logsumexp in the
# forward and recomputed blockwise in the backward (the capability analogue
# of the reference's fused wgrad GEMM accumulation, SURVEY §2.2).
# ---------------------------------------------------------------------------


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_linear_cross_entropy(
    x: jax.Array,       # [n, h] hidden states (flattened tokens)
    w: jax.Array,       # [h, v_padded] unembedding weight
    labels: jax.Array,  # [n] int
    vocab_size: int,
    block: int = 8192,
) -> jax.Array:
    """Per-token CE of ``softmax(x @ w)`` without full fp32 logits."""
    loss, _res = _flce_fwd_impl(x, w, labels, vocab_size, block)
    return loss


def _vocab_blocks(v_padded: int, block: int):
    n_blocks = (v_padded + block - 1) // block
    return n_blocks, n_blocks * block


def _flce_fwd_impl(x, w, labels, vocab_size, block):
    n, h = x.shape
    v_padded = w.shape[1]
    n_blocks, v_round = _vocab_blocks(v_padded, block)
    # pad w on the vocab axis so the scan has uniform blocks; padded columns
    # are masked to -inf below
    if v_round != v_padded:
        w = jnp.pad(w, ((0, 0), (0, v_round - v_padded)))
    wb = w.reshape(h, n_blocks, block).transpose(1, 0, 2)  # [nb, h, block]

    def body(carry, inp):
        m, l, tgt = carry
        w_blk, i = inp
        logits = jax.lax.dot_general(
            x, w_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [n, block]
        col = i * block + jnp.arange(block)
        logits = jnp.where(col[None, :] < vocab_size, logits, -jnp.inf)
        blk_max = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        l = l * jnp.exp(m - new_m) + jnp.sum(
            jnp.exp(logits - new_m[:, None]), axis=-1)
        # target logit if it falls in this block
        in_blk = (labels >= i * block) & (labels < (i + 1) * block)
        idx = jnp.clip(labels - i * block, 0, block - 1)
        tl = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        tgt = jnp.where(in_blk, tl, tgt)
        return (new_m, l, tgt), None

    m0 = jnp.full((n,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((n,), jnp.float32)
    t0 = jnp.zeros((n,), jnp.float32)
    (m, l, tgt), _ = jax.lax.scan(
        body, (m0, l0, t0), (wb, jnp.arange(n_blocks)))
    lse = m + jnp.log(l)
    # residuals keep the ORIGINAL w: saving the padded copy would hold a
    # second full [h, v_round] array live through the whole backward
    return lse - tgt, (x, labels, lse)


def _flce_fwd(x, w, labels, vocab_size, block):
    loss, (x_res, labels_res, lse) = _flce_fwd_impl(
        x, w, labels, vocab_size, block)
    return loss, (x_res, w, labels_res, lse)


def _flce_bwd(vocab_size, block, res, g):
    x, w, labels, lse = res
    n, h = x.shape
    orig_v = w.shape[1]
    n_blocks, v_round = _vocab_blocks(orig_v, block)
    if v_round != orig_v:
        # re-pad locally (cheap; fuses) instead of having saved the padded
        # copy in the residuals
        w = jnp.pad(w, ((0, 0), (0, v_round - orig_v)))
    wb = w.reshape(h, n_blocks, block).transpose(1, 0, 2)

    def body(dx, inp):
        w_blk, i = inp
        logits = jax.lax.dot_general(
            x, w_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        col = i * block + jnp.arange(block)
        valid = col[None, :] < vocab_size
        p = jnp.where(valid, jnp.exp(logits - lse[:, None]), 0.0)
        onehot = (labels[:, None] == col[None, :]).astype(jnp.float32)
        d_logits = (p - onehot) * g[:, None]          # [n, block] fp32
        d_cast = d_logits.astype(w_blk.dtype)
        dx = dx + jax.lax.dot_general(
            d_cast, w_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw_blk = jax.lax.dot_general(
            x, d_cast, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [h, block]
        return dx, dw_blk

    dx0 = jnp.zeros((n, h), jnp.float32)
    dx, dwb = jax.lax.scan(body, dx0, (wb, jnp.arange(n_blocks)))
    dw = dwb.transpose(1, 0, 2).reshape(h, v_round)[:, :orig_v]
    import numpy as _np

    dlabels = _np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dx.astype(x.dtype), dw.astype(w.dtype), dlabels


fused_linear_cross_entropy.defvjp(_flce_fwd, _flce_bwd)
