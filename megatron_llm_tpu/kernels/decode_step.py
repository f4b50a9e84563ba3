"""Fused single-token decode step: the whole layer stack in ONE Pallas call.

Why this kernel exists: small-batch decode of a narrow model is bound by
the *sequential per-op chain*, not bytes: a layer's step time sits well
above its weight-read floor, flat in KV-cache size and unchanged (as a
roofline fraction) by int8, and fusing sibling GEMVs buys nothing because
XLA already overlaps independent matmuls.  The fix is to remove the chain:
run the
entire decode step — every layer's norm → qkv GEMVs → RoPE → decode
attention → output projection → norm → MLP GEMVs — as a single Pallas
kernel with grid ``(num_layers, cache_blocks)``.  The Pallas pipeline
streams each layer's weights and KV-cache blocks HBM→VMEM exactly once,
double-buffered against compute, while the residual stream lives in a
VMEM scratch carried across grid steps.  One kernel launch per decode
step puts the step on the HBM-bandwidth roofline instead of the
op-dispatch latency wall.

This file holds THREE kernels sharing that design: the dense
whole-stack step (``fused_decode_step``, fixed-stride caches), its
paged twin reading the serving block pool through per-slot block
tables (``fused_decode_step_paged``), and the batched variable-length
speculative verify (``fused_decode_verify_paged``, a W-wide window per
slot with in-flight K/V splicing).  Scope (eligibility enforced by
:func:`fused_decode_eligible` / :func:`fused_paged_decode_eligible` /
:func:`fused_paged_verify_eligible`): dense pre-LN RMSNorm GLU decoder
layers (the Llama family), rotary positions, no biases, single new
token (per window row), no active mesh / no head-sharding submesh,
per-layer working set within the VMEM budget.

Weight precision is a per-class matrix (ops/quant.py:PrecisionPolicy):
the attention and MLP projection classes are each bf16/f32, int8
per-output-channel, or int4 group-wise, in any combination — both
classes plain, or both quantized (int8×int8, int4×int4, and the mixed
int8×int4 pairs).  int8 tiles stream into VMEM and the
per-output-column scale is an epilogue after each dot (the algebra of
ops/quant.py:mm), applied to q/k BEFORE RoPE because the rotation
mixes adjacent columns carrying different scales.  int4 tiles stream
PACKED (two nibbles per byte) and unpack + group-scale-dequantize in
the tile load (``_int4_tile``) — group scales vary along the
contraction axis, so they cannot be an output epilogue; the fp copy
exists only in VMEM/registers and HBM stays at the half-byte width.
The KV cache may be plain bf16/f32 OR the int8 ``{"q", "scale"}`` form
of ops/kv_quant.py — dequantization is fused at the attention tile
load, and the new token's K/V are requantized in-register so their
in-kernel attention fold matches what later steps read back from the
quantized cache.  Everything else — prefill, meshes, BERT/T5, 7B-width
layers, partially-quantized classes, non-uniform int4 group sizes —
keeps the composed path (models/transformer.py:stack_forward_cached).
The reference's serving loop runs one token per python-level
ForwardStep through the whole module tree
(megatron/text_generation/forward_step.py:44-213); this is the
TPU-first answer to the same loop.

Design notes:
- RoPE at a fixed position is a linear map, so the host passes a tiny
  ``[d, d]`` block-rotation matrix and the kernel applies it with one
  MXU dot per head — no strided lane shuffles inside the kernel (the
  interleaved-pair convention of ops/rope.py is baked into the matrix).
- The new token's K/V never round-trip through HBM: they are computed
  in-kernel, appended to the online-softmax state directly, and emitted
  as ``[L, b, kv, d]`` outputs the caller writes into the cache with the
  usual row-sized dynamic_update_slice (ops/kv_quant.py:cache_update).
- KV blocks past the cache fill level are never fetched: the cache
  BlockSpec index map clamps the block index at the fill level (the
  scalar-prefetch argument), so a short cache in a long buffer costs
  only its own bytes; the compute for clamped blocks is masked out.
- Attention over a cache block is vectorized over every (batch, kv)
  pair at once — broadcast-multiply-reduce on ``(b, kv, block_k, d)``
  arrays (a GEMV batch does not map onto a single MXU dot, and a
  measured ``fori_loop``-over-pairs variant with per-pair 2-D tiles ran
  at ~230 µs/layer: 64 sequential iterations of skinny ``(block_k, 1)``
  VPU ops are issue-latency-bound).  Mosaic unrolls the two leading
  dims, which is exactly the wide straight-line vector code the VPU
  wants here.
- int8 cache scales ride as ``[L, b, kv, max_len, 1]`` operands so the
  ``(block_k, 1)`` trailing block dims stay legal under the TPU tiling
  rule (the flash_decode.py _scale_block_spec trick); a quantized
  cache's new K/V rows come back as fp32 outputs whose values are
  already dequant(quant(row)) — the host-side cache_update requantizes
  them to the exact same int8 rows (idempotent, ops/kv_quant.py), so
  the kernel needs no narrow in-kernel scale stores.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels
from ..ops.kv_quant import fake_quantize_rows

NEG_INF = -1e30


def _phases() -> frozenset:
    """Debug escape hatch: DECODE_STEP_PHASES=project,attn,finish (any
    subset; default all) strips kernel phases so per-phase cost can be
    attributed on hardware.  Timing-only — outputs are garbage when any
    phase is off."""
    import os

    raw = os.environ.get("DECODE_STEP_PHASES")
    if raw is None:
        return frozenset(("project", "attn", "finish"))
    return frozenset(p for p in raw.split(",") if p)


# elementwise gate activation of each GLU family member
# (ops/activations.py composes them over concatenated halves; here gate
# and up are separate operands so the base function applies to the gate)
_GLU_BASE = {
    "swiglu": jax.nn.silu,
    "geglu": functools.partial(jax.nn.gelu, approximate=True),
    "reglu": jax.nn.relu,
    "liglu": lambda x: x,
}


def _int4_tile(ref, s_ref, cdt, gsz: int):
    """Unpack an int4-packed weight tile and fuse its group-scale dequant
    into the tile load: packed int8 ``(rows/2, cols)`` + fp32 scales
    ``(rows/gsz, cols)`` → a ``(rows, cols)`` tile in the compute dtype.

    Nibble order matches ops/quant.py:pack_int4 (even input row in the
    low nibble); sign extension is the same ``(p << 28) >> 28`` int32
    arithmetic as ops/quant.py:unpack_int4, so the kernel's dequantized
    values agree bitwise with the composed path's.  Unlike the int8
    path there is no output epilogue — group scales vary along the
    contraction axis — so the dot consumes a full-precision tile that
    exists only in VMEM/registers while HBM traffic stays at the packed
    half-byte width."""
    p32 = ref[0].astype(jnp.int32)
    low = (p32 << 28) >> 28
    high = (p32 << 24) >> 28
    r2, cols = p32.shape
    v = jnp.stack([low, high], axis=1).reshape(2 * r2, cols)
    scale = s_ref[(0,) * (len(s_ref.shape) - 2)]     # (rows/gsz, cols)
    v = v.astype(jnp.float32).reshape(-1, gsz, cols) * scale[:, None, :]
    return v.reshape(2 * r2, cols).astype(cdt)


def _decode_step_kernel(per_row: bool, aq: int, mq: int, gsz: int,
                        cq8: bool, lsr: int, lt: tuple,
                        nk: int, nm: int, block_k: int,
                        b: int, nq: int, nkv: int, g: int, d: int,
                        eps: float, scale: float, act,
                        lens_ref,
                        x_ref, rot_ref, *refs):
    # per_row: each batch row carries its own fill level (continuous-
    # batching serving, one slot per request).  ``lens_ref`` is then
    # [1 + b]: lens[0] = max fill (drives the cache BlockSpec clamp, so
    # HBM traffic is bounded by the deepest slot), lens[1 + i] = row i's
    # fill (drives the per-row attention mask).  RoPE at per-row
    # positions arrives as precomputed cos/sin row vectors plus the fixed
    # pair-swap permutation in ``rot_ref`` (see fused_decode_step).
    # aq/mq: HBM-resident bits of the attention / MLP projection class
    # (0 = plain, 8 = int8 + [L, 1, out] scale epilogue operands, 4 =
    # packed int4 + [L, n_groups, out] group-scale operands consumed by
    # _int4_tile; gsz is the int4 group size).  cq8: the cache refs are
    # int8 with [L, b, kv, block_k, 1] fp32 per-row scale refs behind
    # them.
    if per_row:
        cos_ref, sin_ref, *refs = refs
    (in_nw_ref, post_nw_ref,
     wq_ref, wk_ref, wv_ref, wo_ref,
     wg_ref, wu_ref, wd_ref, *refs) = refs
    qs_ref = ks_ref = vs_ref = os_ref = None
    if aq:
        (qs_ref, ks_ref, vs_ref, os_ref, *refs) = refs
    gs_ref = us_ref = ds_ref = None
    if mq:
        (gs_ref, us_ref, ds_ref, *refs) = refs
    kc_ref, vc_ref, *refs = refs
    if cq8:
        kcs_ref, vcs_ref, *refs = refs
    # lsr/lt: the grouped LoRA epilogue — lsr = stacked arena rank
    # (n_slots · r, 0 = no LoRA), lt the static target-projection tuple.
    # Operands are one (b_pad, lsr) slot mask plus per-target stacked
    # A/B factor pairs (ops/lora.py arena layout, α/r folded into B).
    lmask_ref = None
    lab_refs = {}
    if lsr:
        lmask_ref, *refs = refs
        for t in lt:
            la_t, lb_t, *refs = refs
            lab_refs[t] = (la_t, lb_t)
    (xo_ref, kr_ref, vr_ref,
     x_scr, q_scr, kn_scr, vn_scr, ctx_scr, xn2_scr,
     m_scr, l_scr, acc_scr, *extra_scr) = refs
    lxa_scr = extra_scr[0] if (lsr and "w_down" in lt) else None
    li = pl.program_id(0)
    ki = pl.program_id(1)
    n_layers = pl.num_programs(0)
    pos = lens_ref[0]
    f32 = jnp.float32
    # compute dtype of the projection dots: mirrors ops/quant.py:mm for
    # quantized weights (int8: inner dot int8→x.dtype, scale as output
    # epilogue; int4: dequantized tile in x.dtype)
    cdt = x_ref.dtype if (aq or mq) else wq_ref.dtype

    def wmat_a(ref, s_ref):  # attention-class tile in compute dtype
        if aq == 4:
            return _int4_tile(ref, s_ref, cdt, gsz)
        return ref[0].astype(cdt) if aq else ref[0]

    def wmat_m(ref, s_ref):  # MLP-class tile in compute dtype
        if mq == 4:
            return _int4_tile(ref, s_ref, cdt, gsz)
        return ref[0].astype(cdt) if mq else ref[0]

    def lora_add(y, xin, t):
        # grouped LoRA epilogue (ops/lora.py arena algebra):
        # y += ((x·A)⊙mask)·B in fp32.  The mask one-hot selects each
        # row's adapter slot's rank columns of the stacked arena, so
        # rows under DIFFERENT adapters coexist in one pair of dots; a
        # slot-less row's all-zero mask row makes its delta exactly
        # ±0.0, keeping base-only rows bit-identical in tokens/logprobs
        if not lsr or t not in lt:
            return y
        la_t, lb_t = lab_refs[t]
        ldims = (((1,), (0,)), ((), ()))
        xa = jax.lax.dot_general(xin, la_t[0], ldims,
                                 preferred_element_type=f32)
        return y + jax.lax.dot_general(xa * lmask_ref[...], lb_t[0],
                                       ldims, preferred_element_type=f32)

    @pl.when(jnp.logical_and(li == 0, ki == 0))
    def _first():
        x_scr[...] = x_ref[...].astype(f32)
        ctx_scr[...] = jnp.zeros(ctx_scr.shape, f32)

    phases = _phases()

    @pl.when(jnp.logical_and(ki == 0, "project" in phases))
    def _project():
        x = x_scr[...]                                   # (b_pad, h) f32
        nw = in_nw_ref[0].astype(f32)                    # (1, h)
        xn = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps) * nw
        xnc = xn.astype(cdt)
        rot = rot_ref[...]                               # (d, d) f32
        dims = (((1,), (0,)), ((), ()))

        def rope_head(y):  # (b_pad, d) f32 → rotated at each row's pos
            z = jax.lax.dot_general(y, rot, dims, preferred_element_type=f32)
            if per_row:
                # rot is the fixed pair-swap permutation here: y·P swaps
                # each (2i, 2i+1) lane pair, and the per-row cos/sin
                # vectors finish the rotation — one MXU dot per head
                # regardless of how many distinct positions the batch has
                return y * cos_ref[...] + z * sin_ref[...]
            return z

        q = jax.lax.dot_general(xnc, wmat_a(wq_ref, qs_ref), dims,
                                preferred_element_type=f32)
        k = jax.lax.dot_general(xnc, wmat_a(wk_ref, ks_ref), dims,
                                preferred_element_type=f32)
        v = jax.lax.dot_general(xnc, wmat_a(wv_ref, vs_ref), dims,
                                preferred_element_type=f32)
        if aq == 8:
            # per-output-column scale epilogue (ops/quant.py:mm algebra),
            # BEFORE RoPE: the rotation mixes the (2i, 2i+1) column pair,
            # whose scales differ (int4 group scales are already folded
            # into the tile by _int4_tile)
            q = q * qs_ref[0]
            k = k * ks_ref[0]
            v = v * vs_ref[0]
        q = lora_add(q, xn, "wq")
        k = lora_add(k, xn, "wk")
        v = lora_add(v, xn, "wv")
        for j in range(nkv):
            kj = rope_head(k[:, j * d:(j + 1) * d])
            vj = v[:, j * d:(j + 1) * d]
            if cq8:
                # requantize in-register exactly as the host-side cache
                # write will (ops/kv_quant.py:quantize_rows is idempotent
                # on these values), so this token's in-kernel attention
                # fold matches what later steps read back from the cache
                kj = fake_quantize_rows(kj)
                vj = fake_quantize_rows(vj)
            kr_ref[0, :, j, :] = kj[:b].astype(kr_ref.dtype)
            vr_ref[0, :, j, :] = vj[:b].astype(vr_ref.dtype)
            kn_scr[:, j, :] = kj[:b]
            vn_scr[:, j, :] = vj[:b]
        for hq in range(nq):
            qh = rope_head(q[:, hq * d:(hq + 1) * d])
            q_scr[hq % g, :, hq // g, :] = qh[:b]
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, f32)
        l_scr[...] = jnp.zeros(l_scr.shape, f32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    # --- online-softmax accumulation over this cache block (every tick),
    # vectorized over all (batch, kv) pairs.  Blocks past the fill level
    # arrive clamped (stale data) and are fully masked: s = NEG_INF
    # everywhere → p = 0, m/l/acc unchanged.
    @pl.when(jnp.logical_and(ki < nk, "attn" in phases))
    def _attend():
        k4 = kc_ref[0].astype(f32)                       # (b, nkv, bk, d)
        v4 = vc_ref[0].astype(f32)
        if cq8:
            # dequantize at tile load (ops/kv_quant.py:dequantize_cache
            # algebra): int8 rows stream from HBM, the fp copy exists
            # only in VMEM
            k4 = k4 * kcs_ref[0]                         # ×(b, nkv, bk, 1)
            v4 = v4 * vcs_ref[0]
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2)
        if per_row:
            # each batch row masks at its OWN fill level; rows whose fill
            # lies below the clamped max-fill blocks see only NEG_INF here
            in_range = jnp.concatenate(
                [cols < lens_ref[1 + i] for i in range(b)], axis=0)
        else:
            in_range = cols < pos                        # (1, 1, bk)
        for gg in range(g):
            qv = q_scr[gg]                               # (b, nkv, d) f32
            s = jnp.sum(qv[:, :, None, :] * k4, axis=-1) * scale
            s = jnp.where(in_range, s, NEG_INF)          # (b, nkv, bk)
            m_prev = m_scr[gg][:, :, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[gg] = jnp.broadcast_to(
                alpha * l_scr[gg][:, :, :1]
                + jnp.sum(p, axis=-1, keepdims=True), l_scr[gg].shape)
            acc_scr[gg] = (acc_scr[gg] * alpha
                           + jnp.sum(p[..., None] * v4, axis=2))
            m_scr[gg] = jnp.broadcast_to(m_new, m_scr[gg].shape)

    @pl.when(jnp.logical_and(ki == nk, "finish" in phases))
    def _finish_attn():
        # fold in the new token's K/V (never round-tripped through HBM),
        # apply the output projection + residual, and stage the normed
        # MLP input — the MLP itself runs across the nm chunk ticks
        kn = kn_scr[...]                                 # (b, nkv, d)
        vn = vn_scr[...]
        for gg in range(g):
            qv = q_scr[gg]
            s_new = jnp.sum(qv * kn, axis=-1, keepdims=True) * scale
            m_prev = m_scr[gg][:, :, :1]
            m_fin = jnp.maximum(m_prev, s_new)
            alpha = jnp.exp(m_prev - m_fin)
            p_new = jnp.exp(s_new - m_fin)
            l_fin = alpha * l_scr[gg][:, :, :1] + p_new
            ctx = ((acc_scr[gg] * alpha + p_new * vn)
                   / jnp.where(l_fin == 0.0, 1.0, l_fin))  # (b, nkv, d)
            for j in range(nkv):
                hq = j * g + gg
                ctx_scr[:b, hq * d:(hq + 1) * d] = ctx[:, j, :]

        dims = (((1,), (0,)), ((), ()))
        w_o = wmat_a(wo_ref, os_ref)
        attn = jax.lax.dot_general(
            ctx_scr[...].astype(cdt), w_o, dims,
            preferred_element_type=f32)                   # (b_pad, h)
        if aq == 8:
            attn = attn * os_ref[0]
        attn = lora_add(attn, ctx_scr[...], "wo")
        if lxa_scr is not None:
            # fresh layer: zero the w_down LoRA accumulator the MLP
            # chunk ticks fold into
            lxa_scr[...] = jnp.zeros(lxa_scr.shape, f32)
        x1 = x_scr[...] + attn
        nw2 = post_nw_ref[0].astype(f32)
        xn2_scr[...] = x1 * jax.lax.rsqrt(
            jnp.mean(x1 * x1, axis=-1, keepdims=True) + eps) * nw2
        x_scr[...] = x1

    # one MLP column/row chunk per tick ki ∈ [nk, nk+nm): the chunked
    # w_gate/w_up/w_down blocks stream across ticks instead of arriving
    # as one per-layer burst the pipeline cannot hide (its copy lookahead
    # is a single tick), and the down-projection partial sums accumulate
    # into the residual stream — exact because the GLU activation is
    # elementwise over the chunked ffn columns
    @pl.when(jnp.logical_and(ki >= nk, "finish" in phases))
    def _mlp_chunk():
        dims = (((1,), (0,)), ((), ()))
        xn2c = xn2_scr[...].astype(cdt)
        w_g = wmat_m(wg_ref, gs_ref)
        w_u = wmat_m(wu_ref, us_ref)
        w_d = wmat_m(wd_ref, ds_ref)
        gate = jax.lax.dot_general(xn2c, w_g, dims,
                                   preferred_element_type=f32)
        up = jax.lax.dot_general(xn2c, w_u, dims,
                                 preferred_element_type=f32)
        if mq == 8:
            # int8 gate/up scales chunk with the ffn columns; the w_down
            # scale is per output column, so scaling each partial sum is
            # exact.  (int4 group scales chunk with the ffn ROWS of
            # w_down and are folded in by _int4_tile — exact for the
            # same reason: whole groups live inside one chunk.)
            gate = gate * gs_ref[0]
            up = up * us_ref[0]
        gate = lora_add(gate, xn2_scr[...], "w_gate")
        up = lora_add(up, xn2_scr[...], "w_up")
        hid32 = act(gate) * up
        hid = hid32.astype(cdt)
        part = jax.lax.dot_general(hid, w_d, dims,
                                   preferred_element_type=f32)
        if mq == 8:
            part = part * ds_ref[0]
        if lxa_scr is not None:
            # w_down LoRA contracts over the FULL ffn axis while the
            # down tiles stream f_chunk rows per tick: accumulate this
            # chunk's x·A partial; (·⊙mask)·B applies once after the
            # last chunk (_lora_down) — exact because the chunks
            # partition the contraction
            la_d = lab_refs["w_down"][0]
            lxa_scr[...] = lxa_scr[...] + jax.lax.dot_general(
                hid32, la_d[0], dims, preferred_element_type=f32)
        x_scr[...] = x_scr[...] + part

    if lxa_scr is not None:
        # runs after _mlp_chunk on the same (last-MLP) tick — pl.when
        # blocks execute in definition order — so the accumulator holds
        # every chunk's partial before B is applied
        @pl.when(jnp.logical_and(ki == nk + nm - 1, "finish" in phases))
        def _lora_down():
            ldims = (((1,), (0,)), ((), ()))
            lb_d = lab_refs["w_down"][1]
            x_scr[...] = x_scr[...] + jax.lax.dot_general(
                lxa_scr[...] * lmask_ref[...], lb_d[0], ldims,
                preferred_element_type=f32)

    @pl.when(jnp.logical_and(li == n_layers - 1, ki == nk + nm - 1))
    def _emit():
        xo_ref[...] = x_scr[...].astype(xo_ref.dtype)


def _decode_step_kernel_paged(aq: int, mq: int, gsz: int,
                              cq8: bool, lsr: int, lt: tuple,
                              W: int, tree: bool,
                              ntb: int, nm: int, block_k: int,
                              b: int, nq: int, nkv: int, g: int, d: int,
                              eps: float, scale: float, act,
                              lens_ref, tbl_ref, *refs):
    anc_ref = None
    if tree:
        # third prefetched scalar: flattened [S, W·W] ancestor topology —
        # anc_ref[r, j·W + dd] is the node index of row j's ancestor at
        # tree depth dd (arbitrary for dd >= depth(j): those columns are
        # masked by the per-row lens limit and never score)
        anc_ref, *refs = refs
    (x_ref, rot_ref, cos_ref, sin_ref, *refs) = refs
    # Paged twin of _decode_step_kernel, always per-row (the serving
    # engine's slot batch).  ``lens_ref`` is [1 + b] (lens[0] = max fill,
    # layout parity with the dense kernel; lens[1 + i] = row i's limit —
    # the number of cache positions it may attend); ``tbl_ref``
    # [b // W, ntb] is consumed by the BlockSpec index maps only.
    # The grid's second axis runs (b // W)*ntb attend ticks then nm MLP
    # ticks: attend tick t streams ONE pool block — slot r = t // ntb,
    # logical block j = t % ntb — and updates ALL rows' online-softmax
    # state under the mask (slot_of_row == r) & (cols < limit_row).
    # Non-r rows see only NEG_INF scores, which the recurrence treats as
    # a no-op once the row has any real score (alpha = 1, p underflows
    # to exactly 0.0); garbage accumulated while a row's m is still at
    # the -1e30 start is annihilated by alpha = exp(-1e30 - s) = 0.0 at
    # its first real score — and every row folds the new token's finite
    # score in _finish_attn, so garbage never survives to the output.
    # The full-shape masked update avoids dynamic scratch indexing
    # entirely.
    #
    # W is the speculative verify window: each of the b = S·W rows is
    # (slot s = row // W, window position j = row % W), a query at cache
    # position fill_s + j whose K/V row is appended by this same call.
    # A sequential single-token run would have WRITTEN window rows
    # 0..j-1 into the pool before row j reads them, so the tick splices
    # the slot's in-flight window K/V (kn/vn scratch, converted to the
    # exact values a pool round-trip would return) over tile columns
    # [fill_s, fill_s + W - 1) — the joint online-softmax walk then sees
    # the same values at the same positions in the same order as the
    # sequential steps, which is what makes the verify logits bitwise
    # equal rather than merely close.  W = 1 degenerates to the plain
    # single-token kernel (no splice, slot_of_row == row).
    (in_nw_ref, post_nw_ref,
     wq_ref, wk_ref, wv_ref, wo_ref,
     wg_ref, wu_ref, wd_ref, *refs) = refs
    qs_ref = ks_ref = vs_ref = os_ref = None
    if aq:
        (qs_ref, ks_ref, vs_ref, os_ref, *refs) = refs
    gs_ref = us_ref = ds_ref = None
    if mq:
        (gs_ref, us_ref, ds_ref, *refs) = refs
    kc_ref, vc_ref, *refs = refs
    if cq8:
        kcs_ref, vcs_ref, *refs = refs
    # grouped LoRA epilogue operands (see _decode_step_kernel): one
    # (b_pad, lsr) per-row slot mask + stacked A/B arena pairs per
    # target.  Verify windows repeat each slot's mask row W times, so
    # every window row (and its drafts) scores under the REQUESTER's
    # adapter.
    lmask_ref = None
    lab_refs = {}
    if lsr:
        lmask_ref, *refs = refs
        for t in lt:
            la_t, lb_t, *refs = refs
            lab_refs[t] = (la_t, lb_t)
    (xo_ref, kr_ref, vr_ref,
     x_scr, q_scr, kn_scr, vn_scr, ctx_scr, xn2_scr,
     m_scr, l_scr, acc_scr, *extra_scr) = refs
    lxa_scr = extra_scr[0] if (lsr and "w_down" in lt) else None
    li = pl.program_id(0)
    ki = pl.program_id(1)
    n_layers = pl.num_programs(0)
    nk = (b // W) * ntb                                 # attend ticks
    f32 = jnp.float32
    cdt = x_ref.dtype if (aq or mq) else wq_ref.dtype

    def wmat_a(ref, s_ref):  # attention-class tile in compute dtype
        if aq == 4:
            return _int4_tile(ref, s_ref, cdt, gsz)
        return ref[0].astype(cdt) if aq else ref[0]

    def wmat_m(ref, s_ref):  # MLP-class tile in compute dtype
        if mq == 4:
            return _int4_tile(ref, s_ref, cdt, gsz)
        return ref[0].astype(cdt) if mq else ref[0]

    def lora_add(y, xin, t):
        # grouped LoRA epilogue (ops/lora.py arena algebra):
        # y += ((x·A)⊙mask)·B in fp32.  The mask one-hot selects each
        # row's adapter slot's rank columns of the stacked arena, so
        # rows under DIFFERENT adapters coexist in one pair of dots; a
        # slot-less row's all-zero mask row makes its delta exactly
        # ±0.0, keeping base-only rows bit-identical in tokens/logprobs
        if not lsr or t not in lt:
            return y
        la_t, lb_t = lab_refs[t]
        ldims = (((1,), (0,)), ((), ()))
        xa = jax.lax.dot_general(xin, la_t[0], ldims,
                                 preferred_element_type=f32)
        return y + jax.lax.dot_general(xa * lmask_ref[...], lb_t[0],
                                       ldims, preferred_element_type=f32)

    @pl.when(jnp.logical_and(li == 0, ki == 0))
    def _first():
        x_scr[...] = x_ref[...].astype(f32)
        ctx_scr[...] = jnp.zeros(ctx_scr.shape, f32)

    phases = _phases()

    @pl.when(jnp.logical_and(ki == 0, "project" in phases))
    def _project():
        x = x_scr[...]                                   # (b_pad, h) f32
        nw = in_nw_ref[0].astype(f32)                    # (1, h)
        xn = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps) * nw
        xnc = xn.astype(cdt)
        rot = rot_ref[...]                               # (d, d) pair swap
        dims = (((1,), (0,)), ((), ()))

        def rope_head(y):  # (b_pad, d) f32 → rotated at each row's pos
            z = jax.lax.dot_general(y, rot, dims, preferred_element_type=f32)
            return y * cos_ref[...] + z * sin_ref[...]

        q = jax.lax.dot_general(xnc, wmat_a(wq_ref, qs_ref), dims,
                                preferred_element_type=f32)
        k = jax.lax.dot_general(xnc, wmat_a(wk_ref, ks_ref), dims,
                                preferred_element_type=f32)
        v = jax.lax.dot_general(xnc, wmat_a(wv_ref, vs_ref), dims,
                                preferred_element_type=f32)
        if aq == 8:
            q = q * qs_ref[0]
            k = k * ks_ref[0]
            v = v * vs_ref[0]
        q = lora_add(q, xn, "wq")
        k = lora_add(k, xn, "wk")
        v = lora_add(v, xn, "wv")
        for j in range(nkv):
            kj = rope_head(k[:, j * d:(j + 1) * d])
            vj = v[:, j * d:(j + 1) * d]
            if cq8:
                kj = fake_quantize_rows(kj)
                vj = fake_quantize_rows(vj)
            kr_ref[0, :, j, :] = kj[:b].astype(kr_ref.dtype)
            vr_ref[0, :, j, :] = vj[:b].astype(vr_ref.dtype)
            kn_scr[:, j, :] = kj[:b]
            vn_scr[:, j, :] = vj[:b]
        for hq in range(nq):
            qh = rope_head(q[:, hq * d:(hq + 1) * d])
            q_scr[hq % g, :, hq // g, :] = qh[:b]
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, f32)
        l_scr[...] = jnp.zeros(l_scr.shape, f32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    @pl.when(jnp.logical_and(ki < nk, "attn" in phases))
    def _attend():
        r = ki // ntb
        j = ki - r * ntb
        k4 = kc_ref[0, 0].astype(f32)                    # (nkv, bk, d)
        v4 = vc_ref[0, 0].astype(f32)
        if cq8:
            k4 = k4 * kcs_ref[0, 0]                      # ×(nkv, bk, 1)
            v4 = v4 * vcs_ref[0, 0]
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_k), 2)
        rows = jax.lax.broadcasted_iota(jnp.int32, (b, 1, 1), 0)
        if W == 1:
            in_range = jnp.logical_and(rows == r, cols < lens_ref[1 + r])
        else:
            # splice slot r's in-flight window K/V over the tile columns
            # a sequential run would already have written.  The spliced
            # values are the exact pool ROUND-TRIP of the scratch rows:
            # fake-quantized twice for an int8 pool (the second pass
            # reproduces q·scale as the dequant load computes it), or
            # cast through the pool dtype otherwise — never the raw fp32
            # rows, whose extra precision the sequential path lost at
            # its cache write.  Only window keys 0..W-2 are spliced: key
            # W-1 is read by no later row (each row folds its OWN raw
            # key in _finish_attn, exactly like the sequential step).
            fill_r = lens_ref[1 + r * W]                 # slot r's fill
            kn_all = kn_scr[...]                         # (b, nkv, d)
            vn_all = vn_scr[...]
            if cq8:
                kn_vis = fake_quantize_rows(kn_all)
                vn_vis = fake_quantize_rows(vn_all)
            else:
                kn_vis = kn_all.astype(kr_ref.dtype).astype(f32)
                vn_vis = vn_all.astype(vr_ref.dtype).astype(f32)
            sel_rows = jax.lax.broadcasted_iota(jnp.int32, (b, 1, 1), 0)
            if not tree:
                # column ids at the rank of the tile they mask: Mosaic has
                # no layout for reshaping a bool vector ((1, bk) ->
                # (1, bk, 1) i1 is an "unsupported shape cast")
                c3 = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_k, 1), 1)
                for i in range(W - 1):
                    # one-hot gather of scratch row r·W + i (r is traced,
                    # so no dynamic scratch indexing)
                    sel = (sel_rows == r * W + i).astype(f32)
                    kvi = jnp.sum(kn_vis * sel, axis=0)  # (nkv, d)
                    vvi = jnp.sum(vn_vis * sel, axis=0)
                    hit = c3 == fill_r + i               # (1, bk, 1)
                    k4 = jnp.where(hit, kvi[:, None, :], k4)
                    v4 = jnp.where(hit, vvi[:, None, :], v4)
            else:
                # tree splice: the window rows form a candidate TREE per
                # slot (BFS node order: node 0 = root/pending, depth
                # non-decreasing in node index), so different rows need
                # DIFFERENT keys at the same column — row j's ancestor
                # at depth dd must land at column fill_r + dd, exactly
                # where sequentially decoding j's root path would have
                # written it.  The splice therefore widens to per-row
                # (b, nkv, bk, d) tiles; masked columns (dd >= depth(j))
                # splice arbitrary values whose scores the per-row lens
                # limit replaces with NEG_INF, so p is exactly 0.0 there
                # and the online-softmax recurrence is untouched — the
                # same annihilation argument as the linear splice.  A
                # chain topology (anc[j, dd] = dd, depth(j) = j) makes
                # every row's tile equal to the shared linear splice,
                # which is what keeps chain-tree verify bitwise-equal to
                # the W-window path.
                c4 = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (1, 1, block_k, 1), 2)
                k4 = jnp.broadcast_to(k4[None], (b,) + k4.shape)
                v4 = jnp.broadcast_to(v4[None], (b,) + v4.shape)
                for dd in range(W - 1):
                    kdd = jnp.zeros((b, nkv, d), f32)
                    vdd = jnp.zeros((b, nkv, d), f32)
                    for jj in range(W):
                        # SMEM scalar read with traced r, then a one-hot
                        # gather of scratch row r·W + anc (no dynamic
                        # scratch indexing)
                        a = anc_ref[r, jj * W + dd]
                        sel_a = (sel_rows == r * W + a).astype(f32)
                        kv_a = jnp.sum(kn_vis * sel_a, axis=0)  # (nkv, d)
                        vv_a = jnp.sum(vn_vis * sel_a, axis=0)
                        row_hit = (sel_rows == r * W + jj).astype(f32)
                        kdd = kdd + row_hit * kv_a[None]
                        vdd = vdd + row_hit * vv_a[None]
                    hit = c4 == fill_r + dd          # (1, 1, bk, 1)
                    k4 = jnp.where(hit, kdd[:, :, None, :], k4)
                    v4 = jnp.where(hit, vdd[:, :, None, :], v4)
            # per-row limits: row (s, j) attends cache positions
            # < fill_s + depth_j (its own key folds in _finish_attn);
            # linear windows have depth_j = j — either way the limit is
            # lens[1 + row] = the row's own position
            in_range = jnp.logical_and(
                rows // W == r,
                jnp.concatenate([cols < lens_ref[1 + rr]
                                 for rr in range(b)], axis=0))
        # rank-4 k4/v4 (tree) already carry the row axis; rank-3 tiles
        # broadcast it — elementwise products and the d-axis reduction
        # are identical either way, so the linear path is bit-unchanged
        k4b = k4 if k4.ndim == 4 else k4[None]
        v4b = v4 if v4.ndim == 4 else v4[None]
        for gg in range(g):
            qv = q_scr[gg]                               # (b, nkv, d) f32
            s = jnp.sum(qv[:, :, None, :] * k4b, axis=-1) * scale
            s = jnp.where(in_range, s, NEG_INF)          # (b, nkv, bk)
            m_prev = m_scr[gg][:, :, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[gg] = jnp.broadcast_to(
                alpha * l_scr[gg][:, :, :1]
                + jnp.sum(p, axis=-1, keepdims=True), l_scr[gg].shape)
            acc_scr[gg] = (acc_scr[gg] * alpha
                           + jnp.sum(p[..., None] * v4b, axis=2))
            m_scr[gg] = jnp.broadcast_to(m_new, m_scr[gg].shape)

    @pl.when(jnp.logical_and(ki == nk, "finish" in phases))
    def _finish_attn():
        kn = kn_scr[...]                                 # (b, nkv, d)
        vn = vn_scr[...]
        for gg in range(g):
            qv = q_scr[gg]
            s_new = jnp.sum(qv * kn, axis=-1, keepdims=True) * scale
            m_prev = m_scr[gg][:, :, :1]
            m_fin = jnp.maximum(m_prev, s_new)
            alpha = jnp.exp(m_prev - m_fin)
            p_new = jnp.exp(s_new - m_fin)
            l_fin = alpha * l_scr[gg][:, :, :1] + p_new
            ctx = ((acc_scr[gg] * alpha + p_new * vn)
                   / jnp.where(l_fin == 0.0, 1.0, l_fin))  # (b, nkv, d)
            for j in range(nkv):
                hq = j * g + gg
                ctx_scr[:b, hq * d:(hq + 1) * d] = ctx[:, j, :]

        dims = (((1,), (0,)), ((), ()))
        w_o = wmat_a(wo_ref, os_ref)
        attn = jax.lax.dot_general(
            ctx_scr[...].astype(cdt), w_o, dims,
            preferred_element_type=f32)                   # (b_pad, h)
        if aq == 8:
            attn = attn * os_ref[0]
        attn = lora_add(attn, ctx_scr[...], "wo")
        if lxa_scr is not None:
            # fresh layer: zero the w_down LoRA accumulator the MLP
            # chunk ticks fold into
            lxa_scr[...] = jnp.zeros(lxa_scr.shape, f32)
        x1 = x_scr[...] + attn
        nw2 = post_nw_ref[0].astype(f32)
        xn2_scr[...] = x1 * jax.lax.rsqrt(
            jnp.mean(x1 * x1, axis=-1, keepdims=True) + eps) * nw2
        x_scr[...] = x1

    @pl.when(jnp.logical_and(ki >= nk, "finish" in phases))
    def _mlp_chunk():
        dims = (((1,), (0,)), ((), ()))
        xn2c = xn2_scr[...].astype(cdt)
        w_g = wmat_m(wg_ref, gs_ref)
        w_u = wmat_m(wu_ref, us_ref)
        w_d = wmat_m(wd_ref, ds_ref)
        gate = jax.lax.dot_general(xn2c, w_g, dims,
                                   preferred_element_type=f32)
        up = jax.lax.dot_general(xn2c, w_u, dims,
                                 preferred_element_type=f32)
        if mq == 8:
            gate = gate * gs_ref[0]
            up = up * us_ref[0]
        gate = lora_add(gate, xn2_scr[...], "w_gate")
        up = lora_add(up, xn2_scr[...], "w_up")
        hid32 = act(gate) * up
        hid = hid32.astype(cdt)
        part = jax.lax.dot_general(hid, w_d, dims,
                                   preferred_element_type=f32)
        if mq == 8:
            part = part * ds_ref[0]
        if lxa_scr is not None:
            # w_down LoRA contracts over the FULL ffn axis while the
            # down tiles stream f_chunk rows per tick: accumulate this
            # chunk's x·A partial; (·⊙mask)·B applies once after the
            # last chunk (_lora_down) — exact because the chunks
            # partition the contraction
            la_d = lab_refs["w_down"][0]
            lxa_scr[...] = lxa_scr[...] + jax.lax.dot_general(
                hid32, la_d[0], dims, preferred_element_type=f32)
        x_scr[...] = x_scr[...] + part

    if lxa_scr is not None:
        # runs after _mlp_chunk on the same (last-MLP) tick — pl.when
        # blocks execute in definition order — so the accumulator holds
        # every chunk's partial before B is applied
        @pl.when(jnp.logical_and(ki == nk + nm - 1, "finish" in phases))
        def _lora_down():
            ldims = (((1,), (0,)), ((), ()))
            lb_d = lab_refs["w_down"][1]
            x_scr[...] = x_scr[...] + jax.lax.dot_general(
                lxa_scr[...] * lmask_ref[...], lb_d[0], ldims,
                preferred_element_type=f32)

    @pl.when(jnp.logical_and(li == n_layers - 1, ki == nk + nm - 1))
    def _emit():
        xo_ref[...] = x_scr[...].astype(xo_ref.dtype)


def rope_rotation_matrix(cos: jax.Array, sin: jax.Array,
                         pos: jax.Array, d: int) -> jax.Array:
    """[d, d] linear map equal to interleaved-pair RoPE at ``pos``.

    ``x @ R`` reproduces ops/rope.py:apply_rope for a single position:
    out[2i] = x[2i]·c_i − x[2i+1]·s_i, out[2i+1] = x[2i]·s_i + x[2i+1]·c_i.
    Built outside the kernel (one tiny gather + scatters per decode step)
    so the kernel never does strided lane shuffles.
    """
    c = jax.lax.dynamic_slice(cos, (pos, 0), (1, d // 2))[0]
    s = jax.lax.dynamic_slice(sin, (pos, 0), (1, d // 2))[0]
    i = jnp.arange(d)
    even = jnp.arange(0, d, 2)
    r = jnp.zeros((d, d), jnp.float32)
    r = r.at[i, i].set(jnp.repeat(c, 2))
    r = r.at[even, even + 1].set(s)
    r = r.at[even + 1, even].set(-s)
    return r


def _pair_swap_matrix(d: int) -> jax.Array:
    """[d, d] permutation: ``x @ P`` swaps each (2i, 2i+1) lane pair.

    The per-row RoPE path factors interleaved-pair rotation as
    ``x * C + (x @ P) * S`` with per-row cos/sin vectors (C, S), so a
    batch of rows at DIFFERENT positions still costs one MXU dot per
    head — the single-position path bakes cos/sin into the matrix
    instead (rope_rotation_matrix)."""
    even = jnp.arange(0, d, 2)
    p = jnp.zeros((d, d), jnp.float32)
    p = p.at[even, even + 1].set(1.0)
    p = p.at[even + 1, even].set(1.0)
    return p


def _stack_eligible(cfg, params, platform: str):
    """Config/params portion of the fused-decode predicates, shared by the
    dense and paged variants.  Returns None when the stack cannot fuse,
    else the ``(aq, mq, gsz)`` precision triple: the HBM-resident bits of
    the attention and MLP projection classes (0 plain / 8 int8 / 4 int4
    group-wise — the mixed-precision eligibility matrix) and the int4
    group size (0 when no class is int4).  Each class must be internally
    uniform, and either both classes are quantized or neither — a
    half-quantized stack (quantize_params never produces one) keeps the
    composed path instead of silently dequantizing.

    What the TPU compiler enforces on an accepted stack — interpret mode
    checks none of it, tests/kernels/test_tpu_compile.py does.  Every
    operand block's last two dims divide by (8, 128) or equal the
    array's: hence the lane alignment of d, h, ffn and the head products
    below, the ``[L, 1, out]`` form of the norm and int8 scales, and the
    ``[L, nm, groups, h]`` form of the w_down int4 group scales
    (``_chunk_down_scales`` — the number of group rows an MLP chunk
    streams, ``f_chunk // gsz``, is 11 at ffn 2816 and 43 at
    ffn 11008, neither a multiple of 8, so as a block of the rank-3 array
    it was refused).  An int4 group must not straddle an MLP chunk
    (``f_chunk % gsz``): the chunk's scales could not ride with it."""
    from ..config import PositionEmbeddingType
    from ..ops.activations import is_glu
    from ..ops.attention import _mesh_active
    from ..ops.quant import int4_group_size, weight_bits

    if not getattr(cfg, "fused_decode", True) or platform != "tpu":
        return None
    if _mesh_active():
        # sharded caches/params: the kernel is single-device; the mesh
        # paths keep the composed stack (ops/attention shard_map kernels)
        return None
    if (cfg.layer_pattern or cfg.norm_type != "rmsnorm" or cfg.parallel_attn
            or cfg.num_experts > 0 or cfg.use_bias or cfg.qkv_bias
            or not is_glu(cfg.activation)
            or cfg.activation not in _GLU_BASE
            or cfg.quantize_matmuls != "none"
            or cfg.position_embedding_type != PositionEmbeddingType.ROTARY):
        return None
    layers = params["layers"]
    if "mlp_norm" in layers:
        return None
    if not (is_glu(cfg.activation) and "w_gate" in layers["mlp"]):
        return None
    # The mixed-precision matrix: each projection class (attention
    # wq/wk/wv/wo, MLP w_gate/w_up/w_down) must be internally uniform —
    # a class needing per-projection kernel variants keeps the composed
    # path.  Classes may mix with each other (int8 attention × int4 MLP
    # and the transposes), but plain×quantized mixes decline.
    attn_ws = (layers["attn"]["wq"], layers["attn"]["wk"],
               layers["attn"]["wv"], layers["attn"]["wo"])
    mlp_ws = (layers["mlp"]["w_gate"], layers["mlp"]["w_up"],
              layers["mlp"]["w_down"])

    def class_bits(ws):
        bits = {weight_bits(w) for w in ws}
        return bits.pop() if len(bits) == 1 else None

    aq, mq = class_bits(attn_ws), class_bits(mlp_ws)
    if aq is None or mq is None or (aq == 0) != (mq == 0):
        return None
    gszs = {int4_group_size(w) for w in attn_ws + mlp_ws
            if weight_bits(w) == 4}
    if len(gszs) > 1:
        return None
    gsz = gszs.pop() if gszs else 0
    d = cfg.head_dim
    h = cfg.hidden_size
    if not (d % 128 == 0 and h % 128 == 0 and cfg.ffn_size % 128 == 0
            and (cfg.num_attention_heads * d) % 128 == 0
            and (cfg.kv_heads * d) % 128 == 0):
        return None
    # int4 tiles must split into whole scale groups: the attention tiles
    # contract over h (wq/wk/wv) and nq·d (wo); the MLP gate/up tiles
    # over h and the w_down CHUNKS over f_chunk rows each (the per-tick
    # streaming of _mlp_chunks) — a group straddling a chunk boundary
    # would need cross-tick scale state.
    f_chunk = cfg.ffn_size // _mlp_chunks(cfg.ffn_size)
    if aq == 4 and (h % gsz or (cfg.num_attention_heads * d) % gsz):
        return None
    if mq == 4 and (h % gsz or f_chunk % gsz):
        return None
    return aq, mq, gsz


def _class_itemsizes(params, aq: int, mq: int) -> tuple[float, float]:
    """Per-class HBM bytes/element of the projection weights: 0.5 for
    packed int4, 1 for int8, else the plain dtype width.  Feeds the
    shared ``_pick_block_k``/``_vmem_fit`` probe so the VMEM estimate
    tracks what actually streams."""
    wq = params["layers"]["attn"]["wq"]
    wu = params["layers"]["mlp"]["w_up"]
    attn_item = 0.5 if aq == 4 else 1 if aq == 8 else wq.dtype.itemsize
    mlp_item = 0.5 if mq == 4 else 1 if mq == 8 else wu.dtype.itemsize
    return attn_item, mlp_item


def fused_decode_eligible(cfg, params, k_cache, s: int,
                          platform: str, lora_sr: int = 0) -> bool:
    """Static predicate for the dense fused path: the module-docstring
    scope (RMSNorm GLU rotary stack, single token, no mesh), the
    per-class weight-precision matrix of ``_stack_eligible`` (plain /
    int8 / int4 attention × MLP, plus a plain-or-int8 KV cache in any
    combination), and the VMEM probe with the matching packed itemsizes.

    Factored out (same pattern as ops/attention.decode_kernel_eligible)
    so CPU tests can assert both the accept and every reject arm; the
    paged and verify variants (``fused_paged_decode_eligible``,
    ``fused_paged_verify_eligible``) share every stack check and differ
    only in pool-geometry terms.
    """
    from ..ops.kv_quant import is_quantized_cache

    if s != 1:
        return False
    if lora_sr and lora_sr % 128 != 0:
        # the (h, Sr) arena tiles and (b, Sr) mask need a lane-aligned
        # stacked rank; registries pad n_slots·r or keep the composed path
        return False
    elig = _stack_eligible(cfg, params, platform)
    if elig is None:
        return False
    aq, mq, _ = elig
    cq8 = is_quantized_cache(k_cache)
    kc = k_cache["q"] if cq8 else k_cache
    max_len = kc.shape[3]
    b = kc.shape[1]
    if max_len % 128 != 0:
        return False
    attn_item, mlp_item = _class_itemsizes(params, aq, mq)
    return _pick_block_k(cfg, b, max_len, attn_item, mlp_item,
                         kc.dtype.itemsize, lora_sr=lora_sr) >= 128


def _mesh_shards_stack(mesh) -> bool:
    """True when ``mesh`` shards the layer stack's weights or KV anywhere
    (pp on the layer axis, tp on heads, fsdp on weight residency).

    The whole-stack fused kernels are single-device programs: the
    residual stream crosses every layer inside one dispatch, so a
    head-sharded (tp) stack would need in-kernel collectives after
    wo/w_down, a layer-sharded (pp) stack would need cross-stage
    transfers mid-loop, and an fsdp-split weight would need an
    all-gather before each matmul.  The shard-aware dispatch therefore
    declines whole-stack fusion whenever any of these factors exceeds 1
    and keeps the composed stack, whose per-op paged attention runs the
    kernel per-shard under shard_map
    (ops/attention.py:_sharded_paged_flash_decode) with replicated int32
    tables and the int8 {q, scale} pool leaves moving verbatim."""
    if mesh is None:
        return False
    from ..parallel.mesh import FSDP_AXIS, PIPELINE_AXIS, TENSOR_AXIS

    factor = 1
    for a in (PIPELINE_AXIS, TENSOR_AXIS, FSDP_AXIS):
        if a in mesh.axis_names:
            factor *= mesh.shape[a]
    return factor > 1


def fused_paged_decode_eligible(cfg, params, k_pool, n_slots: int,
                                table_blocks: int, platform: str,
                                mesh=None, lora_sr: int = 0) -> bool:
    """Static predicate for the PAGED fused path (fused_decode_step_paged).

    Same stack scope as fused_decode_eligible, with the shape checks on
    the pool geometry: the kernel's cache tile IS the pool block, so the
    block size must be a legal (>= 128, lane-aligned) Mosaic tile and one
    block per (batch-row, layer) must fit the VMEM estimate.  ``mesh``
    (the sharded serving engine's submesh, engine.start()) makes the
    dispatch shard-aware: a sharded mesh (tp heads, pp layers, or fsdp
    weight residency) keeps the composed stack (see
    ``_mesh_shards_stack``); all-size-1 meshes change nothing."""
    from ..ops.kv_quant import is_quantized_cache

    if n_slots < 1 or table_blocks < 1:
        return False
    if lora_sr and lora_sr % 128 != 0:
        return False
    if _mesh_shards_stack(mesh):
        return False
    elig = _stack_eligible(cfg, params, platform)
    if elig is None:
        return False
    aq, mq, _ = elig
    cq8 = is_quantized_cache(k_pool)
    kc = k_pool["q"] if cq8 else k_pool
    block_k = kc.shape[3]
    if block_k % 128 != 0:
        return False
    attn_item, mlp_item = _class_itemsizes(params, aq, mq)
    # one row's single block streams per tick (cache_rows=1): the cache
    # VMEM term loses its batch factor, but the broadcast-reduce scratch
    # is still over all b rows (the masked no-op trick computes them all)
    return _vmem_fit(cfg, n_slots, block_k, attn_item, mlp_item,
                     1 if cq8 else kc.dtype.itemsize, cache_rows=1,
                     lora_sr=lora_sr)


def fused_paged_verify_eligible(cfg, params, k_pool, n_slots: int,
                                window: int, table_blocks: int,
                                platform: str, mesh=None,
                                tree: bool = False,
                                lora_sr: int = 0) -> bool:
    """Static predicate for the speculative verify kernel
    (fused_decode_verify_paged): the paged predicate with the row batch
    widened to ``n_slots * window`` — the flattened (slot, window-pos)
    rows all carry q/kn/vn scratch, so the VMEM estimate scales with the
    window even though cache traffic still streams one block per tick.
    ``tree`` charges the tree splice's per-row (b, nkv, block_k, d) key
    and value tiles (the shared tiles widen to a row axis), which the
    linear window never materializes.  ``mesh`` makes the dispatch
    shard-aware exactly as in ``fused_paged_decode_eligible``.

    No window width or tree shape is declined for the compiler's sake.
    The one rule it enforces here is on the kernel, not the caller: the
    splice masks are built at the rank of the tile they select
    (``(1, bk, 1)`` linear, ``(1, 1, bk, 1)`` tree) because Mosaic has no
    vector layout for reshaping a bool vector to a higher rank
    ("unsupported shape cast")."""
    from ..ops.kv_quant import is_quantized_cache

    if n_slots < 1 or window < 1 or table_blocks < 1:
        return False
    if lora_sr and lora_sr % 128 != 0:
        return False
    if _mesh_shards_stack(mesh):
        return False
    elig = _stack_eligible(cfg, params, platform)
    if elig is None:
        return False
    aq, mq, _ = elig
    cq8 = is_quantized_cache(k_pool)
    kc = k_pool["q"] if cq8 else k_pool
    block_k = kc.shape[3]
    if block_k % 128 != 0:
        return False
    attn_item, mlp_item = _class_itemsizes(params, aq, mq)
    return _vmem_fit(cfg, n_slots * window, block_k, attn_item, mlp_item,
                     1 if cq8 else kc.dtype.itemsize, cache_rows=1,
                     extra_bcast=2 if tree else 0, lora_sr=lora_sr)


def _mlp_chunks(ffn: int, cap: int = 4) -> int:
    """Number of MLP column/row chunk ticks: the largest divisor of
    ffn/128 not exceeding ``cap`` (chunk widths must stay 128-aligned).
    More chunks spread the per-layer weight DMA across more ticks."""
    lanes = ffn // 128
    for nm in range(cap, 0, -1):
        if lanes % nm == 0:
            return nm
    return 1


def _chunk_down_scales(scale: jax.Array, nm: int) -> jax.Array:
    """w_down int4 group scales ``[L, ffn/gsz, h]`` → ``[L, nm, groups per
    chunk, h]`` (a free split of the group axis).  Each MLP tick streams
    one chunk's groups; as a block of the rank-3 array that is
    ``(1, 11, h)`` of ``(L, 22, h)`` at ffn 2816 (43 of 86 at
    ffn 11008), which breaks the TPU block rule — the last two block dims
    must divide by (8, 128) or equal the array's.  Split this way the
    block is ``(1, 1, groups, h)`` and its last two dims ARE the
    array's."""
    L, groups, h = scale.shape
    return scale.reshape(L, nm, groups // nm, h)


def _down_scale_spec(groups: int, h: int, nk: int, nm: int):
    """BlockSpec of the ``_chunk_down_scales`` operand: MLP tick
    ``ki - nk`` streams its own chunk's ``groups`` rows."""
    def idx(li, ki, *s):
        return (li, jnp.clip(ki - nk, 0, nm - 1), 0, 0)
    return pl.BlockSpec((1, 1, groups, h), idx)


def _default_block_k(cache_int8: bool) -> int:
    """int8 cache blocks are half the bytes: a double-width tile costs
    the same VMEM and amortizes better (flash_decode.py's int8 kernel
    measured ~7% faster at its doubled default)."""
    return 512 if cache_int8 else 256


def _pick_block_k(cfg, b: int, max_len: int, attn_itemsize: float,
                  mlp_itemsize: float, cache_itemsize: int,
                  lora_sr: int = 0) -> int:
    """Largest cache block that fits the VMEM estimate: start from the
    dtype-appropriate default and halve while the budget rejects it (the
    fp32 broadcast-reduce temporaries scale with block_k, so a wide int8
    block can cost more scratch than its HBM-byte savings).  Returns
    < 128 when no legal block fits — the kernel floor, i.e. ineligible."""
    bk = min(_default_block_k(cache_itemsize == 1), max_len)
    while max_len % bk:
        bk //= 2
    while bk >= 128 and not _vmem_fit(cfg, b, bk, attn_itemsize,
                                      mlp_itemsize, cache_itemsize,
                                      lora_sr=lora_sr):
        bk //= 2
    return bk


def _vmem_fit(cfg, b: int, block_k: int, attn_itemsize: float,
              mlp_itemsize: float, cache_itemsize: int,
              budget: int = 100 * 1024 * 1024,
              cache_rows: int | None = None,
              extra_bcast: int = 0,
              lora_sr: int = 0) -> bool:
    """Whole-layer-resident VMEM estimate: the kernel holds one layer's
    weights + two KV blocks, double-buffered, plus fp32 scratch.  Layers
    wider than the budget (e.g. 7B-width: ~354 MB/layer bf16) must keep
    the composed path — Mosaic would fail the scoped-vmem allocation.
    The attention-class, MLP-class, and cache itemsizes are independent
    (the per-tensor precision policy: int8 halves, packed int4 quarters
    the streamed bytes of its class).  int4 classes additionally charge
    for the dequantized fp32 tiles ``_int4_tile`` materializes (plus the
    int32 unpack intermediate) — those live in VMEM even though HBM
    stays packed.  The int8/int4 scale tensors (≤ 1/group_size of the
    blocks) ride inside the budget slack."""
    d = cfg.head_dim
    h = cfg.hidden_size
    nq, nkv, ffn = cfg.num_attention_heads, cfg.kv_heads, cfg.ffn_size
    attn_elts = h * nq * d + 2 * h * nkv * d + nq * d * h
    mlp_elts = (3 if cfg.is_glu else 2) * h * ffn // _mlp_chunks(ffn)
    # paged mode streams one row's block per tick (cache_rows=1); dense
    # mode streams all b rows' blocks together
    cache_elts = 2 * (b if cache_rows is None else cache_rows) \
        * nkv * block_k * d
    blocks = (attn_elts * attn_itemsize + mlp_elts * mlp_itemsize
              + cache_elts * cache_itemsize) * 2  # double-buffered
    b_pad = max(8, -(-b // 8) * 8)
    g = nq // nkv
    # quantized caches materialize scaled fp32 copies of both tile loads;
    # tree splice widens the shared K/V tiles to a per-row axis
    # (extra_bcast more (b, nkv, block_k, d) fp32 temporaries)
    n_tmp = (5 if cache_itemsize == 1 else 3) + extra_bcast
    int4_tmp = 0
    if attn_itemsize == 0.5:
        # _project materializes wq/wk/wv fp32 tiles at once (wo later,
        # smaller); ×2 covers the int32 unpack intermediates
        int4_tmp = max(int4_tmp, 2 * h * (nq + 2 * nkv) * d)
    if mlp_itemsize == 0.5:
        int4_tmp = max(int4_tmp, 2 * mlp_elts)
    scratch = 4 * (2 * b_pad * h + b_pad * nq * d
                   + g * b * nkv * (2 * d + 2 * 128) + 2 * b * nkv * d
                   + int4_tmp
                   # the (b, nkv, block_k, d) broadcast-reduce temporaries
                   + n_tmp * b * nkv * block_k * d)
    lora_bytes = 0
    if lora_sr:
        # stacked LoRA arena blocks (fp32, double-buffered), charged for
        # all seven targets — the predicates don't see the target set,
        # and overcharging only declines fusion.  A factors ride full;
        # gate/up B and down A chunk with the MLP ticks.
        f_chunk = ffn // _mlp_chunks(ffn)
        arena_elts = (h * lora_sr + lora_sr * nq * d            # wq
                      + 2 * (h * lora_sr + lora_sr * nkv * d)   # wk, wv
                      + nq * d * lora_sr + lora_sr * h          # wo
                      + 2 * (h * lora_sr + lora_sr * f_chunk)   # gate, up
                      + f_chunk * lora_sr + lora_sr * h)        # down
        # mask operand + x·A temporaries + the w_down accumulator scratch
        lora_bytes = arena_elts * 4 * 2 + 6 * b_pad * lora_sr * 4
    return int(blocks + scratch + lora_bytes) <= budget


def _lora_specs(lt, lsr, b_pad, h, nq, nkv, d, f_chunk, nk, nm):
    """BlockSpecs for the LoRA mask + per-target stacked A/B arena
    operands, in the kernel's unpacking order (mask, then (A, B) per
    target).  A factors ride whole per layer; the gate/up B columns and
    the down A rows chunk with the MLP ticks, mirroring the base w_gate/
    w_up/w_down streaming so the epilogue adds no per-layer DMA burst."""
    def fixed(shape):
        return pl.BlockSpec(shape, lambda li, ki, *s: (0,) * len(shape))

    def per_layer(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda li, ki, *s: (li,) + (0,) * len(shape))

    def col_chunk():  # gate/up B: walks the ffn columns with MLP ticks
        def idx(li, ki, *s):
            return (li, 0, jnp.clip(ki - nk, 0, nm - 1))
        return pl.BlockSpec((1, lsr, f_chunk), idx)

    def row_chunk():  # down A: walks the ffn rows with MLP ticks
        def idx(li, ki, *s):
            return (li, jnp.clip(ki - nk, 0, nm - 1), 0)
        return pl.BlockSpec((1, f_chunk, lsr), idx)

    specs = [fixed((b_pad, lsr))]
    for t in lt:
        if t in ("wq", "wk", "wv"):
            o = nq * d if t == "wq" else nkv * d
            specs += [per_layer((h, lsr)), per_layer((lsr, o))]
        elif t == "wo":
            specs += [per_layer((nq * d, lsr)), per_layer((lsr, h))]
        elif t in ("w_gate", "w_up"):
            specs += [per_layer((h, lsr)), col_chunk()]
        else:  # w_down
            specs += [row_chunk(), per_layer((lsr, h))]
    return specs


def fused_decode_step(
    cfg,
    stacked,             # params["layers"]: stacked [L, ...] pytree
    x: jax.Array,        # [b, h] — embedded hidden of the ONE new token
    k_cache,             # [L, b, kv_heads, max_len, d] (NOT yet updated),
    #                      or the int8 {"q", "scale"} dict of ops/kv_quant
    v_cache,
    cache_len: jax.Array,  # scalar int32: valid cache rows (= new token
    #                        pos), or a [b] vector of PER-ROW fills (the
    #                        serving engine's slot batch: each request sits
    #                        at its own depth, free slots ride at fill 0)
    rope: tuple,           # (cos, sin) tables from rope_tables(cfg)
    *,
    lora=None,             # (arenas, mask): per-target stacked LoRA A/B
    #                        factors (ops/lora.py:make_arenas layout) +
    #                        a [b, Sr] fp32 per-row slot mask
    #                        (ops/lora.py:slot_mask) — None = base only
    block_k: int | None = None,
    interpret: bool | None = None,
):
    """→ ``(hidden [b, h], k_rows [L, b, kv, 1, d], v_rows ...)``.

    ``hidden`` is the stack output BEFORE the final norm; the caller
    applies final norm + unembedding and writes the returned K/V rows
    into its cache at ``cache_len`` (ops/kv_quant.py:cache_update, which
    accepts the same scalar-or-vector ``cache_len``) — the same contract
    as stack_forward_cached with s=1.

    Weights may be the int8 {"q", "scale"} form (all seven projections,
    as quantize_params produces); the cache may be the int8 dict form.
    For a quantized cache the returned rows are fp32 values the kernel
    already requantized in-register — cache_update's quantize_rows maps
    them back to the exact same int8 rows, so the one host-side write
    stays the single cache write point.

    With a vector ``cache_len``, cache blocks are fetched up to the MAX
    fill only (one clamp for the whole batch: a ragged batch costs the
    deepest row's bytes) and each row masks attention at its own fill.
    """
    from ..ops.kv_quant import is_quantized_cache
    from ..ops.quant import int4_group_size, weight_bits

    if interpret is None:
        interpret = kernels.default_interpret()
    cq8 = is_quantized_cache(k_cache)
    k_arr = k_cache["q"] if cq8 else k_cache
    v_arr = v_cache["q"] if cq8 else v_cache
    b, h = x.shape
    L, _, nkv, max_len, d = k_arr.shape
    nq = cfg.num_attention_heads
    g = nq // nkv
    ffn = cfg.ffn_size
    eps = float(cfg.norm_eps)
    scale = 1.0 / float(np.sqrt(d))
    act = _GLU_BASE[cfg.activation]

    attn_p, mlp_p = stacked["attn"], stacked["mlp"]
    aq = weight_bits(attn_p["wq"])
    mq = weight_bits(mlp_p["w_gate"])
    gsz = (int4_group_size(attn_p["wq"]) if aq == 4
           else int4_group_size(mlp_p["w_gate"]) if mq == 4 else 0)

    lsr, lt = 0, ()
    if lora is not None:
        from ..ops.lora import LORA_TARGETS

        arenas, lmask = lora
        lt = tuple(t for t in LORA_TARGETS if t in arenas)
        lsr = int(arenas[lt[0]]["a"].shape[-1])

    if block_k is None:
        # same probe as fused_decode_eligible, so the block the predicate
        # accepted is the block the call actually launches with
        attn_item, mlp_item = _class_itemsizes({"layers": stacked}, aq, mq)
        block_k = _pick_block_k(cfg, b, max_len, attn_item, mlp_item,
                                1 if cq8 else k_arr.dtype.itemsize,
                                lora_sr=lsr)
    block_k = min(block_k, max_len)
    while max_len % block_k:
        block_k //= 2
    assert block_k >= 128, (max_len, block_k)
    nk = max_len // block_k
    nm = _mlp_chunks(ffn)
    f_chunk = ffn // nm

    b_pad = max(8, -(-b // 8) * 8)
    x_p = x if b_pad == b else jnp.pad(x, ((0, b_pad - b), (0, 0)))
    cache_len = jnp.asarray(cache_len, jnp.int32)
    per_row = cache_len.ndim == 1
    if per_row:
        fills = cache_len
        lens = jnp.concatenate([jnp.max(fills)[None], fills])
        # interleaved-pair RoPE at each row's own position, factored as
        # x·C + (x·P)·S so the kernel needs no per-row matrices
        c_half = rope[0][fills, :d // 2].astype(jnp.float32)  # (b, d/2)
        s_half = rope[1][fills, :d // 2].astype(jnp.float32)
        sign = jnp.where(jnp.arange(d) % 2 == 0, -1.0, 1.0)
        c_rows = jnp.repeat(c_half, 2, axis=-1)
        s_rows = jnp.repeat(s_half, 2, axis=-1) * sign[None, :]
        if b_pad != b:
            c_rows = jnp.pad(c_rows, ((0, b_pad - b), (0, 0)))
            s_rows = jnp.pad(s_rows, ((0, b_pad - b), (0, 0)))
        rot = _pair_swap_matrix(d)
    else:
        rot = rope_rotation_matrix(rope[0], rope[1], cache_len, d)
        lens = jnp.reshape(cache_len, (1,))

    def wm_a(w):  # quantized weights ship their q payload; scales ride
        return w["q"] if aq else w  # separately

    def wm_m(w):
        return w["q"] if mq else w

    # norm scales ride as [L, 1, h]: a (1, 1, h) block keeps the last two
    # dims legal under the TPU (8, 128) tiling rule (a (1, h) block of an
    # [L, h] array has a size-1 sublane dim and is rejected by Mosaic)
    rope_rows = (c_rows, s_rows) if per_row else ()
    # int8 weight scales are [L, out] fp32 → ride as [L, 1, out] (same
    # norm-scale tiling trick); int4 group scales are already rank-3
    # [L, n_groups, out] and ride as-is.  Per-class tuples concatenate in
    # the kernel's unpacking order (qs, ks, vs, os, then gs, us, ds).
    def class_scales(bits, ws):
        if bits == 8:
            return tuple(w["scale"][:, None, :] for w in ws)
        if bits == 4:
            return tuple(w["scale"] for w in ws)
        return ()

    weight_scales = (
        class_scales(aq, (attn_p["wq"], attn_p["wk"], attn_p["wv"],
                          attn_p["wo"]))
        + class_scales(mq, (mlp_p["w_gate"], mlp_p["w_up"],
                            mlp_p["w_down"])))
    if mq == 4:
        weight_scales = weight_scales[:-1] + (
            _chunk_down_scales(weight_scales[-1], nm),)
    # int8 cache scales are [L, b, kv, max_len] fp32 → a trailing unit dim
    # keeps the (block_k, 1) block legal (flash_decode _scale_block_spec)
    cache_scales = (k_cache["scale"][..., None],
                    v_cache["scale"][..., None]) if cq8 else ()
    lora_ops = ()
    if lsr:
        lmask_p = jnp.asarray(lmask, jnp.float32)
        if b_pad != b:
            lmask_p = jnp.pad(lmask_p, ((0, b_pad - b), (0, 0)))
        lora_ops = (lmask_p,) + tuple(
            a for t in lt for a in (arenas[t]["a"], arenas[t]["b"]))
    operands = (
        x_p, rot, *rope_rows,
        stacked["input_norm"]["scale"][:, None, :],
        stacked["post_attn_norm"]["scale"][:, None, :],
        wm_a(attn_p["wq"]), wm_a(attn_p["wk"]), wm_a(attn_p["wv"]),
        wm_a(attn_p["wo"]),
        wm_m(mlp_p["w_gate"]), wm_m(mlp_p["w_up"]), wm_m(mlp_p["w_down"]),
        *weight_scales,
        k_arr, v_arr, *cache_scales, *lora_ops,
    )

    def fixed(shape):
        return pl.BlockSpec(shape, lambda li, ki, lens: (0,) * len(shape))

    def per_layer(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda li, ki, lens: (li,) + (0,) * len(shape))

    def cache_spec():
        # clamp at the fill level: blocks past it are never fetched (the
        # pipeline skips copies whose block index is unchanged); MLP
        # ticks (ki >= nk) also clamp, adding no traffic
        def idx(li, ki, lens):
            last = jnp.maximum(lens[0] - 1, 0) // block_k
            return (li, 0, 0, jnp.minimum(ki, last), 0)
        return pl.BlockSpec((1, b, nkv, block_k, d), idx)

    def mlp_col_spec(rows):
        # gate/up tiles: `rows` is the contraction extent as stored (h,
        # h // 2 packed int4, h // gsz for the group-scale operand)
        def idx(li, ki, lens):
            return (li, 0, jnp.clip(ki - nk, 0, nm - 1))
        return pl.BlockSpec((1, rows, f_chunk), idx)

    def mlp_row_spec(rows):
        # w_down chunks walk the ffn axis: `rows` is one chunk's extent
        # as stored (f_chunk, f_chunk // 2 packed, f_chunk // gsz scales)
        def idx(li, ki, lens):
            return (li, jnp.clip(ki - nk, 0, nm - 1), 0)
        return pl.BlockSpec((1, rows, h), idx)

    def cache_scale_spec():
        # same fill-clamped block walk as cache_spec, trailing unit dim
        def idx(li, ki, lens):
            last = jnp.maximum(lens[0] - 1, 0) // block_k
            return (li, 0, 0, jnp.minimum(ki, last), 0)
        return pl.BlockSpec((1, b, nkv, block_k, 1), idx)

    # int8: one [1, out] scale row per projection; int4: group scales
    # share the q payload's index walk with rows // gsz group rows
    if aq == 8:
        attn_scale_specs = [per_layer((1, nq * d)), per_layer((1, nkv * d)),
                            per_layer((1, nkv * d)), per_layer((1, h))]
    elif aq == 4:
        attn_scale_specs = [per_layer((h // gsz, nq * d)),
                            per_layer((h // gsz, nkv * d)),
                            per_layer((h // gsz, nkv * d)),
                            per_layer((nq * d // gsz, h))]
    else:
        attn_scale_specs = []
    if mq == 8:
        mlp_scale_specs = [mlp_col_spec(1), mlp_col_spec(1),
                           per_layer((1, h))]
    elif mq == 4:
        mlp_scale_specs = [mlp_col_spec(h // gsz), mlp_col_spec(h // gsz),
                           _down_scale_spec(f_chunk // gsz, h, nk, nm)]
    else:
        mlp_scale_specs = []
    # packed int4 payloads store two rows per byte along the contraction
    # axis, so their blocks are half-height
    a_rows = h // 2 if aq == 4 else h
    ao_rows = nq * d // 2 if aq == 4 else nq * d
    m_rows = h // 2 if mq == 4 else h
    md_rows = f_chunk // 2 if mq == 4 else f_chunk
    in_specs = [
        fixed((b_pad, h)), fixed((d, d)),
        *([fixed((b_pad, d))] * 2 if per_row else []),
        per_layer((1, h)), per_layer((1, h)),
        per_layer((a_rows, nq * d)), per_layer((a_rows, nkv * d)),
        per_layer((a_rows, nkv * d)), per_layer((ao_rows, h)),
        mlp_col_spec(m_rows), mlp_col_spec(m_rows), mlp_row_spec(md_rows),
        *attn_scale_specs, *mlp_scale_specs,
        cache_spec(), cache_spec(),
        *([cache_scale_spec(), cache_scale_spec()] if cq8 else []),
        *(_lora_specs(lt, lsr, b_pad, h, nq, nkv, d, f_chunk, nk, nm)
          if lsr else []),
    ]
    out_specs = [
        fixed((b_pad, h)),
        per_layer((b, nkv, d)), per_layer((b, nkv, d)),
    ]
    # quantized caches get fp32 rows back (already dequant(quant(row));
    # the host-side cache_update requantizes them losslessly — see
    # ops/kv_quant.py:fake_quantize_rows)
    row_dt = jnp.float32 if cq8 else k_arr.dtype
    out_shape = [
        jax.ShapeDtypeStruct((b_pad, h), x.dtype),
        jax.ShapeDtypeStruct((L, b, nkv, d), row_dt),
        jax.ShapeDtypeStruct((L, b, nkv, d), row_dt),
    ]
    scratch = [
        pltpu.VMEM((b_pad, h), jnp.float32),           # residual stream
        pltpu.VMEM((g, b, nkv, d), jnp.float32),       # rotated q
        pltpu.VMEM((b, nkv, d), jnp.float32),          # new-token k
        pltpu.VMEM((b, nkv, d), jnp.float32),          # new-token v
        pltpu.VMEM((b_pad, nq * d), jnp.float32),      # attention context
        pltpu.VMEM((b_pad, h), jnp.float32),           # staged MLP input
        pltpu.VMEM((g, b, nkv, 128), jnp.float32),     # online-softmax m
        pltpu.VMEM((g, b, nkv, 128), jnp.float32),     # online-softmax l
        pltpu.VMEM((g, b, nkv, d), jnp.float32),       # online-softmax acc
    ]
    if lsr and "w_down" in lt:
        # w_down LoRA x·A accumulator (see _mlp_chunk / _lora_down)
        scratch.append(pltpu.VMEM((b_pad, lsr), jnp.float32))

    hidden, k_rows, v_rows = pl.pallas_call(
        functools.partial(_decode_step_kernel, per_row, aq, mq, gsz, cq8,
                          lsr, lt, nk, nm, block_k,
                          b, nq, nkv, g, d, eps, scale, act),
        name="decode_step_fused",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, nk + nm),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the whole-layer weight blocks are double-buffered by the
            # pipeline (~2x ~26 MB at hidden 1024, ffn 2816), far past the
            # 16 MB default scoped-vmem limit; v5e has 128 MB physical
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )(lens, *operands)
    return hidden[:b], k_rows[:, :, :, None, :], v_rows[:, :, :, None, :]


def fused_decode_step_paged(
    cfg,
    stacked,             # params["layers"]: stacked [L, ...] pytree
    x: jax.Array,        # [b, h] — embedded hidden of the ONE new token
    k_pool,              # [L, n_blocks, kv_heads, block, d] pool pytree,
    #                      or the int8 {"q", "scale"} dict form
    v_pool,
    tables: jax.Array,   # [b, T] int32 per-slot block tables
    fills: jax.Array,    # [b] int32 per-row fills (free slots at 0)
    rope: tuple,         # (cos, sin) tables from rope_tables(cfg)
    *,
    lora=None,           # (arenas, [b, Sr] slot mask) — see
    #                      fused_decode_step; None = base only
    interpret: bool | None = None,
):
    """Paged fused decode step: the dense kernel's contract — returns
    ``(hidden [b, h], k_rows [L, b, kv, 1, d], v_rows ...)`` — with the
    KV cache read DIRECTLY from the serving block pool via per-slot
    block tables; no dense [b, width] cache is ever materialized.

    The cache tile is one pool block, so HBM cache traffic is the sum of
    each row's live blocks (a 32-token neighbour costs one block while a
    4k-token row costs its 32) instead of b x the deepest row.  The
    caller writes the returned rows into the pool with
    models/model.py:cache_append_rows (quantizing first for an int8
    pool) — the same single-write-point contract as the dense kernel.
    """
    fills = jnp.asarray(fills, jnp.int32)
    return _fused_paged_call(cfg, stacked, x, k_pool, v_pool, tables,
                             fills, fills, rope, window=1, lora=lora,
                             interpret=interpret)


def fused_decode_verify_paged(
    cfg,
    stacked,             # params["layers"]: stacked [L, ...] pytree
    x: jax.Array,        # [S, W, h] — embedded window hiddens: row (s, j)
    #                      is slot s's token at position fills[s] + j
    k_pool,              # [L, n_blocks, kv_heads, block, d] pool pytree,
    #                      or the int8 {"q", "scale"} dict form
    v_pool,
    tables: jax.Array,   # [S, T] int32 per-slot block tables
    fills: jax.Array,    # [S] int32 per-slot committed fills
    rope: tuple,         # (cos, sin) tables from rope_tables(cfg)
    *,
    depths: jax.Array | None = None,  # [S, W] int32 node depths (tree
    #                      mode): row (s, j) sits at cache position
    #                      fills[s] + depths[s, j].  None = linear window
    #                      (depths[s, j] = j implicitly).
    anc: jax.Array | None = None,     # [S, W, W] int32 parent-pointer
    #                      closure: anc[s, j, dd] = node index of row j's
    #                      ancestor at depth dd.  Required iff depths is.
    lora=None,           # (arenas, [S, Sr] per-SLOT mask): every window
    #                      row — the pending token and each draft — is
    #                      verified under its requester's adapter (the
    #                      mask row repeats W times)
    interpret: bool | None = None,
):
    """Batched variable-length speculative verify: the paged fused step
    over a ``W``-wide window per slot in ONE kernel launch.

    Returns ``(hidden [S, W, h], k_rows [L, S·W, kv, 1, d], v_rows ...)``
    — hidden for EVERY window position (the engine's accept logic needs
    all of them), K/V rows in the ``s*W + j`` flattened order
    ``cache_append_rows`` consumes.  Each window position's output is
    bitwise-identical to what ``W`` sequential ``fused_decode_step_paged``
    calls (with the host cache writes in between) would produce: the
    kernel splices the in-flight window K/V over the exact tile columns
    the sequential run would have written (see the kernel docstring), so
    per-row variable draft lengths are handled by the caller simply
    ignoring logits past a row's real drafts — the arity stays fixed and
    the executable is one.

    With ``depths``/``anc`` the window is a candidate TREE per slot
    (BFS node order, node 0 = root, depth non-decreasing in node index,
    the last node deepest): each node attends only its committed history
    plus its own root path, and each node's output is bitwise what
    sequentially decoding that root path would produce.  K/V rows still
    come back in node-index order — the caller compacts the accepted
    path's rows to depth positions afterwards (cache_move_rows).
    """
    S, W, h = x.shape
    fills = jnp.asarray(fills, jnp.int32)
    if depths is None:
        pos = (fills[:, None]
               + jnp.arange(W, dtype=jnp.int32)[None, :]).reshape(-1)
        anc_flat = None
    else:
        pos = (fills[:, None]
               + jnp.asarray(depths, jnp.int32)).reshape(-1)
        anc_flat = jnp.asarray(anc, jnp.int32).reshape(S, W * W)
    if lora is not None:
        # expand the per-slot mask to the flattened (slot, window-pos)
        # row batch: drafts verify under the requester's adapter
        arenas, lmask = lora
        lora = (arenas, jnp.repeat(jnp.asarray(lmask, jnp.float32),
                                   W, axis=0))
    hidden, k_rows, v_rows = _fused_paged_call(
        cfg, stacked, x.reshape(S * W, h), k_pool, v_pool, tables, pos,
        fills, rope, window=W, tree_anc=anc_flat, lora=lora,
        interpret=interpret)
    return hidden.reshape(S, W, h), k_rows, v_rows


def _fused_paged_call(cfg, stacked, x, k_pool, v_pool, tables, pos,
                      fills, rope, *, window: int, tree_anc=None,
                      lora=None, interpret: bool | None = None):
    """Shared launch builder for the paged decode/verify kernels.

    ``x`` is the flattened [b = S·window, h] row batch, ``pos`` the [b]
    per-row cache positions (== ``fills`` when window == 1) driving both
    the RoPE rows and the per-row attention limits; ``fills`` stays [S]
    per-slot for the lens[0] clamp parity.  ``tree_anc`` ([S, W·W] int32,
    flattened ancestor topology) switches the kernel to tree mode and
    rides as a third prefetched scalar."""
    from ..ops.kv_quant import is_quantized_cache
    from ..ops.quant import int4_group_size, weight_bits

    if interpret is None:
        interpret = kernels.default_interpret()
    cq8 = is_quantized_cache(k_pool)
    k_arr = k_pool["q"] if cq8 else k_pool
    v_arr = v_pool["q"] if cq8 else v_pool
    b, h = x.shape
    W = window
    L, _, nkv, block_k, d = k_arr.shape
    ntb = tables.shape[1]
    nq = cfg.num_attention_heads
    g = nq // nkv
    ffn = cfg.ffn_size
    eps = float(cfg.norm_eps)
    scale = 1.0 / float(np.sqrt(d))
    act = _GLU_BASE[cfg.activation]
    nk = (b // W) * ntb                # one attend tick per (slot, block)
    nm = _mlp_chunks(ffn)
    f_chunk = ffn // nm

    b_pad = max(8, -(-b // 8) * 8)
    x_p = x if b_pad == b else jnp.pad(x, ((0, b_pad - b), (0, 0)))
    pos = jnp.asarray(pos, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    lens = jnp.concatenate([jnp.max(fills)[None], pos])
    # interleaved-pair RoPE at each row's own position, factored as
    # x·C + (x·P)·S so the kernel needs no per-row matrices.  Window
    # rows past the table length clamp (their logits are discarded by
    # the caller; the gather must simply stay in bounds).
    rpos = jnp.minimum(pos, rope[0].shape[0] - 1)
    c_half = rope[0][rpos, :d // 2].astype(jnp.float32)  # (b, d/2)
    s_half = rope[1][rpos, :d // 2].astype(jnp.float32)
    sign = jnp.where(jnp.arange(d) % 2 == 0, -1.0, 1.0)
    c_rows = jnp.repeat(c_half, 2, axis=-1)
    s_rows = jnp.repeat(s_half, 2, axis=-1) * sign[None, :]
    if b_pad != b:
        c_rows = jnp.pad(c_rows, ((0, b_pad - b), (0, 0)))
        s_rows = jnp.pad(s_rows, ((0, b_pad - b), (0, 0)))
    rot = _pair_swap_matrix(d)

    lsr, lt = 0, ()
    lora_ops = ()
    if lora is not None:
        from ..ops.lora import LORA_TARGETS

        arenas, lmask = lora
        lt = tuple(t for t in LORA_TARGETS if t in arenas)
        lsr = int(arenas[lt[0]]["a"].shape[-1])
        lmask_p = jnp.asarray(lmask, jnp.float32)
        if b_pad != b:
            lmask_p = jnp.pad(lmask_p, ((0, b_pad - b), (0, 0)))
        lora_ops = (lmask_p,) + tuple(
            a for t in lt for a in (arenas[t]["a"], arenas[t]["b"]))

    attn_p, mlp_p = stacked["attn"], stacked["mlp"]
    aq = weight_bits(attn_p["wq"])
    mq = weight_bits(mlp_p["w_gate"])
    gsz = (int4_group_size(attn_p["wq"]) if aq == 4
           else int4_group_size(mlp_p["w_gate"]) if mq == 4 else 0)

    def wm_a(w):
        return w["q"] if aq else w

    def wm_m(w):
        return w["q"] if mq else w

    # int8 weight scales ride as [L, 1, out]; int4 group scales are
    # already rank-3 [L, n_groups, out] and ride as-is — per-class tuples
    # concatenate in the kernel's unpacking order (see fused_decode_step)
    def class_scales(bits, ws):
        if bits == 8:
            return tuple(w["scale"][:, None, :] for w in ws)
        if bits == 4:
            return tuple(w["scale"] for w in ws)
        return ()

    weight_scales = (
        class_scales(aq, (attn_p["wq"], attn_p["wk"], attn_p["wv"],
                          attn_p["wo"]))
        + class_scales(mq, (mlp_p["w_gate"], mlp_p["w_up"],
                            mlp_p["w_down"])))
    if mq == 4:
        weight_scales = weight_scales[:-1] + (
            _chunk_down_scales(weight_scales[-1], nm),)
    # int8 pool scales are [L, nb, kv, block] fp32 → trailing unit dim
    # keeps the (block_k, 1) block legal (flash_decode _scale_block_spec)
    cache_scales = (k_pool["scale"][..., None],
                    v_pool["scale"][..., None]) if cq8 else ()
    operands = (
        x_p, rot, c_rows, s_rows,
        stacked["input_norm"]["scale"][:, None, :],
        stacked["post_attn_norm"]["scale"][:, None, :],
        wm_a(attn_p["wq"]), wm_a(attn_p["wk"]), wm_a(attn_p["wv"]),
        wm_a(attn_p["wo"]),
        wm_m(mlp_p["w_gate"]), wm_m(mlp_p["w_up"]), wm_m(mlp_p["w_down"]),
        *weight_scales,
        k_arr, v_arr, *cache_scales, *lora_ops,
    )

    # index maps take BOTH prefetched scalars (lens, tables) — varargs
    # keeps the fixed/per-layer specs agnostic to how many ride along
    def fixed(shape):
        return pl.BlockSpec(shape, lambda li, ki, *s: (0,) * len(shape))

    def per_layer(shape):
        return pl.BlockSpec(
            (1,) + shape, lambda li, ki, *s: (li,) + (0,) * len(shape))

    def cache_spec(trailing):
        # attend tick t = r*ntb + j fetches slot r's logical block j via
        # its table, clamped at the slot's own last live block — so HBM
        # traffic is the sum of per-row fills; an empty row's walk lands
        # on the trash block (one fetch, fully masked).  MLP ticks clamp
        # to the final attend tick, adding no traffic.  With a verify
        # window the walk extends to the slot's DEEPEST row's limit
        # (lens[1 + r·W + W-1] = fill_r + W - 1): the fill-boundary and
        # append blocks must stream so the kernel can splice the window
        # K/V over their columns; un-allocated append entries point at
        # the trash block, whose columns are all spliced or masked.
        # Tree mode keeps the same clamp: BFS node order puts the
        # deepest node last, so lens[1 + r·W + W-1] still bounds every
        # row of the slot.
        def idx(li, ki, lens, tbl, *s):
            t = jnp.minimum(ki, nk - 1)
            r = t // ntb
            j = t - r * ntb
            last = jnp.maximum(lens[1 + r * W + W - 1] - 1, 0) // block_k
            return (li, tbl[r, jnp.minimum(j, last)], 0, 0, 0)
        return pl.BlockSpec((1, 1, nkv, block_k, trailing), idx)

    def mlp_col_spec(rows):
        # `rows` is the gate/up contraction extent as stored (h, h // 2
        # packed int4, h // gsz for the group-scale operand)
        def idx(li, ki, *s):
            return (li, 0, jnp.clip(ki - nk, 0, nm - 1))
        return pl.BlockSpec((1, rows, f_chunk), idx)

    def mlp_row_spec(rows):
        # w_down chunks walk the ffn axis: `rows` is one chunk's extent
        # as stored (f_chunk, f_chunk // 2 packed, f_chunk // gsz scales)
        def idx(li, ki, *s):
            return (li, jnp.clip(ki - nk, 0, nm - 1), 0)
        return pl.BlockSpec((1, rows, h), idx)

    if aq == 8:
        attn_scale_specs = [per_layer((1, nq * d)), per_layer((1, nkv * d)),
                            per_layer((1, nkv * d)), per_layer((1, h))]
    elif aq == 4:
        attn_scale_specs = [per_layer((h // gsz, nq * d)),
                            per_layer((h // gsz, nkv * d)),
                            per_layer((h // gsz, nkv * d)),
                            per_layer((nq * d // gsz, h))]
    else:
        attn_scale_specs = []
    if mq == 8:
        mlp_scale_specs = [mlp_col_spec(1), mlp_col_spec(1),
                           per_layer((1, h))]
    elif mq == 4:
        mlp_scale_specs = [mlp_col_spec(h // gsz), mlp_col_spec(h // gsz),
                           _down_scale_spec(f_chunk // gsz, h, nk, nm)]
    else:
        mlp_scale_specs = []
    a_rows = h // 2 if aq == 4 else h
    ao_rows = nq * d // 2 if aq == 4 else nq * d
    m_rows = h // 2 if mq == 4 else h
    md_rows = f_chunk // 2 if mq == 4 else f_chunk
    in_specs = [
        fixed((b_pad, h)), fixed((d, d)),
        fixed((b_pad, d)), fixed((b_pad, d)),
        per_layer((1, h)), per_layer((1, h)),
        per_layer((a_rows, nq * d)), per_layer((a_rows, nkv * d)),
        per_layer((a_rows, nkv * d)), per_layer((ao_rows, h)),
        mlp_col_spec(m_rows), mlp_col_spec(m_rows), mlp_row_spec(md_rows),
        *attn_scale_specs, *mlp_scale_specs,
        cache_spec(d), cache_spec(d),
        *([cache_spec(1), cache_spec(1)] if cq8 else []),
        *(_lora_specs(lt, lsr, b_pad, h, nq, nkv, d, f_chunk, nk, nm)
          if lsr else []),
    ]
    out_specs = [
        fixed((b_pad, h)),
        per_layer((b, nkv, d)), per_layer((b, nkv, d)),
    ]
    row_dt = jnp.float32 if cq8 else k_arr.dtype
    out_shape = [
        jax.ShapeDtypeStruct((b_pad, h), x.dtype),
        jax.ShapeDtypeStruct((L, b, nkv, d), row_dt),
        jax.ShapeDtypeStruct((L, b, nkv, d), row_dt),
    ]
    scratch = [
        pltpu.VMEM((b_pad, h), jnp.float32),           # residual stream
        pltpu.VMEM((g, b, nkv, d), jnp.float32),       # rotated q
        pltpu.VMEM((b, nkv, d), jnp.float32),          # new-token k
        pltpu.VMEM((b, nkv, d), jnp.float32),          # new-token v
        pltpu.VMEM((b_pad, nq * d), jnp.float32),      # attention context
        pltpu.VMEM((b_pad, h), jnp.float32),           # staged MLP input
        pltpu.VMEM((g, b, nkv, 128), jnp.float32),     # online-softmax m
        pltpu.VMEM((g, b, nkv, 128), jnp.float32),     # online-softmax l
        pltpu.VMEM((g, b, nkv, d), jnp.float32),       # online-softmax acc
    ]

    if lsr and "w_down" in lt:
        # w_down LoRA x·A accumulator (see _mlp_chunk / _lora_down)
        scratch.append(pltpu.VMEM((b_pad, lsr), jnp.float32))

    tree = tree_anc is not None
    prefetch = (lens, tables) if not tree \
        else (lens, tables, jnp.asarray(tree_anc, jnp.int32))
    hidden, k_rows, v_rows = pl.pallas_call(
        functools.partial(_decode_step_kernel_paged, aq, mq, gsz, cq8,
                          lsr, lt, W,
                          tree, ntb, nm, block_k,
                          b, nq, nkv, g, d, eps, scale, act),
        name="decode_step_fused",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(L, nk + nm),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=110 * 1024 * 1024,
        ),
        interpret=interpret,
    )(*prefetch, *operands)
    return hidden[:b], k_rows[:, :, :, None, :], v_rows[:, :, :, None, :]
