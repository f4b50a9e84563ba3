"""Pallas fused RMSNorm / LayerNorm kernels (fwd + bwd, fp32 statistics).

TPU-native equivalent of the reference's fused mixed-precision LayerNorm
CUDA kernel (megatron/fused_kernels/layer_norm_cuda_kernel.cu:276-675) — and
a real kernel for RMSNorm, which the reference leaves as plain PyTorch
(megatron/model/fused_layer_norm.py:125-139) even though Llama runs it on
every layer.

Shape convention: the kernel flattens all leading dims into rows and tiles
[block_rows, hidden] through VMEM; statistics (mean/rstd) are computed in
fp32 regardless of input dtype and saved for the backward pass.  The input
gradient is a second Pallas kernel; the weight/bias gradients are cross-row
reductions that XLA already schedules optimally, so they are computed as a
jnp reduction over the recomputed normalized activations (same split the
reference makes: cuComputePartGradGammaBeta is a plain reduction kernel).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import kernels


def _block_rows(hidden: int) -> int:
    # ~1 MB of fp32 activations per block (the bwd kernel holds ~4 live
    # fp32 temporaries of this size; VMEM is 16 MB); ≥8 rows for sublane
    # tiling, rounded down to a multiple of 8.
    rows = max(8, min(1024, (1024 * 1024) // (hidden * 4)))
    return (rows // 8) * 8


def _pad_rows(x, rows_p):
    if x.shape[0] == rows_p:
        return x
    return jnp.pad(x, ((0, rows_p - x.shape[0]), (0, 0)))


# ---------------------------------------------------------------------------
# Forward kernels
# ---------------------------------------------------------------------------


def _rms_fwd_kernel(eps, x_ref, w_ref, y_ref, rstd_ref):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = x * rstd * w_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _ln_fwd_kernel(eps, has_bias, *refs):
    if has_bias:
        x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref = refs
    else:
        x_ref, w_ref, y_ref, mean_ref, rstd_ref = refs
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd * w_ref[:].astype(jnp.float32)
    if has_bias:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


# ---------------------------------------------------------------------------
# Backward (dx) kernels
# ---------------------------------------------------------------------------


def _rms_bwd_kernel(x_ref, w_ref, dy_ref, rstd_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    g = dy_ref[:].astype(jnp.float32) * w_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    c = jnp.mean(g * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (g - xhat * c)).astype(dx_ref.dtype)


def _ln_bwd_kernel(x_ref, w_ref, dy_ref, mean_ref, rstd_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    g = dy_ref[:].astype(jnp.float32) * w_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd
    c1 = jnp.mean(g, axis=-1, keepdims=True)
    c2 = jnp.mean(g * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (g - c1 - xhat * c2)).astype(dx_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _row_call(kernel, n_out, rows_p, hidden, br, dtypes, operands, interpret):
    """Grid over row blocks; weights are broadcast (index 0) per step."""
    nr = rows_p // br
    specs = []
    for op in operands:
        if op.shape == (1, hidden):      # weight/bias
            specs.append(pl.BlockSpec((1, hidden), lambda i: (0, 0)))
        elif op.shape[-1] == 1:           # per-row stats [rows, 1]
            specs.append(pl.BlockSpec((br, 1), lambda i: (i, 0)))
        else:                             # activations [rows, hidden]
            specs.append(pl.BlockSpec((br, hidden), lambda i: (i, 0)))
    out_specs = []
    out_shape = []
    for dt, shape in dtypes[:n_out]:
        if shape[-1] == 1:
            out_specs.append(pl.BlockSpec((br, 1), lambda i: (i, 0)))
        else:
            out_specs.append(pl.BlockSpec((br, hidden), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct(shape, dt))
    return pl.pallas_call(
        kernel,
        name="rmsnorm",
        grid=(nr,),
        in_specs=specs,
        out_specs=out_specs if n_out > 1 else out_specs[0],
        out_shape=out_shape if n_out > 1 else out_shape[0],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*operands)


def _flatten(x):
    hidden = x.shape[-1]
    return x.reshape(-1, hidden), x.shape


# ---------------------------------------------------------------------------
# RMSNorm public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rmsnorm_pallas(x, weight, eps: float = 1e-5,
                   interpret: Optional[bool] = None):
    y, _ = _rms_fwd(x, weight, eps, interpret)
    return y


def _rms_fwd(x, weight, eps, interpret):
    if interpret is None:
        interpret = kernels.default_interpret()
    x2, shape = _flatten(x)
    rows, hidden = x2.shape
    br = _block_rows(hidden)
    rows_p = ((rows + br - 1) // br) * br
    xp = _pad_rows(x2, rows_p)
    w2 = weight.reshape(1, hidden)
    y, rstd = _row_call(
        functools.partial(_rms_fwd_kernel, eps), 2, rows_p, hidden, br,
        [(x.dtype, (rows_p, hidden)), (jnp.float32, (rows_p, 1))],
        [xp, w2], interpret)
    return y[:rows].reshape(shape), (xp, w2, rstd, rows, shape, interpret)


def _rms_fwd_vjp(x, weight, eps, interpret):
    y, res = _rms_fwd(x, weight, eps, interpret)
    return y, res


def _rms_bwd_vjp(eps, interpret_arg, res, dy):
    xp, w2, rstd, rows, shape, interpret = res
    hidden = xp.shape[1]
    br = _block_rows(hidden)
    rows_p = xp.shape[0]
    dyp = _pad_rows(dy.reshape(-1, hidden), rows_p)
    dx = _row_call(
        _rms_bwd_kernel, 1, rows_p, hidden, br,
        [(xp.dtype, (rows_p, hidden))],
        [xp, w2, dyp, rstd], interpret)
    # Weight grad: cross-row reduction, XLA territory.
    xhat = xp.astype(jnp.float32) * rstd
    dw = jnp.sum(dyp.astype(jnp.float32) * xhat, axis=0)
    return dx[:rows].reshape(shape), dw.astype(w2.dtype).reshape(-1)


rmsnorm_pallas.defvjp(_rms_fwd_vjp, _rms_bwd_vjp)


# ---------------------------------------------------------------------------
# LayerNorm public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layernorm_pallas(x, weight, bias, eps: float = 1e-5,
                     interpret: Optional[bool] = None):
    y, _ = _ln_fwd(x, weight, bias, eps, interpret)
    return y


def _ln_fwd(x, weight, bias, eps, interpret):
    if interpret is None:
        interpret = kernels.default_interpret()
    x2, shape = _flatten(x)
    rows, hidden = x2.shape
    br = _block_rows(hidden)
    rows_p = ((rows + br - 1) // br) * br
    xp = _pad_rows(x2, rows_p)
    w2 = weight.reshape(1, hidden)
    has_bias = bias is not None
    operands = [xp, w2] + ([bias.reshape(1, hidden)] if has_bias else [])
    y, mean, rstd = _row_call(
        functools.partial(_ln_fwd_kernel, eps, has_bias), 3, rows_p, hidden,
        br,
        [(x.dtype, (rows_p, hidden)), (jnp.float32, (rows_p, 1)),
         (jnp.float32, (rows_p, 1))],
        operands, interpret)
    res = (xp, w2, mean, rstd, rows, shape, has_bias, interpret)
    return y[:rows].reshape(shape), res


def _ln_fwd_vjp(x, weight, bias, eps, interpret):
    y, res = _ln_fwd(x, weight, bias, eps, interpret)
    return y, res


def _ln_bwd_vjp(eps, interpret_arg, res, dy):
    xp, w2, mean, rstd, rows, shape, has_bias, interpret = res
    hidden = xp.shape[1]
    br = _block_rows(hidden)
    rows_p = xp.shape[0]
    dyp = _pad_rows(dy.reshape(-1, hidden), rows_p)
    dx = _row_call(
        _ln_bwd_kernel, 1, rows_p, hidden, br,
        [(xp.dtype, (rows_p, hidden))],
        [xp, w2, dyp, mean, rstd], interpret)
    xhat = (xp.astype(jnp.float32) - mean) * rstd
    dyf = dyp.astype(jnp.float32)
    dw = jnp.sum(dyf * xhat, axis=0).astype(w2.dtype).reshape(-1)
    db = jnp.sum(dyf, axis=0).astype(w2.dtype).reshape(-1) if has_bias \
        else None
    return dx[:rows].reshape(shape), dw, db


layernorm_pallas.defvjp(_ln_fwd_vjp, _ln_bwd_vjp)
