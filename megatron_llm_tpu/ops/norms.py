"""Normalization ops: RMSNorm and LayerNorm with fp32 statistics.

The reference uses a fused CUDA mixed-precision LayerNorm
(megatron/fused_kernels/layer_norm_cuda_kernel.cu:276-675, wrapped by
megatron/model/fused_layer_norm.py:64) and a plain-PyTorch RMSNorm
(fused_layer_norm.py:125-139).  Here both are expressed as jnp math that XLA
fuses into neighboring ops; a Pallas fused RMSNorm kernel lives in
``megatron_llm_tpu.kernels.rmsnorm`` and is selected by ``rmsnorm`` when the
input is large enough to benefit.  Statistics are always computed in fp32
over bf16/fp16 inputs, matching the reference's mixed-precision contract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rmsnorm_ref(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 accumulation (reference math:
    megatron/model/fused_layer_norm.py:125-139)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + eps)
    return (xf * weight.astype(jnp.float32)).astype(dtype)


def layernorm_ref(
    x: jax.Array,
    weight: jax.Array,
    bias: jax.Array | None,
    eps: float = 1e-5,
) -> jax.Array:
    """LayerNorm with fp32 statistics (reference:
    megatron/fused_kernels/layer_norm_cuda_kernel.cu cuApplyLayerNorm)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(dtype)


def norm_apply(norm_type: str, x, params: dict, eps: float,
               impl: str = "xla") -> jax.Array:
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown norm impl {impl!r} (want 'xla'|'pallas')")
    if norm_type == "rmsnorm_zero":
        # zero-centred: the stored weight is the distance from 1
        return rmsnorm_ref(x, 1.0 + params["scale"].astype(jnp.float32), eps)
    if norm_type == "rmsnorm":
        if impl == "pallas":
            from ..kernels.rmsnorm import rmsnorm_pallas
            return rmsnorm_pallas(x, params["scale"], eps)
        return rmsnorm_ref(x, params["scale"], eps)
    elif norm_type == "layernorm":
        if impl == "pallas":
            from ..kernels.rmsnorm import layernorm_pallas
            return layernorm_pallas(x, params["scale"], params.get("bias"),
                                    eps)
        return layernorm_ref(x, params["scale"], params.get("bias"), eps)
    raise ValueError(f"unknown norm type {norm_type}")


def norm_init(norm_type: str, hidden: int, dtype=jnp.float32) -> dict:
    if norm_type == "rmsnorm_zero":
        return {"scale": jnp.zeros((hidden,), dtype=dtype)}
    if norm_type == "rmsnorm":
        return {"scale": jnp.ones((hidden,), dtype=dtype)}
    elif norm_type == "layernorm":
        return {
            "scale": jnp.ones((hidden,), dtype=dtype),
            "bias": jnp.zeros((hidden,), dtype=dtype),
        }
    raise ValueError(f"unknown norm type {norm_type}")
