"""LoRA factor trees and the stacked multi-adapter arena.

Low-rank adapters (Hu et al 2021) for the Llama-family decoder: each
target projection ``W [in, out]`` gains a rank-``r`` update ``ΔW = A·B ·
α/r`` with ``A [in, r]`` and ``B [r, out]`` (B zero-initialized, so a
fresh adapter is an exact no-op).  Factors are stacked on the leading
layer axis — the same layout as the model's scanned parameter stack —
and kept fp32 regardless of the base precision: the base matmul may
read int8/int4-resident weights (ops/quant.py), the adapter correction
is tiny and full-precision.

The serving-side multiplexing trick (punica / S-LoRA) lives here too:
``n_slots`` resident adapters concatenate along the rank axis into ONE
arena per target, ``A_flat [L, in, n_slots·r]`` / ``B_flat [L, n_slots·
r, out]``, and a per-row one-hot :func:`slot_mask` zeroes every column
block except the row's own adapter between the two dots::

    y += ((x · A_flat) ⊙ mask_row) · B_flat

Masked-out columns contribute exact ``±0.0`` products, so a request's
tokens are bitwise what a single-adapter run produces no matter which
adapters share its batch — the invariant the serving tests pin.  Slot
``-1`` selects no columns at all: the null adapter rides through the
same executable with a zero mask row instead of a second compiled
variant.  ``α/r`` is folded into the arena's B columns at install time
(:func:`install_adapter`), keeping the hot-path epilogue scale-free.

Host-side residency (LRU + ref pinning, metrics) is
``serving/adapters/registry.py``; this module is the pure math + the
adapter checkpoint format (``adapter.npz`` + ``adapter_config.json``)
shared by ``finetune.py --lora_rank``, ``tools/hf_interop.py`` PEFT
import, and the serving registry.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..config import ModelConfig

# Adapter-targetable projections, in the order a layer applies them.
# Keys name leaves of the stacked layer tree:
# wq/wk/wv/wo under ["attn"], w_gate/w_up/w_down under ["mlp"].
LORA_TARGETS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

# PEFT-style default: attention q/v only.
DEFAULT_TARGETS = ("wq", "wv")

_ADAPTER_CONFIG = "adapter_config.json"
_ADAPTER_WEIGHTS = "adapter.npz"


def lora_target_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, int]]:
    """target -> (in_dim, out_dim) of the base projection it adapts."""
    h = cfg.hidden_size
    d = cfg.head_dim
    nq = cfg.num_attention_heads
    nkv = cfg.kv_heads
    ffn = cfg.ffn_size
    shapes = {
        "wq": (h, nq * d),
        "wk": (h, nkv * d),
        "wv": (h, nkv * d),
        "wo": (nq * d, h),
        "w_up": (h, ffn),
        "w_down": (ffn, h),
    }
    if cfg.is_glu:
        shapes["w_gate"] = (h, ffn)
    return shapes


@dataclasses.dataclass
class LoRAAdapter:
    """One adapter: stacked fp32 factors + its hyperparameters.

    ``factors[target] = {"a": [L, in, r], "b": [L, r, out]}``.  Host-side
    container (never passed to jit wholesale); the registry moves the
    leaves into the device arena on install."""

    rank: int
    alpha: float
    targets: Tuple[str, ...]
    factors: Dict[str, Dict[str, jax.Array]]

    @property
    def scale(self) -> float:
        return float(self.alpha) / float(self.rank)

    @property
    def nbytes(self) -> int:
        return sum(int(x.nbytes)
                   for x in jax.tree.leaves(self.factors))


def init_lora_adapter(cfg: ModelConfig, key: jax.Array, rank: int,
                      targets: Optional[Sequence[str]] = None,
                      alpha: Optional[float] = None) -> LoRAAdapter:
    """Fresh adapter: A ~ N(0, 1/in), B = 0 — ΔW starts exactly zero, so
    an untrained adapter leaves the base model bitwise unchanged."""
    targets = tuple(targets) if targets is not None else DEFAULT_TARGETS
    shapes = lora_target_shapes(cfg)
    unknown = [t for t in targets if t not in shapes]
    if unknown:
        raise ValueError(f"unknown LoRA targets {unknown}; "
                         f"choose from {sorted(shapes)}")
    L = cfg.num_layers
    factors: Dict[str, Dict[str, jax.Array]] = {}
    for t in targets:
        fin, fout = shapes[t]
        key, ka = jax.random.split(key)
        factors[t] = {
            "a": (jax.random.normal(ka, (L, fin, rank), jnp.float32)
                  / jnp.sqrt(jnp.float32(fin))),
            "b": jnp.zeros((L, rank, fout), jnp.float32),
        }
    return LoRAAdapter(rank=int(rank),
                       alpha=float(alpha if alpha is not None else rank),
                       targets=targets, factors=factors)


def validate_adapter(cfg: ModelConfig, adapter: LoRAAdapter) -> None:
    """Shape-check an adapter against a model config (load-time guard)."""
    shapes = lora_target_shapes(cfg)
    L = cfg.num_layers
    r = adapter.rank
    for t in adapter.targets:
        if t not in shapes:
            raise ValueError(f"adapter targets unknown projection {t!r}")
        fin, fout = shapes[t]
        a = adapter.factors[t]["a"]
        b = adapter.factors[t]["b"]
        if tuple(a.shape) != (L, fin, r):
            raise ValueError(
                f"adapter {t}.a shape {tuple(a.shape)} != {(L, fin, r)}")
        if tuple(b.shape) != (L, r, fout):
            raise ValueError(
                f"adapter {t}.b shape {tuple(b.shape)} != {(L, r, fout)}")


# ---------------------------------------------------------------------------
# Multi-adapter arena (rank-axis concatenation) + the grouped epilogue
# ---------------------------------------------------------------------------


def make_arenas(cfg: ModelConfig, n_slots: int, rank: int,
                targets: Sequence[str]) -> Dict[str, Dict[str, jax.Array]]:
    """Zeroed device arenas: target -> {"a": [L, in, n_slots·r],
    "b": [L, n_slots·r, out]}.  All-zero columns make an uninstalled
    slot an exact no-op even if a stale mask ever selected it."""
    shapes = lora_target_shapes(cfg)
    L = cfg.num_layers
    sr = n_slots * rank
    return {
        t: {
            "a": jnp.zeros((L, shapes[t][0], sr), jnp.float32),
            "b": jnp.zeros((L, sr, shapes[t][1]), jnp.float32),
        }
        for t in targets
    }


def arena_sr(arenas) -> int:
    """Total stacked rank (n_slots·r) of an arena dict; 0 when empty."""
    if not arenas:
        return 0
    first = next(iter(arenas.values()))
    return int(first["a"].shape[-1])


def slot_mask(slots: jax.Array, n_slots: int, rank: int) -> jax.Array:
    """Per-row arena column mask: fp32 ``[b, n_slots·rank]`` selecting
    the ``rank`` columns of each row's adapter slot; slot ``-1`` (no
    adapter) selects nothing.  Traced-friendly: ``slots`` is a normal
    int32 operand, only ``n_slots``/``rank`` are static."""
    col_slot = jnp.arange(n_slots * rank, dtype=jnp.int32) // rank
    return (slots[:, None] == col_slot[None, :]).astype(jnp.float32)


def lora_delta(x: jax.Array, a: jax.Array, b: jax.Array,
               mask: jax.Array) -> jax.Array:
    """The grouped epilogue for one projection: ``((x·A_flat) ⊙ mask)
    ·B_flat`` in fp32 (α/r already folded into B at install).

    ``x [..., in]``, ``a [in, Sr]``, ``b [Sr, out]``, ``mask [b, Sr]``
    broadcast against x's leading batch axis.  fp32 accumulation with
    fp32 inputs keeps the masked-column contributions exact ±0.0, which
    is what makes mixed-adapter batches bitwise-stable per request."""
    x32 = x.astype(jnp.float32)
    xa = jnp.dot(x32, a, preferred_element_type=jnp.float32)
    while mask.ndim < xa.ndim:
        mask = mask[:, None]
    return jnp.dot(xa * mask, b, preferred_element_type=jnp.float32)


def install_adapter(arenas, factors, slot, scale: float, rank: int):
    """Write one adapter's factor columns into the arena at ``slot``
    (traced int32 — ONE compiled executable serves every slot), folding
    ``scale = α/r`` into the B rows.  Pure/functional; the registry jits
    this with the arena donated."""
    col = jnp.asarray(slot, jnp.int32) * rank
    out = {}
    for t, arena in arenas.items():
        a_new, b_new = arena["a"], arena["b"]
        if t in factors:
            a_cols = factors[t]["a"].astype(jnp.float32)
            b_rows = (factors[t]["b"].astype(jnp.float32)
                      * jnp.float32(scale))
            a_new = jax.lax.dynamic_update_slice(
                a_new, a_cols, (jnp.int32(0), jnp.int32(0), col))
            b_new = jax.lax.dynamic_update_slice(
                b_new, b_rows, (jnp.int32(0), col, jnp.int32(0)))
        else:
            # adapter does not touch this target: zero the slot's columns
            # so whatever lived there before cannot leak into its rows
            za = jnp.zeros(a_new.shape[:-1] + (rank,), jnp.float32)
            zb = jnp.zeros(
                (b_new.shape[0], rank) + b_new.shape[2:], jnp.float32)
            a_new = jax.lax.dynamic_update_slice(
                a_new, za, (jnp.int32(0), jnp.int32(0), col))
            b_new = jax.lax.dynamic_update_slice(
                b_new, zb, (jnp.int32(0), col, jnp.int32(0)))
        out[t] = {"a": a_new, "b": b_new}
    return out


def merge_adapter(params, adapter: LoRAAdapter):
    """Fold ``ΔW = A·B·α/r`` into the base weights (export / the
    single-tenant deployment path).  Requires unquantized base leaves;
    returns a new params tree, base dtype preserved."""
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    mlp = dict(layers["mlp"])
    for t, f in adapter.factors.items():
        group, gname = (attn, "attn") if t in ("wq", "wk", "wv", "wo") \
            else (mlp, "mlp")
        w = group[t]
        if not hasattr(w, "dtype"):
            raise ValueError(
                f"cannot merge adapter into quantized base leaf {t!r}; "
                "merge before quantize_params")
        delta = jnp.einsum("lir,lro->lio", f["a"].astype(jnp.float32),
                           f["b"].astype(jnp.float32)) * adapter.scale
        group[t] = (w.astype(jnp.float32) + delta).astype(w.dtype)
        if gname == "attn":
            layers["attn"] = group
        else:
            layers["mlp"] = group
    out = dict(params)
    out["layers"] = layers
    return out


# ---------------------------------------------------------------------------
# Adapter checkpoint format (shared by finetune.py / hf_interop / registry)
# ---------------------------------------------------------------------------


def save_adapter(path: str, adapter: LoRAAdapter) -> None:
    """Write an adapter-only checkpoint: ``adapter.npz`` (flat
    ``{target}.{a|b}`` arrays) + ``adapter_config.json``."""
    import numpy as np

    os.makedirs(path, exist_ok=True)
    flat = {}
    for t, f in adapter.factors.items():
        flat[f"{t}.a"] = np.asarray(f["a"], np.float32)
        flat[f"{t}.b"] = np.asarray(f["b"], np.float32)
    np.savez(os.path.join(path, _ADAPTER_WEIGHTS), **flat)
    with open(os.path.join(path, _ADAPTER_CONFIG), "w") as fh:
        json.dump({"rank": adapter.rank, "alpha": adapter.alpha,
                   "targets": list(adapter.targets)}, fh, indent=2)


def load_adapter(path: str) -> LoRAAdapter:
    """Load an adapter checkpoint written by :func:`save_adapter` (or
    converted from PEFT by ``tools/hf_interop.py``)."""
    import numpy as np

    with open(os.path.join(path, _ADAPTER_CONFIG)) as fh:
        meta = json.load(fh)
    data = np.load(os.path.join(path, _ADAPTER_WEIGHTS))
    factors: Dict[str, Dict[str, jax.Array]] = {}
    for t in meta["targets"]:
        factors[t] = {"a": jnp.asarray(data[f"{t}.a"], jnp.float32),
                      "b": jnp.asarray(data[f"{t}.b"], jnp.float32)}
    return LoRAAdapter(rank=int(meta["rank"]), alpha=float(meta["alpha"]),
                       targets=tuple(meta["targets"]), factors=factors)
