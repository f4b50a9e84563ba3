"""Operations and bytes the algorithm needs, computed from shapes.

Copied in spirit from ``bench.py:_model_flops_per_token`` (6*N*D plus
attention, GLU-aware) with two corrections: causal attention is counted
at the half it needs, and the arithmetic reads a plain dict of sizes so
that it does not depend on the program's config class.  Recomputed
operations (activation checkpointing) are not counted.
"""

from __future__ import annotations


def sizes_of(model_cfg) -> dict:
    """The sizes the functions below need, from the program's
    ``ModelConfig`` (read here and nowhere else)."""
    return dict(
        hidden=model_cfg.hidden_size, layers=model_cfg.num_layers,
        heads=model_cfg.num_attention_heads, kv_heads=model_cfg.kv_heads,
        head_dim=model_cfg.head_dim, ffn=model_cfg.ffn_size,
        vocab=model_cfg.padded_vocab_size(), glu=bool(model_cfg.is_glu),
        tied=bool(model_cfg.tie_embed_logits))


def matmul_params(s: dict) -> dict:
    """Parameters that take part in a matrix multiplication, by group.
    The embedding gather multiplies nothing; a tied head is one matrix
    used once as the output projection."""
    h, d = s["hidden"], s["head_dim"]
    attn = h * s["heads"] * d + 2 * h * s["kv_heads"] * d + s["heads"] * d * h
    mlp = (3 if s["glu"] else 2) * h * s["ffn"]
    return {"attention_proj": s["layers"] * attn, "mlp": s["layers"] * mlp,
            "head": h * s["vocab"]}


def forward_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward pass, one token of a sequence of ``seq_len``: two
    operations per matmul parameter, plus causal attention — scores and
    context are two matmuls of 2*heads*head_dim*seq_len each, of which
    the causal mask needs half."""
    dense = 2.0 * sum(matmul_params(s).values())
    attention = s["layers"] * 2.0 * s["heads"] * s["head_dim"] * seq_len
    return dense + attention


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """Forward plus backward: three times the forward pass."""
    return 3.0 * forward_flops_per_token(s, seq_len)


def weight_bytes(s: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the weights a decode step reads once: every matmul
    weight (a tied head doubles as the embedding table; an untied table
    is gathered a row per sequence, not streamed).  The norm vectors, a
    hundred-thousandth of this, are left out."""
    return sum(matmul_params(s).values()) * bytes_per_param


def kv_bytes_per_token(s: dict, bytes_per_elt: int = 2) -> int:
    """Key and value bytes one cached position holds, all layers."""
    return 2 * s["layers"] * s["kv_heads"] * s["head_dim"] * bytes_per_elt


def decode_step_bytes(s: dict, live_tokens: float,
                      bytes_per_param: int = 2) -> float:
    """Least bytes one decode step must read: the weights once for the
    whole batch, and the keys and values of every live position."""
    return weight_bytes(s, bytes_per_param) \
        + live_tokens * kv_bytes_per_token(s, bytes_per_param)
