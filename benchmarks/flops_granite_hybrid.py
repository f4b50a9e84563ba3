"""Operations and bytes the granite-4.0-h-micro cell's algorithm needs,
computed from the configuration file's own numbers (``benchmarks/configs/
granite-4.0-h-micro.json``) and from nothing of the program: the work is
counted the same whatever implements it.

A multiply-add is two operations.  Causal attention is counted at the
half it needs.  The state-space layer is counted as the chunked form the
paper gives (Dao, Gu: "Transformers are SSMs", section 6;
``mamba_chunk_size`` positions a chunk), with the causal half of the
products inside a chunk: what another arrangement of it costs more is
that arrangement's overhead, and shows as a lower share of the roofline.
"""

from __future__ import annotations

# the Mamba-2 mixer's counts are the other state-space configuration's
# functions, of the same sizes under the same names: the chunked form's
# products a token, a layer's state and tail a slot, a step's state traffic
from benchmarks.flops_nemotron_h import (  # noqa: F401
    mamba_step_bytes,
    ssd_flops_per_token,
    state_bytes_per_slot,
)


def sizes_of(doc: dict, layers: int | None = None) -> dict:
    """The sizes the functions below need, from the configuration file;
    the kinds of the ``layers`` layers from ``layer_types`` (whole, or
    whole periods: ``derived.layer_pattern`` is one)."""
    kinds = list(doc["layer_types"])
    if layers is not None and int(layers) != len(kinds):
        period = len(doc["derived"]["layer_pattern"])
        if int(layers) % period:
            raise ValueError(f"{layers} layers are not whole periods of "
                             f"{period}")
        kinds = kinds[:period] * (int(layers) // period)
    heads, width = doc["mamba_n_heads"], doc["mamba_d_head"]
    groups, state = doc["mamba_n_groups"], doc["mamba_d_state"]
    return dict(
        hidden=doc["hidden_size"], layers=len(kinds),
        attention_layers=kinds.count("attention"),
        mamba_layers=kinds.count("mamba"),
        heads=doc["num_attention_heads"],
        kv_heads=doc["num_key_value_heads"],
        head_dim=doc["hidden_size"] // doc["num_attention_heads"],
        mamba_heads=heads, mamba_head_dim=width, mamba_inner=heads * width,
        groups=groups, state=state, conv_taps=doc["mamba_d_conv"],
        chunk=doc["mamba_chunk_size"],
        conv_channels=heads * width + 2 * groups * state,
        mlp_width=doc["shared_intermediate_size"], vocab=doc["vocab_size"])


def matmul_params(s: dict) -> dict:
    """The parameters a token meets in a product, by part."""
    h, di, ch = s["hidden"], s["mamba_inner"], s["conv_channels"]
    qd = s["heads"] * s["head_dim"]
    return {
        # [z | x | B | C | dt] in, out
        "mamba": h * (di + ch + s["mamba_heads"]) + di * h,
        # q, k, v, out
        "attention": h * qd + 2 * h * s["kv_heads"] * s["head_dim"] + qd * h,
        # [gate | up] in, down out
        "mlp": 3 * h * s["mlp_width"],
    }


def layer_params(s: dict) -> dict:
    """Every parameter of a part, its RMSNorm's vector with it: the
    products', the convolution and its bias, ``A_log``, ``dt_bias``,
    ``D`` and the gated norm of a mixer."""
    p = matmul_params(s)
    h = s["hidden"]
    return {
        "mamba": (p["mamba"] + (s["conv_taps"] + 1) * s["conv_channels"]
                  + 3 * s["mamba_heads"] + s["mamba_inner"] + h),
        "attention": p["attention"] + h,
        "mlp": p["mlp"] + h,
    }


def param_count(s: dict) -> int:
    """Every parameter of the model: a mixer and an MLP a layer, the
    final norm, the embedding table once (the head is tied to it)."""
    p = layer_params(s)
    return (s["mamba_layers"] * p["mamba"]
            + s["attention_layers"] * p["attention"]
            + s["layers"] * p["mlp"] + s["hidden"]
            + s["vocab"] * s["hidden"])


def weight_bytes(s: dict, bytes_per_param: int = 2) -> int:
    return param_count(s) * bytes_per_param


def prefill_flops(s: dict, tokens: float, prompts: float,
                  mean_square_over_mean: float) -> float:
    """Forward pass of ``prompts`` prompts of ``tokens`` positions in all:
    every matmul parameter a token meets twice, the convolution, the
    state-space products, causal attention (``mean_square_over_mean`` =
    sum n^2 / sum n over the prompts: a position attends half of its
    prompt's on average), and the head once a prompt (a prefill computes
    its last position's logits only)."""
    p = matmul_params(s)
    mamba = (2.0 * p["mamba"] + 2.0 * s["conv_taps"] * s["conv_channels"]
             + ssd_flops_per_token(s))
    attn = 2.0 * p["attention"] \
        + 2.0 * s["heads"] * s["head_dim"] * mean_square_over_mean
    return (tokens * (s["mamba_layers"] * mamba
                      + s["attention_layers"] * attn
                      + s["layers"] * 2.0 * p["mlp"])
            + prompts * 2.0 * s["hidden"] * s["vocab"])


def kv_bytes_per_position(s: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of one position in every attention layer."""
    return (2 * s["attention_layers"] * s["kv_heads"] * s["head_dim"]
            * bytes_per_value)


def decode_step_bytes(s: dict, live: float, live_tokens: float,
                      bytes_per_param: int = 2) -> float:
    """Least bytes one decode step of ``live`` slots holding
    ``live_tokens`` cached positions must move: every weight once (the
    tied table as the head reads it; the embedding takes a row a slot),
    the state-space states read and written, the live keys and values."""
    return (weight_bytes(s, bytes_per_param) + mamba_step_bytes(s, live)
            + live_tokens * kv_bytes_per_position(s, bytes_per_param))
