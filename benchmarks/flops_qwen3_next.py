"""Operations and bytes the Qwen3-Next cell's algorithm needs, computed
from the configuration file's own numbers (``benchmarks/configs/
qwen3-next-80b-a3b.json``) and from nothing of the program: the work is
counted the same whatever implements it.

A multiply-add is two operations.  Causal attention is counted at the
half it needs.  The delta rule is counted as the chunked rule the paper
gives (Gated Delta Networks, section 3; 64 positions a chunk): what a
different arrangement of it costs more is that arrangement's overhead,
and shows as a lower share of the roofline.
"""

from __future__ import annotations

CHUNK = 64


def sizes_of(doc: dict, layers: int | None = None) -> dict:
    """The sizes the functions below need, from the configuration file:
    the held experts and vocabulary (``num_experts``, ``vocab_size``: the
    reduced keys) beside the router's published width."""
    period = doc["full_attention_interval"]
    layers = int(layers if layers is not None else doc["num_hidden_layers"])
    return dict(
        hidden=doc["hidden_size"], layers=layers,
        full_layers=layers // period, linear_layers=layers - layers // period,
        heads=doc["num_attention_heads"], kv_heads=doc["num_key_value_heads"],
        head_dim=doc["head_dim"],
        key_heads=doc["linear_num_key_heads"],
        value_heads=doc["linear_num_value_heads"],
        key_dim=doc["linear_key_head_dim"],
        value_dim=doc["linear_value_head_dim"],
        conv_taps=doc["linear_conv_kernel_dim"],
        expert_width=doc["moe_intermediate_size"],
        shared_width=doc["shared_expert_intermediate_size"],
        held_experts=doc["num_experts"],
        router_outputs=doc["published"]["num_experts"],
        top_k=doc["num_experts_per_tok"], vocab=doc["vocab_size"])


def layer_params(s: dict) -> dict:
    """Parameters of one layer by group (norm vectors, ``A_log`` and
    ``dt_bias``, some thousands a layer, are left out)."""
    h = s["hidden"]
    kd, vd = s["key_heads"] * s["key_dim"], s["value_heads"] * s["value_dim"]
    qd = s["heads"] * s["head_dim"]
    return {
        # [q | k | v | z], [b | a], the convolution over q | k | v, out
        "deltanet": (h * (2 * kd + 2 * vd) + h * 2 * s["value_heads"]
                     + s["conv_taps"] * (2 * kd + vd) + vd * h),
        # [q | gate], k, v, out
        "attention": (h * 2 * qd + 2 * h * s["kv_heads"] * s["head_dim"]
                      + qd * h),
        "router": h * s["router_outputs"],
        "expert": 3 * h * s["expert_width"],
        "shared_expert": 3 * h * s["shared_width"] + h,
    }


def weight_bytes(s: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the weights this chip holds: every layer's mixer, router,
    shared expert and held experts, the embedding's and the head's held
    rows.  (The program keeps the router in float32: 4 MB more a layer.)"""
    p = layer_params(s)
    common = p["router"] + p["shared_expert"] \
        + s["held_experts"] * p["expert"]
    total = (s["linear_layers"] * (p["deltanet"] + common)
             + s["full_layers"] * (p["attention"] + common)
             + 2 * s["vocab"] * s["hidden"])
    return total * bytes_per_param


def held_assignments_per_token(s: dict) -> float:
    """The expert choices of one token in one layer that this chip holds,
    where the router spreads its choices evenly."""
    return s["top_k"] * s["held_experts"] / s["router_outputs"]


def expert_flops_per_assignment(s: dict) -> float:
    """One token through one routed expert: gate, up and down."""
    return 6.0 * s["hidden"] * s["expert_width"]


def delta_rule_flops_per_token(s: dict) -> float:
    """The chunked gated delta rule, one position of one layer, all value
    heads.  A chunk of C positions of one head, key width dk, value width
    dv: K K^T (2 C^2 dk); the unit lower triangular solve for the
    pseudo-values and their keys (C^2 (dk + dv)); their product with the
    state, the query's and the state's update (3 x 2 C dk dv); Q K^T and
    its product with the pseudo-values, the causal half of each
    (C^2 dk + C^2 dv)."""
    c, dk, dv = CHUNK, s["key_dim"], s["value_dim"]
    chunk = (2 * c * c * dk + c * c * (dk + dv) + 6 * c * dk * dv
             + c * c * dk + c * c * dv)
    return s["value_heads"] * chunk / c


def prefill_flops(s: dict, tokens: float, prompts: float,
                  mean_square_over_mean: float,
                  held_per_token: float | None = None) -> float:
    """Forward pass of ``prompts`` prompts of ``tokens`` positions in all:
    every matmul parameter a token meets twice (of the routed experts the
    held choices only), the convolution, the delta rule, causal attention
    (``mean_square_over_mean`` = sum n^2 / sum n over the prompts: a
    position attends half of its prompt's on average), and the head once
    a prompt (a prefill computes its last position's logits only)."""
    p = layer_params(s)
    held = (held_assignments_per_token(s) if held_per_token is None
            else held_per_token)
    moe = 2.0 * (p["router"] + p["shared_expert"]) \
        + held * expert_flops_per_assignment(s)
    linear = 2.0 * p["deltanet"] + delta_rule_flops_per_token(s) + moe
    attn = 2.0 * s["heads"] * s["head_dim"] * mean_square_over_mean
    full = 2.0 * p["attention"] + attn + moe
    return (tokens * (s["linear_layers"] * linear + s["full_layers"] * full)
            + prompts * 2.0 * s["hidden"] * s["vocab"])
