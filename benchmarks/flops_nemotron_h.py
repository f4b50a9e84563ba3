"""Operations and bytes the Nemotron-3-Super cell's algorithm needs,
computed from the configuration file's own numbers (``benchmarks/configs/
nemotron-3-super-120b-a12b.json``) and from nothing of the program: the
work is counted the same whatever implements it.

A multiply-add is two operations.  Causal attention is counted at the
half it needs.  The state-space layer is counted as the chunked form the
paper gives (Dao, Gu: "Transformers are SSMs", section 6;
``chunk_size`` positions a chunk), with the causal half of the products
inside a chunk: what another arrangement of it costs more is that
arrangement's overhead, and shows as a lower share of the roofline.
"""

from __future__ import annotations

import math


def sizes_of(doc: dict, layers: int | None = None) -> dict:
    """The sizes the functions below need, from the configuration file:
    the held experts and vocabulary (``n_routed_experts``, ``vocab_size``:
    the reduced keys) beside the router's published width; the kinds of
    the ``layers`` layers from ``derived.layer_pattern``, whole periods."""
    period = doc["derived"]["layer_pattern"]
    layers = int(layers if layers is not None else doc["num_hidden_layers"])
    if layers % len(period):
        raise ValueError(f"{layers} layers are not whole periods of "
                         f"{len(period)}")
    kinds = period * (layers // len(period))
    heads, width = doc["mamba_num_heads"], doc["mamba_head_dim"]
    return dict(
        hidden=doc["hidden_size"], layers=layers,
        attention_layers=kinds.count("attention"),
        mamba_layers=kinds.count("mamba"), moe_layers=kinds.count("mlp"),
        heads=doc["num_attention_heads"], kv_heads=doc["num_key_value_heads"],
        head_dim=doc["head_dim"],
        mamba_heads=heads, mamba_head_dim=width, mamba_inner=heads * width,
        groups=doc["n_groups"], state=doc["ssm_state_size"],
        conv_taps=doc["conv_kernel"], chunk=doc["chunk_size"],
        conv_channels=heads * width
        + 2 * doc["n_groups"] * doc["ssm_state_size"],
        expert_width=doc["moe_intermediate_size"],
        latent=doc["moe_latent_size"],
        shared_width=doc["moe_shared_expert_intermediate_size"],
        held_experts=doc["n_routed_experts"],
        router_outputs=doc["published"]["n_routed_experts"],
        top_k=doc["num_experts_per_tok"], vocab=doc["vocab_size"])


def layer_params(s: dict) -> dict:
    """Parameters by group (the norm vectors, ``A_log``, ``dt_bias``,
    ``D`` and the router's bias, some thousands a layer, are left out)."""
    h, di, ch = s["hidden"], s["mamba_inner"], s["conv_channels"]
    qd = s["heads"] * s["head_dim"]
    return {
        # [z | x | B | C | dt], the convolution and its bias, out
        "mamba": (h * (di + ch + s["mamba_heads"])
                  + (s["conv_taps"] + 1) * ch + di * h),
        # q, k, v, out
        "attention": h * qd + 2 * h * s["kv_heads"] * s["head_dim"] + qd * h,
        "router": h * s["router_outputs"],
        # into the experts' latent and back
        "latent": 2 * h * s["latent"],
        "expert": 2 * s["latent"] * s["expert_width"],
        "shared_expert": 2 * h * s["shared_width"],
    }


def weight_bytes(s: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the weights this chip holds: every layer's part, the held
    experts, the embedding's and the head's held rows.  (The program keeps
    the router in float32: 4 MB more a layer that routes.)"""
    p = layer_params(s)
    moe = p["router"] + p["latent"] + p["shared_expert"] \
        + s["held_experts"] * p["expert"]
    total = (s["mamba_layers"] * p["mamba"]
             + s["attention_layers"] * p["attention"]
             + s["moe_layers"] * moe + 2 * s["vocab"] * s["hidden"])
    return total * bytes_per_param


def held_assignments_per_token(s: dict) -> float:
    """The expert choices of one token in one layer that this chip holds,
    where the router spreads its choices evenly."""
    return s["top_k"] * s["held_experts"] / s["router_outputs"]


def expert_flops_per_assignment(s: dict) -> float:
    """One token through one routed expert: up and down, in the latent."""
    return 4.0 * s["latent"] * s["expert_width"]


def ssd_flops_per_token(s: dict) -> float:
    """The chunked state-space form, one position of one layer, all heads.
    A chunk of Q positions, G groups of state width N, H heads of width P:
    ``C B^T`` a group and its product with ``dt x`` a head, the causal
    half of each (G Q^2 N + H Q^2 P); the chunk's end state ``B^T (dt x)``
    and ``C S_prev``, a head each (2 x 2 Q P N H)."""
    q, g, n = s["chunk"], s["groups"], s["state"]
    hh, p = s["mamba_heads"], s["mamba_head_dim"]
    return (g * q * q * n + hh * q * q * p + 4 * hh * q * p * n) / q


def prefill_flops(s: dict, tokens: float, prompts: float,
                  mean_square_over_mean: float,
                  held_per_token: float | None = None) -> float:
    """Forward pass of ``prompts`` prompts of ``tokens`` positions in all:
    every matmul parameter a token meets twice (of the routed experts the
    held choices only), the convolution, the state-space products, causal
    attention (``mean_square_over_mean`` = sum n^2 / sum n over the
    prompts: a position attends half of its prompt's on average), and the
    head once a prompt (a prefill computes its last position's logits
    only)."""
    p = layer_params(s)
    held = (held_assignments_per_token(s) if held_per_token is None
            else held_per_token)
    moe = 2.0 * (p["router"] + p["latent"] + p["shared_expert"]) \
        + held * expert_flops_per_assignment(s)
    mamba = 2.0 * p["mamba"] + ssd_flops_per_token(s)
    attn = 2.0 * p["attention"] \
        + 2.0 * s["heads"] * s["head_dim"] * mean_square_over_mean
    return (tokens * (s["mamba_layers"] * mamba
                      + s["attention_layers"] * attn
                      + s["moe_layers"] * moe)
            + prompts * 2.0 * s["hidden"] * s["vocab"])


def state_bytes_per_slot(s: dict) -> int:
    """What one state-space layer keeps a sequence, float32: the state a
    head (head width x state width) and the convolution's tail."""
    return 4 * (s["mamba_heads"] * s["mamba_head_dim"] * s["state"]
                + (s["conv_taps"] - 1) * s["conv_channels"])


def mamba_step_bytes(s: dict, slot_steps: float) -> float:
    """Least bytes the state-space layers move for ``slot_steps`` (slot,
    decode step) pairs: every layer reads and writes that slot's state
    and tail once."""
    return 2.0 * s["mamba_layers"] * slot_steps * state_bytes_per_slot(s)


def chosen_held_experts(s: dict, live: float, shares=None) -> float:
    """How many of a layer's held experts ``live`` tokens choose among
    them: expert ``e`` is missed by all with probability ``(1 -
    shares[e]) ** live``, ``shares[e]`` the share of tokens that choose
    it (None: the router spreads its choices evenly, ``top_k /
    router_outputs`` each)."""
    if shares is None:
        shares = [s["top_k"] / s["router_outputs"]] * s["held_experts"]
    return sum(1.0 - (1.0 - q) ** live for q in shares)


def decode_step_bytes(s: dict, live: float, live_tokens: float,
                      chosen: float | None = None,
                      bytes_per_param: int = 2) -> float:
    """Least bytes one decode step of ``live`` slots holding
    ``live_tokens`` cached positions must move: every weight once but the
    embedding table (a row a slot) and the held experts nobody chose
    (``chosen`` a layer are read; None: the even spread's), the
    state-space states read and written, the live keys and values."""
    p = layer_params(s)
    if chosen is None:
        chosen = chosen_held_experts(s, live)
    moe = p["router"] + p["latent"] + p["shared_expert"] \
        + chosen * p["expert"]
    weights = (s["mamba_layers"] * p["mamba"]
               + s["attention_layers"] * p["attention"]
               + s["moe_layers"] * moe + s["vocab"] * s["hidden"])
    kv = 2 * s["attention_layers"] * s["kv_heads"] * s["head_dim"]
    return (weights * bytes_per_param + mamba_step_bytes(s, live)
            + live_tokens * kv * bytes_per_param)
