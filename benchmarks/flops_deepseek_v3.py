"""Operations and bytes the kanana-2 cell's algorithm needs, computed from
the configuration file's own numbers (``benchmarks/configs/
kanana-2-30b-a3b.json``, a ``deepseek_v3`` config) and from nothing of the
program: the work is counted the same whatever implements it.

A multiply-add is two operations.  Causal attention is counted at the
half it needs, at the widths the mathematics has: a score is a product
over ``qk_nope_head_dim + qk_rope_head_dim`` columns, a weighted sum over
``v_head_dim`` (a kernel that pads the value to the key's width does
more and is credited no more).  A decode step's attention is counted in
the absorbed form, the only one that reads ONE row a cached position: a
head's score over the row's ``kv_lora_rank + qk_rope_head_dim`` columns,
its weighted sum over the first ``kv_lora_rank``.
"""

from __future__ import annotations


def sizes_of(doc: dict, layers: int | None = None) -> dict:
    """The sizes the functions below need, from the configuration file."""
    layers = int(layers if layers is not None else doc["num_hidden_layers"])
    dense = min(int(doc["first_k_dense_replace"]), layers)
    return dict(
        hidden=doc["hidden_size"], layers=layers, dense_layers=dense,
        expert_layers=layers - dense, heads=doc["num_attention_heads"],
        rank=doc["kv_lora_rank"], nope=doc["qk_nope_head_dim"],
        rope=doc["qk_rope_head_dim"], v=doc["v_head_dim"],
        dense_width=doc["intermediate_size"],
        expert_width=doc["moe_intermediate_size"],
        experts=doc["n_routed_experts"],
        shared_width=doc["n_shared_experts"] * doc["moe_intermediate_size"],
        top_k=doc["num_experts_per_tok"], vocab=doc["vocab_size"])


def layer_params(s: dict) -> dict:
    """Parameters of one layer by group."""
    h, H = s["hidden"], s["heads"]
    return {
        # W_q, W_kva, W_kvb, W_o and the latent's norm
        "attention": (h * H * (s["nope"] + s["rope"])
                      + h * (s["rank"] + s["rope"])
                      + s["rank"] * H * (s["nope"] + s["v"])
                      + H * s["v"] * h + s["rank"]),
        "norms": 2 * h,
        "dense_mlp": 3 * h * s["dense_width"],
        # the router's matrix and its selection bias
        "router": h * s["experts"] + s["experts"],
        "expert": 3 * h * s["expert_width"],
        "shared_expert": 3 * h * s["shared_width"],
    }


def param_count(s: dict) -> int:
    """Every parameter this chip holds: the leading dense layers, the
    expert layers with all their experts, the embedding, the untied head
    and the final norm."""
    p = layer_params(s)
    common = p["attention"] + p["norms"]
    return (s["dense_layers"] * (common + p["dense_mlp"])
            + s["expert_layers"] * (common + p["router"] + p["shared_expert"]
                                    + s["experts"] * p["expert"])
            + 2 * s["vocab"] * s["hidden"] + s["hidden"])


def weight_bytes(s: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the weights this chip holds, at the configuration's two
    bytes a parameter (the program keeps the routers in float32: half a
    megabyte more a layer)."""
    return param_count(s) * bytes_per_param


def row_bytes(s: dict, bytes_per_elt: int = 2) -> int:
    """What one cached position holds a layer: the latent and the
    rotated key part, once."""
    return (s["rank"] + s["rope"]) * bytes_per_elt


def prefill_flops(s: dict, tokens: float, prompts: float,
                  sum_squares: float) -> float:
    """Forward pass of ``prompts`` prompts of ``tokens`` positions in all
    (``sum_squares`` = sum n^2 over them): every matmul parameter a token
    meets twice (of the routed experts its ``top_k``), causal attention in
    the expanded form, and the head once a prompt (a prefill computes its
    last position's logits only)."""
    p = layer_params(s)
    attn = 2.0 * (p["attention"] - s["rank"])
    dense = attn + 2.0 * p["dense_mlp"]
    expert = attn + 2.0 * (p["router"] - s["experts"] + p["shared_expert"]) \
        + s["top_k"] * 2.0 * p["expert"]
    return (tokens * (s["dense_layers"] * dense
                      + s["expert_layers"] * expert)
            + flash_flops(s, sum_squares)
            + prompts * 2.0 * s["hidden"] * s["vocab"])


def flash_flops(s: dict, sum_squares: float) -> float:
    """Causal attention of the expanded form, all layers: a position
    attends half of its prompt's on average, a score over ``nope + rope``
    columns and a weighted sum over ``v``, two operations each."""
    return (s["layers"] * s["heads"] * (s["nope"] + s["rope"] + s["v"])
            * sum_squares)


def expert_flops(s: dict, tokens: float) -> float:
    """The routed experts' products for ``tokens`` positions, all expert
    layers: each position meets its ``top_k`` experts' three matrices
    twice (the shared expert and the router are not the grouped
    kernel's)."""
    return (tokens * s["expert_layers"] * s["top_k"]
            * 2.0 * layer_params(s)["expert"])


def chosen_experts(s: dict, live: float) -> float:
    """The experts of a layer that a step of ``live`` slots reads, where
    the router spreads its choices evenly: those at least one of the
    ``live x top_k`` choices fell on."""
    e = s["experts"]
    return e * (1.0 - (1.0 - s["top_k"] / e) ** live)


def decode_step_bytes(s: dict, live: float, positions: float,
                      bytes_per_param: int = 2) -> float:
    """Least bytes one decode step of ``live`` slots holding ``positions``
    cached positions in all must read: every weight once but the
    embedding table (a row a slot is gathered) and the experts nobody
    chose, and every cached position's row in every layer."""
    p = layer_params(s)
    common = p["attention"] + p["norms"]
    weights = (s["dense_layers"] * (common + p["dense_mlp"])
               + s["expert_layers"] * (
                   common + p["router"] + p["shared_expert"]
                   + chosen_experts(s, live) * p["expert"])
               + s["vocab"] * s["hidden"] + s["hidden"])
    return weights * bytes_per_param \
        + positions * row_bytes(s, bytes_per_param) * s["layers"]


def latent_walk_seconds(s: dict, positions: float, hbm_bytes_per_s: float,
                        flops_per_s: float) -> float:
    """The least time the absorbed attention of one decode step takes
    over ``positions`` cached positions, all layers: the larger of the
    rows' bytes over the bandwidth and the products over the peak (every
    head's score over the whole row, its weighted sum over the latent)."""
    by_bytes = positions * row_bytes(s) / hbm_bytes_per_s
    by_flops = (positions * s["heads"] * 2.0
                * (2 * s["rank"] + s["rope"]) / flops_per_s)
    return s["layers"] * max(by_bytes, by_flops)
