"""Configuration dataclasses for the TPU-native Megatron-LLM rebuild.

The reference uses a single argparse namespace with 16 argument groups frozen
into a global singleton (reference: megatron/arguments.py:15-35,
megatron/global_vars.py:24-27).  Here configuration is explicit, typed and
threaded through call sites: a frozen ``ModelConfig`` describing the network,
a ``ParallelConfig`` describing the device mesh, and a ``TrainConfig`` for the
runtime.  ``validate()`` performs the same derivations the reference does in
``validate_args`` (megatron/arguments.py:53-350): data-parallel size from the
world size, dtype resolution, sequence-parallel gating on TP>1, etc.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, NamedTuple, Optional, Sequence

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Enums (reference: megatron/model/enums.py:6-28)
# ---------------------------------------------------------------------------


class PositionEmbeddingType:
    ROTARY = "rotary"
    ABSOLUTE = "absolute"
    NONE = "none"


class BlockKind(NamedTuple):
    """The static half of what a block kind is (models/transformer.py
    binds each kind's initialiser and mixer to it, once): what a layer of
    the kind keeps between positions, and whether it has a feed-forward
    part behind its mixer, under a norm of its own.  ``keeps``: "kv" a
    layer of the KV pool; "window" a ring of ``sliding_window`` keys and
    values a slot and no pool layer; "linear", "mamba", "ssm1" recurrent
    states under the names models/model.py:REC_STATE_KINDS gives them;
    "" nothing.  ``reads``: what an earlier layer hands it at the same
    position: "memory" the last "ssm1" layer's, "kv" the one "full"
    layer's keys and values.  ``of_runs``: the kind stands in a stack
    written as runs (``ModelConfig.layer_runs``) alone."""

    keeps: str = ""
    ffn: bool = True
    reads: str = ""
    of_runs: bool = False


# What every count of layers, ``init_rec_state`` and the layer scan read.
# A block of two parts is a mixer and then the feed-forward part (the
# experts where num_experts > 0), each under a norm of its own; a block of
# one part (``ffn`` False, or "mlp": no mixer) is ``h + f(norm(h))``.
KINDS = {
    "full": BlockKind("kv"),                # softmax attention: latent
    #   (kv_lora_rank) or differential (diff_attention) where the model's is
    "linear": BlockKind("linear"),          # Gated DeltaNet
    "ssm": BlockKind("mamba"),              # a Mamba-2 mixer
    "window": BlockKind("window"),          # attention over a window
    "attention": BlockKind("kv", ffn=False),
    "mamba": BlockKind("mamba", ffn=False),
    "mlp": BlockKind(),
    "ssm1": BlockKind("ssm1", of_runs=True),    # a Mamba-1 mixer
    "gmu": BlockKind(reads="memory", of_runs=True),   # a gated memory unit
    "cross": BlockKind(reads="kv", of_runs=True),     # a query's attention
}


_DTYPES = {
    "float32": jnp.float32,
    "fp32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "float16": jnp.float16,
    "fp16": jnp.float16,
}


def resolve_dtype(name: str):
    return _DTYPES[name]


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    """Architecture description covering the reference model zoo.

    Covers GPT / Llama-1/2 / Code Llama / Falcon variants
    (reference: megatron/model/{gpt_model,llama_model,falcon_model}.py).
    """

    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_attention_heads: int = 32
    # GQA/MQA: number of distinct KV heads (reference: --num_attention_heads_kv,
    # megatron/model/transformer.py:441-456).
    num_kv_heads: Optional[int] = None
    ffn_hidden_size: Optional[int] = None  # derived: 4*h, or 8/3*h for GLU
    max_position_embeddings: int = 4096
    # normalization
    norm_type: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-5
    # activations: "swiglu"|"geglu"|"reglu"|"liglu"|"gelu"|"squared_relu"
    activation: str = "swiglu"
    # positions
    position_embedding_type: str = PositionEmbeddingType.ROTARY
    rope_theta: float = 10000.0
    # RoPE scaling: "linear" position interpolation (Code-Llama long
    # context; reference: megatron/model/positional_embeddings.py:7-13)
    # or "llama3" piecewise frequency scaling (Llama-3.1 — extension
    # beyond the reference).  The llama3 fields mirror HF's rope_scaling
    # dict and are ignored under "linear".
    rope_scaling_factor: float = 1.0
    rope_scaling_type: str = "linear"
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_positions: Optional[int] = None
    # yarn-only knobs (extrapolation/interpolation rotation bounds and an
    # explicit attention temperature override)
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: Optional[float] = None
    # serving: "int8" stores the decode KV cache as int8 + per-row scales
    # (ops/kv_quant.py) — half the cache HBM traffic per decode step;
    # training is unaffected (the cache exists only on the decode path)
    kv_cache_quant: str = "none"
    # structure flags
    use_bias: bool = False  # bias on linear layers (GPT yes, Llama no)
    qkv_bias: bool = False  # Falcon-7B style attention bias
    tie_embed_logits: bool = False  # GPT ties; Llama/Falcon untied
    parallel_attn: bool = False  # Falcon: attn and MLP in parallel
    parallel_layernorm: bool = False  # Falcon-40B: separate LN for MLP branch
    # dropout (0 for llama/falcon pretraining)
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    # numerics
    params_dtype: str = "bfloat16"
    # softmax/logit scaling
    apply_query_key_layer_scaling: bool = False
    attention_softmax_in_fp32: bool = True
    # embedding
    make_vocab_size_divisible_by: int = 128
    # initialization
    init_method_std: float = 0.02
    use_scaled_init: bool = True  # scale output-layer init by 1/sqrt(2*layers)
    # attention impl: "flash" (pallas kernel) | "dot" (XLA einsum path).
    # "dot" is the default until the Pallas kernel covers all shapes; with
    # an attention bias or attention dropout "flash" takes the einsum path
    # (the kernel has neither), and it never stands in for a kernel that
    # fails to import or compile.
    attention_impl: str = "dot"
    # Pallas flash-attention tile sizes (attention_impl="flash").  1024² is
    # the validated default.
    flash_block_q: int = 1024
    flash_block_k: int = 1024
    # LIMA layer-dependent dropout (Zhou et al 2023; reference
    # transformer.py:964-971): hidden dropout ramps linearly from 0 at the
    # first layer to hidden_dropout at the last.
    lima_dropout: bool = False
    # Stochastic depth (reference DropPath, transformer.py:43-64): the
    # residual branch of layer i is dropped per *sample* with probability
    # linspace(0, drop_path_rate, L)[i].
    drop_path_rate: float = 0.0
    # norm impl: "pallas" (fused RMSNorm/LayerNorm kernel) | "xla" (jnp
    # math XLA fuses into neighbors; the default — XLA's fusion is already
    # near-bandwidth-bound for norms).
    norm_impl: str = "xla"
    # Quantized TRAINING matmuls: "none" (default) | "int8" — the layer
    # projection matmuls (QKV/out, MLP up/gate/down) run W8A8 on the int8
    # MXU (per-token activation scales x per-channel weight scales,
    # dynamic); the backward evaluates the dense formulas on the
    # dequantized int8 operands (TE semantics); master weights,
    # embeddings, lm_head, norms and the attention einsum stay bf16/fp32.
    # The TPU analogue of the reference's optional TransformerEngine FP8
    # (megatron/model/transformer.py:932-951, off by default there too).
    # No speed is claimed for it: no cell of the benchmark runs it (not
    # measured: PERF.md section 7), and by design the backward, two
    # thirds of a step's FLOPs, is unquantized and the operands are
    # quantized anew a call.  Prefer the flag only under
    # activation-memory pressure.  Note the
    # int8 dots escape the "selective" remat policy as int32 saveables —
    # pair with recompute="full" at memory-tight shapes.
    # ops/quant.py:int8_training_matmul.
    quantize_matmuls: str = "none"
    # recompute: "none" | "selective" | "full"
    recompute: str = "selective"
    # When set (to a mesh axis name, canonically "cp"), attention runs the
    # ring-attention context-parallel path: seq dim sharded over this axis,
    # K/V blocks rotated with ppermute (parallel/ring_attention.py).  Set by
    # the runtime when ParallelConfig.context_parallel > 1.
    context_parallel_axis: Optional[str] = None
    # Balanced zigzag cp layout: the sequence arrives pre-permuted by
    # zigzag_indices and causal ring work is ~halved.  Set by the runtime
    # from ParallelConfig.context_parallel_layout.
    context_parallel_zigzag: bool = False
    # Megatron sequence parallelism (reference:
    # core/tensor_parallel/layers.py:225-296): norm/dropout regions run with
    # the sequence dim sharded 1/tp.  Expressed as sharding constraints on
    # the residual stream at layer boundaries (models/transformer.py) from
    # which GSPMD derives the all-gather-before-matmul /
    # reduce-scatter-after-matmul pattern those reference layers hand-code.
    # Set (to the tp mesh axis name) by the runtime when
    # ParallelConfig.sequence_parallel and tensor_parallel > 1.
    sequence_parallel_axis: Optional[str] = None
    # Mixture-of-experts (extension beyond the reference, which has no MoE —
    # SURVEY §2.1 checklist).  num_experts == 0 → dense MLP everywhere.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coeff: float = 0.01
    # Routing group size (GShard grouping): capacity and the [*, g, E, C]
    # dispatch tensors are per-group, keeping dispatch cost linear in seq
    # length.  The effective group is the largest divisor of the (local)
    # sequence length ≤ this bound.
    moe_group_size: int = 512
    # Parallel-friendly sequence length used for activation layouts.
    seq_length: int = 4096
    # lm head
    tokentype_size: int = 0  # BERT-style token types (0 = disabled)
    # encoder-decoder (T5): decoder depth; None → same as num_layers
    # (encoder depth).  Decoder-only families ignore this.
    num_decoder_layers: Optional[int] = None
    # Fused blockwise linear+CE training head (never materializes fp32
    # logits — parallel/cross_entropy.fused_linear_cross_entropy).  Opt-in:
    # saves ~[b,s,vocab] fp32 of HBM when the head dominates memory, at
    # the price of a backward that recomputes the head's matmul.
    fused_lm_head: bool = False
    # Width of an attention head where it is not hidden / heads.
    kv_channels: Optional[int] = None
    # Hybrid stacks: the block kinds (``KINDS``) of one period of layers.
    # The stack is that period scanned num_layers / len(period) times
    # (models/transformer.py:scan_stack): ONE run of ``stack_runs``.
    # () = every layer "full": the one-kind stack.
    layer_pattern: tuple = ()
    # Gated DeltaNet geometry: key heads x key width, value heads x value
    # width (value heads a multiple of key heads), causal depthwise
    # convolution taps over the q|k|v channels.
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    # Mamba-2 geometry: heads x head width (their product is the inner
    # width), B/C groups x state width (a head reads group
    # head // (heads / groups)), causal depthwise convolution taps over
    # the x|B|C channels, positions a chunk of the chunked (SSD) form.
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    mamba_n_groups: int = 8
    mamba_state_size: int = 128
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    # softmax-attention variants: share of each head's width that is
    # rotated (rotate-half over the first rotary_percent * head_dim
    # dimensions; 1.0 = the interleaved full-width rotation of ops/rope.py),
    # per-head RMSNorm of q and k, and a sigmoid gate on the attention
    # output projected beside q (wq is then [h, heads * 2 * head_dim]:
    # per head, query then gate).
    rotary_percent: float = 1.0
    qk_norm: bool = False
    attn_output_gate: bool = False
    # rotate-half at every share (a share under 1 is rotated so whatever
    # this says), the angles from the positions and no table; under
    # rope_scaling_type "yarn" the rotated dimensions take YaRN's
    # frequencies and cos and sin its factor (ops/rope.py:rotation_of)
    rope_rotate_half: bool = False
    # a sigmoid gate a HEAD, from the layer's input (``wg`` [hidden,
    # heads], kept and applied in float32 as the router is): a head's
    # output is multiplied by its gate before the output projection (the
    # head-wise variant of arXiv 2505.06708; attn_output_gate above is the
    # element-wise one, projected beside q)
    attn_head_gate: bool = False
    # Dropless routing (softmax over the router's outputs, top-k,
    # renormalise; no capacity, no drop) beside the capacity routing above.
    # The router keeps moe_router_experts outputs (0 = num_experts) of which
    # this parameter tree holds num_experts consecutive ones starting at
    # moe_expert_offset: one expert-parallel rank's share, whose partial sum
    # the layer returns.  moe_shared_expert_size > 0 adds a shared expert
    # of that width, whole on every rank, under a sigmoid gate of its own
    # unless moe_shared_expert_gated is off.
    # moe_router_scoring "sigmoid": each score is a sigmoid of its own
    # output; the top-k are chosen by score + a per-expert bias (kept in
    # the tree, ``router_bias``), weighted by the scores alone divided by
    # their sum.  Either way the weights are then multiplied by
    # moe_routed_scaling.  moe_latent_size > 0: the routed experts live
    # in a latent of that width, between one down- and one up-projection
    # shared by all of them and whole on every rank; a rank's partial sum
    # is taken in the latent and goes through the up-projection.
    moe_dropless: bool = False
    moe_router_experts: int = 0
    moe_expert_offset: int = 0
    moe_shared_expert_size: int = 0
    moe_shared_expert_gated: bool = True
    moe_router_scoring: str = "softmax"
    moe_routed_scaling: float = 1.0
    moe_latent_size: int = 0
    # Scalar multipliers of the architecture (Granite's, after muP): on
    # the embedding's output; on every part's result before it is added to
    # the residual stream; the softmax scale in place of 1/sqrt(head_dim)
    # (None: that); and what the head's logits are divided by.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # Latent attention (MLA, models/mla.py) as a "full" block's attention
    # part, where kv_lora_rank > 0: the keys and values of all heads are
    # expanded from ONE latent of that width a position, beside one rotated
    # key part of qk_rope_head_dim shared by all heads; a head's query and
    # key are qk_nope_head_dim + qk_rope_head_dim wide, its value
    # v_head_dim.  What is cached a position a layer is the latent and
    # the rotated part, ``latent_row_width`` values and no head axis.
    # (0: the attention of attention_block.)  q_lora_rank, a
    # down-projection of the query, is not carried: anything but None is
    # refused.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: Optional[int] = None
    # That many leading layers hold a dense MLP of moe_dense_ffn_size in
    # place of the experts: they run before the layer scan, with
    # parameters of their own beside the stack (``lead_layers``), and
    # count among num_layers.  moe_n_group > 1 (a router that first picks
    # groups of experts) is not carried and refused.
    moe_first_dense_layers: int = 0
    moe_dense_ffn_size: int = 0
    moe_n_group: int = 1
    # A stack of several runs: ``((period, times), ...)``, each run a
    # period of block kinds scanned ``times`` times, one run after the
    # other (models/transformer.py:scan_stack).  ``layer_pattern`` is then
    # the scanned layers written out, one period, which is what every
    # count of layers reads.  A run hands two things to the runs behind
    # it: the last "ssm1" layer's memory (its scan's output before the
    # gate, read by every "gmu" layer at the same position) and the one
    # "full" layer's keys and values (read by every "cross" layer, which
    # projects a query alone).
    layer_runs: tuple = ()
    # Differential attention (arXiv 2410.05258) as the attention part of
    # the "full", "window" and "cross" kinds: adjacent query heads pair,
    # adjacent key/value heads pair, query pair p reads key pair p // 2;
    # each query head of a pair attends with its own key head over the
    # pair's two value heads side by side (2 x head_dim wide), and the
    # pair's output is RMSNorm(first - lambda * second) * (1 -
    # lambda_init(layer)) (models/diff_attention.py).
    diff_attention: bool = False
    # keys a query of a "window" layer sees, its own among them
    sliding_window: int = 0
    # What a "window" layer of plain attention has of its own (None: the
    # model's): its query heads (the KV heads are the model's, so the
    # query-to-KV-head ratio differs by kind), and its rotation ``(theta,
    # share of the head rotated, scaling factor)`` in place of rope_theta,
    # rotary_percent and rope_scaling_factor (``window_layer_config``).
    window_attention_heads: Optional[int] = None
    window_rope: Optional[tuple] = None
    # the kind of the leading dense layers (moe_first_dense_layers) where
    # it is not the period's first: "full" before a period that starts
    # with "window"
    lead_layer_kind: Optional[str] = None
    # Mamba-1 geometry ("ssm1", models/mamba1.py): inner width, state
    # columns a channel, convolution taps, the step's bottleneck
    mamba1_inner: int = 0
    mamba1_state_size: int = 16
    mamba1_conv_kernel: int = 4
    mamba1_dt_rank: int = 0

    def __post_init__(self):
        # a JSON list (checkpointed arguments, a benchmark's overrides):
        # the config is a static argument of every jitted step, so hashed
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        object.__setattr__(self, "layer_runs", tuple(
            (tuple(period), int(times)) for period, times in self.layer_runs))
        if self.window_rope is not None:
            object.__setattr__(self, "window_rope", tuple(
                float(x) for x in self.window_rope))

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.kv_channels or self.hidden_size // self.num_attention_heads

    @property
    def router_experts(self) -> int:
        return self.moe_router_experts or self.num_experts

    @property
    def latent_row_width(self) -> int:
        """What a latent-attention layer caches a position: the latent
        and the rotated key part (0: keys and values a KV head)."""
        return self.kv_lora_rank and self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def scanned_layers(self) -> int:
        """The layers of the scan: all but the leading dense ones."""
        return self.num_layers - self.moe_first_dense_layers

    @property
    def lead_layer_config(self) -> "ModelConfig":
        """The leading dense layers' configuration: this stack's layer
        without experts, its MLP ``moe_dense_ffn_size`` wide."""
        return dataclasses.replace(
            self, num_experts=0, moe_dropless=False, moe_router_experts=0,
            moe_expert_offset=0, moe_shared_expert_size=0,
            moe_router_scoring="softmax", moe_routed_scaling=1.0,
            moe_latent_size=0, moe_first_dense_layers=0,
            ffn_hidden_size=self.moe_dense_ffn_size)

    @property
    def window_layer_config(self) -> "ModelConfig":
        """A "window" layer's attention as a configuration: this one with
        the window layers' own head count and rotation."""
        theta, share, factor = self.window_rope or (
            self.rope_theta, self.rotary_percent, self.rope_scaling_factor)
        return dataclasses.replace(
            self, num_attention_heads=(self.window_attention_heads
                                       or self.num_attention_heads),
            rope_theta=theta, rotary_percent=share,
            rope_scaling_factor=factor, window_attention_heads=None,
            window_rope=None)

    @property
    def lead_kind(self) -> str:
        """The block kind of the leading dense layers."""
        return self.lead_layer_kind or (self.layer_pattern or ("full",))[0]

    @property
    def layer_kinds(self) -> tuple:
        """The block kind of every layer, in order (the leading dense
        layers are of ``lead_kind``: the period's first, or the one
        stated)."""
        period = self.layer_pattern or ("full",)
        return ((self.lead_kind,) * self.moe_first_dense_layers
                + period * (self.scanned_layers // len(period)))

    @property
    def stack_runs(self) -> tuple:
        """The scanned layers as runs ``((period, times), ...)``: a
        ``layer_pattern`` alone is one run of its periods."""
        period = self.layer_pattern or ("full",)
        return self.layer_runs or (
            (period, self.scanned_layers // len(period)),)

    def layers_keeping(self, what: str) -> int:
        """Layers of a kind that keeps ``what`` (``BlockKind.keeps``)."""
        return sum(KINDS[kind].keeps == what for kind in self.layer_kinds)

    # the layers that keep keys and values (the KV pool's layer axis), a
    # delta-rule state, a state-space state, a Mamba-1 state
    # (serving/slots.py) and a ring of keys and values a slot
    kv_layers = property(lambda self: self.layers_keeping("kv"))
    linear_layers = property(lambda self: self.layers_keeping("linear"))
    mamba_layers = property(lambda self: self.layers_keeping("mamba"))
    mamba1_layers = property(lambda self: self.layers_keeping("ssm1"))
    window_layers = property(lambda self: self.layers_keeping("window"))

    @property
    def cross_layers(self) -> int:
        """Layers that attend with the one "full" layer's keys and
        values: readers of the pool that write nothing to it."""
        return sum(KINDS[kind].reads == "kv" for kind in self.layer_kinds)

    @property
    def v_heads(self) -> int:
        """Value heads a position: under differential attention the two
        value heads of a pair lie side by side as one, twice as wide."""
        return self.kv_heads // 2 if self.diff_attention else self.kv_heads

    @property
    def v_head_width(self) -> int:
        return 2 * self.head_dim if self.diff_attention else self.head_dim

    @property
    def moe_layer_ids(self) -> tuple:
        """The layers that route: every layer with a feed-forward part,
        where the model has experts."""
        if self.num_experts == 0:
            return ()
        return tuple(i for i, kind in enumerate(self.layer_kinds)
                     if KINDS[kind].ffn
                     and i >= self.moe_first_dense_layers)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_channels(self) -> int:
        """x | B | C: what the convolution runs over."""
        return (self.mamba_inner
                + 2 * self.mamba_n_groups * self.mamba_state_size)

    @property
    def ffn_size(self) -> int:
        if self.ffn_hidden_size is not None:
            return self.ffn_hidden_size
        if self.is_glu:
            # llama convention: 2/3 * 4h rounded to multiple of 256
            size = int(2 * 4 * self.hidden_size / 3)
            return 256 * ((size + 255) // 256)
        return 4 * self.hidden_size

    @property
    def is_glu(self) -> bool:
        return self.activation in ("swiglu", "geglu", "reglu", "liglu")

    @property
    def dtype(self):
        return resolve_dtype(self.params_dtype)

    def padded_vocab_size(self, tp: int = 1) -> int:
        """Pad vocab so it divides evenly across TP shards
        (reference: megatron/tokenizer/tokenizer.py:39-63)."""
        multiple = self.make_vocab_size_divisible_by * tp
        return ((self.vocab_size + multiple - 1) // multiple) * multiple

    def validate(self) -> "ModelConfig":
        assert self.kv_channels or (
            self.hidden_size % self.num_attention_heads == 0)
        assert self.num_attention_heads % self.kv_heads == 0
        if self.layer_pattern:
            assert all(kind in KINDS and (self.layer_runs
                                          or not KINDS[kind].of_runs)
                       for kind in self.layer_pattern), (
                f"unknown block kind in {self.layer_pattern!r}")
            assert self.scanned_layers % len(self.layer_pattern) == 0, (
                f"num_layers {self.num_layers} is not whole periods of "
                f"{len(self.layer_pattern)} layers")
            assert (self.linear_num_value_heads
                    % self.linear_num_key_heads == 0)
            assert self.mamba_num_heads % self.mamba_n_groups == 0
        if self.layer_runs:
            self._validate_runs()
        # (the differential family's "window" layers are _validate_runs')
        differential = bool(self.layer_runs) and self.diff_attention
        if "window" in self.layer_pattern:
            assert self.sliding_window > 0, "\"window\" needs sliding_window"
            if not differential:
                self._validate_window()
        elif not differential:
            assert (self.window_attention_heads is None
                    and self.window_rope is None), (
                "window_attention_heads and window_rope are a \"window\" "
                "layer's of plain attention")
        if self.kv_lora_rank:
            self._validate_latent_attention()
        else:
            assert not (self.qk_nope_head_dim or self.qk_rope_head_dim
                        or self.v_head_dim or self.q_lora_rank), (
                "qk_nope_head_dim, qk_rope_head_dim, v_head_dim and "
                "q_lora_rank are latent attention's (kv_lora_rank > 0)")
        if self.moe_first_dense_layers:
            assert self.layer_pattern and self.num_experts > 0, (
                "moe_first_dense_layers: leading dense layers stand "
                "before a period-scanned stack of expert layers")
            assert self.lead_kind == "full", (
                "a leading dense layer is a two-part block (\"full\")")
            assert 0 < self.moe_first_dense_layers < self.num_layers
            assert self.moe_dense_ffn_size > 0, (
                "moe_first_dense_layers needs moe_dense_ffn_size, the "
                "dense MLP's width")
        if self.moe_n_group != 1:
            raise ValueError(
                f"moe_n_group {self.moe_n_group}: group-limited routing "
                "(the router first picks topk_group of n_group groups of "
                "experts) is not carried; models/moe.py routes over all "
                "experts at once")
        assert self.moe_router_scoring in ("softmax", "sigmoid"), (
            f"unknown moe_router_scoring {self.moe_router_scoring!r}")
        if not self.moe_dropless:
            assert (self.moe_router_scoring == "softmax"
                    and self.moe_routed_scaling == 1.0
                    and not self.moe_latent_size), (
                "sigmoid scoring, a routed scaling factor and latent "
                "experts are the dropless route's (moe_dropless)")
        if self.moe_dropless:
            assert self.num_experts > 0
            assert (0 <= self.moe_expert_offset and self.moe_expert_offset
                    + self.num_experts <= self.router_experts), (
                "the held experts lie outside the router's outputs")
            assert self.moe_top_k <= self.router_experts
        if self.parallel_layernorm:
            assert self.parallel_attn, "parallel_layernorm requires parallel_attn"
        assert not (self.fused_lm_head and self.logits_scaling != 1.0), (
            "the fused training head does not divide its logits "
            "(logits_scaling)")
        if self.num_experts > 0 and not self.moe_dropless:
            assert 1 <= self.moe_top_k <= self.num_experts, (
                f"moe_top_k {self.moe_top_k} must be in "
                f"[1, num_experts={self.num_experts}]")
            assert not self.use_bias, (
                "MoE MLPs are bias-free (models/moe.py); use_bias=True with "
                "num_experts > 0 is not supported")
        assert self.kv_cache_quant in ("none", "int8"), (
            f"unknown kv_cache_quant {self.kv_cache_quant!r}")
        assert self.quantize_matmuls in ("none", "int8"), (
            f"unknown quantize_matmuls {self.quantize_matmuls!r}")
        return self

    def _validate_runs(self) -> None:
        """A stack of runs as models/transformer.py carries it: the runs
        written out are the scanned layers; the kinds of runs alone and
        differential attention come together, with what that family
        (``phi4flash_config``) does not have refused."""
        flat = tuple(kind for period, times in self.layer_runs
                     for kind in period * times)
        assert (flat == self.layer_pattern
                and len(flat) == self.scanned_layers), (
            "layer_runs written out is layer_pattern, the scanned layers")
        kinds = {kind: KINDS[kind] for kind in flat}
        if not (self.diff_attention
                or any(k.of_runs for k in kinds.values())):
            return
        assert all(k.of_runs or kind in ("window", "full")
                   for kind, k in kinds.items()), (
            "a stack of runs that attends differentially holds \"full\", "
            f"\"window\" and the kinds of runs alone: {set(kinds)}")
        assert self.diff_attention and self.kv_heads % 2 == 0 and (
            self.num_attention_heads == 2 * self.kv_heads), (
            "a stack of runs attends differentially: query heads pair, "
            "key heads pair, two query pairs a key pair")
        assert (self.position_embedding_type == PositionEmbeddingType.NONE
                and self.num_experts == 0 and not self.parallel_attn
                and self.kv_cache_quant == "none"
                and self.context_parallel_axis is None
                and not self.qk_norm and not self.attn_output_gate), (
            "a stack of runs: no rotation, experts, parallel block, 8-bit "
            "K/V, context parallelism, q/k norm or output gate")
        keeps = [k.keeps for k in kinds.values()]
        reads = [KINDS[kind].reads for kind in flat]
        if "ssm1" in keeps:
            assert self.mamba1_inner > 0 and self.mamba1_dt_rank > 0
        if "memory" in reads:
            assert "ssm1" in [KINDS[kind].keeps
                              for kind in flat[:reads.index("memory")]], (
                "a gated memory unit reads an earlier \"ssm1\" layer's "
                "memory")
        if "kv" in reads:
            assert flat.count("full") == 1 and (
                flat.index("full") < reads.index("kv")), (
                "\"cross\" layers read the keys and values of the one "
                "\"full\" layer before them")

    def _validate_window(self) -> None:
        """A "window" layer of plain attention as models/transformer.py
        carries it: ordinary attention (grouped heads, a rotation, a gate
        a head) on a ring of ``sliding_window`` rows a slot."""
        assert not (self.diff_attention or self.kv_lora_rank
                    or self.parallel_attn or self.attn_output_gate
                    or self.kv_cache_quant != "none"
                    or self.context_parallel_axis is not None), (
            "a \"window\" layer of plain attention: no differential or "
            "latent attention, parallel block, element-wise output gate, "
            "8-bit K/V or context parallelism")
        if self.position_embedding_type == PositionEmbeddingType.ROTARY:
            assert self.rope_rotate_half, (
                "a ring's rows are rotated at their own positions, from "
                "the positions (rope_rotate_half), not from a table")
        w = self.window_layer_config
        assert w.num_attention_heads % self.kv_heads == 0, (
            "the window layers' query heads are whole groups of KV heads")
        assert 0.0 < w.rotary_percent <= 1.0
        assert (w.rope_scaling_factor == 1.0
                or self.rope_scaling_type == "yarn"), (
            "a rotation from the positions is scaled by YaRN's "
            "frequencies or not at all")

    @property
    def row_cut_layer(self) -> Optional[int]:
        """The layer at which a prefill may cut the rows it carries on to
        the one whose logits are asked for: the "full" layer whose keys
        and values every later layer reads, where no later layer keeps
        anything of the positions in between (each reads what an earlier
        layer hands it, alone).
        None: no such layer."""
        kinds = self.layer_kinds
        if not self.cross_layers:
            return None
        at = kinds.index("full")
        return at if all(KINDS[kind].reads
                         for kind in kinds[at + 1:]) else None

    def _validate_latent_attention(self) -> None:
        """Latent attention as models/mla.py carries it; what it does
        not carry is refused by name (ValueError: survives -O)."""
        if self.q_lora_rank is not None:
            raise ValueError(
                f"q_lora_rank {self.q_lora_rank}: a down-projected query "
                "(q_a_proj, its norm, q_b_proj) is not carried; "
                "models/mla.py projects the query directly (q_lora_rank "
                "null)")
        if (self.rope_scaling_type == "yarn"
                or self.rope_scaling_factor != 1.0
                or self.rope_attention_factor is not None):
            raise ValueError(
                "rope scaling with latent attention (YaRN's mscale on the "
                "softmax scale and its interpolated frequencies) is not "
                "carried; models/mla.py rotates at the plain frequencies "
                "(rope_scaling null)")
        if self.kv_cache_quant != "none":
            raise ValueError(
                "kv_cache_quant=int8: a latent row is kept in the "
                "weights' precision, there is no 8-bit latent pool")
        assert (self.qk_nope_head_dim > 0 and self.qk_rope_head_dim > 0
                and self.v_head_dim > 0), (
            "latent attention needs qk_nope_head_dim, qk_rope_head_dim "
            "and v_head_dim")
        assert self.qk_rope_head_dim % 2 == 0
        assert self.layer_pattern == ("full",), (
            "latent attention is the attention part of a period of one "
            "\"full\" block (the layer scan carries the float32 "
            "stream and the expert counters)")
        assert (self.position_embedding_type == PositionEmbeddingType.ROTARY
                and not self.qk_norm and not self.attn_output_gate
                and not self.use_bias and not self.qkv_bias
                and not self.parallel_attn
                and self.attention_dropout == 0.0
                and self.context_parallel_axis is None), (
            "latent attention: rotary positions on its rotated part, no "
            "q/k norm, output gate, bias, parallel block, attention "
            "dropout or context parallelism")


# ---------------------------------------------------------------------------
# Parallelism configuration (reference: megatron/core/parallel_state.py)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh axes for 4-way parallelism.

    The reference builds NCCL groups for TP/PP/DP (parallel_state.py:51-214);
    here the same topology is one ``jax.sharding.Mesh`` with named axes.  The
    mesh is laid out so TP is innermost (fastest-varying — rides ICI), then
    PP, then DP outermost (can span DCN across slices), mirroring the
    reference rank order (parallel_state.py docstring).
    """

    data_parallel: int = 1
    pipeline_parallel: int = 1
    tensor_parallel: int = 1
    # Serving weight-residency sharding (fsdp axis): weights are split
    # 1/fsdp along their non-tp dimension (models/sharding.py:
    # serving_param_specs, per the EasyDel/fjformer ("dp","fsdp","sp")
    # partition-rule family), so per-device *resident* param bytes fall
    # with the mesh without widening the head sharding.  GSPMD inserts
    # the gather-before-use; decode stays compute-identical.  Unused by
    # the training layout (ZeRO-1 covers optimizer state there).
    fsdp: int = 1
    # Megatron-style sequence parallelism: shard activations along seq over
    # the tp axis in norm/dropout regions (reference spread across
    # core/tensor_parallel/layers.py:225-296 etc.).
    sequence_parallel: bool = False
    # virtual pipeline (interleaved 1F1B) chunks per stage
    virtual_pipeline_stages: int = 1
    # expert parallelism axis size (MoE; reference has none — extension)
    expert_parallel: int = 1
    # context parallelism (ring attention over seq) — extension beyond reference
    context_parallel: int = 1
    # "contiguous" (default) or "zigzag": the balanced layout gives each cp
    # rank chunks (r, 2n-1-r) so causal ring work is ~halved
    # (parallel/ring_attention.py zigzag section); training-path only
    context_parallel_layout: str = "contiguous"
    # number of microbatches for pipeline / grad accumulation
    num_microbatches: int = 1
    # windowed rematerialization of the pipeline tick loop: 0 = off (every
    # tick's boundary tensor is saved for backward — fine up to M≈16); W>0
    # checkpoints the scan in windows of W ticks, bounding saved boundaries
    # at ceil(T/W) + 2·W instead of 2·T.  This is the large-M (grad-accum
    # M≥64) memory bound the reference gets from ≤pp in-flight 1F1B
    # (megatron/schedules.py:606-722), at ~+25% FLOPs when on.  With
    # vpp > 1 it requires num_microbatches % pp == 0 (the tight
    # interleaved schedule, whose carry has no circular buffer).
    # -1 = auto: the memory-minimizing W from the analytic model
    # (parallel/pipeline.py:auto_remat_window).
    pipeline_remat_window: int = 0
    # ZeRO-1: shard optimizer state over dp
    # (reference: megatron/optimizer/distrib_optimizer.py)
    use_distributed_optimizer: bool = False
    # Encoder/decoder split-rank pipeline parallelism (T5): the first
    # ``pipeline_split_rank`` stages hold the encoder stack, the rest the
    # decoder (reference: megatron/core/parallel_state.py:110-112,177-184,
    # ``pipeline_model_parallel_split_rank``).  None → pp // 2 when the
    # encdec pipeline is used; ignored by decoder-only families.
    pipeline_split_rank: Optional[int] = None

    @property
    def world_size(self) -> int:
        return (
            self.data_parallel
            * self.fsdp
            * self.pipeline_parallel
            * self.tensor_parallel
            * self.context_parallel
            * self.expert_parallel
        )

    def validate(self) -> "ParallelConfig":
        # sequence_parallel with tp == 1 is a harmless no-op (the reference
        # force-disables it, arguments.py:332-333; here the spec degenerates
        # to the plain activation layout).
        assert self.fsdp >= 1, f"fsdp must be >= 1, got {self.fsdp}"
        if self.pipeline_parallel > 1:
            assert self.num_microbatches >= 1
        assert self.context_parallel_layout in ("contiguous", "zigzag"), (
            f"unknown context_parallel_layout "
            f"{self.context_parallel_layout!r}")
        if self.pipeline_remat_window:
            assert (self.pipeline_remat_window > 0
                    or self.pipeline_remat_window == -1), (
                "pipeline_remat_window: W > 0, or -1 for the "
                "memory-minimizing auto choice (parallel/pipeline.py:"
                "auto_remat_window)")
            if self.virtual_pipeline_stages > 1:
                assert self.num_microbatches % self.pipeline_parallel == 0, (
                    "pipeline_remat_window with vpp > 1 needs "
                    "num_microbatches divisible by pipeline_parallel (the "
                    "tight interleaved schedule; same divisibility the "
                    "reference's interleaved 1F1B asserts) — otherwise the "
                    "legacy circular buffer would be re-saved at every "
                    "window boundary, inflating memory")
        if self.pipeline_split_rank is not None:
            assert 0 < self.pipeline_split_rank < self.pipeline_parallel, (
                f"pipeline_split_rank {self.pipeline_split_rank} must lie "
                f"strictly inside the pipeline ({self.pipeline_parallel} "
                "stages) — at least one stage each for encoder and decoder")
        return self


# ---------------------------------------------------------------------------
# Training configuration (reference: megatron/arguments.py training groups)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    optimizer: str = "adamw"  # "adamw" | "sgd"
    lr: float = 3e-4
    min_lr: float = 3e-5
    weight_decay: float = 0.1
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95
    adam_eps: float = 1e-8
    sgd_momentum: float = 0.9
    clip_grad: float = 1.0
    # LR schedule (reference: megatron/optimizer_param_scheduler.py)
    lr_decay_style: str = "cosine"  # constant|linear|cosine|inverse-square-root
    lr_warmup_iters: int = 0
    lr_warmup_fraction: Optional[float] = None
    lr_decay_iters: Optional[int] = None
    # weight decay ramp (reference: optimizer_param_scheduler.py:42-64)
    start_weight_decay: Optional[float] = None
    end_weight_decay: Optional[float] = None
    weight_decay_incr_style: str = "constant"
    # loss scaling for fp16 (bf16 needs none)
    loss_scale: Optional[float] = None
    initial_loss_scale: float = 2.0**32
    min_loss_scale: float = 1.0
    loss_scale_window: int = 1000
    hysteresis: int = 2
    # master weights dtype
    main_params_dtype: str = "float32"
    use_fp32_grad_accum: bool = True


@dataclass(frozen=True)
class TrainConfig:
    train_iters: int = 1000
    micro_batch_size: int = 1
    global_batch_size: int = 1
    # batch-size ramp [start, increment, samples] (reference: microbatches.py)
    rampup_batch_size: Optional[Sequence[int]] = None
    seq_length: int = 4096
    seed: int = 1234
    # eval
    eval_interval: int = 1000
    eval_iters: int = 10
    # checkpointing
    save: Optional[str] = None
    load: Optional[str] = None
    save_interval: int = 1000
    # retention: keep only the newest N complete checkpoints (0 = keep all)
    keep_latest_checkpoints: int = 0
    # bounded exponential-backoff retries around orbax/tensorstore I/O
    checkpoint_retries: int = 3
    # anomaly defense (resilience/anomaly.py): a step whose loss is
    # non-finite — or exceeds the accepted-loss EWMA by z_threshold
    # deviations (0 = spike detection off) — is skipped bitwise; after
    # anomaly_rollback_after consecutive data anomalies (0 = never) the
    # driver reloads the last checkpoint and skips past the poisoned data
    # window, giving up after anomaly_max_rollbacks.
    anomaly_z_threshold: float = 0.0
    anomaly_ewma_alpha: float = 0.02
    anomaly_warmup_steps: int = 20
    anomaly_rollback_after: int = 0
    anomaly_max_rollbacks: int = 10
    # logging
    log_interval: int = 10
    tensorboard_dir: Optional[str] = None
    wandb_project: Optional[str] = None
    wandb_name: Optional[str] = None
    # exits
    exit_interval: Optional[int] = None
    exit_duration_mins: Optional[float] = None
    # data
    data_path: Optional[Sequence[Any]] = None
    split: str = "969,30,1"
    # metrics evaluated during validation (reference: megatron/metrics.py)
    metrics: Sequence[str] = ()
    # iterations whose fwd/bwd is skipped (fault injection;
    # reference: --skip_iters, megatron/training.py:397-399)
    skip_iters: Sequence[int] = ()
    # jax.profiler trace window: write a TensorBoard-viewable device
    # profile of iterations [profile_step_start, profile_step_end] to
    # profile_dir.  The TPU-idiomatic deep-dive the reference leaves to
    # external nsys (SURVEY §5 notes no in-tree integration); the
    # steady-state default [11, 13] skips compile/warmup iterations.
    profile_dir: Optional[str] = None
    profile_step_start: int = 11
    profile_step_end: int = 13


@dataclass(frozen=True)
class RuntimeConfig:
    """Top-level bundle threaded through the runtime (replaces get_args())."""

    model: ModelConfig = field(default_factory=ModelConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    train: TrainConfig = field(default_factory=TrainConfig)

    def validate(self) -> "RuntimeConfig":
        # Wire context parallelism into the model: attention switches to the
        # ring path (parallel/ring_attention.py) when the cp axis is real,
        # and back off it when a checkpointed config is re-validated with
        # cp == 1 (e.g. single-host inference on a cp-trained model).
        if self.parallel.context_parallel > 1:
            if self.model.context_parallel_axis is None:
                object.__setattr__(
                    self, "model",
                    dataclasses.replace(self.model,
                                        context_parallel_axis="cp"))
            assert self.model.attention_dropout == 0.0, (
                "ring attention (context_parallel > 1) does not support "
                "attention dropout")
            assert self.train.seq_length % self.parallel.context_parallel == 0, (
                f"seq_length {self.train.seq_length} must divide by "
                f"context_parallel {self.parallel.context_parallel}")
            zigzag = self.parallel.context_parallel_layout == "zigzag"
            if zigzag:
                assert self.train.seq_length % (
                    2 * self.parallel.context_parallel) == 0, (
                    "zigzag layout needs seq_length divisible by 2*cp")
                assert self.parallel.pipeline_parallel == 1, (
                    "zigzag cp layout is not plumbed through the pipeline "
                    "schedule; use the contiguous layout with pp > 1")
            if self.model.context_parallel_zigzag != zigzag:
                # set AND clear: a checkpointed zigzag config re-validated
                # with layout="contiguous" must drop the sticky model flag
                object.__setattr__(
                    self, "model",
                    dataclasses.replace(self.model,
                                        context_parallel_zigzag=zigzag))
        elif self.model.context_parallel_axis is not None:
            object.__setattr__(
                self, "model",
                dataclasses.replace(self.model, context_parallel_axis=None,
                                    context_parallel_zigzag=False))
        # Wire sequence parallelism into the model as a residual-stream
        # constraint axis (set AND clear, same re-validation contract as cp).
        sp_axis = ("tp" if (self.parallel.sequence_parallel
                            and self.parallel.tensor_parallel > 1) else None)
        if self.model.sequence_parallel_axis != sp_axis:
            object.__setattr__(
                self, "model",
                dataclasses.replace(self.model,
                                    sequence_parallel_axis=sp_axis))
        if self.model.fused_lm_head and (
                self.parallel.tensor_parallel > 1
                or self.parallel.context_parallel > 1
                or self.parallel.pipeline_parallel > 1):
            # validated here (not in the loss fn) because the pipelined
            # path never reaches compute_loss at all
            import warnings

            warnings.warn(
                "fused_lm_head=True is inactive under tp/cp/pp "
                "parallelism; the plain logits+CE path will run",
                stacklevel=2)
        if self.parallel.expert_parallel > 1:
            assert self.model.num_experts > 0, (
                "expert_parallel > 1 requires a MoE model (num_experts > 0)")
            assert self.model.num_experts % self.parallel.expert_parallel == 0, (
                f"num_experts {self.model.num_experts} must divide by "
                f"expert_parallel {self.parallel.expert_parallel}")
        self.model.validate()
        self.parallel.validate()
        mb = self.train.micro_batch_size
        gb = self.train.global_batch_size
        dp = self.parallel.data_parallel
        assert gb % (mb * dp) == 0, (
            f"global_batch_size {gb} must divide by micro_batch {mb} * dp {dp}"
        )
        return self

    @property
    def grad_accum_steps(self) -> int:
        return self.train.global_batch_size // (
            self.train.micro_batch_size * self.parallel.data_parallel
        )

    # -- (de)serialization for checkpoints (args-in-checkpoint parity;
    #     reference: megatron/checkpointing.py:267-285,476-559) --

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        d = self.to_dict()
        return json.dumps(d, indent=2, default=str)

    @classmethod
    def from_dict(cls, d: dict) -> "RuntimeConfig":
        # every checkpoint written before the whole-stack decode kernel
        # went stores its switch; nothing reads it any more
        model = {k: v for k, v in d.get("model", {}).items()
                 if k != "fused_decode"}
        return cls(
            model=ModelConfig(**model),
            parallel=ParallelConfig(**{k: tuple(v) if isinstance(v, list) else v
                                       for k, v in d.get("parallel", {}).items()}),
            optimizer=OptimizerConfig(**d.get("optimizer", {})),
            train=TrainConfig(**{k: tuple(v) if isinstance(v, list) else v
                                 for k, v in d.get("train", {}).items()}),
        )

    @classmethod
    def from_json(cls, s: str) -> "RuntimeConfig":
        return cls.from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# Model presets (reference model zoo: docs + finetune.py model size args)
# ---------------------------------------------------------------------------


def llama2_config(size: str = "7b", **overrides) -> ModelConfig:
    base = dict(
        norm_type="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        position_embedding_type=PositionEmbeddingType.ROTARY,
        use_bias=False,
        tie_embed_logits=False,
        vocab_size=32000,
        max_position_embeddings=4096,
        seq_length=4096,
    )
    sizes = {
        "7b": dict(hidden_size=4096, num_layers=32, num_attention_heads=32,
                   ffn_hidden_size=11008),
        "13b": dict(hidden_size=5120, num_layers=40, num_attention_heads=40,
                    ffn_hidden_size=13824),
        "70b": dict(hidden_size=8192, num_layers=80, num_attention_heads=64,
                    num_kv_heads=8, ffn_hidden_size=28672),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


def llama1_config(size: str = "7b", **overrides) -> ModelConfig:
    cfg = dict(max_position_embeddings=2048, seq_length=2048, norm_eps=1e-6)
    llama1_sizes = {
        "30b": dict(hidden_size=6656, num_layers=60, num_attention_heads=52,
                    ffn_hidden_size=17920),
        "65b": dict(hidden_size=8192, num_layers=80, num_attention_heads=64,
                    ffn_hidden_size=22016),
    }
    if size in llama1_sizes:
        cfg.update(llama1_sizes[size])
        cfg.update(overrides)
        return llama2_config("7b", **cfg)
    if size not in ("7b", "13b"):
        raise KeyError(f"unknown llama-1 size {size!r}")
    cfg.update(overrides)
    return llama2_config(size, **cfg)


def codellama_config(size: str = "34b", **overrides) -> ModelConfig:
    base = dict(
        vocab_size=32016,
        rope_theta=1000000.0,
        max_position_embeddings=16384,
        seq_length=16384,
    )
    sizes = {
        "7b": dict(hidden_size=4096, num_layers=32, num_attention_heads=32,
                   ffn_hidden_size=11008),
        "13b": dict(hidden_size=5120, num_layers=40, num_attention_heads=40,
                    ffn_hidden_size=13824),
        "34b": dict(hidden_size=8192, num_layers=48, num_attention_heads=64,
                    num_kv_heads=8, ffn_hidden_size=22016),
    }
    base.update(sizes[size])
    base.update(overrides)
    return llama2_config("7b", **base)


def llama3_config(size: str = "8b", **overrides) -> ModelConfig:
    """Llama-3 (beyond the reference's family list, but mostly free
    here: GQA, configurable rope_theta and the 128k-token tokenizer
    vocab are existing capabilities).  Llama-3.1 long-context
    checkpoints are supported via ``rope_scaling_type="llama3"``
    (piecewise frequency scaling, ops/rope.py:llama3_scaled_inv_freq) —
    config_from_hf maps the HF rope_scaling dict automatically."""
    base = dict(
        vocab_size=128256,
        rope_theta=500000.0,
        max_position_embeddings=8192,
        seq_length=8192,
        make_vocab_size_divisible_by=128,
    )
    sizes = {
        "8b": dict(hidden_size=4096, num_layers=32, num_attention_heads=32,
                   num_kv_heads=8, ffn_hidden_size=14336),
        "70b": dict(hidden_size=8192, num_layers=80,
                    num_attention_heads=64, num_kv_heads=8,
                    ffn_hidden_size=28672),
    }
    if size not in sizes:
        raise KeyError(f"unknown llama-3 size {size!r} "
                       f"(have {sorted(sizes)}; pass --model_size 8b)")
    base.update(sizes[size])
    base.update(overrides)
    return llama2_config("7b", **base)


def llama31_config(size: str = "8b", **overrides) -> ModelConfig:
    """Llama-3.1: llama3 dims + 128k context via the HF "llama3"
    piecewise RoPE frequency scaling (factor 8, low 1, high 4, original
    8192 — the rope_scaling dict every Llama-3.1 HF config ships)."""
    base = dict(
        max_position_embeddings=131072,
        seq_length=8192,  # trainable window; positions beyond are scaled
        rope_scaling_type="llama3",
        rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_positions=8192,
    )
    base.update(overrides)
    return llama3_config(size, **base)


def falcon_config(size: str = "7b", **overrides) -> ModelConfig:
    """Falcon: MQA/GQA, parallel attention, LayerNorm, gelu, rotary
    (reference: megatron/model/falcon_model.py:18-29)."""
    base = dict(
        norm_type="layernorm",
        norm_eps=1e-5,
        # HF Falcon uses exact (erf) GELU; matching it keeps logit parity
        # within verify_correctness tolerances.
        activation="gelu_exact",
        position_embedding_type=PositionEmbeddingType.ROTARY,
        use_bias=False,
        tie_embed_logits=True,
        parallel_attn=True,
        vocab_size=65024,
        max_position_embeddings=2048,
        seq_length=2048,
    )
    sizes = {
        "7b": dict(hidden_size=4544, num_layers=32, num_attention_heads=71,
                   num_kv_heads=1, ffn_hidden_size=4 * 4544),
        "40b": dict(hidden_size=8192, num_layers=60, num_attention_heads=128,
                    num_kv_heads=8, ffn_hidden_size=4 * 8192,
                    parallel_layernorm=True),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


def qwen3_next_config(size: str = "80b-a3b", **overrides) -> ModelConfig:
    """Qwen3-Next: three Gated DeltaNet layers to one gated softmax
    attention layer (256-wide heads, a quarter of each rotated, q and k
    normalised per head), zero-centred RMSNorm, and in every layer 512
    softmax-routed experts (top 10, renormalised, no drop) beside a
    sigmoid-gated shared one; untied head.  Served only: the training step
    does not run the chunked delta rule or the dropless experts.

    ``80b-a3b`` is the published model.  ``80b-a3b-ep2-rank0`` is what one
    chip of an expert-parallel pair holds: experts 0-255 of the 512 (the
    router keeps its 512 outputs and 10 choices) and rows 0-75967 of the
    151936-row embedding and head."""
    base = dict(
        norm_type="rmsnorm_zero",
        norm_eps=1e-6,
        activation="swiglu",
        position_embedding_type=PositionEmbeddingType.ROTARY,
        rope_theta=1.0e7,
        rotary_percent=0.25,
        use_bias=False,
        tie_embed_logits=False,
        hidden_size=2048,
        num_layers=48,
        num_attention_heads=16,
        num_kv_heads=2,
        kv_channels=256,
        qk_norm=True,
        attn_output_gate=True,
        layer_pattern=("linear", "linear", "linear", "full"),
        ffn_hidden_size=512,
        num_experts=512,
        moe_top_k=10,
        moe_dropless=True,
        moe_shared_expert_size=512,
        # a whole 16k-position prompt is routed at once: a held expert
        # then multiplies some hundreds of rows and not some tens
        moe_group_size=16384,
        vocab_size=151936,
        max_position_embeddings=262144,
        seq_length=4096,
        recompute="none",
    )
    sizes = {
        "80b-a3b": dict(),
        # (75968 = 1187 x 64: the half table is not whole 128-row tiles)
        "80b-a3b-ep2-rank0": dict(num_experts=256, moe_router_experts=512,
                                  moe_expert_offset=0, vocab_size=75968,
                                  make_vocab_size_divisible_by=64),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


# hybrid_override_pattern of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
# (M: Mamba-2, E: experts, *: attention).  Not periodic: between attention
# layers lie runs of 7, 8, 8, 10, 10, 10, 10, 8 and 9 layers.
NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM"
    "*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
_NEMOTRON_KINDS = {"M": "mamba", "E": "mlp", "*": "attention"}


def nemotron_h_config(size: str = "3-super-120b-a12b",
                      **overrides) -> ModelConfig:
    """Nemotron-H (``model_type: nemotron_h``): every layer is one part
    under one RMSNorm, a Mamba-2 mixer, softmax attention without any
    position rotation (32 heads on 2 KV heads of 128) or LatentMoE: 512
    sigmoid-scored experts (top 22 by score + bias, weights renormalised
    and scaled by 5) of two matrices with relu^2 between, in a 1024-wide
    latent, beside an un-gated shared expert on the full stream; untied
    head.  Served only, as every hybrid stack here.

    ``3-super-120b-a12b`` is the published model; its 88-layer pattern is
    not periodic, so it is written out whole as one period (any other
    ``num_layers`` fails ``validate``).  ``3-super-120b-a12b-ep4-rank0``
    is what one chip of an expert-parallel four holds of one pipeline
    stage: the pattern's layers 25-35 (``*EMEMEMEMEM``, which recurs at
    36, 47 and 58), experts 0-127 of the 512 (the router keeps its 512
    outputs and 22 choices) and rows 0-32767 of the 131072-row embedding
    and head.  The multi-token-prediction module is left out."""
    kinds = tuple(_NEMOTRON_KINDS[c] for c in NEMOTRON_3_SUPER_PATTERN)
    base = dict(
        norm_type="rmsnorm",
        norm_eps=1e-5,
        activation="squared_relu",
        position_embedding_type=PositionEmbeddingType.NONE,
        use_bias=False,
        tie_embed_logits=False,
        hidden_size=4096,
        num_layers=88,
        layer_pattern=kinds,
        num_attention_heads=32,
        num_kv_heads=2,
        kv_channels=128,
        mamba_num_heads=128,
        mamba_head_dim=64,
        mamba_n_groups=8,
        mamba_state_size=128,
        mamba_conv_kernel=4,
        mamba_chunk_size=128,
        ffn_hidden_size=2688,
        num_experts=512,
        moe_top_k=22,
        moe_dropless=True,
        moe_router_scoring="sigmoid",
        moe_routed_scaling=5.0,
        moe_latent_size=1024,
        moe_shared_expert_size=5376,
        moe_shared_expert_gated=False,
        # a prefill bucket is routed at once (22 pairs a token: 2048
        # tokens are 45056 sorted pairs in the kernel's scalar memory)
        moe_group_size=2048,
        vocab_size=131072,
        max_position_embeddings=262144,
        seq_length=4096,
        recompute="none",
    )
    sizes = {
        "3-super-120b-a12b": dict(),
        "3-super-120b-a12b-ep4-rank0": dict(
            num_layers=11, layer_pattern=kinds[25:36], num_experts=128,
            moe_router_experts=512, moe_expert_offset=0, vocab_size=32768),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


def granite_hybrid_config(size: str = "4.0-h-micro",
                          **overrides) -> ModelConfig:
    """Granite 4.0-H (``model_type: granitemoehybrid``): every layer is
    two parts, a mixer and a gated SiLU MLP, each under an RMSNorm of its
    own; the mixer is a Mamba-2 mixer (one B/C group shared by all its
    heads, the gated norm over the whole inner width) and in every tenth
    layer softmax attention (32 heads on 8 KV heads of 64) without any
    position rotation.  Four scalars: the embedding's output times
    ``embedding_multiplier``, every part's result times
    ``residual_multiplier`` before it is added, ``attention_multiplier``
    as the softmax scale in place of ``1/sqrt(head_dim)``, the tied
    head's logits divided by ``logits_scaling``.  No experts
    (``num_local_experts`` 0: the shared MLP is the whole feed-forward
    part).  Served only, as every hybrid stack here.

    ``4.0-h-micro`` is the published 40-layer model: attention at layers
    5, 15, 25 and 35, a period of ten."""
    base = dict(
        norm_type="rmsnorm",
        norm_eps=1e-5,
        activation="swiglu",
        position_embedding_type=PositionEmbeddingType.NONE,
        use_bias=False,
        tie_embed_logits=True,
        recompute="none",
        seq_length=4096,
    )
    sizes = {
        "4.0-h-micro": dict(
            hidden_size=2048, num_layers=40,
            layer_pattern=("ssm",) * 5 + ("full",) + ("ssm",) * 4,
            num_attention_heads=32, num_kv_heads=8, kv_channels=64,
            ffn_hidden_size=8192,
            mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=1,
            mamba_state_size=128, mamba_conv_kernel=4, mamba_chunk_size=256,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            attention_multiplier=0.015625, logits_scaling=8.0,
            vocab_size=100352, max_position_embeddings=131072),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


def deepseek_v3_config(size: str = "kanana-2-30b-a3b",
                       **overrides) -> ModelConfig:
    """``model_type: deepseek_v3`` without a query down-projection: every
    layer is latent attention (MLA, models/mla.py: 32 heads whose keys
    and values are expanded from one 512-wide latent a position, queries
    and keys 128 + 64 wide of which the 64 are rotated, values 128) and a
    feed-forward part, each under an RMSNorm of its own.  The first
    ``first_k_dense_replace`` layers hold a dense gated SiLU MLP; every
    other layer 128 sigmoid-scored experts (top 6 by score + bias over
    all of them at once, weights renormalised and scaled by 2.448) of
    three matrices, width 768, beside an un-gated shared expert of two
    experts' width; untied head.  Served only.

    ``kanana-2-30b-a3b`` is kakaocorp/kanana-2-30b-a3b-instruct-2601 as
    published (48 layers).  ``kanana-2-30b-a3b-pp8-stage0`` is the first
    of eight pipeline stages of six layers: the dense layer and five
    expert layers, every expert and the whole vocabulary.  ``head_dim``
    stays hidden / heads = 64 (what the published config calls
    ``head_dim``: its rotary width); the attention's own widths are the
    four latent-attention fields."""
    base = dict(
        norm_type="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        position_embedding_type=PositionEmbeddingType.ROTARY,
        rope_theta=1.0e6,
        use_bias=False,
        tie_embed_logits=False,
        hidden_size=2048,
        num_layers=48,
        num_attention_heads=32,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        layer_pattern=("full",),
        moe_first_dense_layers=1,
        moe_dense_ffn_size=6144,
        ffn_hidden_size=768,
        num_experts=128,
        moe_top_k=6,
        moe_dropless=True,
        moe_router_scoring="sigmoid",
        moe_routed_scaling=2.448,
        moe_shared_expert_size=1536,
        moe_shared_expert_gated=False,
        # a whole 16k-position prompt is routed at once (6 pairs a token)
        moe_group_size=16384,
        vocab_size=128256,
        max_position_embeddings=32768,
        seq_length=4096,
        recompute="none",
    )
    sizes = {
        "kanana-2-30b-a3b": dict(),
        "kanana-2-30b-a3b-pp8-stage0": dict(num_layers=6),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


def phi4flash_config(size: str = "mini-flash-reasoning",
                     **overrides) -> ModelConfig:
    """``model_type: phi4flash`` (SambaY, arXiv 2507.06607): a decoder of
    Mamba-1 and window-attention layers in turn, one full-attention layer
    whose keys and values are the only ones cached a position, and behind
    it a second decoder of gated memory units (gated by the last Mamba-1
    layer's memory at the same position) and cross-attention layers that
    read the full layer's keys and values.  Every layer is a mixer and a
    gated SiLU MLP under a LayerNorm (weight and bias) each; attention is
    differential (``diff_attention``), biased on its projections; no
    position rotation or table; tied head.  Served only.

    ``mini-flash-reasoning`` is the published 32-layer model: three runs,
    8 x (Mamba-1, window 512), (Mamba-1, full), 7 x (gated memory unit,
    cross-attention).  ``layer_runs`` as an override rebuilds
    ``layer_pattern`` and ``num_layers`` from the runs (a test's or a
    rehearsal's small stack)."""
    runs = ((("ssm1", "window"), 8), (("ssm1", "full"), 1),
            (("gmu", "cross"), 7))
    base = dict(
        norm_type="layernorm",
        norm_eps=1e-5,
        activation="swiglu",
        position_embedding_type=PositionEmbeddingType.NONE,
        use_bias=False,
        qkv_bias=True,
        tie_embed_logits=True,
        use_scaled_init=False,
        diff_attention=True,
        recompute="none",
        seq_length=4096,
    )
    sizes = {
        "mini-flash-reasoning": dict(
            hidden_size=2560, num_attention_heads=40, num_kv_heads=20,
            kv_channels=64, ffn_hidden_size=10240, sliding_window=512,
            mamba1_inner=5120, mamba1_state_size=16, mamba1_conv_kernel=4,
            mamba1_dt_rank=160, vocab_size=200064,
            max_position_embeddings=262144),
    }
    base.update(sizes[size])
    base.update(overrides)
    runs = tuple((tuple(p), int(n)) for p, n in base.pop("layer_runs", runs))
    flat = tuple(kind for period, times in runs for kind in period * times)
    if "layer_runs" not in overrides and base.get(
            "num_layers", len(flat)) != len(flat):
        raise ValueError(
            f"num_layers {base['num_layers']}: the published stack is "
            f"{len(flat)} layers in three runs; another depth is another "
            "layer_runs")
    base.update(layer_runs=runs, layer_pattern=flat, num_layers=len(flat))
    return ModelConfig(**base).validate()


def laguna_config(size: str = "xs.2-pp8-stage0", **overrides) -> ModelConfig:
    """``model_type: laguna`` (poolside/Laguna-XS.2, "33B-A3B"): every
    layer attention and a feed-forward part, each under an RMSNorm of its
    own, no bias.  Attention is full in every fourth layer (0, 4, 8 ...:
    48 query heads) and over a window of 512 keys in the others (64 query
    heads), 8 key/value heads of 128 in both, each kind with a rotation
    of its own: the window layers the whole head at theta 10 000, the
    full layers the first half of the head at theta 500 000 with YaRN's
    frequencies and factor (rotate-half, from the positions); one sigmoid
    gate a head from the layer's input.  Layer 0 holds a dense gated SiLU
    MLP of 8192; every other layer 256 sigmoid-scored experts of 512 (top
    8 by score + bias, weights renormalised and scaled by 2.5) beside an
    un-gated shared expert of 512; untied head.  Served only.

    The stack is the leading dense layer (``lead_layer_kind`` "full") and
    periods of ``("window", "window", "window", "full")``.
    ``xs.2-pp8-stage0`` is the first of eight pipeline stages of five
    layers: the dense layer and one whole period, every expert and the
    whole vocabulary.  ``xs.2`` names the published 40 layers, which are
    the leading layer, NINE periods and a last run of three window
    layers: 39 scanned layers are not whole periods, so it is refused
    here: the layer scan carries them as a stack of runs (``layer_runs``
    ``(((w, w, w, f), 9), ((w, w, w), 1))`` behind the leading layer),
    serving them is a preset's and a cell's change (ROADMAP R3 a0).
    ``layer_pattern`` as an override (a test's or a rehearsal's small
    stack) with a depth that is not whole periods gives the leading layer
    and one period."""
    pattern = ("window", "window", "window", "full")
    base = dict(
        norm_type="rmsnorm",
        norm_eps=1e-6,
        activation="swiglu",
        position_embedding_type=PositionEmbeddingType.ROTARY,
        rope_rotate_half=True,
        # the full layers' rotation: the model's
        rope_theta=500000.0,
        rotary_percent=0.5,
        rope_scaling_type="yarn",
        rope_scaling_factor=64.0,
        rope_original_max_positions=4096,
        rope_beta_fast=64.0,
        rope_beta_slow=1.0,
        rope_attention_factor=1.4158883083359672,
        # the window layers': the whole head, theta 10 000, unscaled
        window_rope=(10000.0, 1.0, 1.0),
        window_attention_heads=64,
        sliding_window=512,
        attn_head_gate=True,
        use_bias=False,
        tie_embed_logits=False,
        hidden_size=2048,
        num_layers=40,
        num_attention_heads=48,
        num_kv_heads=8,
        kv_channels=128,
        layer_pattern=pattern,
        lead_layer_kind="full",
        moe_first_dense_layers=1,
        moe_dense_ffn_size=8192,
        ffn_hidden_size=512,
        num_experts=256,
        moe_top_k=8,
        moe_dropless=True,
        moe_router_scoring="sigmoid",
        moe_routed_scaling=2.5,
        moe_shared_expert_size=512,
        moe_shared_expert_gated=False,
        # a whole 16k-position prompt is routed at once (8 pairs a token)
        moe_group_size=16384,
        vocab_size=100352,
        max_position_embeddings=262144,
        seq_length=4096,
        recompute="none",
    )
    sizes = {
        "xs.2": dict(),
        "xs.2-pp8-stage0": dict(num_layers=5),
    }
    base.update(sizes[size])
    base.update(overrides)
    lead, period = base["moe_first_dense_layers"], len(base["layer_pattern"])
    if (base["num_layers"] - lead) % period:
        if "layer_pattern" not in overrides:
            raise ValueError(
                f"num_layers {base['num_layers']}: this preset takes "
                f"the leading layer and whole periods of {period}; the "
                "published 40 layers end in a run of three window layers "
                "(a stack of runs: layer_runs, not this preset's yet)")
        base["num_layers"] = lead + period
    return ModelConfig(**base).validate()


def gpt_config(size: str = "345m", **overrides) -> ModelConfig:
    """GPT-2/3 style: learned absolute positions, LayerNorm, gelu, tied
    embeddings, biases (reference: megatron/model/gpt_model.py)."""
    base = dict(
        norm_type="layernorm",
        norm_eps=1e-5,
        activation="gelu",
        position_embedding_type=PositionEmbeddingType.ABSOLUTE,
        use_bias=True,
        tie_embed_logits=True,
        vocab_size=50257,
        max_position_embeddings=1024,
        seq_length=1024,
    )
    sizes = {
        "125m": dict(hidden_size=768, num_layers=12, num_attention_heads=12),
        "345m": dict(hidden_size=1024, num_layers=24, num_attention_heads=16),
        "1.3b": dict(hidden_size=2048, num_layers=24, num_attention_heads=32),
    }
    base.update(sizes[size])
    base.update(overrides)
    return ModelConfig(**base).validate()


def tiny_config(**overrides) -> ModelConfig:
    """Small llama-style config for tests."""
    base = dict(
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_attention_heads=4,
        num_kv_heads=2,
        ffn_hidden_size=128,
        max_position_embeddings=128,
        seq_length=32,
        params_dtype="float32",
        attention_impl="dot",
        recompute="none",
        make_vocab_size_divisible_by=8,
    )
    base.update(overrides)
    return ModelConfig(**base).validate()


PRESETS = {
    "llama2-7b": lambda: llama2_config("7b"),
    "llama2-13b": lambda: llama2_config("13b"),
    "llama2-70b": lambda: llama2_config("70b"),
    "llama1-7b": lambda: llama1_config("7b"),
    "llama3-8b": lambda: llama3_config("8b"),
    "llama3-70b": lambda: llama3_config("70b"),
    "llama3.1-8b": lambda: llama31_config("8b"),
    "llama3.1-70b": lambda: llama31_config("70b"),
    "codellama-7b": lambda: codellama_config("7b"),
    "codellama-34b": lambda: codellama_config("34b"),
    "falcon-7b": lambda: falcon_config("7b"),
    "falcon-40b": lambda: falcon_config("40b"),
    "gpt-345m": lambda: gpt_config("345m"),
    "qwen3-next-80b-a3b": lambda: qwen3_next_config("80b-a3b"),
    "granite-4.0-h-micro": lambda: granite_hybrid_config("4.0-h-micro"),
    "kanana-2-30b-a3b": lambda: deepseek_v3_config("kanana-2-30b-a3b"),
    "phi-4-mini-flash-reasoning": lambda: phi4flash_config(
        "mini-flash-reasoning"),
    "laguna-xs.2-pp8-stage0": lambda: laguna_config("xs.2-pp8-stage0"),
    "tiny": tiny_config,
}


def get_preset(name: str) -> ModelConfig:
    return PRESETS[name]()
