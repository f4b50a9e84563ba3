#!/usr/bin/env python
"""Main training entry point: pretrain / finetune / instruction-tune
Llama 1/2, Code Llama, Falcon and GPT on TPU.

TPU-native counterpart of the reference driver (finetune.py:252-265 →
initialize_megatron → pretrain): argparse groups mirror the reference's
argument groups (megatron/arguments.py:15-35), resolved into the typed
``RuntimeConfig``, then handed to ``megatron_llm_tpu.training.driver.
pretrain``.

Examples:
  python finetune.py --model llama2 --model_size 7b \\
      --data_path data/corpus_text_document --tokenizer_type sentencepiece \\
      --tokenizer_model tokenizer.model --save ckpts/ --train_iters 1000 \\
      --global_batch_size 64 --micro_batch_size 4 --tp 8 --sequence_parallel
  python finetune.py --model tiny --mock_data --train_iters 10   # smoke run
"""

from __future__ import annotations

import argparse
import sys


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)

    g = p.add_argument_group("model")
    g.add_argument("--model", default="llama2",
                   choices=["llama", "llama2", "llama3", "llama3.1",
                            "codellama", "falcon", "gpt", "tiny"])
    g.add_argument("--model_size", default="7b")
    g.add_argument("--seq_length", type=int, default=None)
    g.add_argument("--rope_scaling_factor", type=float, default=1.0)
    g.add_argument("--rope_scaling_type", default=None,
                   choices=["linear", "llama3", "yarn"],
                   help="RoPE scaling style (with --rope_scaling_factor); "
                        "llama3/yarn also need --rope_original_max_positions")
    g.add_argument("--rope_original_max_positions", type=int, default=None)
    g.add_argument("--num_experts", type=int, default=0,
                   help="MoE experts per layer (0 = dense)")
    g.add_argument("--moe_top_k", type=int, default=2)
    g.add_argument("--moe_capacity_factor", type=float, default=1.25)
    g.add_argument("--moe_aux_loss_coeff", type=float, default=0.01)
    g.add_argument("--params_dtype", default="bfloat16",
                   choices=["float32", "bfloat16", "float16"])
    g.add_argument("--attention_impl", default="flash",
                   choices=["flash", "dot"])
    g.add_argument("--recompute", default="selective",
                   choices=["none", "selective", "full"])
    g.add_argument("--quantize_matmuls", default="none",
                   choices=["none", "int8"],
                   help="W8A8 projection matmuls on the int8 MXU with "
                        "straight-through backward (the TE-FP8 analogue, "
                        "ref transformer.py:932-951)")
    g.add_argument("--hidden_dropout", type=float, default=None,
                   help="residual dropout rate (default: model preset)")
    g.add_argument("--lima_dropout", action="store_true",
                   help="layer-dependent dropout ramp 0->hidden_dropout "
                        "(LIMA, reference transformer.py:964-971)")
    g.add_argument("--drop_path_rate", type=float, default=0.0,
                   help="stochastic-depth rate at the last layer "
                        "(reference DropPath, transformer.py:43-64)")

    g = p.add_argument_group("lora")
    g.add_argument("--lora_rank", type=int, default=0,
                   help="train a LoRA adapter of this rank against the "
                        "frozen base model instead of full finetuning "
                        "(0 = off); checkpoints are adapter-only and "
                        "servable via serving/adapters/")
    g.add_argument("--lora_targets", nargs="*", default=None,
                   help="projections to adapt (default: wq wv); choose "
                        "from wq wk wv wo w_gate w_up w_down")
    g.add_argument("--lora_alpha", type=float, default=None,
                   help="LoRA alpha (default: rank, i.e. scale 1.0)")

    g = p.add_argument_group("parallelism")
    g.add_argument("--tp", "--tensor_parallel", type=int, default=1,
                   dest="tp")
    g.add_argument("--pp", "--pipeline_parallel", type=int, default=1,
                   dest="pp")
    g.add_argument("--dp", "--data_parallel", type=int, default=0, dest="dp",
                   help="0 = infer from device count / (tp*pp*cp)")
    g.add_argument("--ep", "--expert_parallel", type=int, default=1,
                   help="expert-parallel axis size (MoE)")
    g.add_argument("--cp_layout", "--context_parallel_layout",
                   default="contiguous", choices=["contiguous", "zigzag"],
                   help="zigzag balances causal ring-attention work "
                        "(~2x faster cp attention; pp=1 only)")
    g.add_argument("--cp", "--context_parallel", type=int, default=1,
                   dest="cp")
    g.add_argument("--virtual_pipeline_stages", type=int, default=1)
    g.add_argument("--pipeline_remat_window", type=int, default=0,
                   help="checkpoint the pipeline tick loop in windows of W "
                        "ticks: bounds activation memory at large "
                        "grad-accum counts (M>=64) for ~+25%% FLOPs; "
                        "0 = off, -1 = memory-minimizing auto choice; "
                        "with vpp>1 needs num_microbatches %% pp == 0")
    g.add_argument("--sequence_parallel", action="store_true")
    g.add_argument("--use_distributed_optimizer", action="store_true")

    g = p.add_argument_group("training")
    g.add_argument("--train_iters", type=int, default=1000)
    g.add_argument("--micro_batch_size", type=int, default=1)
    g.add_argument("--global_batch_size", type=int, default=1)
    g.add_argument("--rampup_batch_size", type=int, nargs=3, default=None)
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--lr", type=float, default=3e-4)
    g.add_argument("--min_lr", type=float, default=3e-5)
    g.add_argument("--lr_decay_style", default="cosine",
                   choices=["constant", "linear", "cosine",
                            "inverse-square-root"])
    g.add_argument("--lr_warmup_iters", type=int, default=0)
    g.add_argument("--weight_decay", type=float, default=0.1)
    g.add_argument("--clip_grad", type=float, default=1.0)
    g.add_argument("--adam_beta1", type=float, default=0.9)
    g.add_argument("--adam_beta2", type=float, default=0.95)
    g.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    g.add_argument("--skip_iters", type=int, nargs="*", default=())

    g = p.add_argument_group("checkpointing")
    g.add_argument("--save", default=None)
    g.add_argument("--load", default=None)
    g.add_argument("--save_interval", type=int, default=1000)
    g.add_argument("--use_checkpoint_args", action="store_true")

    g = p.add_argument_group("data")
    g.add_argument("--data_path", nargs="*", default=None,
                   help="corpus prefix(es), optionally weighted: "
                        "[w1 prefix1 w2 prefix2 ...]")
    g.add_argument("--split", default="969,30,1")
    g.add_argument("--instruction_data", action="store_true",
                   help="role-tagged instruction dataset "
                        "(<prefix>_text/_role pairs)")
    g.add_argument("--scalar_loss_mask", type=float, default=0.0)
    g.add_argument("--mock_data", action="store_true",
                   help="synthetic random tokens (smoke tests)")
    g.add_argument("--data_cache_dir", default=None)

    g = p.add_argument_group("tokenizer")
    g.add_argument("--tokenizer_type", default="null")
    g.add_argument("--tokenizer_model", default=None)
    g.add_argument("--vocab_extra_ids_list", nargs="*", default=None)

    g = p.add_argument_group("eval/logging")
    g.add_argument("--eval_interval", type=int, default=1000)
    g.add_argument("--eval_iters", type=int, default=10)
    g.add_argument("--log_interval", type=int, default=10)
    g.add_argument("--metrics", nargs="*", default=())
    g.add_argument("--tensorboard_dir", default=None)
    g.add_argument("--wandb_project", default=None)
    g.add_argument("--wandb_name", default=None)
    g.add_argument("--profile_dir", default=None,
                   help="write a jax.profiler device trace of a few "
                        "steady-state iterations here (TensorBoard "
                        "profile plugin viewable)")
    g.add_argument("--profile_step_start", type=int, default=11)
    g.add_argument("--profile_step_end", type=int, default=13)
    g.add_argument("--exit_interval", type=int, default=None)
    g.add_argument("--exit_duration_mins", type=float, default=None)

    return p.parse_args(argv)


def build_config(args):
    import jax

    from megatron_llm_tpu.config import (
        OptimizerConfig,
        ParallelConfig,
        RuntimeConfig,
        TrainConfig,
        codellama_config,
        falcon_config,
        gpt_config,
        llama1_config,
        llama2_config,
        llama3_config,
        llama31_config,
        tiny_config,
    )

    overrides = dict(
        params_dtype=args.params_dtype,
        attention_impl=args.attention_impl,
        recompute=args.recompute,
        quantize_matmuls=args.quantize_matmuls,
    )
    if args.seq_length:
        overrides["seq_length"] = args.seq_length
    if args.rope_scaling_factor != 1.0:
        overrides["rope_scaling_factor"] = args.rope_scaling_factor
    if args.rope_scaling_type:
        overrides["rope_scaling_type"] = args.rope_scaling_type
    if args.rope_original_max_positions:
        overrides["rope_original_max_positions"] = \
            args.rope_original_max_positions
    if args.hidden_dropout is not None:
        overrides["hidden_dropout"] = args.hidden_dropout
    if args.lima_dropout:
        if not args.hidden_dropout:
            raise SystemExit(
                "--lima_dropout ramps 0 -> hidden_dropout across layers, "
                "but hidden_dropout is 0 (the preset default) - pass a "
                "nonzero --hidden_dropout for it to have any effect")
        overrides["lima_dropout"] = True
    if args.drop_path_rate:
        overrides["drop_path_rate"] = args.drop_path_rate
    if args.num_experts:
        overrides.update(
            num_experts=args.num_experts, moe_top_k=args.moe_top_k,
            moe_capacity_factor=args.moe_capacity_factor,
            moe_aux_loss_coeff=args.moe_aux_loss_coeff)
    builders = {
        "llama": lambda: llama1_config(args.model_size, **overrides),
        "llama2": lambda: llama2_config(args.model_size, **overrides),
        "llama3": lambda: llama3_config(args.model_size, **overrides),
        "llama3.1": lambda: llama31_config(args.model_size, **overrides),
        "codellama": lambda: codellama_config(args.model_size, **overrides),
        "falcon": lambda: falcon_config(args.model_size, **overrides),
        "gpt": lambda: gpt_config(args.model_size, **overrides),
        "tiny": lambda: tiny_config(**overrides),
    }
    model = builders[args.model]()
    # check the effective factor (preset may supply it, e.g. llama3.1's 8.0)
    if args.rope_scaling_type and model.rope_scaling_factor == 1.0:
        raise SystemExit(
            "--rope_scaling_type has no effect with rope_scaling_factor=1.0 "
            "— pass --rope_scaling_factor (or a preset that sets one)")

    dp = args.dp
    if dp <= 0:
        denom = args.tp * args.pp * args.cp * args.ep
        dp = max(1, len(jax.devices()) // denom)
    parallel = ParallelConfig(
        data_parallel=dp,
        pipeline_parallel=args.pp,
        tensor_parallel=args.tp,
        context_parallel=args.cp,
        context_parallel_layout=args.cp_layout,
        expert_parallel=args.ep,
        virtual_pipeline_stages=args.virtual_pipeline_stages,
        pipeline_remat_window=args.pipeline_remat_window,
        sequence_parallel=args.sequence_parallel,
        use_distributed_optimizer=args.use_distributed_optimizer,
        num_microbatches=max(
            1, args.global_batch_size // (args.micro_batch_size * dp)),
    )
    optimizer = OptimizerConfig(
        optimizer=args.optimizer,
        lr=args.lr,
        min_lr=args.min_lr,
        weight_decay=args.weight_decay,
        adam_beta1=args.adam_beta1,
        adam_beta2=args.adam_beta2,
        clip_grad=args.clip_grad,
        lr_decay_style=args.lr_decay_style,
        lr_warmup_iters=args.lr_warmup_iters,
    )
    train = TrainConfig(
        train_iters=args.train_iters,
        micro_batch_size=args.micro_batch_size,
        global_batch_size=args.global_batch_size,
        rampup_batch_size=tuple(args.rampup_batch_size)
        if args.rampup_batch_size else None,
        seq_length=args.seq_length or model.seq_length,
        seed=args.seed,
        eval_interval=args.eval_interval,
        eval_iters=args.eval_iters,
        save=args.save,
        load=args.load,
        save_interval=args.save_interval,
        log_interval=args.log_interval,
        tensorboard_dir=args.tensorboard_dir,
        wandb_project=args.wandb_project,
        wandb_name=args.wandb_name,
        exit_interval=args.exit_interval,
        profile_dir=args.profile_dir,
        profile_step_start=args.profile_step_start,
        profile_step_end=args.profile_step_end,
        exit_duration_mins=args.exit_duration_mins,
        data_path=args.data_path,
        split=args.split,
        metrics=tuple(args.metrics),
        skip_iters=tuple(args.skip_iters),
    )
    cfg = RuntimeConfig(model=model, parallel=parallel, optimizer=optimizer,
                        train=train)

    # --use_checkpoint_args: config wins from the checkpoint
    # (reference checkpointing.py:476-559, hook at initialize.py:41-43)
    if args.use_checkpoint_args and args.load:
        from megatron_llm_tpu.checkpointing import load_config_from_checkpoint

        saved = load_config_from_checkpoint(args.load)
        cfg = RuntimeConfig(model=saved.model, parallel=saved.parallel,
                            optimizer=saved.optimizer, train=train)
    return cfg.validate()


class _MockDataset:
    """Deterministic random-token dataset for smoke tests."""

    def __init__(self, vocab_size: int, seq_length: int, n: int = 4096,
                 seed: int = 0):
        self.vocab = vocab_size
        self.seq = seq_length
        self.n = n
        self.seed = seed

    def __len__(self):
        return self.n

    def __getitem__(self, idx):
        rng = __import__("numpy").random.default_rng(self.seed + idx)
        return {"text": rng.integers(
            0, self.vocab, self.seq + 1).astype("int64")}


def build_datasets(args, cfg):
    from megatron_llm_tpu.data.blendable_dataset import (
        BlendableDataset,
        parse_data_paths,
    )
    from megatron_llm_tpu.data.gpt_dataset import build_gpt_datasets
    from megatron_llm_tpu.data.instruction_dataset import (
        build_instruction_datasets,
    )

    if args.mock_data:
        ds = _MockDataset(cfg.model.vocab_size, cfg.train.seq_length)
        return ds, _MockDataset(cfg.model.vocab_size, cfg.train.seq_length,
                                n=256, seed=10_000), None
    assert args.data_path, "--data_path or --mock_data required"

    if args.instruction_data:
        assert len(args.data_path) == 1, (
            "instruction data takes a single prefix")
        return build_instruction_datasets(
            args.data_path[0], args.split, cfg.train.seq_length,
            cfg.train.seed, scalar_loss_mask=args.scalar_loss_mask)

    weights, prefixes = parse_data_paths(args.data_path)
    total_samples = cfg.train.train_iters * cfg.train.global_batch_size
    eval_samples = cfg.train.eval_iters * cfg.train.global_batch_size
    nums = [total_samples, eval_samples, eval_samples]
    per_prefix = [
        build_gpt_datasets(prefix, args.split, nums, cfg.train.seq_length,
                           cfg.train.seed, args.data_cache_dir)
        for prefix in prefixes
    ]
    out = []
    for i in range(3):
        # keep weights aligned with the prefixes that produced this split
        pairs = [(p[i], w) for p, w in zip(per_prefix, weights)
                 if p[i] is not None]
        if not pairs:
            out.append(None)
        elif len(pairs) == 1:
            out.append(pairs[0][0])
        else:
            out.append(BlendableDataset(
                [d for d, _ in pairs], [w for _, w in pairs], nums[i]))
    return tuple(out)


def main(argv=None) -> int:
    from megatron_llm_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = parse_args(argv)
    cfg = build_config(args)

    from megatron_llm_tpu.training.driver import pretrain, print_rank_0

    eod = None
    if args.tokenizer_type and args.tokenizer_type != "null" \
            and args.tokenizer_model:
        from megatron_llm_tpu.tokenizer.tokenizer import build_tokenizer

        # accept both "--x a b" and the comma-joined "--x a,b" forms (the
        # preprocess tool documents the comma form)
        extra = args.vocab_extra_ids_list
        if extra:
            extra = [t for item in extra for t in item.split(",") if t]
        tok = build_tokenizer(args.tokenizer_type, args.tokenizer_model,
                              extra)
        eod = tok.eod
        if tok.vocab_size > cfg.model.vocab_size:
            # extra special tokens grew the tokenizer beyond the preset
            # model vocab (reference pads vocab from the tokenizer,
            # megatron/tokenizer/tokenizer.py:39-63) — grow the embedding
            # so the new ids are real rows, not clamped aliases.
            import dataclasses as _dc

            from megatron_llm_tpu.config import RuntimeConfig as _RC

            cfg = _RC(
                model=_dc.replace(cfg.model, vocab_size=tok.vocab_size),
                parallel=cfg.parallel, optimizer=cfg.optimizer,
                train=cfg.train).validate()
            print_rank_0(f" vocab grown to {tok.vocab_size} "
                         f"(tokenizer extra ids)")

    print_rank_0(f"model: {args.model} {args.model_size} | "
                 f"mesh: dp={cfg.parallel.data_parallel} "
                 f"pp={cfg.parallel.pipeline_parallel} "
                 f"cp={cfg.parallel.context_parallel} "
                 f"tp={cfg.parallel.tensor_parallel} | "
                 f"gbs={cfg.train.global_batch_size} "
                 f"seq={cfg.train.seq_length}")
    train_ds, valid_ds, test_ds = build_datasets(args, cfg)

    if args.lora_rank:
        # adapter-only finetune against a frozen base: the base comes
        # from --load (params-only restore; the optimizer state of a
        # full checkpoint is never read) or fresh init for smoke runs,
        # and --save receives an adapter-only checkpoint
        import jax as _jax

        from megatron_llm_tpu import checkpointing
        from megatron_llm_tpu.models import model as model_lib
        from megatron_llm_tpu.training.lora import lora_finetune

        if cfg.train.load:
            base = checkpointing.load_params_for_inference(
                cfg.train.load, cfg.model)
            print_rank_0(f" loaded frozen base from {cfg.train.load}")
        else:
            print_rank_0(" no --load: LoRA against a fresh random base "
                         "(smoke runs only)")
            base = model_lib.init_params(
                _jax.random.key(cfg.train.seed), cfg.model)
        lora_finetune(cfg, base, train_ds, rank=args.lora_rank,
                      targets=args.lora_targets, alpha=args.lora_alpha,
                      eod_token=eod, save=cfg.train.save)
        return 0

    pretrain(cfg, train_ds, valid_ds, test_ds, eod_token=eod)
    return 0


if __name__ == "__main__":
    sys.exit(main())
