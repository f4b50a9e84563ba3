"""checkpoint_util / merge_datasets / push_to_hub CLI tests.

Mirrors the reference's incremental conversion suite
(tests/test_llama_weights.py): hf→native, native→hf round trip with logit
parity, resave (the reshard equivalent), dataset merging.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax
import jax.numpy as jnp

from megatron_llm_tpu.data.indexed_dataset import (
    MMapIndexedDataset,
    write_dataset,
)
from megatron_llm_tpu.tools import checkpoint_util, hf_interop, merge_datasets
from megatron_llm_tpu.tools.verify_correctness import verify


def tiny_hf_llama():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False, attn_implementation="eager",
    )
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(hf_cfg).eval()


@pytest.mark.incremental
class TestConversionPipeline:
    @pytest.fixture(scope="class")
    def converted(self, tmp_path_factory):
        """``(root, hf)``: a tiny HF Llama saved under ``root/hf_in`` and
        converted to ``root/native`` through the CLI, built once for
        whichever of the class's tests a worker is dealt."""
        root = tmp_path_factory.mktemp("conv")
        hf = tiny_hf_llama()
        hf.save_pretrained(str(root / "hf_in"))
        checkpoint_util.main([
            "hf-to-native",
            "--hf_path", str(root / "hf_in"),
            "--output", str(root / "native"),
        ])
        return root, hf

    def test_hf_to_native(self, converted):
        root, _ = converted
        assert (root / "native" / "iter_release").exists() or any(
            (root / "native").iterdir())

    def test_native_logit_parity(self, converted):
        root, hf = converted
        from megatron_llm_tpu import checkpointing

        cfg = checkpointing.load_config_from_checkpoint(
            str(root / "native")).model
        params = checkpointing.load_params_for_inference(
            str(root / "native"), cfg)
        batches = [np.random.default_rng(0).integers(0, 128, (2, 32))]
        report = verify(cfg, params, hf, batches, tolerance=1e-3)
        assert report["passed"], report

    def test_resave_roundtrip(self, converted):
        root, hf = converted
        checkpoint_util.main([
            "resave",
            "--load", str(root / "native"),
            "--output", str(root / "resaved"),
        ])
        from megatron_llm_tpu import checkpointing

        cfg = checkpointing.load_config_from_checkpoint(
            str(root / "resaved")).model
        params = checkpointing.load_params_for_inference(
            str(root / "resaved"), cfg)
        batches = [np.random.default_rng(1).integers(0, 128, (2, 32))]
        report = verify(cfg, params, hf, batches, tolerance=1e-3)
        assert report["passed"], report

    def test_native_to_hf_roundtrip(self, converted):
        root, hf = converted
        checkpoint_util.main([
            "native-to-hf",
            "--load", str(root / "native"),
            "--output", str(root / "hf_out"),
            "--hf_base", str(root / "hf_in"),
        ])
        reloaded = transformers.AutoModelForCausalLM.from_pretrained(
            str(root / "hf_out")).eval()
        orig_sd = hf.state_dict()
        new_sd = reloaded.state_dict()
        for k, v in orig_sd.items():
            if k.endswith("rotary_emb.inv_freq"):
                continue
            np.testing.assert_allclose(
                new_sd[k].float().numpy(), v.float().numpy(),
                atol=1e-6, err_msg=k)


def test_falcon_roundtrip_to_hf():
    """falcon_to_hf is the exact inverse of falcon_from_hf."""
    hf_cfg = transformers.FalconConfig(
        vocab_size=96, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=1, multi_query=True,
        parallel_attn=True, bias=False, new_decoder_architecture=False,
        layer_norm_epsilon=1e-5,
    )
    torch.manual_seed(1)
    hf = transformers.FalconForCausalLM(hf_cfg).eval()
    cfg = hf_interop.config_from_hf(
        hf_cfg, "falcon", params_dtype="float32", attention_impl="dot",
        recompute="none", make_vocab_size_divisible_by=8)
    params = hf_interop.falcon_from_hf(hf.state_dict(), cfg)
    sd = hf_interop.falcon_to_hf(params, cfg)
    orig = {k: v.float().numpy() for k, v in hf.state_dict().items()}
    for k, v in sd.items():
        if k not in orig:
            continue
        np.testing.assert_allclose(v, orig[k], atol=1e-6, err_msg=k)


def test_gpt2_roundtrip_to_hf():
    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_embd=48, n_layer=2, n_head=4, n_positions=64,
    )
    torch.manual_seed(2)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfg = hf_interop.config_from_hf(
        hf_cfg, "gpt2", params_dtype="float32", attention_impl="dot",
        recompute="none", make_vocab_size_divisible_by=8)
    params = hf_interop.gpt2_from_hf(hf.state_dict(), cfg)
    sd = hf_interop.gpt2_to_hf(params, cfg)
    orig = {k: v.float().numpy() for k, v in hf.state_dict().items()}
    for k, v in sd.items():
        if k not in orig:
            continue
        np.testing.assert_allclose(v, orig[k], atol=1e-6, err_msg=k)


def test_merge_datasets(tmp_path):
    a = [[1, 2, 3], [4, 5]]
    b = [[6, 7, 8, 9], [10]]
    write_dataset(str(tmp_path / "a"), a)
    write_dataset(str(tmp_path / "b"), b)
    rc = merge_datasets.main([
        "--input", str(tmp_path / "a"), str(tmp_path / "b"),
        "--output_prefix", str(tmp_path / "merged"),
    ])
    assert rc == 0
    ds = MMapIndexedDataset(str(tmp_path / "merged"))
    docs = [np.asarray(ds[i]).tolist() for i in range(len(ds))]
    assert docs == a + b


def test_push_to_hub_export_only(tmp_path):
    from megatron_llm_tpu.tools import push_to_hub

    hf = tiny_hf_llama()
    hf.save_pretrained(str(tmp_path / "hf_in"))
    checkpoint_util.main([
        "hf-to-native",
        "--hf_path", str(tmp_path / "hf_in"),
        "--output", str(tmp_path / "native"),
    ])
    rc = push_to_hub.main([
        "--load", str(tmp_path / "native"),
        "--export_only", "--output", str(tmp_path / "export"),
        "--hf_base", str(tmp_path / "hf_in"),
    ])
    assert rc == 0
    assert any((tmp_path / "export").glob("*.safetensors")) or any(
        (tmp_path / "export").glob("pytorch_model*"))
