"""Full-scale-dims synthetic trust path: the reference's strongest
correctness guarantee, stage for stage.

Mirrors /root/reference/tests/test_llama_weights.py:91-201 — meta→megatron
conversion, hf→megatron conversion, verify_correctness (avg max |Δlogit| ≤
0.001), reshard, megatron→HF round trip — minus live weights (hub egress is
blocked in this environment).  Weights are random but the *dims are real
Llama-2-7B widths* (hidden 4096, ffn 11008, 32 heads × d128, vocab 32000)
at depth 2: every matmul shape, qkv rotate-half permutation, vocab padding
and shard split is exercised at exactly the 7B geometry; depth only repeats
layers.  The reshard stage loads the converted checkpoint tp=8-sharded on
the virtual mesh and asserts logit parity, which is what the reference's
tp=2/pp=2 shard/unshard cycle establishes.
"""

import fcntl
import json
import os

import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

import jax
import jax.numpy as jnp

from test_meta_interop import _meta_dict_from_native, _shard_meta_dict

from megatron_llm_tpu import checkpointing
from megatron_llm_tpu.tools import checkpoint_util, hf_interop
from megatron_llm_tpu.tools.verify_correctness import verify

# Llama-2-7B widths (docs/guide's 7B config; reference tests run the real
# 7B), reduced to 2 layers so the fp32 CPU pipeline stays tractable.
WIDTH = dict(
    vocab_size=32000,
    hidden_size=4096,
    intermediate_size=11008,
    num_hidden_layers=2,
    num_attention_heads=32,
    num_key_value_heads=32,
    max_position_embeddings=128,
    rms_norm_eps=1e-5,
)

TOL = 1e-3  # reference: avg(max |Δlogit|) ≤ 0.001 (test_llama_weights.py:117)


def _batches(n=2, b=1, s=16, seed=0):
    g = np.random.default_rng(seed)
    return [g.integers(0, WIDTH["vocab_size"], (b, s)) for _ in range(n)]


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves_with_path(a), jax.tree.leaves_with_path(b)
    assert len(la) == len(lb)
    for (path, x), (_, y) in zip(la, lb):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"mismatch at {jax.tree_util.keystr(path)}")


def _hf_config():
    return transformers.LlamaConfig(
        tie_word_embeddings=False, attn_implementation="eager", **WIDTH)


def _built_once(root, name, build):
    """``root/name``, built by whichever xdist worker asks first
    (``build(path)``); the others wait on the lock and find it there.  A
    checkpoint is 2.7 GB and a conversion minutes: one copy a session."""
    out, lock, built = root / name, root / f"{name}.lock", \
        root / f"{name}.built"
    with open(lock, "w") as held:
        fcntl.flock(held, fcntl.LOCK_EX)    # let go when ``held`` closes
        if not built.exists():
            build(out)
            built.touch()
    return out


def _cli(root, command, source, output, **flags):
    """One ``checkpoint_util`` conversion into ``root/output``, run once a
    session; → that path."""
    def build(out):
        args = [command, *source, "--output", str(out)]
        for flag, value in flags.items():
            args += [f"--{flag}", str(value)]
        checkpoint_util.main(args)
    return _built_once(root, output, build)


def _synthesize_upstream(root):
    """The two upstream weight formats — an HF Llama directory and a
    2-shard Meta release dir — from ONE random model, so every stage has
    a ground truth."""
    torch.manual_seed(7)
    hf = transformers.LlamaForCausalLM(_hf_config()).eval()
    hf.save_pretrained(str(root / "hf_in"))

    # Meta dir: native tree (via the HF converter) → meta layout →
    # Meta-style column/row shards + params.json.
    cfg = hf_interop.config_from_hf(_hf_config(), "llama",
                                    params_dtype="float32")
    native = hf_interop.llama_from_hf(hf.state_dict(), cfg,
                                      dtype=np.float32)
    meta_sd = _meta_dict_from_native(native, cfg)
    (root / "meta_in").mkdir()
    for i, shard in enumerate(_shard_meta_dict(meta_sd, 2)):
        torch.save({k: torch.tensor(v) for k, v in shard.items()},
                   root / "meta_in" / f"consolidated.0{i}.pth")
    (root / "meta_in" / "params.json").write_text(json.dumps({
        "dim": WIDTH["hidden_size"],
        "n_layers": WIDTH["num_hidden_layers"],
        "n_heads": WIDTH["num_attention_heads"],
        "multiple_of": 256,
        "norm_eps": WIDTH["rms_norm_eps"],
        "vocab_size": WIDTH["vocab_size"],
    }))


@pytest.mark.incremental
class TestTrustPath7BWidth:
    """Every stage takes what it needs from class-scoped fixtures, so it
    passes on whichever xdist worker it is dealt to, with or without the
    stages before it.  What a fixture stands for is built once a session,
    in a directory the session's workers share."""

    @pytest.fixture(scope="class")
    def root(self, tmp_path_factory):
        """The session's directory: a worker's base is a child of it."""
        base = tmp_path_factory.getbasetemp()
        if os.environ.get("PYTEST_XDIST_WORKER"):
            base = base.parent
        root = base / "trust7b"
        root.mkdir(exist_ok=True)
        _built_once(root, "upstream", lambda _: _synthesize_upstream(root))
        return root

    @pytest.fixture(scope="class")
    def hf(self, root):
        """The random model the upstream formats were written from."""
        return transformers.LlamaForCausalLM.from_pretrained(
            str(root / "hf_in"), attn_implementation="eager").eval()

    @pytest.fixture(scope="class")
    def native_meta(self, root):
        """The real CLI's meta→native checkpoint."""
        return _cli(root, "meta-to-native",
                    ["--meta_dir", str(root / "meta_in")], "native_meta")

    @pytest.fixture(scope="class")
    def native_hf(self, root):
        """The real CLI's hf→native checkpoint."""
        return _cli(root, "hf-to-native",
                    ["--hf_path", str(root / "hf_in")], "native_hf")

    @pytest.fixture(scope="class")
    def resaved(self, root, native_hf):
        """``native_hf`` resaved through the real CLI."""
        return _cli(root, "resave", ["--load", str(native_hf)], "resaved")

    def test_7bw_synthetic_weights_exist(self, root):
        """Stage 0 (≙ test_path_exists)."""
        assert (root / "hf_in").is_dir() and (root / "meta_in").is_dir()

    def test_7bw_meta_to_native(self, hf, native_meta):
        """Stage 1 (≙ test_meta2mega): real CLI meta→native, then the
        verify_correctness harness vs the HF implementation."""
        cfg = checkpointing.load_config_from_checkpoint(
            str(native_meta)).model
        assert cfg.ffn_size == WIDTH["intermediate_size"]
        params = checkpointing.load_params_for_inference(
            str(native_meta), cfg)
        report = verify(cfg, params, hf, _batches(), tolerance=TOL)
        assert report["passed"], report

    def test_7bw_hf_to_native(self, hf, native_hf):
        """Stage 2 (≙ test_hf2mega)."""
        cfg = checkpointing.load_config_from_checkpoint(
            str(native_hf)).model
        params = checkpointing.load_params_for_inference(
            str(native_hf), cfg)
        report = verify(cfg, params, hf, _batches(seed=1),
                        tolerance=TOL)
        assert report["passed"], report

    def test_7bw_meta_and_hf_paths_agree(self, native_meta, native_hf):
        """Stage 3 (≙ test_metallama_verification): the two conversion
        routes must produce BIT-IDENTICAL native params — the rotate-half
        permutation applied on the HF path must exactly invert what the
        Meta layout already has."""
        cfg = hf_interop.config_from_hf(_hf_config(), "llama",
                                        params_dtype="float32")
        a = checkpointing.load_params_for_inference(str(native_meta), cfg)
        b = checkpointing.load_params_for_inference(str(native_hf), cfg)
        _assert_trees_equal(a, b)

    def test_7bw_reshard_tp8_logit_parity(self, hf, resaved):
        """Stage 4 (≙ test_shard_unshard tp=2/pp=2): resave through the
        real CLI, load the result SHARDED tp=8 on the mesh, and assert
        logit parity — reshard-on-load is this framework's equivalent of
        the reference's offline shard/unshard cycle (checkpoints are
        logical arrays; tools/checkpoint_util.py:resave docstring)."""
        from jax.sharding import NamedSharding

        from megatron_llm_tpu.config import ParallelConfig
        from megatron_llm_tpu.models import model as model_lib
        from megatron_llm_tpu.models import sharding as shard_lib
        from megatron_llm_tpu.parallel import mesh as mesh_lib

        cfg = checkpointing.load_config_from_checkpoint(str(resaved)).model
        params = checkpointing.load_params_for_inference(str(resaved), cfg)
        parallel = ParallelConfig(tensor_parallel=8)
        mesh = mesh_lib.build_mesh(parallel)
        specs = shard_lib.param_specs(cfg, parallel)
        params = shard_lib.shard_params(params, specs, mesh)
        tokens = _batches(n=1, seed=2)[0]
        with mesh_lib.use_mesh(mesh):
            got = np.asarray(jax.jit(
                lambda p, t: model_lib.forward(cfg, p, t)
            )(params, jnp.asarray(tokens, jnp.int32)), np.float32)
        with torch.no_grad():
            want = hf(torch.tensor(tokens)).logits.float().numpy()
        max_err = np.abs(got[..., :WIDTH["vocab_size"]] - want).max()
        assert max_err <= TOL, f"tp=8 max |Δlogit| = {max_err}"

    def test_7bw_native_to_hf_roundtrip(self, root, hf, resaved):
        """Stage 5 (≙ test_mega2hf/test_unsharded2hf): back to HF format,
        weights bit-exact against the original."""
        hf_out = _cli(root, "native-to-hf", ["--load", str(resaved)],
                      "hf_out", hf_base=root / "hf_in")
        reloaded = transformers.AutoModelForCausalLM.from_pretrained(
            str(hf_out)).eval()
        orig, new = hf.state_dict(), reloaded.state_dict()
        for k, v in orig.items():
            if k.endswith("rotary_emb.inv_freq"):
                continue
            np.testing.assert_allclose(
                new[k].float().numpy(), v.float().numpy(), atol=1e-6,
                err_msg=k)
