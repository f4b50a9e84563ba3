"""The small rules PR 21 (chip bring-up) put in one place each: where the
compile cache goes, which native library loads, how a mesh is assigned,
and where a serving pool is born."""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

from megatron_llm_tpu.config import ParallelConfig, tiny_config
from megatron_llm_tpu.parallel import mesh as mesh_lib
from megatron_llm_tpu.utils import compile_cache, native


_KEY_OPTIONS = ("jax_compilation_cache_include_metadata_in_key",
                "jax_traceback_in_locations_limit")


@pytest.fixture
def key_options():
    """What ``enable_compile_cache`` sets for the process, put back."""
    before = {k: getattr(jax.config, k) for k in _KEY_OPTIONS}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


def test_compile_cache_env_var_wins_and_sets_nothing(monkeypatch, tmp_path,
                                                     key_options):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_keys_on_names_not_on_where_the_code_lies(
        monkeypatch, tmp_path, key_options):
    """Scope and kernel names are part of the key (a profile never shows
    the names of an executable compiled before they changed), and they
    reach the compiled operation's ``op_name``, which a TPU profile
    shows; no file, line or caller is: another checkout, or an edit that
    shifts lines, finds the same entry."""
    from megatron_llm_tpu.ops import activations

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key

    def caller(x):
        with jax.named_scope("mlp"):
            return activations.gelu(x)

    lowered = jax.jit(caller).lower(np.ones(4, np.float32))
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    assert '"jit(caller)/mlp/tanh"' in asm
    assert ".py" not in asm and "callsite" not in asm
    assert 'op_name="jit(caller)/mlp/' in lowered.compile().as_text()


@pytest.mark.parametrize("backend,want", [
    ("cpu", None), ("tpu", str(compile_cache._CHECKOUT_CACHE))])
def test_compile_cache_default_dir(monkeypatch, backend, want):
    """Unset: ``<checkout>/.jax_cache`` — fixed, under the repo root —
    except on the CPU backend, where the cache stays off."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (want or before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert compile_cache._CHECKOUT_CACHE.name == ".jax_cache"
    assert (compile_cache._CHECKOUT_CACHE.parent / "chip_smoke.py").exists()


def test_native_library_is_keyed_on_its_source(tmp_path):
    """A binary is only ever loaded for the source it was built from: a
    stale ``.so`` — any name, any mtime — is never picked up."""
    src, lib = tmp_path / "h.cpp", tmp_path / "libh.so"
    (tmp_path / "libh.so").write_bytes(b"stale, not even an ELF file")
    src.write_text('extern "C" int answer() { return 1; }\n')
    one = native.compile_and_load(src, lib)
    if one is None:
        pytest.skip("no C++ toolchain")
    assert one.answer() == 1
    src.write_text('extern "C" int answer() { return 2; }\n')
    assert native.compile_and_load(src, lib).answer() == 2
    built = sorted(p.name for p in tmp_path.glob("libh.*.so"))
    assert len(built) == 2 and "libh.so" not in built


def test_build_mesh_lets_the_assignment_fail(monkeypatch):
    """No naive reshape in place of a failed topology-aware assignment."""
    from jax.experimental import mesh_utils

    def refuse(shape, devices=None, **kw):
        raise ValueError("cannot place this shape")

    monkeypatch.setattr(mesh_utils, "create_device_mesh", refuse)
    with pytest.raises(ValueError, match="cannot place"):
        mesh_lib.build_mesh(ParallelConfig(tensor_parallel=2))


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_block_pool_is_born_on_its_submesh(devices, kv_quant):
    """A replica's pool is allocated under its target sharding, on its
    own devices — never on the default device first."""
    from megatron_llm_tpu.models import sharding as shard_lib
    from megatron_llm_tpu.serving.block_pool import BlockPool

    cfg = tiny_config(kv_cache_quant=kv_quant)
    mesh = mesh_lib.build_mesh(ParallelConfig(tensor_parallel=2),
                               devices=devices[2:4])
    pool = BlockPool(cfg, 6, 8, mesh=mesh)
    k_spec, _ = shard_lib.kv_pool_specs(cfg, mesh)
    for leaf, spec in zip(jax.tree.leaves(pool.k_pool),
                          jax.tree.leaves(
                              k_spec, is_leaf=lambda x: isinstance(
                                  x, jax.sharding.PartitionSpec))):
        assert leaf.sharding.device_set == set(devices[2:4])
        assert leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                              leaf.ndim)
        assert not np.asarray(leaf).any()


@pytest.mark.parametrize("tp,stays_on", [(1, True), (2, False), (8, True)])
def test_submesh_turns_the_persistent_cache_off(tp, stays_on):
    """An executable over some but not all of the chips, read back from
    the cache, halts the TPU (PR 21): making such a mesh turns the cache
    off; a mesh over all the devices, or one, leaves it alone."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", True)
    try:
        mesh_lib.build_mesh(ParallelConfig(tensor_parallel=tp))
        assert jax.config.jax_enable_compilation_cache == stays_on
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
