"""Test bootstrap: hermetic 8-device CPU mesh.

The reference's distributed tests need real GPUs under torchrun
(tests/test_utilities.py:6-30 in the reference).  Here every parallelism
test runs on CPU with 8 virtual XLA devices, so the full tp/pp/dp/sp test
matrix is hermetic (SURVEY.md §4).
"""

import os

# The suite needs an 8-device CPU mesh.  XLA_FLAGS and JAX_PLATFORMS are
# read at backend initialization (first jax.devices()), so setting them
# here, before jax is imported, is early enough.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache — OFF unless JAX_TEST_COMPILE_CACHE=<dir>
# opts in, whatever utils/compile_cache.py or the environment would do:
# tier-1 calls the entry points in-process.  A warm cache cuts the suite
# from ~9-16 min to well under that, BUT on this jax/XLA version (0.9.0,
# XLA:CPU) deserialized executables of collective-heavy shard_map
# programs intermittently SIGABRT at their first host fetch (observed
# 3/4 warm full-suite runs, moving between tests/models/test_moe.py and
# tests/parallel/test_ring_attention.py; cold runs never abort).  Until
# that upstream bug is fixed, correctness of a default `pytest tests/`
# run beats speed.
_cache_dir = os.environ.get("JAX_TEST_COMPILE_CACHE", "")
if _cache_dir and _cache_dir != "off":
    jax.config.update("jax_compilation_cache_dir",
                      os.path.abspath(_cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
else:
    jax.config.update("jax_enable_compilation_cache", False)

import gc  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


# Every XLA:CPU executable a process holds maps a dozen regions of memory,
# jax keeps every executable it ever compiled, and Linux gives a process
# vm.max_map_count regions, 65 530 by default: past it the next
# compilation's mmap fails and the worker dies of a segmentation fault
# inside ``backend_compile_and_load``, in whatever test happens to compile
# next.  A worker of the six-worker tier-1 run that draws the serving and
# generation tests reached 65 230 (PR 51: three whole runs of three lost
# a worker that way, each in another test).  Dropping jax's caches frees
# the executables and their regions (2910 -> 600 for 200 small ones);
# between two modules nothing that a test still holds is lost but compile
# time.
_MAPPED_REGIONS_HIGH = 44_000


@pytest.fixture(scope="module", autouse=True)
def _bounded_mapped_regions():
    yield
    try:
        with open("/proc/self/maps") as f:
            regions = sum(1 for _ in f)
    except OSError:      # no procfs: nothing to count, nothing to bound
        return
    if regions > _MAPPED_REGIONS_HIGH:
        jax.clear_caches()
        gc.collect()


# ---------------------------------------------------------------------------
# Incremental marker: later steps of a pipeline test skip after an earlier
# failure (parity with reference tests/conftest.py:23-60).
# ---------------------------------------------------------------------------

_incremental_failures: dict = {}


def pytest_runtest_makereport(item, call):
    if "incremental" in item.keywords and call.excinfo is not None:
        cls = item.getparent(pytest.Class)
        if cls is not None:
            _incremental_failures.setdefault(cls.name, item.name)


def pytest_runtest_setup(item):
    if "incremental" in item.keywords:
        cls = item.getparent(pytest.Class)
        if cls is not None and cls.name in _incremental_failures:
            pytest.xfail(
                f"previous step failed ({_incremental_failures[cls.name]})"
            )


# The markers (``incremental``, ``tpu``, ``chaos``, ``slow``) are registered
# once, in pyproject.toml's ``[tool.pytest.ini_options]``.


# The heavyweight end-to-end tests, each dominated by XLA compiles of
# large sharded programs (the persistent compile cache is off, see above).
# Tier-1 is the driver's command: ``-m 'not slow'`` over six xdist workers
# (``--dist load``) under a 1470 s limit, so a test here costs none of
# that budget and guards nothing a PR is held to.  Centralized here
# instead of per-file markers so the list mirrors `--durations` output
# directly.
_SLOW_TESTS = {
    "test_int8_training_composes_with_pipeline",
    "test_two_process_dryrun",
    "test_train_step_with_context_parallelism",
    "test_train_step_with_zigzag_layout",
    "test_moe_train_step_ep",
    "test_moe_through_pipeline",
    "test_moe_model_forward_and_grad",
    "test_pipeline_matches_reference",
    "test_windowed_remat_matches_unwindowed",
    "test_full_train_step_dp_sharded_batch_argument",
    "test_retrieval_loss_trains",
    "test_pretrain_ict_entrypoint",
    "test_pretrain_bert_entrypoint",
    "test_pretrain_t5_entrypoint",
    "test_zero1_state_equivalence",
    "test_save_load_resume_equivalence",
    "test_memory_scales_with_T_not_quadratically",
    "test_streamed_pipeline_memory_fits_model",
    "test_windowed_remat_bounds_memory_at_large_M",
    "test_pretrain_end_to_end",
    "test_pretrain_resume",
    "test_droppath_training_smoke_grads_finite",
    "test_tp_loss_and_grads_match_unsharded",
    "test_dense_index_retrieves_own_context",
    "test_tp_sharded_loss_and_grads_match_unsharded",
    "test_pretrain_t5_entrypoint_tensor_parallel",
    "test_pretrain_bert_entrypoint_tensor_parallel",
    "test_windowed_remat_bounds_memory_vpp2_large_M",
    # full-scale-dims trust path: the whole incremental chain is slow-
    # marked together so the fast tier never skips a stage another stage
    # depends on
    "test_7bw_synthetic_weights_exist",
    "test_7bw_meta_to_native",
    "test_7bw_hf_to_native",
    "test_7bw_meta_and_hf_paths_agree",
    "test_7bw_reshard_tp8_logit_parity",
    "test_7bw_native_to_hf_roundtrip",
    "test_pretrain_ict_entrypoint_tensor_parallel",
    # compound-fault chaos soak: minutes of kill/rebuild cycles; the CI
    # chaos job (`pytest -m chaos`) still runs it
    "test_chaos_soak_compound_faults",
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        base = item.name.split("[")[0]
        if base in _SLOW_TESTS:
            matched.add(base)
            item.add_marker(pytest.mark.slow)
    # A renamed/removed test must not silently linger here, eroding the
    # fast-tier guarantee.  Only enforce on full-suite collections (a
    # path-restricted run legitimately collects a subset).
    stale = _SLOW_TESTS - matched
    # "Full suite" = every positional arg is this tests/ dir or an
    # ancestor of it (subdirectory/file runs legitimately collect subsets).
    tests_root = os.path.dirname(os.path.abspath(__file__))
    def _covers_suite(arg):
        p = os.path.abspath(arg.split("::")[0])
        return os.path.isdir(p) and (
            p == tests_root or tests_root.startswith(p + os.sep))
    full_suite = (all(_covers_suite(a) for a in config.args)
                  and not config.getoption("ignore", None)
                  and not config.getoption("ignore_glob", None)
                  and not config.getoption("deselect", None))
    if stale and full_suite:
        raise pytest.UsageError(
            f"_SLOW_TESTS entries matched no collected test: {sorted(stale)}"
            " — remove or rename them in tests/conftest.py")
