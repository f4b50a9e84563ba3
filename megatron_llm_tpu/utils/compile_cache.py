"""Where the program keeps XLA's persistent compilation cache.

Every entry point (finetune.py, pretrain_{bert,t5,ict}.py, the generation
server CLI, chip_smoke.py, the benchmark) calls
:func:`enable_compile_cache` once, before its first compile.  A whole-step
program of a 7B-width model compiles for a quarter of a minute to a
minute and a half on a TPU, and a process that starts with no compiled
code pays that for every jitted step.
"""

from __future__ import annotations

import logging
import os
from pathlib import Path
from typing import Optional

import jax

from ..obs import compile as obs_compile

logger = logging.getLogger(__name__)

# fixed, never a temp name, pid or time: the directory is part of the
# cache key, so one that moves never hits (listed in .gitignore)
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent cache on and return its directory, or None
    where it stays off.

    ``JAX_COMPILATION_CACHE_DIR`` decides when it is set — jax reads it
    itself and nothing is set in code, so the cache lands there and
    nowhere else.  Otherwise the directory is ``<checkout>/.jax_cache``.
    On the CPU backend the cache stays off unless the variable asks for
    it: XLA:CPU executables of collective-heavy ``shard_map`` programs
    intermittently abort when read back from a warm cache (jax 0.9.0;
    tests/conftest.py), and nothing there compiles for minutes.

    Cache on or off, the process's compilations are recorded from here on
    (``obs/compile.py``): what a start compiled, loaded or only traced
    again is read from those records.
    """
    obs_compile.install()
    # The cache's key leaves an operation's metadata out by default, so a
    # program whose scope names changed (jax.named_scope, a kernel's name)
    # would be handed the executable compiled before the change — and a
    # device profile of it would show the old names, or none.  The names
    # are what profiles are read by (docs/observability.md): key on them.
    # Metadata also holds source locations — by default a Python traceback
    # of absolute paths and line numbers for every operation — so a cache
    # filled from one checkout would miss from another, and an edit that
    # shifts a line in any caller would miss everywhere.  So operations
    # are lowered with their name path and no source location: the key
    # holds the computation and its names, and nothing of where the code
    # lies.  The price: a profile or an HLO dump names an operation by its
    # path (``jit(step)/attention/flash_fwd``), not by file and line.
    # (``jax_include_full_tracebacks_in_locations=False`` would keep one
    # frame, but cuts the name path from the compiled operation's
    # ``op_name``: a TPU profile then shows ``dot_general``, PR 24.)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)


def disable_compile_cache(why: str) -> None:
    """Turn the persistent cache off for the rest of this process, loudly.

    For the one case where a cached executable is wrong: a program over
    some but not all of the process's chips (a replica's submesh), read
    back from the cache, halts the TPU at its first collective ("Core
    halted unexpectedly ... Invalid logical z: enhanced-barrier", jax
    0.9.0 / libtpu 0.0.34; PR 21, four v5e chips: the same two-replica
    run passed compiling fresh and lost a replica with a warm cache,
    same machine or another).  Programs over all the chips, or one, load
    fine.  ``parallel/mesh.py:build_mesh`` calls this where such a mesh
    is made."""
    if not jax.config.jax_enable_compilation_cache:
        return
    from jax.experimental.compilation_cache import compilation_cache

    logger.warning("persistent compile cache off for this process: %s", why)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
