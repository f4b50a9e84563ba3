"""Shared compile-on-demand + ctypes loader for the native (C++) helpers.

Used by data/index_helpers.py and tokenizer/native_bpe.py so the g++
invocation, staleness check and failure logging live in one place.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import subprocess
from pathlib import Path
from typing import Optional

logger = logging.getLogger(__name__)


def compile_and_load(src: Path, lib: Path,
                     timeout: int = 120) -> Optional[ctypes.CDLL]:
    """Compile ``src`` if this version of it has no library yet, then
    CDLL-load it.

    The library's file name carries a hash of the source
    (``libfoo.<sha>.so`` beside ``lib``), so a binary is only ever loaded
    for the source it was built from.  Built libraries are git-ignored,
    and a checkout copied from disk brings stale ones along with
    arbitrary mtimes: what runs is built from what git would commit.

    Returns None (with an info log — the fallback path changes behavior
    like RNG streams or throughput, so it must be visible) when the
    toolchain or the source is unavailable.  The compile writes to a
    temp name and renames, so parallel workers racing the build load a
    complete library or compile their own.
    """
    try:
        digest = hashlib.sha256(src.read_bytes()).hexdigest()[:12]
    except OSError:
        logger.info("native helper %s: source unavailable; using the "
                    "Python fallback", src.name)
        return None
    lib = lib.with_suffix(f".{digest}.so")
    if not lib.exists():
        tmp = lib.with_suffix(f".tmp{id(object())}.so")
        try:
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-o", str(tmp), str(src)],
                check=True, capture_output=True, timeout=timeout,
            )
            tmp.replace(lib)  # atomic publish
        except Exception:
            tmp.unlink(missing_ok=True)
            logger.info("native helper %s: compile unavailable; using "
                        "the Python fallback", src.name)
            return None
    try:
        return ctypes.CDLL(str(lib))
    except OSError:
        logger.info("native helper %s: load failed; using the Python "
                    "fallback", lib.name)
        return None
