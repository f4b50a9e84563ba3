"""Real-TPU tier bootstrap: fail fast when no TPU answers.

The test modules here call ``jax.devices()`` at import, i.e. during
collection, where a backend that cannot start would take the whole
``pytest`` run down with it.  Probe the backend in a bounded subprocess
at conftest import and ignore this directory's collection when no TPU
answers, so only the hardware tier is skipped (a bare ``pytest`` from the
repo root still runs the CPU tiers and keeps their exit status).  The
probe child exits before anything here touches JAX: a chip belongs to one
process at a time.
"""

import os
import subprocess
import sys
import warnings

_PROBE_TIMEOUT = int(os.environ.get("TPU_PROBE_TIMEOUT_S", "240"))


def _first_device_kind(timeout_s: int) -> str:
    """The first device's kind, asked of a child with a hard timeout: a
    backend that hangs at start-up hangs *inside a C call*."""
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].device_kind)"],
            capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise TimeoutError(f"device probe exceeded {timeout_s}s "
                           "(accelerator unreachable?)")
    lines = (out.stdout or "").strip().splitlines()
    if out.returncode != 0 or not lines:
        tail = (out.stderr or "").strip().splitlines()[-1:] or ["?"]
        raise RuntimeError(f"device probe failed: {tail[0]}")
    return lines[-1]


collect_ignore_glob: list = []

try:
    _kind = _first_device_kind(_PROBE_TIMEOUT)
    if "tpu" not in _kind.lower():
        raise RuntimeError(f"first device is {_kind!r}, not a TPU")
except (TimeoutError, RuntimeError, OSError) as e:
    warnings.warn(
        f"tests_tpu: skipping the hardware tier — {e}", stacklevel=1)
    collect_ignore_glob = ["test_*.py"]
