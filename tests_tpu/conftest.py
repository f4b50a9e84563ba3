"""Real-TPU tier bootstrap: fail fast when no TPU answers.

The test modules here call ``jax.devices()`` at import, i.e. during
collection, where a backend that cannot start would take the whole
``pytest`` run down with it.  Probe the backend with bench.py's bounded
subprocess probe at conftest import and ignore this directory's
collection when no TPU answers, so only the hardware tier is skipped (a
bare ``pytest`` from the repo root still runs the CPU tiers and keeps
their exit status).  The probe child exits before anything here touches
JAX: a chip belongs to one process at a time.
"""

import os
import sys
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import bench  # noqa: E402  (repo-root module; same probe as the driver)

_PROBE_TIMEOUT = int(os.environ.get("TPU_PROBE_TIMEOUT_S", "240"))

collect_ignore_glob: list = []

try:
    _kind, _count = bench._detect_device(timeout_s=_PROBE_TIMEOUT)
    if "tpu" not in _kind.lower():
        raise RuntimeError(f"first device is {_kind!r}, not a TPU")
except (TimeoutError, RuntimeError, OSError) as e:
    warnings.warn(
        f"tests_tpu: skipping the hardware tier — {e}", stacklevel=1)
    collect_ignore_glob = ["test_*.py"]
