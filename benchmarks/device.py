"""The device as JAX reports it, its published peaks, its memory and the
compile accounting (``CompileClock`` and the cache rule are copied from
``chip_smoke.py``)."""

from __future__ import annotations

import json
import os
from pathlib import Path

HERE = Path(__file__).resolve().parent


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def describe(chips: int, cpu_rehearsal: bool) -> dict:
    """→ ``{"platform", "kind", "count"}`` of the first ``chips``
    devices.  Without ``cpu_rehearsal`` anything but a TPU is an error:
    there is no CPU fallback."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if cpu_rehearsal:
        if platform != "cpu":
            raise NoAccelerator("--cpu-rehearsal runs on the CPU backend only")
    elif platform != "tpu":
        raise NoAccelerator(f"no TPU: jax.devices()[0].platform is "
                            f"{platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, jax sees "
                            f"{len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device that is not in
    ``peaks.json`` is an error, not a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmarks/peaks.json")
    return table[device_kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices
    (0 where the backend reports none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def enable_compile_cache(root: Path) -> str | None:
    """JAX's persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache`` —
    through the program's own ``utils/compile_cache.py``, which fixes the
    same path.  Everything is cached, however quick its compile."""
    import jax
    from megatron_llm_tpu.utils.compile_cache import (
        enable_compile_cache as program_cache)

    where = program_cache()
    if (where is not None and not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and Path(where) != Path(root) / ".jax_cache"):
        raise RuntimeError(f"the program keeps its compile cache in {where}, "
                           f"outside this checkout ({root})")
    if where is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


class CompileClock:
    """Seconds jax spent tracing, lowering and compiling, and how many
    executables the backend compiled (a read from the persistent cache
    counts as its compile): the window must see none."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1
