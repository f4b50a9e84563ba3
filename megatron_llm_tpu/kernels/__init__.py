"""Pallas TPU kernels."""

import jax


def default_interpret() -> bool:
    """``interpret`` for a kernel call that does not pass one: compiled
    by Mosaic on a TPU, the Pallas interpreter everywhere else (the CPU
    test mesh).  The kernels read it through this package, so a
    deviceless compile for a described TPU steers every kernel at once
    by patching this one name; ``chip_smoke.py`` checks the executable
    for ``tpu_custom_call`` rather than trusting the answer."""
    return jax.default_backend() != "tpu"
