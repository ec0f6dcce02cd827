"""Pallas TPU kernels: each package holds ``kernel.py`` (the
``pallas_call``), ``ops.py`` (the jitted public wrapper) and ``ref.py``
(a pure-jnp oracle)."""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """Run kernel bodies in the Pallas interpreter only on the CPU
    backend (tests); every accelerator backend gets the compiled
    kernel."""
    return jax.default_backend() == "cpu"
