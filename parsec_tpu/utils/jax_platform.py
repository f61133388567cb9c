"""Which platform a run was explicitly started on.

JAX with libtpu installed and no chip falls back to the CPU platform
with a log line; code that then picks a CPU-only behaviour from
``jax.default_backend()`` (Pallas interpret mode, a CPU-only device
registry, a shrunken benchmark) hides the missing chip. The decisions
that used to read the backend read the REQUEST instead: the
``jax_platforms`` config, which JAX fills from ``JAX_PLATFORMS`` at
import and which tests and CPU dry runs set to ``cpu``. Reading it
initialises no backend.
"""

from __future__ import annotations


def cpu_requested() -> bool:
    """Did the run ask JAX for the CPU platform first? (False when it
    asked for none and JAX picks on its own.)"""
    import jax
    first = (jax.config.jax_platforms or "").split(",")[0]
    return first.strip().lower() == "cpu"


def pin_cpu_platform() -> None:
    """Put THIS process and every process it starts on the CPU platform.
    Raises when an accelerator backend is already up: the chip would
    stay claimed whatever the config says afterwards."""
    import os

    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() != "cpu":
        raise RuntimeError(
            f"cannot pin the CPU platform: the {jax.default_backend()} "
            "backend is already initialised in this process")
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
