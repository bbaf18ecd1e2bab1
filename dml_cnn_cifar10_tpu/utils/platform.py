"""Backend-platform selection helpers.

The program never picks a platform for itself: JAX takes the TPU where
there is one, and ``JAX_PLATFORMS=cpu`` in the environment holds it to
the CPU. :func:`force_cpu` exists for what the environment variable
cannot do alone: the test suite, the multichip dry run and the
multi-process simulation scripts need N *virtual* CPU devices to build a
mesh on, which is an ``XLA_FLAGS`` setting that must land before the
backend initializes. Importing ``jax`` (without touching devices) is
safe here — the backend only initializes on first use.
"""

from __future__ import annotations

import os
import re
from typing import Optional

_COUNT_RE = re.compile(r"--xla_force_host_platform_device_count=(\d+)")


def on_tpu() -> bool:
    """The one platform switch the Pallas kernels consult
    (``ops/optimizer.py``, ``ops/flash_attention.py``): compiled Mosaic
    on a TPU backend; off it, the Pallas interpreter (flash, CPU tests)
    or the XLA expression (fused update).
    Callers go through the module (``platform_lib.on_tpu()``) so
    ``tests/test_tpu_lowering.py`` can patch this one name to
    cross-lower the chip branch on the CPU mesh."""
    import jax
    return jax.default_backend() == "tpu"


def accelerator_expected() -> bool:
    """True when this process's JAX is headed for an accelerator, read
    WITHOUT initializing a backend — so a process that must stay off the
    chip (a chip belongs to one process at a time) can still ask: the
    requested platforms (config, else ``JAX_PLATFORMS``), else whether
    libtpu is installed for jax to find."""
    import importlib.util

    import jax

    plats = (jax.config.jax_platforms
             or os.environ.get("JAX_PLATFORMS") or "").lower()
    tokens = {t.strip() for t in plats.split(",") if t.strip()}
    if tokens:
        return tokens != {"cpu"}
    return importlib.util.find_spec("libtpu") is not None


def force_cpu(virtual_devices: Optional[int] = None) -> None:
    """Pin the CPU backend, optionally with N virtual devices.

    Must be called before anything initializes the XLA backend
    (``jax.devices()``, any computation, ``jax.distributed.initialize``).
    A pre-existing device-count flag with a DIFFERENT value is an error —
    silently keeping it would strand callers on the wrong mesh size.
    """
    if virtual_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        m = _COUNT_RE.search(flags)
        if m is None:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{virtual_devices}").strip()
        elif int(m.group(1)) != virtual_devices:
            raise RuntimeError(
                f"XLA_FLAGS already pins "
                f"{m.group(1)} host-platform devices; caller asked for "
                f"{virtual_devices}. Unset XLA_FLAGS or reconcile.")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
