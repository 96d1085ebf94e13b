"""JAX backend selection for driver entry points.

- :func:`force_cpu` — pin the host-CPU backend (optionally as an
  N-virtual-device mesh). For tests, multichip dryruns and CPU
  reference legs.
- :func:`auto_backend` — what every example driver calls first: an
  explicit ``JAX_PLATFORMS=cpu`` is honored; otherwise the process must
  find a TPU, and a run without one FAILS instead of continuing on the
  CPU under an accelerator's name.
"""

from __future__ import annotations

import os
from typing import Optional


def force_cpu(n_devices: Optional[int] = None):
    """Pin jax to the host-CPU backend. ``n_devices`` requests that many
    virtual CPU devices (honored only if the flag is not already set).
    Call before the first jax compute. Returns the jax module."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def auto_backend():
    """Example-driver entry guard. Under ``JAX_PLATFORMS=cpu`` pin the
    CPU; otherwise initialize the backend IN THIS PROCESS (a chip
    belongs to one process — no probe child), raise unless it is a TPU,
    and place the persistent compile cache. Returns the jax module."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return force_cpu()

    import jax

    from ibamr_tpu import obs

    # the first jax.devices() is the process reaching the chip: 9-22 s
    # of every set-up on the benchmark's machines
    with obs.span("setup/backend_init"):
        platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"no TPU: jax found platform {platform!r}. Set "
            f"JAX_PLATFORMS=cpu to run on the CPU on purpose.")
    from ibamr_tpu.serve.aot_cache import enable_persistent_cache

    enable_persistent_cache(jax)
    return jax
