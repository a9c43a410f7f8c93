"""Device discovery (reference: utils.py:6-8 filtered ``device_lib.list_local_devices``
for GPUs; the TPU-native equivalent asks the JAX runtime)."""

from __future__ import annotations

from typing import List, Optional

import jax


def get_available_devices(platform: Optional[str] = None) -> List[str]:
    """Return device name strings, e.g. ``['TPU:0', 'TPU:1']``.

    ``platform`` filters like the reference filtered ``device_type == 'GPU'``.
    """
    devices = jax.devices() if platform is None else jax.devices(platform)
    return [f"{d.platform.upper()}:{d.id}" for d in devices]


def apply_platform_env() -> None:
    """Apply ``JAX_PLATFORMS`` through jax's config, so it holds even when
    jax was imported (and its platform choice read) before the variable was
    set — backend initialization itself is lazy, so the config route still
    works then. A platform jax refuses raises: running on another backend
    than the one asked for is never what the caller wanted. Call at the top
    of any standalone driver/script; the CLI does this automatically."""
    import os

    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms:
        jax.config.update("jax_platforms", platforms)
