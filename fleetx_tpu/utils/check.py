"""Startup device check (reference ``utils/check.py:250-277`` — the GPU
check becomes a device check; the supported jax is the one
``requirements.txt`` pins)."""

from __future__ import annotations

import os

from fleetx_tpu.utils.log import logger


def check_devices(expect_tpu: bool = False) -> None:
    """Log the device inventory and refuse a platform nobody asked for.

    A backend that does not initialise raises (``jax.devices()`` does). A
    config that names ``device: tpu`` on another platform raises too —
    unless the operator asked for the CPU by name (``JAX_PLATFORMS=cpu``,
    which is how the tests and every CPU recipe run): a run that quietly
    lands on the CPU trains at a thousandth of the speed and says nothing.
    """
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    logger.info("devices: %d x %s (%s)", len(devices), platform,
                devices[0].device_kind)
    if expect_tpu and platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise RuntimeError(
            f"config requests device: tpu but the backend is {platform!r}; "
            f"set JAX_PLATFORMS=cpu to run on the CPU on purpose")


def check_config(cfg: dict) -> None:
    """Run the startup checks for a parsed config."""
    glb = dict(cfg.get("Global") or {})
    check_devices(expect_tpu=str(glb.get("device", "")).lower() == "tpu")
