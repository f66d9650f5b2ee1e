"""Which device a run is on, said in every record.

A number measured on the CPU backend is not a device number, so every
script that prints a record names the device JAX gave it, and a script
whose numbers are device numbers refuses to run without a TPU unless the
caller asked for the CPU from OUTSIDE (``JAX_PLATFORMS=cpu``: tests, CI
smokes and dry runs). Nothing here selects a platform.
"""
from __future__ import annotations

import os

__all__ = ["require_accelerator"]


def require_accelerator(what: str) -> dict:
    """``{platform, device_kind, device_count}`` as JAX reports them, for
    the caller's record; exits nonzero when JAX found no TPU and the
    environment did not explicitly ask for ``JAX_PLATFORMS=cpu``."""
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if record["platform"] != "tpu" and not asked_cpu:
        raise SystemExit(
            f"{what}: JAX found no TPU ({record}); a CPU run must be asked "
            "for from outside with JAX_PLATFORMS=cpu"
        )
    return record
