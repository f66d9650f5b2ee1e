"""Device check and the table of peaks.

The peaks are the chip's published ones, keyed by ``device_kind`` as JAX
reports it. A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]  # the same chip under its other name


class NoAccelerator(SystemExit):
    """Raised (exit code 2) when JAX shows no TPU or too few chips."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "benchmarks/lib/device.py with its source before measuring on it"
        ) from None


def device_record(chips: int, rehearsal: bool) -> dict:
    """``{platform, kind, count}`` as JAX reports them. Exits 2, having built
    nothing, when the run is not a rehearsal and JAX shows no TPU or fewer
    chips than the cell asks for."""
    import jax

    devices = jax.devices()
    record = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        if record["platform"] != "cpu":
            raise NoAccelerator(2)
        return record
    if record["platform"] != "tpu" or record["count"] < chips:
        import sys

        print(
            f"# benchmark: JAX shows {record}, the cell needs {chips} TPU "
            "chip(s); nothing was built and no result is printed",
            file=sys.stderr,
        )
        raise NoAccelerator(2)
    peaks_for(record["kind"])  # an unknown chip is an error before any work
    return record


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell uses."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
