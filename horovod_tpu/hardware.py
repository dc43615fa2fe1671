"""Accelerator peak-FLOPs lookup for MFU accounting.

One tiny, dependency-free table shared by the live telemetry
(``hvd_step_mfu`` in :mod:`horovod_tpu.callbacks`), the perf sentry and
``bench.py`` — per-chip peak dense bf16 FLOPs by ``jax.Device.device_kind``
(public spec sheets). ``HOROVOD_PEAK_FLOPS`` overrides the table, which is
also how CPU test runs get a real (if synthetic) MFU denominator.
"""

from __future__ import annotations

# Peak dense bf16 FLOPs per chip, keyed by the exact ``device_kind``
# string the runtime reports; the MFU denominator. "TPU v5 lite" is what
# a v5e reports under jax 0.9.0 / libtpu 0.0.34 (chip_smoke.py prints it).
PEAK_BF16_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def device_info():
    """The device a number was measured on, as jax reports it. Every
    benchmark JSON line carries these three fields, so no rate can be read
    without the platform, chip kind and chip count it came from."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def peak_flops_for_kind(device_kind):
    """Peak per-chip FLOPs for a ``device_kind`` string: the table entry
    on an exact match, 0.0 ("no MFU") for the CPU. Any other kind raises
    — a chip the table does not list has no peak to assume, and an MFU
    against a guessed one would be wrong without saying so."""
    kind = str(device_kind)
    if kind in PEAK_BF16_FLOPS:
        return float(PEAK_BF16_FLOPS[kind])
    if kind == "cpu":
        return 0.0
    raise ValueError(
        f"no peak FLOPs known for device kind {kind!r}: add it to "
        "horovod_tpu.hardware.PEAK_BF16_FLOPS with its source, or set "
        "HOROVOD_PEAK_FLOPS to the per-chip peak")


def peak_flops_per_chip(config=None, device=None):
    """The MFU denominator: ``config.peak_flops`` (HOROVOD_PEAK_FLOPS)
    when set, else the table entry for ``device`` (default: the first
    jax device). Returns 0.0 on the CPU — the callers treat 0 as "no MFU
    available", never divide by it — and raises for a chip the table
    does not know."""
    if config is not None and getattr(config, "peak_flops", 0.0) > 0.0:
        return float(config.peak_flops)
    if device is None:
        import jax
        device = jax.devices()[0]
    return peak_flops_for_kind(device.device_kind)
