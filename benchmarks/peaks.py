"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a share of a peak nobody looked up is not a measurement."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def require_tpu(chips):
    """The result's ``device`` entry and the chip's peaks. Raises
    ``SystemExit`` — before anything is built and with nothing printed on
    standard output — unless JAX's devices are ``chips`` TPUs of a kind in
    :data:`PEAKS`."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{first.platform!r} ({first.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), JAX "
                         f"found {len(devices)}")
    if first.device_kind not in PEAKS:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{first.device_kind!r}; add them to peaks.py with "
                         "their source")
    return ({"platform": first.platform, "kind": first.device_kind,
             "count": len(devices)}, PEAKS[first.device_kind])
