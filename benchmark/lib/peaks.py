"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. A device that is not here is an error, never a default.

"TPU v5 lite": Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s
chip-to-chip interconnect."""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, too few chips, or a chip without peaks."""


def require_chips(chips: int):
    """The first ``chips`` devices of a TPU whose peaks are known; raises
    :class:`NoChip` otherwise. Tests replace this one name."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no accelerator: JAX reports platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports "
                     f"{len(devices)}")
    if devices[0].device_kind not in PEAKS:
        raise NoChip(f"no peaks known for device_kind "
                     f"{devices[0].device_kind!r}; have {sorted(PEAKS)}")
    return devices[:chips]
