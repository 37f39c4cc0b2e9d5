"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.devices()[0].device_kind``. A device that is not here is an error:
no roofline share is computed against a guessed peak."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDevice(ValueError):
    pass


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in the peak table "
            f"({sorted(PEAKS)}); add its published peaks with their source"
        ) from None
