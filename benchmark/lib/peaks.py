"""Peaks of the chips the benchmark knows, keyed by jax's device_kind,
and the bytes the audit delta sweep needs.  A device that is not in the
table is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page): one
# chip, 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                    "int8_ops": 393e12, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                "int8_ops": 393e12, "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}")
    return DEVICE_PEAKS[device_kind]


def _pow2(n: int, minimum: int = 1) -> int:
    b = max(1, minimum)
    while b < n:
        b *= 2
    return b


def delta_sweep_bytes(sizes: dict, rows: int) -> int:
    """Bytes one delta sweep of `rows` churned rows has to move through
    HBM, from the configuration's sizes alone (whatever implements it):
    read the churned rows' packed columns, read every constraint's
    parameters, write the churned columns of the int8 candidate mask,
    and read back what the capped result needs, per constraint a count
    and `cap` candidate indices (int32)."""
    c = sizes["templates"]
    row_bytes = sizes["packed_row_bytes"]
    param_bytes = sizes["constraint_param_bytes"]
    cap = sizes["violations_limit"]
    return (rows * row_bytes            # churned rows in
            + c * param_bytes           # constraint parameters in
            + c * rows                  # mask cells out, int8
            + c * (1 + cap) * 4)        # counts + kept candidates out
