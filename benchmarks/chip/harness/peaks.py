"""Published peaks of one chip, keyed by the ``device_kind`` string JAX
reports.  Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s).  A kind not listed is an error,
never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no peaks listed for device kind {device_kind!r}; add its "
            "published peaks to harness/peaks.py") from None
