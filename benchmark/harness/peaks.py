"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

An accelerator that is not in the table is an error, never a default.
bf16 FLOP/s copied from the program's ``bench._PEAK_BF16`` (public spec
sheets); HBM bytes/s from the same sheets (Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s; "TPU v4": 275, 1200;
"TPU v5p": 459, 2765; "TPU v6e" / Trillium: 918, 1640).
"""

PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_s": 1200e9},
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v5p": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
    "TPU v6e": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
}


def peak(device_kind, what):
    """``what`` in {"bf16_flops", "hbm_bytes_per_s"} of ``device_kind``."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"device kind {device_kind!r} has no entry in "
            "benchmark/harness/peaks.py; add its published peaks first"
        )
    return PEAKS[device_kind][what]
