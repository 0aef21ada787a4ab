"""Published peaks of one chip, keyed by the ``device_kind`` JAX
reports. A kind that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM, 16 GB HBM per chip
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "chipbench/peaks.py has no peaks for device kind %r; add a "
            "row with its source" % (device_kind,)) from None
