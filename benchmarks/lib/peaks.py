"""Published peaks, keyed by `jax.devices()[0].device_kind`. A device
that is not here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 16 GB HBM at 819 GB/s per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peak for device kind %r; add it to "
                       "benchmarks/lib/peaks.py with its source"
                       % (device_kind,)) from None
