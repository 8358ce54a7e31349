"""Peak rates of the chips the benchmark knows, keyed by ``device_kind`` as JAX
reports it. A device that is not here is an error, never a default."""

# Google Cloud documentation, "TPU v5e" (system architecture table): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip.
_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9, "source": "cloud.google.com/tpu/docs/v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates known for device_kind {device_kind!r}: add it to benchmarks/lib/peaks.py "
                       f"with its source (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
