"""The benchmark's own table of device peaks, keyed by ``device_kind``.

Kept here and not read from ``mxnet_tpu.goodput.PEAK_FLOPS_BY_KIND`` so
that no change to the program moves the yardstick (the numbers were
copied from that table's v5e row and from the source below).

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e per-chip peaks)",
    },
}


def peaks_for(device_kind):
    """The peak row of a device kind. An unknown kind is an error, not a
    default: a share of somebody else's peak is not a number."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"perfbench/peaks.py has no row for device kind "
            f"{device_kind!r}; add one with its source") from None
