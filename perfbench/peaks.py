"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` string JAX reports.  A device that is not in the table
is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, HBM at
    # 819 GB/s per chip, 1,600 Gbit/s of inter-chip interconnect per
    # chip: the sum of a chip's links, so the chips of a 2 x 2 host,
    # which reach two neighbours each, cannot pass it.  "TPU v5 lite"
    # is what jax.devices() reports on the chip (chip run, PR 21).
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peak(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"perfbench.peaks: no published peak for device kind "
            f"{device_kind!r}; add it to PEAKS with its source") from None
