"""Published peaks per chip, keyed by `device_kind` as JAX reports it.

Copied from `spark_rapids_tpu/trace/ledger.py:DEVICE_PEAKS` (PR 21),
so that no later PR to the program moves the roofline.  A device that
is not in this table is an error, never a default.
"""

#: Google Cloud documentation, "TPU v5e": 819 GB/s of HBM bandwidth,
#: 197 TFLOP/s in bf16, 16 GB of HBM per chip
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9},
}
