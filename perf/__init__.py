"""The benchmark of tnc_tpu on the chip: harness, cells as data, yardstick."""
