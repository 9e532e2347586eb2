"""chipbench: the benchmark the driver runs on the chip (see README.md)."""
