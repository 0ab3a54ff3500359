"""Pallas TPU kernels. Selected when running on a TPU
(FLAGS_use_pallas_kernels) and compiled by Mosaic there; the CPU tests
and the chip_smoke dry run ask for the Pallas interpreter explicitly
(FLAGS_pallas_interpret)."""
