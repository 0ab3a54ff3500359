"""Pallas TPU kernels. Which of them a traced program may hold — compiled
by Mosaic on a TPU, run in the Pallas interpreter where the CPU tests and
the chip_smoke dry run ask for it, or left to XLA — is ``ops/placement.py``'s
answer, and nothing here decides it."""
