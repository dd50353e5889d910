"""The SpGEMM benchmark: C = A * A on Table-3 matrices through the
program's ``SpgemmService``, measured on the chip.  See ``harness.py``."""
