"""The benchmark of tpusppy on the TPU: see README.md beside this file."""
