"""Pallas TPU kernels (flash attention, paged/ragged paged attention)."""
