"""Pallas TPU kernels (flash attention, paged/ragged paged attention, latent attention for a prompt chunk)."""
