"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §2):
morton encoding, brute-force kNN (MXU), ray-box casting, flash attention.
Validated in interpret mode against the pure-jnp oracles in ref.py."""
from . import ops, ref
from .ops import bruteforce_knn, flash_attention, morton64, ray_box_nearest

__all__ = ["ops", "ref", "morton64", "bruteforce_knn", "ray_box_nearest",
           "flash_attention"]
