"""Operations and bytes of the cluster-fused combine kernel, and the
chip's peaks, for its roofline share.

`combine_cluster_pallas` (repro/kernels/intersect) reads a (G, Q, L, W)
uint32 bitmap tensor and a (G, Q, S, 3) int32 program tensor, and writes
the (G, Q, W) result bitmaps and (G, Q) candidate counts. The bytes
below are what one call must move between HBM and the core at the
least, from the batch's own shape (the `combine.call` span's): every
input once, every output once, none of the padding to the kernel's
shape class or tiles, so a share of the HBM roofline computed from them
is a lower bound of the kernel's. The kernel runs
far below its arithmetic peak (a few integer operations a word), so its
roofline is the memory one.
"""

from __future__ import annotations

WORD = 4                      # bytes of a uint32 bitmap word, an int32
# integer vector operations a word: per program step, the branch-free
# AND / OR / ANDNOT select (xor, and, or, and, and, not, or); per result
# word, the SWAR popcount (12) and its sum into the count tile (1)
OPS_PER_STEP = 7
OPS_PER_RESULT = 13
# HBM bandwidth in bytes a second, by `jax.Device.device_kind`
# (Cloud TPU v5e system architecture: 819 GB/s a chip)
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def combine_cluster_bytes(G: int, Q: int, L: int, W: int, S: int) -> int:
    """Bytes one call moves at the least: bitmaps and programs in,
    result bitmaps and counts out."""
    return WORD * (G * Q * L * W + G * Q * S * 3 + G * Q * W + G * Q)


def combine_cluster_ops(G: int, Q: int, L: int, W: int, S: int) -> int:
    """Integer vector operations of one call (L sets no work: layers are
    only read)."""
    return G * Q * W * (OPS_PER_STEP * S + OPS_PER_RESULT)


def hbm_bytes_per_s(device_kind: str) -> float | None:
    return HBM_BYTES_PER_S.get(device_kind)
