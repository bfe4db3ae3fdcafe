"""Operations and bytes the algorithms need, counted from their shapes.

Training counts are per real (unpadded) trained word: one SGNS window of
the FULL-W2V schedule (paper §3) with the fixed context width W_f, N
negatives and rows of ``row_bytes`` bytes as the configuration stores
them. The count is a property of the algorithm, not of an
implementation: a kernel that skips padding, tiles windows or fuses
gathers trains the same words and is charged the same work.

* FLOPs per window: the correlation GEMM ``(2W_f, d) x (d, N+1)`` and the
  two update GEMMs of the same size (2 FLOPs per multiply-add each), plus
  ~4 FLOPs per sigmoid over the ``2W_f x (N+1)`` pairings.
* Bytes per window: the lifetime ring buffer reads and writes one context
  row per slide (each position enters the ring once and leaves it once),
  and the N+1 output rows are read and written once per window.

At d=128, W_f=3, N=5 this is 27,792 FLOP and 7,168 B per word.

Serving counts are per top-k batch: one sweep over the ``(V, d)`` table.
"""
from __future__ import annotations


def window_flops(w_f: int, negatives: int, dim: int) -> int:
    k, m = 2 * w_f, negatives + 1
    return 3 * 2 * k * m * dim + 4 * k * m


def window_bytes(w_f: int, negatives: int, dim: int,
                 row_bytes: int = 4) -> int:
    del w_f  # the ring buffer moves one context row per slide at any W_f
    m = negatives + 1
    return (2 * dim + 2 * dim * m) * row_bytes


def sweep_bytes(vocab: int, dim: int, row_bytes: int = 4) -> int:
    """Bytes one top-k batch must read: the whole normalized table."""
    return vocab * dim * row_bytes


def query_flops(vocab: int, dim: int) -> int:
    """FLOPs to score one query row against every candidate."""
    return 2 * vocab * dim


def least_seconds(flops: float, nbytes: float, peak) -> tuple:
    """The roofline: the larger of compute time and memory time at the
    chip's peaks, and which of the two binds."""
    t_flops = flops / peak.bf16_flops
    t_bytes = nbytes / peak.hbm_bytes_per_s
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
