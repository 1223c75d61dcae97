"""Algorithmic operations and least bytes of the BLAS and LAPACK work.

Counts are of the mathematics, never of how a chip implements it: a
complex multiply-add is 4 real multiplies and 4 real adds (4x the real
count at equal sizes), and an emulated fp64 product counts its fp64
operations once, not its passes.  Bytes are the least a call must move
through HBM: every operand read once and the result written once.
"""
from __future__ import annotations

import numpy as np

_COMPLEX_FACTOR = 4.0


def _factor(dtype) -> float:
    return _COMPLEX_FACTOR if np.dtype(dtype).kind == "c" else 1.0


def itemsize(dtype) -> int:
    return np.dtype(dtype).itemsize


def gemm_flops(m: int, n: int, k: int, dtype, batch: int = 1) -> float:
    """C(m x n) = A(m x k) B(k x n): 2mnk real operations, for each of
    ``batch`` products."""
    return 2.0 * m * n * k * _factor(dtype) * batch


def gemm_bytes(m: int, n: int, k: int, dtype, has_c: bool = False,
               batch: int = 1) -> float:
    """A and B read, C written (and read first when ``has_c``), for each
    of ``batch`` products."""
    return float((m * k + k * n + m * n * (2 if has_c else 1))
                 * itemsize(dtype) * batch)


def getrf_flops(n: int, dtype) -> float:
    """LU with partial pivoting of an n x n matrix: 2/3 n^3."""
    return 2.0 / 3.0 * n ** 3 * _factor(dtype)


def getrs_flops(n: int, nrhs: int, dtype) -> float:
    """Two triangular solves against nrhs columns: 2 n^2 nrhs."""
    return 2.0 * n * n * nrhs * _factor(dtype)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the operations
    over the peak rate and the bytes over the HBM bandwidth.  Every
    dtype is held to the bf16 peak: the chip has no faster unit for
    fp64 or complex64 work."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
