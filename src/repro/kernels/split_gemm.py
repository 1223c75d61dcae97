"""Split-precision fp64 GEMM for the `pallas` dispatch venue.

:mod:`repro.core.precision` owns the decomposition math (slices, cross
passes, error bounds); this module binds its injectable fp32 pass
primitive to the Pallas GEMM kernel, which is what finally gives fp64
a real path onto the MXU: the f64 operands never reach the systolic
array — their fp32/bf16 slices do, and the fp64 re-accumulation runs
on the VPU/XLA side.

On backends without compiled Pallas the pass primitive degrades to the
plain XLA fp32 matmul, exactly like every other `kernel_*` entry point
in :mod:`repro.kernels.ops` — so the venue's split path runs anywhere
tier-1 does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import precision
from repro.kernels.gemm import gemm as pallas_gemm


def _kernel_compiled() -> bool:
    return jax.default_backend() == "tpu"


def pass_mm(block: int = 0) -> precision.MatMul:
    """The fp32 slice-product primitive for the `pallas` venue."""
    if not _kernel_compiled():
        return lambda a, b: jnp.matmul(a, b,
                                       preferred_element_type=jnp.float32)
    kw = {n: int(block) for n in ("bm", "bk", "bn")} if block > 0 else {}
    kern = functools.partial(pallas_gemm, out_dtype=jnp.float32, **kw)

    def mm(a, b):
        if a.shape[-1] == 0:    # empty contraction: no K grid axis
            return jnp.zeros(a.shape[:-1] + b.shape[-1:], jnp.float32)
        return kern(a, b)

    return mm


def matmul(a: jax.Array, b: jax.Array, scheme: str, *,
           block: int = 0) -> jax.Array:
    """fp64 ``A @ B`` via split slices on the Pallas GEMM kernel."""
    return precision.matmul(a, b, scheme, mm=pass_mm(block))


def syrk(a: jax.Array, scheme: str, *, trans: bool = False,
         block: int = 0) -> jax.Array:
    return precision.syrk(a, scheme, trans=trans, mm=pass_mm(block))


def trsm(a: jax.Array, b: jax.Array, scheme: str, *, left_side: bool = True,
         lower: bool = True, trans_a: bool = False, unit_diag: bool = False,
         block: int = 0) -> jax.Array:
    return precision.trsm(a, b, scheme, left_side=left_side, lower=lower,
                          trans_a=trans_a, unit_diag=unit_diag,
                          mm=pass_mm(block))
