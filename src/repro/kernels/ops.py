"""Dispatch layer: Pallas kernels on TPU, XLA reference elsewhere.

``matmul``/``trsm``/``attention`` are what the BLAS surface and the model
stack call. Backend selection:

* Operands on a TPU -> Pallas kernels (compiled), except dtypes the MXU
  lacks.
* Operands on the CPU -> XLA reference by default (the Pallas kernels are
  TPU programs; they execute on CPU only under ``interpret=True``, which
  is for correctness tests, not speed). Set ``SCILIB_PALLAS=1`` to force
  the interpreted kernels there (used by the test suite).

The BLAS entry points choose when the call is lowered, for the platform
of its operands, not for the default backend: on a TPU host the
runtime's host path computes on the CPU device, and a TPU kernel cannot
be lowered there.

Precision mapping for the TPU target (DESIGN.md): BLAS ``s/c`` run native
(f32/c64 — complex decomposes onto real MXU gemms); ``d/z`` have no MXU
equivalent and stay on the XLA path (host BLAS in the offload picture).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.gemm import gemm as pallas_gemm
from repro.kernels.syrk import syrk as pallas_syrk
from repro.kernels.trsm import trsm as pallas_trsm


def _backend() -> str:
    return jax.default_backend()


def use_pallas() -> bool:
    env = os.environ.get("SCILIB_PALLAS", "")
    if env == "0":
        return False
    return env == "1" or _backend() == "tpu"


def _interpret() -> bool:
    return _backend() != "tpu"


def _by_platform(pallas_fn, ref_fn):
    """The kernel for wherever the call is lowered: ``pallas_fn`` compiled
    on a TPU; on any other platform ``ref_fn``, or ``pallas_fn``
    interpreted under ``SCILIB_PALLAS=1``.  Only the chosen branch is
    lowered, so a CPU lowering never sees the TPU kernel."""
    env = os.environ.get("SCILIB_PALLAS", "")
    if env == "0":
        return ref_fn
    other = (functools.partial(pallas_fn, interpret=True) if env == "1"
             else ref_fn)
    if _backend() != "tpu":
        return other
    tpu = functools.partial(pallas_fn, interpret=False)
    return lambda *args: jax.lax.platform_dependent(*args, tpu=tpu,
                                                    default=other)


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """C = A @ B. Complex decomposes into real Pallas GEMMs (zgemm on the
    MXU via its real/imaginary planes — 4M algorithm)."""
    return _by_platform(_pallas_matmul, ref.matmul)(a, b)


def _pallas_matmul(a, b, *, interpret):
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        ar, ai = jnp.real(a), jnp.imag(a)
        br, bi = jnp.real(b), jnp.imag(b)
        f = functools.partial(_mm2d, interpret=interpret)
        rr = _batched(f, ar, br)
        ii = _batched(f, ai, bi)
        ri = _batched(f, ar, bi)
        ir = _batched(f, ai, br)
        return jax.lax.complex(rr - ii, ri + ir).astype(a.dtype)
    if a.dtype == jnp.float64 and not interpret:
        return ref.matmul(a, b)      # no f64 MXU path
    return _batched(functools.partial(_mm2d, interpret=interpret), a, b)


def _mm2d(a, b, interpret):
    return pallas_gemm(a, b, interpret=interpret)


def _batched(f, a, b):
    if a.ndim == 2 and b.ndim == 2:
        return f(a, b)
    # normalize leading batch dims then vmap the 2-D kernel
    bshape = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
    a = jnp.broadcast_to(a, bshape + a.shape[-2:])
    b = jnp.broadcast_to(b, bshape + b.shape[-2:])
    af = a.reshape((-1,) + a.shape[-2:])
    bf = b.reshape((-1,) + b.shape[-2:])
    out = jax.vmap(f)(af, bf)
    return out.reshape(bshape + out.shape[-2:])


def trsm(a: jax.Array, b: jax.Array, *, side: str = "L", uplo: str = "L",
         trans: str = "N", diag: str = "N") -> jax.Array:
    kw = dict(side=side, uplo=uplo, trans=trans, diag=diag)
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        # complex substitution needs complex VPU ops: XLA path (DESIGN.md)
        return ref.trsm(a, b, **kw)
    return _by_platform(functools.partial(pallas_trsm, **kw),
                        functools.partial(ref.trsm, **kw))(a, b)


def syrk(a: jax.Array, *, uplo: str = "L", trans: str = "N") -> jax.Array:
    kw = dict(uplo=uplo, trans=trans)
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        return ref.syrk(a, **kw)
    return _by_platform(functools.partial(pallas_syrk, **kw),
                        functools.partial(ref.syrk, **kw))(a)


def attention(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
              kv_len=None, chunk_q=0):
    """Attention entry point for the model stack. The flash kernel handles
    prefill/train (Tq > 8, fully-live cache); decode rows and partial
    caches fall back to the XLA path. ``chunk_q`` selects the causal
    query-chunked XLA formulation (flash-style flop/memory saving that
    also compiles for the CPU dry-run)."""
    tq = q.shape[-2]
    if (use_pallas() and tq == 1 and kv_len is not None and causal
            and window == 0):
        return decode_attention(q, k, v, kv_len, softcap=softcap,
                                scale=scale, interpret=_interpret())
    if use_pallas() and tq >= 8 and kv_len is None:
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               interpret=_interpret())
    if (chunk_q and causal and window == 0 and kv_len is None
            and tq > chunk_q and tq % chunk_q == 0):
        return ref.attention_chunked(q, k, v, chunk_q=chunk_q,
                                     softcap=softcap, scale=scale)
    return ref.attention(q, k, v, causal=causal, window=window,
                         softcap=softcap, scale=scale, kv_len=kv_len)


# ---------------------------------------------------------------------------
# The `pallas` dispatch venue (OffloadConfig.kernel_path / SCILIB_KERNELS)
# ---------------------------------------------------------------------------
# `kernel_*` are the entry points behind the runtime's third execution
# venue: on the TPU backend they run the Pallas kernels compiled, with the
# block edge taken from OffloadConfig.kernel_block; on every other backend
# they run the direct XLA formulation (interpret-mode Pallas is a
# correctness harness, orders of magnitude off), so the venue's remaining
# edge there is the epilogue-free closures built in repro.core.blas.

#: BLAS bases the `pallas` venue can execute; everything else stays on the
#: generic XLA offload path.
KERNEL_BASES = ("gemm", "syrk", "trsm")

#: Bases with a split-precision formulation (repro.kernels.split_gemm,
#: SCILIB_PRECISION): fp64 decomposed onto fp32/bf16 slice passes.  This
#: is the only fp64 gemm path the venue has — the MXU itself has no f64
#: mode.
SPLIT_KERNEL_BASES = ("gemm", "syrk", "trsm")


def kernel_available(base: str, dtype, precision: str = "") -> bool:
    """Capability test for the `pallas` venue: does `base` at `dtype` have
    a kernel? Complex syrk/trsm need complex VPU ops the kernels lack;
    complex gemm decomposes onto real MXU gemms (4M).

    fp64 gemm has no MXU path, so it is only available when a split
    scheme is active (``precision``, via repro.kernels.split_gemm) —
    never silently through the reference matmul: a True here must mean
    the venue executes something other than the plain XLA formulation,
    or the venue prober times the wrong path and can mis-lock."""
    if base not in KERNEL_BASES:
        return False
    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        return base == "gemm"
    if jnp.dtype(dtype) == jnp.float64 and base == "gemm":
        return bool(precision) and base in SPLIT_KERNEL_BASES
    return True


def _kernel_compiled() -> bool:
    return _backend() == "tpu"


def _block_kw(block: int, names=("bm", "bk", "bn")):
    b = int(block)
    return {n: b for n in names} if b > 0 else {}


def _split_matmul(a: jax.Array, b: jax.Array, precision: str,
                  block: int) -> jax.Array:
    from repro.kernels import split_gemm   # lazy: split_gemm pulls in core
    f = functools.partial(split_gemm.matmul, scheme=precision, block=block)
    return _batched(f, a, b)


def kernel_matmul(a: jax.Array, b: jax.Array, *, block: int = 0,
                  precision: str = "") -> jax.Array:
    """C = A @ B on the `pallas` venue. A zero-length contraction (k = 0)
    skips the kernel outright — its K grid axis would launch nothing and
    leave the accumulator unwritten.

    fp64 runs only with a split ``precision`` scheme (slice passes on
    the fp32 kernel); without one this venue has no f64 kernel — the
    reference fallback below mirrors what ``kernel_available`` already
    refuses, it is not a secret second path."""
    if a.dtype == jnp.float64 and precision and a.shape[-1]:
        return _split_matmul(a, b, precision, block)
    if a.shape[-1] == 0 or not _kernel_compiled():
        return ref.matmul(a, b)
    f = functools.partial(pallas_gemm, **_block_kw(block))
    if jnp.issubdtype(a.dtype, jnp.complexfloating):
        ar, ai = jnp.real(a), jnp.imag(a)
        br, bi = jnp.real(b), jnp.imag(b)
        rr = _batched(f, ar, br)
        ii = _batched(f, ai, bi)
        ri = _batched(f, ar, bi)
        ir = _batched(f, ai, br)
        return jax.lax.complex(rr - ii, ri + ir).astype(a.dtype)
    if a.dtype == jnp.float64:
        return ref.matmul(a, b)      # kernel_available(f64) is False
    return _batched(f, a, b)


def kernel_syrk(a: jax.Array, *, uplo: str = "L", trans: str = "N",
                block: int = 0) -> jax.Array:
    if not _kernel_compiled() or jnp.issubdtype(a.dtype,
                                                jnp.complexfloating):
        return ref.syrk(a, uplo=uplo, trans=trans)
    return pallas_syrk(a, uplo=uplo, trans=trans,
                       **_block_kw(block, ("bm", "bk")))


def kernel_trsm(a: jax.Array, b: jax.Array, *, side: str = "L",
                uplo: str = "L", trans: str = "N", diag: str = "N",
                block: int = 0) -> jax.Array:
    del block   # the recursion's base edge is fixed (trsm.BASE)
    if not _kernel_compiled() or jnp.issubdtype(a.dtype,
                                                jnp.complexfloating):
        return ref.trsm(a, b, side=side, uplo=uplo, trans=trans, diag=diag)
    return pallas_trsm(a, b, side=side, uplo=uplo, trans=trans, diag=diag)
